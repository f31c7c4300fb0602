"""AOT precompilation of predicted post-failure execution plans.

Oobleck's templates ARE the recovery plans: the planner precomputes, at
startup, the pipeline template for every feasible node count, so losing a
host never re-plans from scratch. What that leaves unbounded on the XLA
side is COMPILATION — the re-matched template's stage programs (new layer
grouping, new chip count per stage) have never been built, so the first
post-recovery step pays a cold XLA compile exactly when the job is trying
to prove it recovered. Observed worst case on the CPU gate: 480 s for the
MoE fused-stage re-plan.

RecoveryPrecompiler closes that gap. On a background thread it

  1. walks `engine.predict_replan` — the SAME host-algebra + template
     re-match that `reconfigure()` runs at failure time — for every
     single-host loss from the current topology, chained `depth` failures
     deep (depth 2 covers n-1 and n-2 worlds);
  2. instantiates each predicted plan WITHOUT materializing parameters
     (`materialize_params=False`: meshes, shardings and jitted stage fns
     only — no arrays, no optimizer state);
  3. AOT-lowers and compiles every process-local stage executable
     (fwd/bwd/efwd, plus best-effort gradient-sum fill and
     optimizer-update programs, and the collective that sums a layer's
     gradients between congruent pipelines) against abstract inputs
     carrying the exact shardings the live path will dispatch with.

Warmth propagates through two layers:

  * building a predicted layout puts its jit objects into the process's
    one table of programs (`execution/pipeline.PROGRAMS`) under the keys
    `stage_program_key` gives the live layout's, so an in-place
    `reconfigure()` (single-controller) finds them there;
  * every AOT compile writes the serialized executable into JAX's
    persistent compilation cache (utils/compile_cache.py), which is what
    survives the respawn-based multi-host recovery — the fresh process
    retraces and DESERIALIZES (~10x-100x faster than compiling) instead
    of cold-compiling. This is the only warm path across a process
    boundary: AOT does not prime the in-process jit dispatch cache even
    within one process.

Multi-host notes: only stages addressable from this process are compiled
(executables cannot load onto non-addressable devices), and persistent
cache keys on CPU embed the device assignment — predicted entries are
exact for survivor worlds whose device ids are unchanged (victim = last
host, the common drain/preemption shape) and a best-effort prefix
otherwise. Every per-stage failure is swallowed and counted: the
precompiler must never take down the training loop it exists to protect.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Any

import jax
import numpy as np

from oobleck_tpu.execution.pipeline import optimizer_update_program
from oobleck_tpu.utils import background

logger = logging.getLogger("oobleck.precompile")


def _sds(aval, sharding) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(np.shape(aval), aval.dtype, sharding=sharding)


class RecoveryPrecompiler:
    """Background AOT compiler for the engine's predicted recovery plans.

    Lifecycle: construct -> start() -> (training runs) -> failure ->
    reconfigure() finds warm executables. `wait()` blocks until the walk
    finishes — tests that kill a worker at a fixed early step use it
    (via OOBLECK_PRECOMPILE_WAIT=1) to make warmth deterministic.
    """

    def __init__(self, engine, depth: int = 2):
        self.engine = engine
        self.depth = depth
        self.stats: dict[str, Any] = {
            "plans": 0, "stages_compiled": 0, "stages_cached": 0,
            "aux_compiled": 0, "errors": 0, "elapsed_s": None,
            "reroute_feasible": 0, "reroute_infeasible": 0,
            "grow_plans": 0,
        }
        self._done_keys: set = set()
        self._layer_avals: dict | None = None   # made once, for `_aot_dp_sums`
        self._thread: threading.Thread | None = None
        self._cancel = threading.Event()

    # -- lifecycle ------------------------------------------------------ #

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self._run, name="oobleck-precompile", daemon=True
        )
        self._thread.start()

    def cancel(self) -> None:
        """Ask the walk to stop at the next plan/stage boundary. Used when
        re-arming after a reconfigure: the old thread would otherwise keep
        compiling stale-topology plans (and touching engine.pipelines/plan)
        exactly while recovery is spending its time budget."""
        self._cancel.set()

    def wait(self, timeout: float | None = None) -> None:
        if self._thread is not None:
            self._thread.join(timeout)

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    # -- plan walk ------------------------------------------------------ #

    def _run(self) -> None:
        t0 = time.perf_counter()
        try:
            # Snapshot the topology under the engine lock: reconfigure()
            # mutates pipelines/plan on the training thread, and a walk
            # over a half-updated view would predict from garbage.
            with self.engine._lock:
                live_pipelines = list(self.engine.pipelines)
            for pipes in self._predicted_pipelines(live_pipelines):
                if self._cancel.is_set():
                    break
                self.stats["plans"] += 1
                for pipe in pipes:
                    if self._cancel.is_set():
                        break
                    self._aot_pipeline(pipe)
                self._aot_dp_sums(pipes)
        except Exception:
            # The walk itself failing (planner infeasibility at the root,
            # model without sample_batch, ...) degrades to cold recovery.
            self.stats["errors"] += 1
            logger.exception("recovery precompile walk failed")
        self.stats["elapsed_s"] = round(time.perf_counter() - t0, 2)
        logger.info(
            "recovery precompile %s: %d plans, %d stage programs compiled "
            "(%d already warm, %d aux, %d errors) in %.1fs",
            "cancelled" if self._cancel.is_set() else "done",
            self.stats["plans"], self.stats["stages_compiled"],
            self.stats["stages_cached"], self.stats["aux_compiled"],
            self.stats["errors"], self.stats["elapsed_s"],
        )
        # Mirror the walk's outcome into the metrics plane: each re-arm is a
        # fresh instance, so incrementing by this run's totals keeps the
        # process-lifetime counters cumulative.
        from oobleck_tpu.utils import metrics

        reg = metrics.registry()
        reg.counter("oobleck_precompile_plans_total",
                    "Recovery plans walked by the AOT precompiler").inc(
                        self.stats["plans"])
        stages = reg.counter(
            "oobleck_precompile_stages_total",
            "Stage programs seen by the AOT precompiler, by outcome")
        for result, key in (("compiled", "stages_compiled"),
                            ("cached", "stages_cached"),
                            ("aux", "aux_compiled"), ("error", "errors")):
            if self.stats[key]:
                stages.inc(self.stats[key], result=result)
        from oobleck_tpu.utils.compile_cache import cache_event

        cache_event("hit", self.stats["stages_cached"])
        cache_event("miss", self.stats["stages_compiled"])

    def _predicted_pipelines(self, live_pipelines):
        """Yield lists of (non-materialized) PipelineInstances: first the
        LIVE pipelines (the matched-at-n template — warms the respawn path
        for a restart at unchanged size), then every predicted plan for
        1..depth chained single-host losses."""
        engine = self.engine
        yield list(live_pipelines)

        cph = engine.chips_per_host
        frontier = [[sorted({r // cph for r in p.ranks})
                     for p in live_pipelines]]
        # Annotate each first-loss prediction with the degrade plane's
        # verdict: a reroute-feasible loss will likely never touch the
        # fallback executables being warmed below, but the walk still
        # compiles them — the planner can refuse a classifier-feasible
        # reroute at failure time (the slowdown bound depends on op
        # durations measured then), and the fallback must stay warm for
        # that refusal.
        from oobleck_tpu.degrade.classify import classify_failure

        ranks_list = [list(p.ranks) for p in live_pipelines]
        for lost in sorted({h for g in frontier[0] for h in g}):
            rep = classify_failure(lost, ranks_list, cph)
            self.stats["reroute_feasible" if rep.feasible
                       else "reroute_infeasible"] += 1
            logger.info(
                "predicted loss of host %d: degrade verdict %s",
                lost, rep.as_record()["reason"],
            )
            # A reroute keeps the surviving pipelines as they are and sums
            # between them alone: another set of owners, another program.
            self._aot_dp_sums([p for p, hosts in zip(live_pipelines,
                                                     frontier[0])
                               if lost not in hosts])
        seen_groups: set = set()
        for _ in range(self.depth):
            next_frontier = []
            for groups in frontier:
                for lost in sorted({h for g in groups for h in g}):
                    if self._cancel.is_set():
                        return
                    try:
                        plan, assignment, _idle = engine.predict_replan(
                            {lost}, current=groups
                        )
                    except Exception:
                        continue  # infeasible below min_hosts: nothing to warm
                    sig = tuple(sorted(tuple(g) for g in assignment))
                    if sig in seen_groups:
                        continue
                    seen_groups.add(sig)
                    next_frontier.append(assignment)
                    yield self._instantiate(plan, assignment)
            frontier = next_frontier
        yield from self._predicted_grow(live_pipelines)

    def _predicted_grow(self, live_pipelines):
        """Warm the most likely post-GROW plan: one arriving host folded
        in as new DP pipeline(s) via engine.predict_grow — the SAME fit
        the live grow_dp arm runs at JOIN time, so the program keys
        match exactly. Only when a free device block exists to bind the
        prediction against (the joiner's chips, by construction, are not
        in engine.devices yet); grow_reshape recompiles by design (every
        stage changes shape) and absorb_spare compiles nothing."""
        engine = self.engine
        cph = engine.chips_per_host
        try:
            if engine.multihost:
                return  # multihost grows defer to the spare pool
            bound = {id(d) for d in engine.devices}
            pool = [d for d in jax.devices() if id(d) not in bound]
            if len(pool) < cph:
                return
            current = [sorted({r // cph for r in p.ranks})
                       for p in live_pipelines]
            # The next joiner gets the next ORIGINAL host index — exactly
            # what _admit_hosts will hand out.
            plan, assignment, _idle = engine.predict_grow(
                {len(engine._host_index)}, current=current)
            if plan is None:
                return  # no template fits a lone arrival: absorb, no compile
        except Exception:
            self.stats["errors"] += 1
            logger.debug("grow prediction failed", exc_info=True)
            return
        self.stats["grow_plans"] += 1
        logger.info(
            "predicted one-host join: warming post-grow plan (%d pipelines)",
            len(plan.instances),
        )
        yield self._instantiate(plan, assignment,
                                devices=list(engine.devices) + pool[:cph])

    def _instantiate(self, plan, host_assignment, devices=None):
        """Build the predicted plan's PipelineInstances: full stage layout
        (meshes, shardings, jitted stage fns registered in the SHARED exec
        cache) but no parameter arrays."""
        from oobleck_tpu.execution.pipeline import PipelineInstance
        from oobleck_tpu.execution.reconfigure import hosts_to_ranks

        engine = self.engine
        if devices is None:
            devices = engine.devices
        assignments = plan.assignments(ranks=[
            hosts_to_ranks(hosts, engine.chips_per_host)
            for hosts in host_assignment
        ])
        process_of_rank = (
            [r // engine.chips_per_host for r in range(len(devices))]
            if engine.multihost else None
        )
        pipes = []
        for a in assignments:
            try:
                pipes.append(PipelineInstance(
                    pipeline_id=a.pipeline_index,
                    template=a.template,
                    ranks=list(a.ranks),
                    # Same interleave-or-fallback decision reconfigure()
                    # will make for this plan (record=False: a predicted
                    # fallback is not an event) — required for the chunks'
                    # program keys to match at failure time.
                    virtual_stages=engine._effective_virtual_stages(
                        a.template.num_stages, a.num_microbatches,
                        a.pipeline_index, record=False,
                    ),
                    model=engine.model,
                    devices=devices,
                    num_microbatches=a.num_microbatches,
                    total_num_microbatches=plan.total_num_microbatches,
                    microbatch_size=engine.args.job.microbatch_size,
                    seq_len=engine.seq_len,
                    params=None,
                    tensor_parallel=engine.args.execution.tensor_parallel,
                    sequence_parallel=engine.args.execution.sequence_parallel,
                    fsdp=engine.args.execution.fsdp,
                    process_of_rank=process_of_rank,
                    comm=engine.comm,
                    materialize_params=False,
                ))
            except Exception:
                self.stats["errors"] += 1
                logger.exception(
                    "predicted pipeline %d (ranks %s) failed to instantiate",
                    a.pipeline_index, list(a.ranks),
                )
        return pipes

    # -- per-stage AOT -------------------------------------------------- #

    def _aot_pipeline(self, pipe) -> None:
        last_layer = pipe.model.num_pipeline_layers - 1
        for st in pipe.stages:
            if self._cancel.is_set():
                return
            if not st.is_local or not st.fwd:
                continue
            for c, chunk_layers in enumerate(st.chunks):
                if self._cancel.is_set():
                    return
                if not chunk_layers:
                    continue  # a visit this stage passes through
                is_first = chunk_layers[0] == 0
                is_last = chunk_layers[-1] == last_layer
                key = pipe.stage_program_key(st, c)
                if key in self._done_keys:
                    self.stats["stages_cached"] += 1
                    continue
                try:
                    # One chunk per fence hold: compiling concurrently with
                    # the train thread's dispatch/readback/staging crashes
                    # the XLA CPU runtime (utils/background.py — the PR-3
                    # respawn flake); yielding between chunks bounds how
                    # long the train loop can wait on a compile.
                    with background.device_work("precompile"):
                        self._aot_chunk(pipe, st, c, chunk_layers,
                                        is_first, is_last)
                    self._done_keys.add(key)
                except Exception:
                    self.stats["errors"] += 1
                    logger.exception(
                        "AOT compile failed for stage %d chunk %d "
                        "(layers %s, ranks %s)",
                        st.stage_index, c, list(chunk_layers), list(st.ranks),
                    )

    def _aot_chunk(self, pipe, st, c: int, chunk_layers,
                   is_first: bool, is_last: bool) -> None:
        rng = jax.random.PRNGKey(0)
        params_avals = tuple(
            jax.tree.map(
                _sds,
                # Close over the layer index: init_layer branches on it in
                # Python, so it must stay concrete under eval_shape.
                jax.eval_shape(lambda r, _li=li: pipe.model.init_layer(r, _li),
                               rng),
                st.param_shardings[li],
            )
            for li in chunk_layers
        )
        x_aval = None
        if not is_first:
            # The carry of the chunk before this one in virtual-stage order
            # (`chunk_layers[0] - 1`'s, but where a looped model's visits
            # bring the range's last layer's back to its first).
            x_aval = jax.tree.map(
                lambda a: _sds(a, st.batch_sharding),
                pipe._edge_aval(pipe.input_edge_layer(st.stage_index, c)),
            )
        mb_aval = None
        if st.needs_batch:
            sample = pipe.model.sample_batch(pipe.microbatch_size, pipe.seq_len)
            mb_aval = {k: _sds(v, st.batch_sharding) for k, v in sample.items()}

        # Training never runs the last virtual stage's forward-only program
        # (its `bwd` returns the loss); eval_step does, where the stage has
        # no eval program with metrics.
        if not is_last or st.efwd[c] is None:
            st.fwd[c].lower(params_avals, x_aval, mb_aval).compile()
            self.stats["stages_compiled"] += 1
        # The running gradient sum `bwd` takes (donated) and returns has
        # the parameters' avals: tree, shapes, dtypes, shardings.
        if is_last:
            st.bwd[c].lower(
                params_avals, params_avals, x_aval, mb_aval).compile()
        else:
            dy_aval = jax.tree.map(
                lambda a: _sds(a, st.batch_sharding),
                pipe._edge_aval(chunk_layers[-1]),
            )
            st.bwd[c].lower(
                params_avals, params_avals, x_aval, mb_aval, dy_aval
            ).compile()
        self.stats["stages_compiled"] += 1
        if st.efwd[c] is not None:
            st.efwd[c].lower(params_avals, x_aval, mb_aval).compile()
            self.stats["stages_compiled"] += 1

        # Aux programs, best-effort (small next to a stage fwd+bwd, but the
        # MoE recovery hang showed eager fallbacks here are not free): the
        # chunk's gradient-sum fill and the per-layer optimizer update.
        try:
            st.zero[c].lower(params_avals).compile()
            self.stats["aux_compiled"] += 1
            self._aot_opt_update(chunk_layers, st, params_avals)
        except Exception:
            self.stats["errors"] += 1
            logger.debug("aux AOT warm failed for stage %d chunk %d",
                         st.stage_index, c, exc_info=True)

    def _aot_dp_sums(self, pipes) -> None:
        """Build the gradient sum between the plan's pipelines where it is
        a collective (`engine.CollectiveGroup`: a layer's owners on
        congruent stages). Its executable never comes from the persistent
        cache (`engine.dp_sum_program`): `PROGRAMS` holds it, and the
        data-parallel engine the re-instantiation builds over equal
        pipelines takes it from there, in this process. The anchor path's
        programs take their operands' shapes as they come and are not
        warmed."""
        from oobleck_tpu.execution.engine import DataParallelEngine
        from oobleck_tpu.parallel.cross_host import layer_avals

        if self.engine.multihost or len(pipes) < 2:
            return
        for group in DataParallelEngine(pipes).collective_groups:
            # One model a walk: the owners' chips and the layers say the rest.
            key = ("dp_sum", group.mesh, group.layers)
            if self._cancel.is_set():
                return
            if key in self._done_keys:
                continue
            try:
                if self._layer_avals is None:
                    self._layer_avals = layer_avals(self.engine.model)
                with background.device_work("precompile"):
                    group.program(jax.tree.leaves(
                        [self._layer_avals[li] for li in group.layers]))
                self._done_keys.add(key)
                self.stats["aux_compiled"] += 1
            except Exception:
                self.stats["errors"] += 1
                logger.debug("gradient-sum AOT warm failed for layers %s",
                             list(group.layers), exc_info=True)

    def _aot_opt_update(self, layer_ids, st, params_avals) -> None:
        import optax

        from jax.sharding import NamedSharding, PartitionSpec

        optimizer = self.engine.optimizer
        fn = optimizer_update_program(optimizer)
        replicated = NamedSharding(st.mesh, PartitionSpec())
        for li, p_aval in zip(layer_ids, params_avals):
            key = ("opt_update",
                   tuple(str(a) for a in jax.tree.leaves(p_aval)))
            if key in self._done_keys:
                continue
            sharding_tree = st.param_shardings[li]
            # Mirrors engine._place_opt_state: Adam mu/nu avals take the
            # param shardings, scalar bookkeeping leaves go replicated.
            state_aval = optax.tree_map_params(
                optimizer,
                lambda leaf, sh: _sds(leaf, sh),
                jax.eval_shape(optimizer.init, p_aval),
                sharding_tree,
                transform_non_params=lambda leaf: _sds(leaf, replicated),
                is_leaf=lambda x: hasattr(x, "mesh"),
            )
            fn.lower(p_aval, state_aval, p_aval).compile()
            self._done_keys.add(key)
            self.stats["aux_compiled"] += 1
