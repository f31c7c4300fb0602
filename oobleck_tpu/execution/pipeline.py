"""MPMD pipeline instance: one heterogeneous pipeline over a chip subset.

Capability match for the reference's OobleckPipeline
(/root/reference/oobleck/execution/pipeline.py:430-617) re-designed for the
single-controller JAX runtime:

  * each stage is a contiguous layer range on its own sub-`Mesh` (the chips
    the template assigned); stage programs are jit-compiled with GSPMD
    shardings — fsdp parameter sharding within a stage replaces the
    reference's manual FlatParamHandle hooks (layer.py:96-225), and the
    microbatch is *split* over the stage's chips (true ZeRO-style DP) rather
    than redundantly computed as the reference does;
  * stage-to-stage activations/gradients move with `jax.device_put` between
    sub-meshes (ICI path on TPU) instead of NCCL p2p with a metadata header
    (pipeline.py:288-427) — shapes are static, no protocol needed;
  * the 1F1B instruction streams (execution.schedule) are interpreted by a
    dependency-driven loop; backward recomputes the stage forward inside the
    jitted VJP, so only per-microbatch stage *inputs* are stashed, as in
    1F1B. Inside that VJP a layer's checkpoint (`ops/remat.checkpoint_layer`)
    keeps the layer's input and what the flash forward kernel wrote (O and
    the row logsumexp) and recomputes the rest: the forward kernel runs once
    a layer in `jit_bwd`, and a layer on the XLA path keeps its input
    alone. The LAST virtual stage has
    nothing to send forward: its forward gives the step one scalar, which
    its backward computes anyway. So training runs ONE program per
    microbatch there, `jit_bwd` = value-and-gradient (loss, grads, dx); its
    FORWARD instruction only stashes the input. A one-stage pipeline
    therefore shows no `jit_fwd` while it trains; `eval_step` still runs
    the forward-only program;
  * microbatch gradients accumulate inside `jit_bwd`: a chunk's running
    sum is a donated operand that comes back with the microbatch's
    gradients added, in its own buffers. A step holds one gradient set per
    chunk and dispatches no program that only adds. XLA fuses that add into
    the product that gives a gradient; where a kernel gives it (the routed
    experts' dW, `ops/moe.py`) the model says so and the sum goes down into
    the kernel, which starts from it: on a one-device stage no pass over a
    gradient only adds;
  * a chunk with routed layers says where it routed: its `jit_bwd` has one
    more OUTPUT, the layers' loads (`ops/moe.load_of`: each held expert's
    rows and the row tiles in use, int32 [routed layers, held + 1]), taken
    from the layers' primal call. `train_step` sums a chunk's microbatches
    on the device and leaves the sums on `self.load`; the engine reads them
    where it reads the loss. `fwd` and `eval_fwd` have no such output, a
    chunk without a routed layer has none anywhere, and with
    `OOBLECK_TELEMETRY=0` no chunk has;
  * a model may say that a range of its layers is gone through several
    times a microbatch over one set of weights (`models/base.py`: the
    layer-list contract's `repeated_layers`, `num_passes`). Nothing in the
    job says so; the pipeline derives the VISITS. Where ONE stage holds the
    whole range its chunk's walk repeats the range inside the one program
    (`StageRuntime.walks`: a folded loop), and the program runs the repeats
    as the trips of ONE `lax.scan` whose body is the range's layers once:
    the carry has one tree and one set of shapes over the whole range, so
    it is the loop's carry as it is the pipeline's, the parameters are
    closed over, and autodiff sums a weight's gradient over the trips. The
    program's text holds the range once however many passes there are.
    Where the template's cut falls inside the range, every stage holds
    `num_passes` chunks that are THE SAME repeated layers (chunk 0 with
    the stage's layers in front of the range, the last chunk with those
    behind it), scheduled as the interleaved schedule schedules `v`
    chunks: the carry leaving the last stage that holds repeated layers
    goes back to the first. Either way a layer's parameters are held once
    and its gradient sum, keyed by layer, takes every visit's addition
    before anything downstream reads it;
  * the jitted programs live in ONE table for the life of the process
    (`PROGRAMS`), keyed by everything their traces read: re-instantiation
    after a failure finds what the recovery precompiler's predicted layouts
    built — the pre-compile-per-template idea from SURVEY §7.3.1.
"""

from __future__ import annotations

import functools
import logging
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from oobleck_tpu.execution.schedule import (
    Instruction,
    Op,
    all_instructions,
    send_activation_dest,
    send_grad_dest,
    validate_interleaving,
)
from oobleck_tpu.models.base import layer_walk, param_bytes, repeated
from oobleck_tpu.obs import spans, telemetry
from oobleck_tpu.ops import checkpoint_layer
from oobleck_tpu.planning.templates import PipelineTemplate
from oobleck_tpu.utils import metrics

logger = logging.getLogger("oobleck.pipeline")


# Where ONE stage holds a model's whole repeated range, its visits are
# folded into the stage's one program. False schedules them as chunks there
# too (a backward then runs its visit's forward a second time): the switch
# of a measurement or a test, which no argument and no environment reads.
FOLD_WHOLE_RANGE = True

_ORDER_CACHE: dict[tuple[int, int, int], list[Instruction]] = {}


def canonical_order(S: int, M: int, v: int = 1) -> list[Instruction]:
    """The total execution order the dependency-driven greedy interpreter
    produces for the 1F1B (v=1) or interleaved (v>1) streams — a pure
    function of (stages, microbatches, virtual stages), so every
    jax.distributed process derives the IDENTICAL order without
    communicating. This is what makes cross-process edge collectives
    deadlock-free: any two processes issue their shared transfers in the
    same relative order."""
    key = (S, M, v)
    if key in _ORDER_CACHE:
        return _ORDER_CACHE[key]
    streams = [deque(s) for s in all_instructions(S, M, v)]
    acts: set[tuple[int, int, int]] = set()    # (stage, chunk, mb)
    gacts: set[tuple[int, int, int]] = set()
    order: list[Instruction] = []

    def ready(ins: Instruction) -> bool:
        if ins.op == Op.RECV_ACTIVATION:
            return (ins.stage, ins.chunk, ins.microbatch) in acts
        if ins.op == Op.RECV_GRAD:
            return (ins.stage, ins.chunk, ins.microbatch) in gacts
        return True

    progress = True
    while any(streams):
        if not progress:
            pending = [(s[0].op, s[0].stage, s[0].chunk, s[0].microbatch)
                       for s in streams if s]
            raise RuntimeError(f"pipeline schedule deadlock: {pending}")
        progress = False
        for q in streams:
            while q and ready(q[0]):
                ins = q.popleft()
                order.append(ins)
                if ins.op == Op.SEND_ACTIVATION:
                    ds, dc = send_activation_dest(ins.stage, ins.chunk, S)
                    acts.add((ds, dc, ins.microbatch))
                elif ins.op == Op.SEND_GRAD:
                    ds, dc = send_grad_dest(ins.stage, ins.chunk, S)
                    gacts.add((ds, dc, ins.microbatch))
                progress = True
    _ORDER_CACHE[key] = order
    return order


def _project_spec(spec: P, keep: frozenset) -> P:
    """Project a model PartitionSpec onto a stage mesh, keeping only the axis
    names in `keep` (subset of {"fsdp", "tensor"}); everything else becomes
    replicated."""
    out = []
    for entry in spec:
        names = entry if isinstance(entry, tuple) else (entry,)
        names = tuple(n for n in names if n in keep)
        out.append(names[0] if len(names) == 1 else (tuple(names) or None))
    return P(*out)


def grad_zero(params_tuple):
    """A chunk's zero-filled gradient sum, one per step: what the first
    microbatch's backward accumulates into (`jit_grad_zero` in a device
    trace). The parameters give the tree, shapes and dtypes and are not
    read: jit drops them from the program's operands."""
    return jax.tree.map(jnp.zeros_like, params_tuple)


@jax.jit
def load_sum(loads):
    """A chunk's load over a step: the sum of its microbatches' (`jit_
    load_sum` in a device trace; a few hundred bytes a routed layer)."""
    return sum(loads[1:], start=loads[0])


def fold_of(walk: tuple[int, ...]):
    """(front, body, trips, behind) of a chunk's walk, `front + body * trips
    + behind`: the layers applied once in front of what the walk repeats,
    the repeated range, how often, and the layers behind it. A walk that
    repeats nothing is all `front` (no body, one trip)."""
    body = tuple(li for li in dict.fromkeys(walk) if walk.count(li) > 1)
    if not body:
        return walk, (), 1, ()
    first, trips = walk.index(body[0]), walk.count(body[0])
    front, behind = walk[:first], walk[first + len(body) * trips:]
    assert front + body * trips + behind == walk, walk
    return front, body, trips, behind


# A folded loop's gradient sums RIDE THE LOOP'S CARRY. Autodiff of a scan
# sums a closed-over weight's gradient over the trips in accumulators of
# its own, filled with zeros before the backward loop and added to the
# chunk's running sum after it: a second set of the repeated layers'
# float32 gradients in the program's temporaries and two more passes over
# them a microbatch. Instead the running sum itself is the accumulator:
# beside the pipeline's carry the loop carries, per repeated layer, a tree
# shaped as its parameters whose VALUE nothing reads (the parameters stand
# in) and whose COTANGENT is the sum. `_route` sends a trip's gradient of a
# weight into that cotangent instead of the weight's own, `_carried` keeps
# the tree a carry (a carry the body hands on untouched is made a constant
# of the scan, and a constant's gradient is summed from zeros), and
# `_seeded` starts the backward loop's cotangent from the running sum. The
# parameters' gradient then arrives as running sum + every trip's gradient.


@jax.custom_vjp
def _route(p, riding):
    """`p`, whose gradient goes to `riding`'s cotangent."""
    return p


_route.defvjp(lambda p, riding: (p, None), lambda _, ct: (None, ct))


@jax.custom_vjp
def _carried(riding):
    """`riding` as it is, and not for `lax.scan` to see through."""
    return riding


_carried.defvjp(lambda riding: (riding, None), lambda _, ct: (ct,))


@jax.custom_vjp
def _seeded(carry, riding, sums):
    """`carry`; `riding`'s cotangent starts from `sums`."""
    return carry


_seeded.defvjp(lambda carry, riding, sums: (carry, sums),
               lambda sums, ct: (ct, sums, None))


def _accumulate(acc, backward, *, layers, below, looped):
    """A chunk's new gradient sum and whatever else its backward gives.
    `backward(**sums)` returns (grads, rest). `below` marks, a layer, the
    leaves whose sum goes down into the program (a tree of booleans, or
    None): those leaves of `acc` go down as `sums` and come back in `grads`
    as sum + gradient. Of a layer in `looped` that is every leaf, as it
    is: the loop's carry takes them (`_route`). Of another layer they are
    the leaves the model sums in a kernel (`ops/moe.GradSum`, named after
    layer and leaf), each taken by exactly one kernel call or the trace
    fails. Every other leaf is `acc + grads`."""
    if not any(jax.tree.leaves(below)):
        grads, rest = backward()
        return jax.tree.map(jnp.add, acc, grads), rest
    from oobleck_tpu.ops import moe

    names = []

    def hand(li, marks, layer_acc):
        def leaf(path, a, on):
            if not on:
                return None
            names.append(f"layer{li}{jax.tree_util.keystr(path)}")
            return moe.GradSum(a, names[-1])

        if marks is None:
            return None
        if li in looped:
            return layer_acc
        return jax.tree_util.tree_map_with_path(leaf, layer_acc, marks)

    sums = tuple(map(hand, layers, below, acc))
    with moe.handing_sums(names):
        grads, rest = backward(sums=sums)
    new = tuple(
        jax.tree.map(jnp.add, a, g) if marks is None else jax.tree.map(
            lambda a_, g_, on: g_ if on else a_ + g_, a, g, marks)
        for a, g, marks in zip(acc, grads, below))
    return new, rest


# Every jitted program of this process that bakes something in (a model, a
# mesh, an optimizer, a flat layout), by a key of VALUES: all its trace reads
# that its operands do not carry. The process never empties it, so a
# reconfiguration, the recovery precompiler and a second engine find what
# was built before. No `id` keys anything: the table outlives the object.
PROGRAMS: dict[tuple, Any] = {}


def optimizer_update_program(optimizer):
    """THE jitted per-layer step (`jit_optimizer_update` in a device trace)
    of every optimizer built from `optimizer`'s arguments
    (`parallel/train.Optimizer.built_from`). PipelineInstance and the
    recovery precompiler both take it from here, so the precompiled program
    is the one the live path runs."""
    key = ("optimizer_update", optimizer.built_from)
    if key not in PROGRAMS:
        def optimizer_update(g, state, p):
            updates, new_state = optimizer.update(g, state, p)
            return optax.apply_updates(p, updates), new_state

        PROGRAMS[key] = jax.jit(optimizer_update)
    return PROGRAMS[key]


@dataclass
class StageRuntime:
    stage_index: int
    layer_ids: tuple[int, ...]             # ALL layers on this stage (chunks flattened)
    ranks: tuple[int, ...]
    mesh: Mesh
    batch_sharding: NamedSharding          # [mb, ...] layouts (dim 0 = sample)
    param_shardings: dict[int, Any]        # layer -> NamedSharding tree
    param_pspecs: dict[int, Any]           # layer -> PartitionSpec tree
    # Contiguous layer ranges per virtual-stage chunk held here. One entry
    # (== layer_ids) under canonical 1F1B; v entries interleaved, chunk c
    # being virtual stage c*S + stage_index.
    chunks: tuple[tuple[int, ...], ...] = ()
    # The layer applications of each chunk's program, in order: the chunk
    # itself, but for a chunk that holds a model's whole repeated range
    # folded (`num_passes` times the range).
    walks: tuple[tuple[int, ...], ...] = ()
    tp: int = 1                            # tensor-parallel degree in-stage
    sp: int = 1                            # sequence-parallel degree in-stage
    use_fsdp: bool = False                 # params + batch sharded over fsdp
    manual: bool = True                    # model has the ShardCtx path
    needs_batch: bool = True               # any layer here reads the batch
    process: int | None = None             # owning process (multi-host MPMD)
    is_local: bool = True                  # this process owns the stage
    fwd: list[Callable | None] = field(default_factory=list)   # per chunk
    bwd: list[Callable | None] = field(default_factory=list)   # per chunk
    efwd: list[Callable | None] = field(default_factory=list)  # eval fwd w/ metrics
    zero: list[Callable | None] = field(default_factory=list)  # gradient-sum fill
    # Leaves a chunk's backward sums inside a kernel (`_sums_in_kernel`).
    kernel_sums: list[int] = field(default_factory=list)
    # Layers whose load a chunk's backward hands out (`_load_layers`).
    load_layers: list[tuple[int, ...]] = field(default_factory=list)

    @property
    def ctx(self):
        """ShardCtx for manual-collective execution; None = plain program.

        Only causal-LM families (gpt/llama) implement the Megatron-style
        embed/apply_block/head_loss_shifted contract the manual shard_map
        program calls; every other family runs the generic apply_layer
        program, where GSPMD handles any batch sharding (use_fsdp then means
        within-stage data parallelism with replicated params). With sp > 1
        the stage's activations are sharded over a `seq` axis and attention
        runs Ulysses/ring inside the stage mesh — long-context composed
        with elastic pipelines (round-4 weak #5)."""
        if not self.manual or (self.tp == 1 and not self.use_fsdp
                               and self.sp == 1):
            return None
        from oobleck_tpu.models.gpt import ShardCtx

        return ShardCtx(
            tensor="tensor" if self.tp > 1 else None,
            fsdp="fsdp" if self.use_fsdp else None,
            seq="seq" if self.sp > 1 else None,
        )


class PipelineInstance:
    """One pipeline: stages over chip subsets, 1F1B interpreter, grads."""

    def __init__(
        self,
        pipeline_id: int,
        template: PipelineTemplate,
        ranks: list[int],
        model,
        devices: list,
        num_microbatches: int,
        total_num_microbatches: int,
        microbatch_size: int,
        seq_len: int,
        params: dict[int, Any] | None = None,
        tensor_parallel: int = 1,
        sequence_parallel: int = 1,
        fsdp: int = -1,
        process_of_rank: list[int] | None = None,
        comm=None,
        materialize_params: bool = True,
        virtual_stages: int = 1,
    ):
        """`process_of_rank` + `comm` switch on multi-host MPMD execution:
        stages owned by other jax.distributed processes are skipped locally
        and stage-to-stage edges that cross processes ride `comm` (a
        parallel.cross_host.ProcessComm) — the TPU-native analog of the
        reference's node-spanning pipelines over NCCL p2p
        (/root/reference/oobleck/execution/pipeline.py:582-617).

        `materialize_params=False` builds the full stage layout (meshes,
        shardings, stage fns) without allocating parameter arrays — the
        recovery precompiler instantiates predicted post-failure layouts
        this way purely to AOT-compile their executables.

        `virtual_stages` > 1 runs the interleaved-1F1B schedule: the model
        is split into num_stages * v contiguous chunks, physical stage i
        holding chunks {c*S + i} — the template's chip assignment per
        physical stage is kept, its layer partition is superseded by the
        even v-way split (the template profiled a contiguous S-way cut; an
        interleaved layout needs S*v cuts).

        A model that repeats a range of its layers (`models/base.repeated`)
        decides its own visits (module docstring) and takes no
        `virtual_stages`."""
        assert len(ranks) == template.num_chips, (len(ranks), template.num_chips)
        self.pipeline_id = pipeline_id
        self.template = template
        self.ranks = list(ranks)
        self.model = model
        self.num_microbatches = num_microbatches
        self.total_num_microbatches = total_num_microbatches
        self.microbatch_size = microbatch_size
        self.seq_len = seq_len
        self.comm = comm
        self._process_of_rank = process_of_rank
        # Filled by each train_step: per-stage dispatch busy seconds, read
        # by the engine's measured pipeline-bubble gauge; per-op dispatch
        # durations feed the schedule-replay bubble simulation; dispatch
        # stall = time spent flushing batched cross-stage device_puts.
        self.last_stage_busy_s: dict[int, float] = {}
        self.last_op_times: dict[tuple[int, int, str], tuple[float, int]] = {}
        self.last_dispatch_stall_s: float = 0.0
        # Opt-in calibration mode: block on each compute's result inside the
        # timed region so last_op_times records true per-op durations
        # instead of async-dispatch enqueue times (which absorb upstream
        # backpressure and misattribute the whole step's drain to whichever
        # op happens to block). Also splits comm from compute: cross-stage
        # activation/grad transfers are sent eagerly (unbatched) and timed
        # as kinds "cf"/"cb", which stay OUT of stage-busy — they are the
        # overlappable component the degrade planner's effective_comm
        # projection discounts. Serializes execution — tests only,
        # never the training hot path.
        self.sync_op_timing = False
        my_process = comm.process_index if comm is not None else None

        S = len(template.stages)
        v = max(1, int(virtual_stages))
        L = model.num_pipeline_layers
        if v > 1:
            validate_interleaving(S, num_microbatches, v)
            if L < S * v:
                raise ValueError(
                    f"interleaved schedule needs at least num_stages * "
                    f"virtual_stages = {S * v} pipeline layers, model has {L}"
                )
        # What the model repeats: folded into one stage's program, or
        # visited as `passes` chunks a stage.
        rep, passes = repeated(model)
        self.repeated_layers, self.num_passes = rep, passes
        holders = [i for i, stage in enumerate(template.stages)
                   if set(stage.layer_indices) & set(rep)]
        visits = passes > 1 and not (FOLD_WHOLE_RANGE and len(holders) == 1)
        if passes > 1 and v > 1:
            raise ValueError(
                f"{type(model).__name__} goes through layers {rep[0]}.."
                f"{rep[-1]} {passes} times: its visits are its chunks, and "
                f"virtual_stages={v} cannot be laid over them")
        if visits:
            v = passes
            validate_interleaving(S, num_microbatches, v)
        self.virtual_stages = v
        # chunks_of_stage[i][c] = layer range of virtual stage c*S + i. The
        # template's layer cut stands when v == 1; interleaving re-cuts the
        # model into S*v even contiguous ranges (the template only profiled
        # an S-way cut) while keeping the template's chip assignment.
        # A looped model's visits: chunk c of a stage is the stage's share
        # of the repeated range, the same layers for every c, with the
        # stage's layers in front of the range on the first visit alone and
        # those behind it on the last alone (a stage without a repeated
        # layer has empty chunks between: the carry passes through).
        if visits:
            chunks_of_stage = [
                tuple(
                    tuple(li for li in stage.layer_indices
                          if li in rep or (li < rep[0] and c == 0)
                          or (li > rep[-1] and c == v - 1))
                    for c in range(v)
                )
                for stage in template.stages
            ]
        elif v == 1:
            chunks_of_stage = [
                (tuple(stage.layer_indices),) for stage in template.stages
            ]
        else:
            ranges = np.array_split(np.arange(L), S * v)
            chunks_of_stage = [
                tuple(
                    tuple(int(x) for x in ranges[c * S + i])
                    for c in range(v)
                )
                for i in range(S)
            ]

        tp = max(1, tensor_parallel)
        sp = max(1, sequence_parallel)
        if tp > 1 or sp > 1:
            cfg = model.config
            if not hasattr(model, "head_loss_shifted"):
                raise ValueError(
                    f"{type(model).__name__} has no manual-collective "
                    "support (head_loss_shifted); set tensor_parallel=1 "
                    "and sequence_parallel=1"
                )
            if tp > 1 and cfg.num_heads % tp != 0:
                raise ValueError(
                    f"num_heads={cfg.num_heads} not divisible by "
                    f"tensor_parallel={tp}"
                )
        if sp > 1:
            cfg = model.config
            if seq_len % sp != 0:
                raise ValueError(
                    f"seq_len={seq_len} not divisible by "
                    f"sequence_parallel={sp}"
                )
            # Ulysses runs on TP-LOCAL heads (H/tp), and ALiBi models
            # auto-route to it (ring cannot carry position-dependent
            # bias, models/gpt.py attention_sublayer).
            uses_ulysses = (
                getattr(cfg, "attention_impl", "auto") == "ulysses"
                or getattr(cfg, "position_embedding", "learned") == "alibi"
            )
            if uses_ulysses and (cfg.num_heads // tp) % sp != 0:
                raise ValueError(
                    f"ulysses needs TP-local heads divisible by the seq "
                    f"axis: ({cfg.num_heads} // tp={tp}) % sp={sp} != 0"
                )
        self.sp = sp

        # Per-layer PartitionSpec trees. Families with manual-TP sharding
        # rules (gpt/llama) declare them via param_specs; everything else
        # (bert/t5/vit/resnet/clip/swin, reference module/model.py:21-33)
        # gets replicated specs synthesized from the layer's abstract shape —
        # the reference's equivalent is NO_SHARD FlatParamHandles
        # (layer.py:96-111) for any family, no per-family code.
        manual = hasattr(model, "head_loss_shifted")
        if hasattr(model, "param_specs"):
            _specs = model.param_specs(stacked=False)

            def spec_tree(li: int):
                name = model.layer_name(li)
                return (
                    _specs["embed"] if name == "embed"
                    else _specs["head"] if name == "head"
                    else _specs["blocks"]
                )
        else:
            _spec_rng = jax.random.PRNGKey(0)

            def spec_tree(li: int):
                shapes = jax.eval_shape(
                    lambda r: model.init_layer(r, li), _spec_rng
                )
                return jax.tree.map(lambda _: P(), shapes)

        self.stages: list[StageRuntime] = []
        cursor = 0
        for si, stage in enumerate(template.stages):
            stage_layers = tuple(dict.fromkeys(
                li for ch in chunks_of_stage[si] for li in ch
            ))
            stage_ranks = tuple(self.ranks[cursor:cursor + stage.num_chips])
            cursor += stage.num_chips
            stage_devices = np.array([devices[r] for r in stage_ranks])
            if stage.num_chips % (tp * sp) != 0:
                raise ValueError(
                    f"stage {si} has {stage.num_chips} chips, not divisible "
                    f"by tensor_parallel*sequence_parallel={tp}*{sp}"
                )
            # fsdp semantics: -1 auto (shard over the chips/(tp*sp)
            # remainder when the microbatch allows, else replicate), 1 =
            # never shard params, N = must equal chips/(tp*sp) and be
            # honorable or it's an error.
            fsdp_deg = stage.num_chips // (tp * sp)
            if fsdp not in (-1, 1, fsdp_deg):
                raise ValueError(
                    f"stage {si}: fsdp={fsdp} requested but chips/(tp*sp) = "
                    f"{stage.num_chips}/{tp * sp} = {fsdp_deg}"
                )
            use_fsdp = (
                fsdp != 1 and fsdp_deg > 1
                and microbatch_size % fsdp_deg == 0
            )
            if fsdp == fsdp_deg and fsdp > 1 and not use_fsdp:
                raise ValueError(
                    f"stage {si}: explicit fsdp={fsdp} cannot be honored: "
                    f"microbatch_size={microbatch_size} not divisible by it"
                )
            if fsdp == -1 and fsdp_deg > 1 and not use_fsdp:
                logger.info(
                    "stage %d: %d chips replicate params (microbatch %d "
                    "not divisible by fsdp degree %d)",
                    si, stage.num_chips, microbatch_size, fsdp_deg,
                )
            # Axis order (fsdp, seq, tensor): tensor innermost (highest-
            # bandwidth collectives on neighboring chips), seq between.
            mesh = Mesh(
                stage_devices.reshape(fsdp_deg, sp, tp),
                ("fsdp", "seq", "tensor"),
            )
            keep = frozenset(
                a for a, on in (
                    ("fsdp", use_fsdp),
                    ("tensor", tp > 1),
                ) if on
            )
            # sp > 1 (manual causal-LM only): tokens [B, S] shard S over
            # `seq`. The 1-entry spec stays for generic families whose
            # batch fields can be 1-d (labels [B]).
            batch_spec = (
                P("fsdp" if use_fsdp else None, "seq") if sp > 1
                else P("fsdp") if use_fsdp else P(None)
            )
            param_shardings: dict[int, Any] = {}
            param_pspecs: dict[int, Any] = {}
            for li in stage_layers:
                pspecs = jax.tree.map(
                    lambda s: _project_spec(s, keep),
                    spec_tree(li),
                    is_leaf=lambda x: isinstance(x, P),
                )
                param_pspecs[li] = pspecs
                param_shardings[li] = jax.tree.map(
                    lambda s: NamedSharding(mesh, s),
                    param_pspecs[li],
                    is_leaf=lambda x: isinstance(x, P),
                )
            batch_layers = set(getattr(
                model, "batch_layers",
                {0, model.num_pipeline_layers - 1},
            ))
            if process_of_rank is not None:
                stage_procs = {process_of_rank[r] for r in stage_ranks}
                if len(stage_procs) != 1:
                    # Mirrors the reference's planner feasibility rule that
                    # two nodes never share one stage
                    # (pipeline_template.cpp:193-214): a stage is one host's
                    # chips, so its jits stay process-local.
                    raise ValueError(
                        f"stage {si} spans processes {sorted(stage_procs)}; "
                        "multi-host MPMD requires host-local stages"
                    )
                stage_process = stage_procs.pop()
                stage_local = stage_process == my_process
            else:
                stage_process, stage_local = None, True
            self.stages.append(StageRuntime(
                stage_index=si,
                layer_ids=stage_layers,
                ranks=stage_ranks,
                mesh=mesh,
                batch_sharding=NamedSharding(mesh, batch_spec),
                param_shardings=param_shardings,
                param_pspecs=param_pspecs,
                chunks=chunks_of_stage[si],
                walks=(chunks_of_stage[si] if visits else tuple(
                    layer_walk(model, ch) for ch in chunks_of_stage[si])),
                tp=tp,
                sp=sp,
                use_fsdp=use_fsdp,
                manual=manual,
                needs_batch=bool(batch_layers & set(stage_layers)),
                process=stage_process,
                is_local=stage_local,
            ))
            if passes > 1 and self.stages[-1].ctx is not None:
                raise ValueError(
                    f"{type(model).__name__} repeats layers: generic stage "
                    "path only (no tensor, sequence or manual fsdp split)")

        # Parameters: dict layer -> pytree placed on the owning stage's mesh.
        # Multi-host: only this process's stages materialize (remote device
        # placement is neither possible nor needed — the owning process
        # materializes its own, from the same seed-42 stream).
        self.params: dict[int, Any] = {}
        if materialize_params:
            rng = jax.random.PRNGKey(42)  # reference fixes seed 42 (model.py:18)
            for st in self.stages:
                if not st.is_local:
                    continue
                for li in st.layer_ids:
                    if params is not None and li in params:
                        src = params[li]
                    else:
                        src = self.model.init_layer(rng, li)
                    self.params[li] = jax.device_put(src, st.param_shardings[li])
        # The optimizer's step in the order it asks the allocator for memory
        # (`apply_updates`): the layers by their parameters' bytes, largest
        # first, ties in layer order. Sizes alone decide it, so it is worked
        # out here, where the parameters are placed; a reconfiguration
        # builds a new instance and so takes the order again.
        self.update_order: tuple[int, ...] = tuple(sorted(
            self.params, key=lambda li: (-param_bytes(self.params[li]), li)))

        self.grads: dict[int, Any] = {}
        # Where the last train_step routed (its docstring), or None; and
        # the layers that can say, with what the host keeps of each (the
        # model's `load_layers`: a label, the rows of a row tile).
        self.load: tuple | None = None
        marks = getattr(model, "load_layers", None)
        self.load_info: dict[int, tuple[str, int]] = (
            {} if marks is None else marks(microbatch_size * seq_len))
        self.last_eval_metrics: tuple[float, float] | None = None
        # Static activation avals for cross-process edges (computed lazily:
        # single-controller runs never need them).
        self._act_avals: list | None = None
        self._build_stage_fns()

    # ------------------------------------------------------------------ #

    @property
    def num_stages(self) -> int:
        return len(self.stages)

    def stage_of_layer(self, layer_idx: int) -> int:
        for st in self.stages:
            if layer_idx in st.layer_ids:
                return st.stage_index
        raise KeyError(layer_idx)

    def owns_layer(self, layer_idx: int) -> bool:
        return layer_idx in self.params

    def op_time_split(self) -> tuple[float, float]:
        """(compute_s, comm_s) of the last step from ``last_op_times``:
        compute is the summed "f"/"b" durations (recorded every step —
        async enqueue times in normal mode, true durations under
        ``sync_op_timing``); comm is the summed "cf"/"cb" transfer
        durations, which only exist under sync_op_timing — in async mode
        the split degrades honestly to (dispatch-observed compute, 0)
        rather than fabricating a comm estimate. Feeds the per-step
        telemetry sample (obs/telemetry.py)."""
        compute = comm = 0.0
        for (_stage, _chunk, kind), (total, _n) in self.last_op_times.items():
            if kind in ("f", "b"):
                compute += total
            elif kind in ("cf", "cb"):
                comm += total
        return compute, comm

    # ------------------------------------------------------------------ #

    def stage_program_key(self, st: StageRuntime, c: int) -> tuple:
        """The key of stage `st`'s chunk `c` in `PROGRAMS`: everything the
        traces of its `fwd` / `bwd` / `eval_fwd` / `grad_zero` read that is
        not an operand. A field a trace reads and this key lacks is a wrong
        program handed out silently, so this is the list, by reader.
        `_stage_apply`:
          * the model: its class (the methods called, `num_pipeline_layers`)
            and its config (`remat`, the dtype, every width). A model IS its
            class and its frozen config: what else an instance holds is
            made from the config, and nothing in `models/` or `ops/` reads
            the environment;
          * the chunk's layers, which also say whether it is the first
            (layer 0: no `x`, reads the tokens) and the last (the model's
            last layer: returns the loss), and its WALK: the layers in the
            order and as often as the program applies them (a folded loop
            repeats a range `num_passes` times, the config's). A chunk's
            place among a looped model's visits is in its layers (the
            first has those in front of the range, the last those behind)
            and nowhere else: the carry has one shape over all visits, so
            the visits between are one program;
          * `st.ctx` and the specs of `x` and the tokens: `st.tp`, `st.sp`,
            `st.use_fsdp` (`st.manual` is the model's class's);
          * `st.mesh`, which the `shard_map` is built over: the stage's
            DEVICES in their (fsdp, seq, tensor) arrangement, not their
            ranks in one engine's list;
          * `st.param_pspecs`: the model's specs projected on `use_fsdp`
            and `tp > 1`, all above;
          * `microbatch_size`, `seq_len`: the manual loss's divisor.
        `_build_stage_fns`:
          * `total_num_microbatches`, the loss's scale in `bwd`;
          * `st.param_shardings`, the gradient sum's `out_shardings`:
            `st.param_pspecs` on `st.mesh`;
          * whether the last chunk has an eval program: the class, `st.ctx`.
          * `_load_layers`: the chunk's layers whose load `bwd` hands out
            (the model's `load_layers` at `microbatch_size * seq_len`
            tokens, `st.ctx` and the telemetry ring's switch decide them):
            whether that output is there.
        `_sums_below`, `_accumulate`:
          * the marks themselves, flattened (the model's `sums_in_kernel`,
            `st.mesh.size` and the backend decide them; the walk, which
            layers' sums ride a folded loop's carry).
        Not in the key, because a process has ONE: the backend
        (`ops/kernel.on_tpu`) and JAX's configuration flags. Read by
        no trace: the pipeline's id and own microbatch count
        (`adopt_microbatches`), the stage's ranks and owning process."""
        layers = st.chunks[c]
        marks, tree = jax.tree.flatten(self._sums_below(st, c))
        return (
            type(self.model), self.model.config, layers, st.walks[c], st.mesh,
            st.tp, st.sp, st.use_fsdp, self.microbatch_size, self.seq_len,
            self.total_num_microbatches, tuple(self._load_layers(st, c)),
            tuple(marks), tree,
        )

    def _stage_apply(self, st: StageRuntime, layers: tuple[int, ...],
                     walk: tuple[int, ...]):
        """Stage program over one chunk's contiguous `layers` (== the whole
        stage under canonical 1F1B; one of v chunks interleaved), applied
        in the order of `walk`: `layers` itself, each once in a Python
        loop, but for a folded loop, which names a range several times.
        There the layers in front run as ever, the range's repeats are the
        trips of one `lax.scan` over the range's layers (`fold_of`: the
        carry keeps one tree and one set of shapes over the range, so it
        can be a loop's), then the layers behind; a repeated layer's
        gradient is the sum over the trips, and so is its load."""
        model = self.model
        last_layer = model.num_pipeline_layers - 1
        remat = bool(getattr(model.config, "remat", False))
        ctx = st.ctx

        if ctx is None:
            # Generic stage program over the LayerListModel protocol: every
            # family (causal LM, MLM encoder, enc-dec, image) runs through
            # apply_layer, with the last layer's logits fed to the model's
            # own loss_from_logits — the engine is objective-agnostic like
            # the reference's (pipeline.py:169-216).
            def layer_fn(li, handed, give_load):
                # Gradient sums handed to a layer are inputs of its
                # checkpoint, like its parameters: kept, not recomputed.
                # Its load is one more OUTPUT of the checkpoint, an
                # integer one: the primal call's, which nothing
                # differentiates and the recompute has no reader for.
                kw = {} if handed is None else {"grad_sums": handed}
                if give_load:
                    kw["return_load"] = True
                fn = lambda p, c, b: model.apply_layer(li, p, c, b, **kw)
                if remat and 0 < li < last_layer:
                    fn = checkpoint_layer(fn)
                return fn

            ends = walk[-1] == last_layer
            front, body, trips, behind = fold_of(walk[:-1] if ends else walk)

            def apply(params_tuple, x, batch, with_metrics=False, sums=None,
                      load_layers=None):
                """The chunk's output (the carry, or the loss). With
                `load_layers`, the chunk's layers that are to hand out
                their load: (that, their loads in layer order as ONE int32
                [layers, held + 1] in a tuple, or () for no such layer)."""
                sums = sums or (None,) * len(layers)
                held = dict(zip(layers, zip(params_tuple, sums)))

                def through(lis, carry, held=held):
                    """The carry after the layers `lis`, each applied once,
                    and {layer: load} of those that hand theirs out."""
                    loads = {}
                    for li in lis:
                        p, handed = held[li]
                        give_load = li in (load_layers or ())
                        carry = layer_fn(li, handed, give_load)(
                            p, carry, batch)
                        if give_load:
                            carry, loads[li] = carry
                    return carry, loads

                carry, loads = through(front, x)
                if body:
                    # The passes are the trips of ONE loop: the carry has
                    # one tree and one set of shapes over the whole range
                    # (`models/base.py`), so it is the loop's, and the
                    # parameters and the batch are closed over. A weight's
                    # gradient is summed over the trips: into the running
                    # sum where one was handed down (`_route`), by
                    # autodiff's own accumulators otherwise. A layer
                    # applied several times says its load once, the sum.
                    ps = {li: held[li][0] for li in body}
                    running = {li: held[li][1] for li in body}
                    rides = None not in running.values()

                    def one_pass(state, _):
                        c, riding = state
                        used = _route(ps, riding) if rides else ps
                        c, by_layer = through(
                            body, c, {li: (used[li], None) for li in body})
                        return (c, _carried(riding) if rides else None), (
                            by_layer)

                    (carry, riding), by_trip = jax.lax.scan(
                        one_pass, (carry, ps if rides else None), None,
                        length=trips)
                    if rides:
                        carry = _seeded(carry, riding, running)
                    loads.update(jax.tree.map(lambda l: l.sum(0), by_trip))
                    carry, more = through(behind, carry)
                    loads.update(more)
                if ends:
                    p, _ = held[last_layer]
                    logits = model.apply_layer(last_layer, p, carry, batch)
                    loss = model.loss_from_logits(logits, batch)
                    if with_metrics:
                        # Task metric next to the loss (the reference
                        # builds an accuracy metric the engine never
                        # reports, dataset.py:39-54 — reported here).
                        c, n = model.accuracy_from_logits(logits, batch)
                        return loss, c, n
                    carry = loss
                if load_layers is None:
                    return carry
                return carry, ((jnp.stack(list(loads.values())),)
                               if loads else ())

            return apply

        # Manual-collective stage program: the stage's chips form a
        # (fsdp, tensor) sub-mesh and the model's ShardCtx path runs under
        # shard_map — the same Megatron f/g + fsdp-gather machinery as the
        # fused SPMD step (parallel/train.py), per stage. Gradient reductions
        # fall out of the shard_map in_spec transposes.
        is_first = layers[0] == 0
        is_last = layers[-1] == last_layer
        batch_axes = (
            (("fsdp",) if ctx.fsdp else ())
            + (("seq",) if ctx.seq else ())
        )
        block_fn = lambda p, x: model.apply_block(p, x, ctx)
        block = checkpoint_layer(block_fn) if remat else block_fn
        denom = float(self.microbatch_size * (self.seq_len - 1))
        seq_ax = "seq" if st.sp > 1 else None
        x_spec = P("fsdp" if st.use_fsdp else None, seq_ax, None)
        tok_spec = P("fsdp" if st.use_fsdp else None, seq_ax)

        def core(*ops):
            it = iter(ops)
            params_tuple = next(it)
            x = None if is_first else next(it)
            tokens = next(it) if is_first else None
            targets = next(it) if is_last else None
            mask = next(it) if is_last else None
            carry = x
            for li, p in zip(layers, params_tuple):
                if li == 0:
                    carry = model.embed(p, tokens, ctx)
                elif li == last_layer:
                    loss_sum = model.head_loss_shifted(p, carry, targets, mask, ctx)
                    if batch_axes:
                        loss_sum = jax.lax.psum(loss_sum, batch_axes)
                    return loss_sum / denom
                else:
                    carry = block(p, carry)
            return carry

        in_specs: list[Any] = [tuple(st.param_pspecs[li] for li in layers)]
        if not is_first:
            in_specs.append(x_spec)
        if is_first:
            in_specs.append(tok_spec)
        if is_last:
            in_specs.extend([tok_spec, tok_spec])
        out_spec = P() if is_last else x_spec
        smap = jax.shard_map(
            core, mesh=st.mesh, in_specs=tuple(in_specs), out_specs=out_spec
        )

        def apply(params_tuple, x, batch, load_layers=None):
            """As the generic `apply`; no layer of this path hands out a
            load (`_load_layers`), so its loads are the empty tuple."""
            tokens = batch["input_ids"] if batch is not None else None
            ops: list[Any] = [params_tuple]
            if not is_first:
                ops.append(x)
            if is_first:
                ops.append(tokens)
            if is_last:
                # Pre-shifted targets + validity mask: computed on the full
                # (logically unsharded) tokens so the next-token shift never
                # crosses a shard boundary (cf. parallel/train.py loss_fn).
                targets = jnp.concatenate(
                    [tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], axis=-1
                )
                mask = jnp.broadcast_to(
                    (jnp.arange(tokens.shape[-1]) < tokens.shape[-1] - 1)
                    .astype(jnp.float32),
                    tokens.shape,
                )
                ops.extend([targets, mask])
            out = smap(*ops)
            return out if load_layers is None else (out, ())

        return apply

    def _build_stage_fns(self) -> None:
        """jit each chunk's forward and (recomputing) backward, or take
        them from `PROGRAMS` where the process built them before
        (`stage_program_key`).

        Microbatch gradients accumulate INSIDE the backward program: `acc`,
        the chunk's running gradient sum (the parameters' tree, dtypes and
        shardings), is a donated operand and the new sum comes back in its
        buffers, so a step holds one gradient set per chunk and no separate
        add runs. `zero(params)` fills the sum the step's first microbatch
        adds to (0 + g is g): one backward program per chunk, whichever
        microbatch. The new sum is `acc + grads`, an add XLA fuses into the
        product that gives the gradient, for every leaf but those the
        model takes INTO a kernel (`_sums_in_kernel`): a custom call's
        output takes no fused add, so the kernel starts from the sum and
        that leaf of `grads` already is the new sum (`_accumulate`).

        Every other chunk's `bwd(params, acc, x, batch, dy)` returns
        (new sum, dx). The last virtual stage's `bwd(params, acc, x,
        batch)` is the loss's value-and-gradient and returns (loss, new
        sum, dx): the unscaled microbatch loss its `fwd` would give,
        gradients of loss / total microbatches. train_step never calls that
        chunk's `fwd` (eval_step does). A chunk with `_load_layers` returns
        their loads as one output more, last, from the forward its
        backward differentiates: the operands are what they were."""
        last_layer = self.model.num_pipeline_layers - 1
        scale = 1.0 / self.total_num_microbatches
        for st in self.stages:
            st.fwd = [None] * len(st.chunks)
            st.bwd = [None] * len(st.chunks)
            st.efwd = [None] * len(st.chunks)
            st.zero = [None] * len(st.chunks)
            st.kernel_sums = [0] * len(st.chunks)
            st.load_layers = [()] * len(st.chunks)
            if not st.is_local:
                continue
            for c, chunk_layers in enumerate(st.chunks):
                if not chunk_layers:
                    # A visit on which this stage has nothing to apply
                    # (`train_step` passes the carry through).
                    continue
                is_last = chunk_layers[-1] == last_layer
                self._count_loop(st.walks[c], is_last)
                below = self._sums_below(st, c)
                looped = fold_of(st.walks[c])[1]
                st.kernel_sums[c] = sum(jax.tree.leaves([
                    marks for li, marks in zip(chunk_layers, below)
                    if li not in looped]))
                load_layers = st.load_layers[c] = self._load_layers(st, c)
                key = self.stage_program_key(st, c)
                if key in PROGRAMS:
                    st.fwd[c], st.bwd[c], st.efwd[c], st.zero[c] = (
                        PROGRAMS[key])
                    continue
                apply = self._stage_apply(st, chunk_layers, st.walks[c])

                # What `bwd` differentiates: (the chunk's output, its
                # loads in a tuple: of a chunk that hands out none, empty).
                loaded = functools.partial(apply, load_layers=load_layers)
                accumulate = functools.partial(
                    _accumulate, layers=chunk_layers, below=below,
                    looped=looped)
                # The sum leaves each program where it came in: the
                # parameters' own shardings, or the donation does not take.
                acc_shardings = tuple(
                    st.param_shardings[li] for li in chunk_layers)

                def fwd(params_tuple, x, tokens, _apply=apply):
                    return _apply(params_tuple, x, tokens)

                if is_last:
                    # The loss and d(loss·scale)/d(params, x) from one
                    # forward: the loss rides out as the aux value,
                    # unscaled, exactly what `fwd` returns.
                    def bwd(params_tuple, acc, x, tokens, _loaded=loaded,
                            _accumulate=accumulate):
                        def backward(**sums):
                            def loss_fn(pt, x_):
                                loss, loads = _loaded(pt, x_, tokens, **sums)
                                return loss * scale, (loss, loads)

                            if x is None:
                                (_, aux), grads = jax.value_and_grad(
                                    lambda pt: loss_fn(pt, None),
                                    has_aux=True)(params_tuple)
                                return grads, (*aux, None)
                            (_, aux), (grads, dx) = jax.value_and_grad(
                                loss_fn, argnums=(0, 1),
                                has_aux=True)(params_tuple, x)
                            return grads, (*aux, dx)

                        new, (loss, loads, dx) = _accumulate(acc, backward)
                        return (loss, new, dx, *loads)

                    out_shardings = (None, acc_shardings, None)
                else:
                    def bwd(params_tuple, acc, x, tokens, dy, _loaded=loaded,
                            _accumulate=accumulate):
                        def backward(**sums):
                            if x is None:
                                # First chunk: differentiate wrt params only.
                                _, vjp, loads = jax.vjp(
                                    lambda pt: _loaded(pt, None, tokens,
                                                       **sums),
                                    params_tuple, has_aux=True)
                                return vjp(dy)[0], (None, loads)
                            _, vjp, loads = jax.vjp(
                                lambda pt, x_: _loaded(pt, x_, tokens,
                                                       **sums),
                                params_tuple, x, has_aux=True)
                            grads, dx = vjp(dy)
                            return grads, (dx, loads)

                        new, (dx, loads) = _accumulate(acc, backward)
                        return (new, dx, *loads)

                    out_shardings = (acc_shardings, None)
                out_shardings += (None,) * bool(load_layers)

                st.fwd[c] = jax.jit(fwd)
                st.bwd[c] = jax.jit(bwd, donate_argnums=1,
                                    out_shardings=out_shardings)
                st.zero[c] = jax.jit(grad_zero, out_shardings=acc_shardings)
                if (is_last and st.ctx is None
                        and hasattr(self.model, "accuracy_from_logits")):
                    def eval_fwd(params_tuple, x, tokens, _apply=apply):
                        return _apply(params_tuple, x, tokens,
                                      with_metrics=True)

                    st.efwd[c] = jax.jit(eval_fwd)
                PROGRAMS[key] = (
                    st.fwd[c], st.bwd[c], st.efwd[c], st.zero[c])

    def _count_loop(self, walk: tuple[int, ...], is_last: bool) -> None:
        """What a local chunk's programs hold of a looped model:
        applications of repeated layers, the passes among them that are
        one loop's trips (`fold_of`), and exits. Counted where a
        pipeline takes its programs, built or found, so a pipeline's
        chunks add up to what ONE microbatch goes through, whoever
        implements the loop."""
        if self.num_passes == 1:
            return
        _, body, trips, _ = fold_of(walk)
        reg = metrics.registry()
        reg.counter(
            "oobleck_loop_block_visits_total",
            "Applications of a model's repeated layers in the local chunks "
            "of the pipelines instantiated in this process (one microbatch "
            "each)",
        ).inc(sum(li in self.repeated_layers for li in walk))
        reg.counter(
            "oobleck_loop_scanned_passes_total",
            "Passes that are the trips of ONE loop inside a local chunk's "
            "program (a folded walk: `num_passes`; passes that are visits "
            "of a stage: 0), in the pipelines instantiated in this process",
        ).inc(trips if body else 0)
        if is_last:
            reg.counter(
                "oobleck_loop_exits_total",
                "Exits (one a pass) in the last chunks of the pipelines "
                "instantiated in this process, of a model that repeats layers",
            ).inc(self.num_passes)

    def _edge_layer(self, stage: int, chunk: int) -> int:
        """The last layer applied up to and including virtual stage
        (stage, chunk): the layer whose carry leaves that chunk (a chunk
        with nothing to apply passes on what it was given)."""
        S = self.num_stages
        vs = chunk * S + stage
        while not self.stages[vs % S].chunks[vs // S]:
            vs -= 1
        return self.stages[vs % S].chunks[vs // S][-1]

    def input_edge_layer(self, stage: int, chunk: int) -> int:
        """The layer whose carry ENTERS virtual stage (stage, chunk), which
        is not the first."""
        vs = chunk * self.num_stages + stage - 1
        return self._edge_layer(vs % self.num_stages, vs // self.num_stages)

    def _zeros_for(self, st: StageRuntime, layers: tuple[int, ...]):
        """Zero-filled gradient sums of `layers` alone: what a visit adds
        to for the layers no earlier visit of the step has touched (the
        embedding, on a looped model's first chunk)."""
        shardings = tuple(st.param_shardings[li] for li in layers)
        leaves, tree = jax.tree.flatten(shardings)
        key = ("grad_zero", tuple(leaves), tree)
        if key not in PROGRAMS:
            PROGRAMS[key] = jax.jit(grad_zero, out_shardings=shardings)
        return PROGRAMS[key](tuple(self.params[li] for li in layers))

    def _load_layers(self, st: StageRuntime, c: int) -> tuple[int, ...]:
        """The layers of stage `st`'s chunk `c` whose load the chunk's
        `bwd` hands out (`apply_layer(return_load=True)`): the model's
        `load_layers` among them, on the generic stage path (routed
        experts run on no other), while the telemetry ring, their one
        reader, is on."""
        if st.ctx is not None or not telemetry.telemetry().enabled:
            return ()
        return tuple(li for li in st.chunks[c] if li in self.load_info)

    def _sums_below(self, st: StageRuntime, c: int) -> tuple:
        """A layer of stage `st`'s chunk `c`, the leaves whose running
        gradient sum goes down into the chunk's backward program
        (`_accumulate`): a tree of booleans over the layer's parameters,
        or None. Every leaf of a layer the chunk's walk repeats (its sum
        rides the folded loop's carry), and what `_sums_in_kernel` says of
        another."""
        looped = fold_of(st.walks[c])[1]
        return tuple(
            jax.tree.map(lambda _: True, st.param_shardings[li])
            if li in looped else self._sums_in_kernel(st, li)
            for li in st.chunks[c])

    def _sums_in_kernel(self, st: StageRuntime, li: int):
        """The leaves of layer `li` whose running gradient sum goes down
        into a kernel, as the model marks them (a tree of booleans over the
        layer's parameters), or None. Only on a stage of one device: there
        no mesh axis reduces gradients (a reduction would sum the running
        sum with them) and no partitioner stands between the donated sum
        and the kernel that writes into it."""
        marks = getattr(self.model, "sums_in_kernel", None)
        # A repeated layer's sum takes several additions a microbatch and
        # a kernel takes the sum once: XLA's add has it.
        if marks is None or st.mesh.size > 1 or li in self.repeated_layers:
            return None
        return marks(li, st.param_shardings[li])

    # ------------------------------------------------------------------ #

    @staticmethod
    def _as_batch_dict(batch) -> dict[str, np.ndarray]:
        """Accept legacy [num_mb, mb, seq] token arrays or batch dicts."""
        if isinstance(batch, dict):
            # Loader output is already host numpy; asarray is shape
            # normalization, not a device readback.
            # oobleck: allow[OBL002] -- host batch normalization
            return {k: np.asarray(v) for k, v in batch.items()}
        return {"input_ids": np.asarray(batch)}  # oobleck: allow[OBL002] -- host batch normalization

    def _place_batch(self, batch: dict[str, np.ndarray]):
        """Per-microbatch batch placement onto every stage that reads it
        (embed, loss head, and any model-declared mid-pipeline consumer
        like T5's bridge). Shared by train/eval. Remote stages place
        nothing (their owning process places its own copy — dataloaders are
        deterministic and advanced in lockstep on every process)."""
        M = next(iter(batch.values())).shape[0]
        per_stage: dict[int, list[dict] | None] = {}
        for st in self.stages:
            if not st.needs_batch or not st.is_local:
                per_stage[st.stage_index] = None
                continue
            per_stage[st.stage_index] = [
                {k: jax.device_put(batch[k][m], st.batch_sharding)
                 for k in batch}
                for m in range(M)
            ]
        return per_stage, M

    # -- multi-host participation --------------------------------------- #

    @property
    def participates_locally(self) -> bool:
        """Whether this process owns any stage of this pipeline."""
        return any(st.is_local for st in self.stages)

    def _edge_aval(self, src_last_layer: int):
        """Static aval of the activation flowing out of the chunk whose last
        layer is src_last_layer (gradients mirror it)."""
        if self._act_avals is None:
            from oobleck_tpu.parallel.cross_host import activation_avals

            self._act_avals = activation_avals(
                self.model, self.microbatch_size, self.seq_len
            )
        return self._act_avals[src_last_layer]

    def _move_edge(self, value, src: StageRuntime, dst: StageRuntime,
                   aval_layer: int):
        """Move an activation/gradient across a virtual-stage edge.
        Same-process: a device_put between sub-meshes (ICI path).
        Cross-process: a 2-process collective
        (parallel/cross_host.ProcessComm.send). Returns the value placed on
        dst's batch sharding, or None when this process does not own dst.
        aval_layer is the last layer of the chunk PRODUCING the value (the
        gradient for a chunk's input has the shape of the previous chunk's
        output)."""
        if src.is_local and dst.is_local:
            return jax.device_put(value, dst.batch_sharding)
        received = self.comm.send(
            value if src.is_local else None,
            src.process, dst.process, self._edge_aval(aval_layer),
        )
        if dst.is_local:
            return jax.device_put(received, dst.batch_sharding)
        return None

    def adopt_microbatches(self, new_num_microbatches: int) -> None:
        """Degraded-mode reroute: run this replica at a different per-step
        microbatch count from the next train_step on, WITHOUT recompiling.

        Safe because nothing compiled depends on the per-pipeline count:
        no stage program's trace reads it (`stage_program_key`) and
        total_num_microbatches is preserved by rerouting (the borrowed
        microbatches exist either way, so the 1/total gradient scale baked
        into the last stage's backward stays exact). train_step reads
        self.num_microbatches fresh each call and canonical_order caches
        per (S, M, v), so the next step simply interprets the longer
        stream."""
        validate_interleaving(self.num_stages, new_num_microbatches,
                              self.virtual_stages)
        self.num_microbatches = new_num_microbatches

    def train_step(self, batch, placed=None):
        """One iteration over this pipeline's microbatches.

        batch: {field: [num_microbatches, microbatch_size, ...]} (or a bare
        token array for causal LM). `placed` optionally carries the batch
        already staged on-device by a DeviceStager (the per-stage dict
        _place_batch returns), taking the device_put off the critical path.
        Fills self.grads (sum over microbatches, scaled by 1/total global
        microbatches) and returns the mean loss over this pipeline's
        microbatches as a device scalar. Leaves on self.load where the
        step routed: a local chunk's routed layers and the sum of their
        loads over the microbatches, still on the device, `((layers, int32
        [layers, held + 1]), ...)`, or None where no local chunk hands one
        out (`_load_layers`).
        """
        batch = self._as_batch_dict(batch)
        S, M = self.num_stages, self.num_microbatches
        v = self.virtual_stages
        last_vs = S * v - 1
        assert next(iter(batch.values())).shape[0] == M
        # Last step's gradients have been applied: free them before this
        # step's sums are allocated.
        self.grads = {}
        if placed is None:
            # No DeviceStager staged this batch ahead of time
            # (execution/dataloader.py) — place on the critical path.
            placed, _ = self._place_batch(batch)

        # All transient state keyed (stage, chunk, mb).
        acts: dict[tuple, Any] = {}    # chunk input activations
        gacts: dict[tuple, Any] = {}   # chunk output gradients
        stash: dict[tuple, Any] = {}   # forward input stash for bwd
        losses: list[Any] = []
        grads: dict[int, Any] = {}
        loads: dict[tuple[int, ...], list] = {}   # chunk layers -> a microbatch
        # Per-stage dispatch busy time this step, for the engine's measured
        # pipeline-bubble gauge, plus per-(stage, chunk, op) durations for
        # the schedule-replay simulation. Wall-clock around the fwd/bwd
        # dispatch: exact on CPU (synchronous), a dispatch-cost floor under
        # async device execution.
        stage_busy: dict[int, float] = {}
        op_times: dict[tuple[int, int, str], tuple[float, int]] = {}
        dispatch_stall = 0.0
        # FORWARD instructions of local stages: "run" dispatched the
        # chunk's forward program, "folded" left it to the backward's
        # value-and-gradient (the last virtual stage).
        fwd_dispatches = {"run": 0, "folded": 0}
        # Gradient sums of local chunks: started from a zero fill, added
        # to inside a backward program.
        accumulated = {"backward": 0, "zero_fill": 0, "moe_tgmm": 0}
        # A microbatch's visits of each local stage (its FORWARDs).
        stage_visits: dict[int, int] = {}

        def record_op(stage, chunk, kind, dt):
            tot, n = op_times.get((stage, chunk, kind), (0.0, 0))
            op_times[(stage, chunk, kind)] = (tot + dt, n + 1)
            # Comm kinds ("cf"/"cb": cross-stage activation/grad transfers)
            # are the overlappable component — they do not occupy the stage's
            # compute, so they stay out of the bubble gauge's busy time and
            # feed the planner's effective_comm projection separately.
            if kind in ("f", "b"):
                stage_busy[stage] = stage_busy.get(stage, 0.0) + dt

        def chunk_params(st, c):
            return tuple(self.params[li] for li in st.chunks[c])

        # Same-process cross-stage transfers are batched: consecutive SEND
        # instructions in the canonical order accumulate here and flush as
        # ONE jax.device_put(list, list) right before the next compute
        # dispatch needs them — one transfer program per tick instead of a
        # put per edge (the DataParallelEngine's pack trick, applied to the
        # pipeline hot path). The device_put itself is async; nothing
        # blocks on transfer completion.
        pending_sends: list[tuple[Any, Any, dict, tuple]] = []

        def flush_sends():
            nonlocal dispatch_stall
            if not pending_sends:
                return
            t0 = time.perf_counter()
            with spans.region("pipeline.flush_sends"):
                moved = jax.device_put(
                    [p[0] for p in pending_sends],
                    [p[1] for p in pending_sends],
                )
            for (_, _, store, key), mv in zip(pending_sends, moved):
                store[key] = mv
            pending_sends.clear()
            dispatch_stall += time.perf_counter() - t0

        def execute(ins: Instruction) -> None:
            st = self.stages[ins.stage]
            m, c = ins.microbatch, ins.chunk
            key = (ins.stage, c, m)
            vs = c * S + ins.stage
            is_first = vs == 0
            is_last = vs == last_vs
            stage_batch = placed[ins.stage]
            if ins.op in (Op.LOAD_MICROBATCH, Op.RECV_ACTIVATION,
                          Op.RECV_GRAD):
                pass  # inputs materialize at FORWARD / BACKWARD
            elif ins.op == Op.FORWARD:
                if not st.is_local:
                    return
                flush_sends()
                stage_visits[ins.stage] = stage_visits.get(ins.stage, 0) + 1
                if not st.chunks[c]:
                    stash[(ins.stage, c, m, "out")] = acts.pop(key)
                    return
                x = None if is_first else acts[key]
                stash[key] = x
                fwd_dispatches["folded" if is_last else "run"] += 1
                if is_last:
                    # Nothing but the loss would leave this forward, and
                    # BACKWARD's program returns it: dispatch nothing. The
                    # "f" entry stays (0 s) for last_op_times' readers.
                    record_op(ins.stage, c, "f", 0.0)
                    return
                mb = stage_batch[m] if stage_batch is not None else None
                if self.sync_op_timing and x is not None:
                    # oobleck: allow[OBL002] -- opt-in per-op profiling mode
                    jax.block_until_ready(x)  # exclude upstream wait
                t0 = time.perf_counter()
                out = st.fwd[c](chunk_params(st, c), x, mb)
                if self.sync_op_timing:
                    # oobleck: allow[OBL002] -- opt-in per-op profiling mode
                    jax.block_until_ready(out)
                record_op(ins.stage, c, "f", time.perf_counter() - t0)
                stash[(ins.stage, c, m, "out")] = out
            elif ins.op == Op.SEND_ACTIVATION:
                ds, dc = send_activation_dest(ins.stage, c, S)
                nxt = self.stages[ds]
                if not (st.is_local or nxt.is_local):
                    return
                y = stash.pop((ins.stage, c, m, "out"), None)
                aval_layer = self._edge_layer(ins.stage, c)
                if st.is_local and nxt.is_local:
                    if self.sync_op_timing and y is not None:
                        # Timed mode sends eagerly (no batching) so each
                        # edge's transfer cost is attributed to its own
                        # (stage, chunk) as comm kind "cf".
                        t0 = time.perf_counter()
                        moved = jax.device_put(y, nxt.batch_sharding)
                        # oobleck: allow[OBL002] -- opt-in per-op profiling mode
                        jax.block_until_ready(moved)
                        record_op(ins.stage, c, "cf",
                                  time.perf_counter() - t0)
                        acts[(ds, dc, m)] = moved
                        return
                    pending_sends.append(
                        (y, nxt.batch_sharding, acts, (ds, dc, m)))
                    return
                t0 = time.perf_counter()
                moved = self._move_edge(y, st, nxt, aval_layer=aval_layer)
                if moved is not None:
                    if self.sync_op_timing:
                        # oobleck: allow[OBL002] -- opt-in per-op profiling mode
                        jax.block_until_ready(moved)
                        record_op(ins.stage, c, "cf",
                                  time.perf_counter() - t0)
                    acts[(ds, dc, m)] = moved
            elif ins.op == Op.BACKWARD:
                if not st.is_local:
                    return
                flush_sends()
                if not st.chunks[c]:
                    stash[(ins.stage, c, m, "dx")] = gacts.pop(key)
                    return
                x = stash.pop(key)
                mb = stage_batch[m] if stage_batch is not None else None
                if self.sync_op_timing:
                    dy_wait = gacts.get(key)
                    if dy_wait is not None:
                        # oobleck: allow[OBL002] -- opt-in per-op profiling mode
                        jax.block_until_ready(dy_wait)
                # The chunk's running sum goes in donated and comes back
                # with this microbatch's gradients added; the step's first
                # BACKWARD of a chunk starts it from zeros, so every
                # microbatch runs the same program. The sums are keyed by
                # LAYER: a looped model's visits name the same layers and
                # add to the same sums.
                chunk_layers, params = st.chunks[c], chunk_params(st, c)
                fresh = tuple(li for li in chunk_layers if li not in grads)
                if len(fresh) == len(chunk_layers):
                    acc = st.zero[c](params)
                    accumulated["zero_fill"] += 1
                else:
                    if fresh:
                        grads.update(zip(fresh, self._zeros_for(st, fresh)))
                        accumulated["zero_fill"] += 1
                    acc = tuple(grads.pop(li) for li in chunk_layers)
                t0 = time.perf_counter()
                if is_last:
                    loss, acc, dx, *load = st.bwd[c](params, acc, x, mb)
                    losses.append(loss)
                else:
                    dy = gacts.pop(key)
                    acc, dx, *load = st.bwd[c](params, acc, x, mb, dy)
                if load:
                    loads.setdefault(st.load_layers[c], []).extend(load)
                if self.sync_op_timing:
                    # oobleck: allow[OBL002] -- opt-in per-op profiling mode
                    jax.block_until_ready(acc)
                record_op(ins.stage, c, "b", time.perf_counter() - t0)
                grads.update(zip(chunk_layers, acc))
                accumulated["backward"] += 1
                accumulated["moe_tgmm"] += st.kernel_sums[c]
                if dx is not None:
                    stash[(ins.stage, c, m, "dx")] = dx
                acts.pop(key, None)
            elif ins.op == Op.SEND_GRAD:
                ds, dc = send_grad_dest(ins.stage, c, S)
                prev = self.stages[ds]
                if not (st.is_local or prev.is_local):
                    return
                dx = stash.pop((ins.stage, c, m, "dx"), None)
                # The gradient entering chunk (ins.stage, c) has the shape
                # of the PRODUCING chunk's output activation.
                aval_layer = self._edge_layer(ds, dc)
                if st.is_local and prev.is_local:
                    if self.sync_op_timing and dx is not None:
                        # oobleck: allow[OBL002] -- opt-in per-op profiling mode
                        jax.block_until_ready(dx)  # exclude bwd compute
                        t0 = time.perf_counter()
                        moved = jax.device_put(dx, prev.batch_sharding)
                        # oobleck: allow[OBL002] -- opt-in per-op profiling mode
                        jax.block_until_ready(moved)
                        record_op(ins.stage, c, "cb",
                                  time.perf_counter() - t0)
                        gacts[(ds, dc, m)] = moved
                        return
                    pending_sends.append(
                        (dx, prev.batch_sharding, gacts, (ds, dc, m)))
                    return
                t0 = time.perf_counter()
                moved = self._move_edge(dx, st, prev, aval_layer=aval_layer)
                if moved is not None:
                    if self.sync_op_timing:
                        # oobleck: allow[OBL002] -- opt-in per-op profiling mode
                        jax.block_until_ready(moved)
                        record_op(ins.stage, c, "cb",
                                  time.perf_counter() - t0)
                    gacts[(ds, dc, m)] = moved

        # Execute the canonical total order (identical on every process;
        # dependency-valid by construction — see canonical_order).
        with spans.region("pipeline.dispatch"):
            for ins in canonical_order(S, M, v):
                execute(ins)
            flush_sends()
            # One small program a routed chunk: its M loads as one sum.
            self.load = tuple(
                (layers, load_sum(tuple(per_mb)) if len(per_mb) > 1
                 else per_mb[0])
                for layers, per_mb in loads.items()) or None

        self.grads = grads
        self.last_stage_busy_s = stage_busy
        self.last_op_times = op_times
        self.last_dispatch_stall_s = dispatch_stall
        dispatches = metrics.registry().counter(
            "oobleck_pipeline_forward_dispatches_total",
            "FORWARD instructions of local pipeline stages: run as a "
            "program, or folded into the last stage's backward")
        for mode, n in fwd_dispatches.items():
            if n:
                dispatches.inc(n, mode=mode)
        accumulations = metrics.registry().counter(
            "oobleck_pipeline_grad_accumulations_total",
            "Microbatch gradient sums of local pipeline chunks: added to "
            "inside a backward program, or started from a zero fill; "
            "moe_tgmm: leaves a backward summed inside that kernel")
        for where, n in accumulated.items():
            if n:
                accumulations.inc(n, where=where)
        visited = metrics.registry().counter(
            "oobleck_pipeline_stage_visits_total",
            "Visits of a local pipeline stage by a microbatch (FORWARD "
            "instructions dispatched to it): one a microbatch, `num_passes` "
            "where a looped model's range is cut across stages")
        for stage, n in stage_visits.items():
            visited.inc(n, stage=str(stage))
        if not losses:
            return None  # last stage lives on another process
        loss = sum(losses[1:], start=losses[0]) / len(losses)
        return loss

    # ------------------------------------------------------------------ #

    def eval_step(self, batch):
        """Forward-only loss over this pipeline's microbatches (no backward
        instructions, no gradient memory); returns the mean loss."""
        batch = self._as_batch_dict(batch)
        S, v = self.num_stages, self.virtual_stages
        last_vs = S * v - 1
        placed, M = self._place_batch(batch)
        losses = []
        correct = count = None
        for m in range(M):
            x = None
            for vs in range(S * v):
                st = self.stages[vs % S]
                c = vs // S
                is_last = vs == last_vs
                out = None
                if not st.chunks[c]:
                    out = x
                elif st.is_local:
                    stage_batch = placed[st.stage_index]
                    mb = stage_batch[m] if stage_batch is not None else None
                    params = tuple(self.params[li] for li in st.chunks[c])
                    if is_last and st.efwd[c] is not None:
                        loss, cc, nn = st.efwd[c](params, x, mb)
                        correct = cc if correct is None else correct + cc
                        count = nn if count is None else count + nn
                        out = loss
                    else:
                        out = st.fwd[c](params, x, mb)
                if is_last:
                    if st.is_local:
                        losses.append(out)
                else:
                    nxt = self.stages[(vs + 1) % S]
                    if st.is_local or nxt.is_local:
                        x = self._move_edge(
                            out, st, nxt,
                            aval_layer=self._edge_layer(st.stage_index, c))
                    else:
                        x = None
        self.last_eval_metrics = (
            None if count is None
            # oobleck: allow[OBL002] -- eval step, off the train loop
            else (float(correct), float(count))
        )
        if not losses:
            return None  # last stage lives on another process
        return sum(losses[1:], start=losses[0]) / len(losses)

    def apply_updates(self, optimizer, opt_state: dict[int, Any],
                      synced_grads: dict[int, Any]) -> dict[int, Any]:
        """Per-layer optimizer step with (possibly DP-synced) grads.
        CONSUMES `opt_state`: each layer's new state is written into the
        dict that was given, and that dict is returned.

        The update runs as ONE jitted program per layer signature (jax.jit
        specializes per input shapes/shardings internally). Eager optax is
        catastrophic on multi-chip stages: global-norm clipping dispatches
        one tiny program PER LEAF over sharded arrays — on a 2-chip
        expert-sharded MoE stage under jax.distributed that turned a step
        into minutes of collective-compile churn (the round-5 elastic-MoE
        recovery hang).

        The layers go in `update_order`, largest first, and the step keeps
        nothing it has replaced: once layer k's update is enqueued, neither
        `self.params` nor `opt_state` refers to its old arrays. A layer's
        outputs are its new weights and AdamW's two moments, so the largest
        layer (a vocabulary's head, an expert stack) asks for three blocks
        of its own size. Asked for first, they come out of what `jit_bwd`'s
        temporaries just gave back, and a step's last allocations are a
        block's small leaves. Asked for last, with every old moment still
        held, they met memory the small leaves had cut up, and the runtime
        defragmented with the device idle (`ouro-2.6b.steady`, PERF.md
        section 6, PR 66). The layers' updates share nothing, so any order
        gives the same bits.

        No donation: `jax.device_put(x, x's own device)` returns a new Array
        over THE SAME buffer, and donating `x` deletes both. The benchmark's
        `hold` (`benchmarks/runners/train_hostloss.py:457`) keeps moments
        that way, and the live mirror (`engine._write_mirror`) holds the
        pre-step arrays themselves. Whoever else holds an old array keeps it
        alive by holding it; the step only stops being one of the holders."""
        fn = optimizer_update_program(optimizer)
        for li in self.update_order:
            self.params[li], opt_state[li] = fn(
                synced_grads[li], opt_state[li], self.params[li])
        return opt_state

    def init_opt_state(self, optimizer) -> dict[int, Any]:
        return {li: optimizer.init(p) for li, p in self.params.items()}
