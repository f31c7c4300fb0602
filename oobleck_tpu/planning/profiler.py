"""Per-layer profiler: the planner's input.

Capability match for the reference profiler
(/root/reference/oobleck/planning/profiler.py:241-323), TPU-native:

  * forward latency: each layer jitted and timed on the local device, the
    clock stopped by a host readback of the result;
  * backward latency: the layer's VJP jitted and timed the same way —
    *measured*, not the reference's 3x-forward estimate (profiler.py:104);
  * memory: exact parameter bytes + activation output bytes from abstract
    evaluation (no allocation);
  * collective latencies (allreduce within a host / across hosts): measured
    with a real psum when multiple devices are visible, otherwise an
    ICI/DCN bandwidth-latency model — one chip cannot measure multi-chip
    collectives.

Results are cached as JSON with the reference's file layout
(profiler.py:255-257, 290-319): {cache}/{model}-{tag}/mb{N}.json,
allreduce_in_node.json, allreduce_across_nodes.json, model_args.json,
so the planner is fully decoupled from profiling.
"""

from __future__ import annotations

import json
import math
import os
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from oobleck_tpu.config import training_seq_len
from oobleck_tpu.models.base import param_bytes, passes_of
from oobleck_tpu.planning.templates import LayerProfile

# What a cached profile was measured WITH, beside the model and the tag: a
# change to the timed programs raises it, and a cache written under another
# number (or, before there was one, under none) is measured again. 2: a
# layer's parameters are arguments of the timed program (PR 35); closed over,
# as constants, the compiler could fold casts into them, so the layer times
# the planner reads may differ from a version-1 cache's.
PROFILE_VERSION = 2

WARMUP = 2
ITERS = 3  # matches reference profiler.py:18-19
# In-graph repetitions per timed call. A dispatch plus readback is not free
# next to one layer: on a TPU v5e a trivial program costs 1.7 ms round trip
# where one gpt2 124M block forward (microbatch 8, seq 1024) takes 4.1 ms as
# a single timed call and 3.3 ms per iteration inside the scan (chip run,
# PR 21). So each timed call scans the layer REPS times on-device and the
# overhead (measured with a trivial program) is subtracted before dividing.
REPS = 16

# Bandwidth-latency model constants for unmeasurable collectives.
# ICI (intra-host, chip-to-chip): ~1e11 B/s effective allreduce bandwidth,
# ~10us base latency per hop; DCN (cross-host): ~2.5e10 B/s, ~50us base.
ICI_BW = 1.0e11
ICI_LAT_MS = 0.01
DCN_BW = 2.5e10
DCN_LAT_MS = 0.05


def default_cache_dir() -> Path:
    return Path(
        os.environ.get("OOBLECK_TPU_CACHE", "/tmp/oobleck_tpu")
    ) / "profiles"


def get_profile_path(model_name: str, model_tag: str) -> Path:
    return default_cache_dir() / f"{model_name}-{model_tag}"


def _sync(x) -> float:
    """Force completion; returns a value to defeat DCE."""
    return float(jnp.sum(jax.tree.leaves(x)[0].ravel()[0]))


def _time_call(fn, *args) -> float:
    """Median wall-time of fn(*args) in ms with warmup + readback sync."""
    for _ in range(WARMUP):
        _sync(fn(*args))
    times = []
    for _ in range(ITERS):
        t0 = time.perf_counter()
        _sync(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    times.sort()
    return times[len(times) // 2]


_overhead_cache: list[float] = []


def _dispatch_overhead_ms() -> float:
    """Round-trip cost of a trivial dispatch+readback."""
    if not _overhead_cache:
        f = jax.jit(lambda x: x + 1.0)
        _overhead_cache.append(_time_call(f, jnp.float32(0.0)))
    return _overhead_cache[0]


def _time_repeated(fn_once, x0, *fixed, reps: int = REPS) -> float:
    """Time `fn_once(x, *fixed)` by scanning it `reps` times inside one
    jit call.

    Each iteration's input is data-perturbed by 0 derived from the previous
    output, forcing a sequential chain XLA cannot hoist or CSE (a float*0 is
    not folded). Returns per-iteration ms with dispatch overhead removed.
    `fixed` (a layer's parameters, its input) are ARGUMENTS of the timed
    program, as they are of a stage program: closed over they are constants
    of hundreds of megabytes, which the compiler takes minutes to embed and
    may fold a weight's cast into (698 s of `moonlight-16b-a3b.steady`'s
    cold start for four kinds of layer, my chip run, PR 35).
    """
    def perturb(x, leaf):
        zero = leaf * 0.0
        return jax.tree.map(
            lambda v: v + zero.astype(v.dtype), x
        )

    def run(x, *fixed):
        def body(carry, _):
            x, acc = carry
            out = fn_once(x, *fixed)
            leaf = jax.tree.leaves(out)[0].ravel()[0].astype(jnp.float32)
            return (perturb(x, leaf), acc + leaf), None

        (_, acc), _ = jax.lax.scan(body, (x, jnp.float32(0.0)), None, length=reps)
        return acc

    total = _time_call(jax.jit(run), x0, *fixed)
    return max((total - _dispatch_overhead_ms()) / reps, 1e-4)


def allreduce_time_model(nbytes: int, n: int, *, cross_host: bool) -> float:
    """Ring-allreduce time estimate in ms for n participants."""
    if n <= 1:
        return 0.0
    bw, lat = (DCN_BW, DCN_LAT_MS) if cross_host else (ICI_BW, ICI_LAT_MS)
    volume = 2 * (n - 1) / n * nbytes
    return lat * math.ceil(math.log2(n)) + volume / bw * 1e3


def _measure_allreduce(nbytes: int, devices: list) -> float:
    """Measured psum across `devices` in ms (when hardware is available)."""
    n = len(devices)
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(devices, ("x",))
    # Round up to a multiple of n: P("x") requires dim 0 divisible by the
    # mesh size (layer param counts are arbitrary, e.g. t5-tiny's 778).
    elems = -(-max(nbytes // 4, n) // n) * n
    arr = jnp.ones((elems,), jnp.float32)
    arr = jax.device_put(arr, NamedSharding(mesh, P("x")))

    def psum_fn(a):
        return jax.shard_map(
            lambda v: jax.lax.psum(v, "x"), mesh=mesh,
            in_specs=P("x"), out_specs=P(None), axis_names={"x"},
        )(a)

    fn = jax.jit(psum_fn)
    return _time_call(fn, arr)


def profile_execution_layers(model, microbatch_size: int, seq_len: int | None = None
                             ) -> list[dict]:
    """Time each pipeline layer's forward and backward on the local device.

    Returns the reference's mb{N}.json rows: {forward, backward,
    mem_required: [param_bytes, activation_bytes]} per layer
    (cf. reference profile_execution_layers, profiler.py:41-123).

    A layer the model repeats (`models/base.repeated`) is timed ONCE and
    charged what a microbatch costs: its forward, its backward and the
    bytes it hands on (under remat a visit saves its input, so R visits
    save R) times its passes, its parameters once; `"passes"` says how
    often, for a reader that wants one application's.
    """
    seq_len = training_seq_len(model.config, seq_len)
    rng = jax.random.PRNGKey(0)
    batch = model.sample_batch(microbatch_size, seq_len)
    results = []
    last_layer = model.num_pipeline_layers - 1
    # Layers whose name shares a numbered prefix (block_i, enc_i, dec_i) are
    # structurally identical by construction: measure the first of each
    # prefix and reuse (the reference times every fx-split layer because its
    # shards can differ).
    proto_rows: dict[str, dict] = {}
    carry_t = None  # previous layer's output shape tree (eval_shape)

    def _ones_like_tree(shapes):
        return jax.tree.map(lambda s: jnp.ones(s.shape, s.dtype), shapes)

    def charged(row: dict, idx: int) -> dict:
        passes = passes_of(model, idx)
        if passes == 1:
            return dict(row)
        params, saved = row["mem_required"]
        return {"forward": row["forward"] * passes,
                "backward": row["backward"] * passes,
                "mem_required": [params, saved * passes], "passes": passes}

    for idx in range(model.num_pipeline_layers):
        name = model.layer_name(idx)
        prefix = name.rsplit("_", 1)[0] if "_" in name else None

        params = model.init_layer(rng, idx)

        # Uniform layer signature: x is the layer's input (the batch for the
        # embed layer, activations otherwise) so the repeated-scan timer can
        # chain it. `batch` rides along for mid-pipeline consumers (T5's
        # bridge reads decoder_input_ids).
        if idx == 0:
            def fwd(x, p):
                return model.apply_layer(0, p, None, x)
            x0 = batch
        else:
            def fwd(x, p, i=idx):
                return model.apply_layer(i, p, x, batch)
            x0 = _ones_like_tree(carry_t)

        out_t = jax.eval_shape(fwd, x0, params)
        reused = proto_rows.get(prefix) if prefix else None
        if reused is not None:
            results.append(charged(reused, idx))
            carry_t = out_t
            continue
        pbytes = param_bytes(params)
        fwd_ms = _time_repeated(fwd, x0, params)
        ct0 = _ones_like_tree(out_t)

        if idx == 0:
            # Embed backward: VJP wrt params only (int inputs give no
            # activation cotangent to chain on) — measured, not the
            # reference's 3x-forward estimate (profiler.py:41-123) nor the
            # earlier 2x guess here.
            def bwd(ct, p):
                _, vjp = jax.vjp(
                    lambda p_: model.apply_layer(0, p_, None, batch), p
                )
                return vjp(ct)
            bwd_fixed = (params,)
        else:
            # VJP wrt (activations, params) — both cotangent paths, like the
            # real backward. jax.vjp re-runs the forward inside, so this cost
            # includes recompute, as execution under a layer's checkpoint
            # does; the flash forward kernel runs once here and once there
            # (its O and LSE are kept: ops/remat.checkpoint_layer).
            def bwd(ct, x, p, i=idx):
                _, vjp = jax.vjp(
                    lambda x_, p_: model.apply_layer(i, p_, x_, batch), x, p
                )
                return vjp(ct)
            bwd_fixed = (x0, params)

        bwd_ms = _time_repeated(bwd, ct0, *bwd_fixed)

        act_bytes = sum(
            math.prod(s.shape) * s.dtype.itemsize
            for s in jax.tree.leaves(out_t)
        )
        row = {
            "forward": fwd_ms,
            "backward": bwd_ms,
            "mem_required": [int(pbytes), int(act_bytes)],
        }
        if prefix:
            proto_rows[prefix] = row
        results.append(charged(row, idx))
        carry_t = out_t
    return results


def profile_allreduce_in_node(model, chips_per_host: int) -> list[dict]:
    """Per-layer allreduce time for 1,2,4.. chips within a host (ICI).

    Measured when the chips are actually visible, modeled otherwise
    (cf. reference profile_allreduce_in_node, profiler.py:187-234).
    LOCAL devices only — in a live jax.distributed world, jax.devices()
    includes other hosts' chips, and an "in-node" mesh spanning processes
    is both semantically wrong and a deadlock (profiling is per-process,
    not lockstep; the peer never joins the collective).
    """
    devices = jax.local_devices()
    rng = jax.random.PRNGKey(0)
    rows = []
    for idx in range(model.num_pipeline_layers):
        pbytes = param_bytes(model.init_layer(rng, idx))
        row = {}
        n = 1
        while n <= chips_per_host:
            if n == 1:
                row["1"] = 0.0
            elif len(devices) >= n:
                row[str(n)] = _measure_allreduce(pbytes, devices[:n])
            else:
                row[str(n)] = allreduce_time_model(pbytes, n, cross_host=False)
            n *= 2
        rows.append(row)
    return rows


def profile_allreduce_across_nodes(model, max_hosts: int) -> list[dict]:
    """Per-layer allreduce time across 1..max_hosts hosts (DCN model;
    cf. reference profiler.py:141-185). Offline fallback — in a live
    multi-host world the engine replaces these rows with MEASURED psums
    over real process meshes (measure_allreduce_across_processes)."""
    rng = jax.random.PRNGKey(0)
    rows = []
    for idx in range(model.num_pipeline_layers):
        pbytes = param_bytes(model.init_layer(rng, idx))
        row = {"1": 0.0}
        for n in range(2, max_hosts + 1):
            row[str(n)] = allreduce_time_model(pbytes, n, cross_host=True)
        rows.append(row)
    return rows


def measure_allreduce_across_processes(comm, sizes_bytes: list[int],
                                       iters: int = ITERS
                                       ) -> dict[tuple[int, int], float]:
    """MEASURED cross-host allreduce profile over a live jax.distributed
    world: for each distinct byte size and each process-subset prefix
    {0..n-1} (n = 2..P), time a real psum over the process mesh the DP
    engine itself uses. The reference measures torch.distributed allreduce
    across 1..N node groups and feeds the planner
    (/root/reference/oobleck/planning/profiler.py:141-234); these are the
    TPU/DCN equivalents, riding the same ProcessComm process-mesh
    collectives as training.

    COLLECTIVE: every process of `comm` must call with identical
    `sizes_bytes` (processes >= n skip group n in lockstep — the same
    total-order discipline the DP engine uses). Returns {(nbytes, n): ms}
    complete only on processes < 2 (process 0 broadcasts its table via
    _broadcast-style psum at the call site)."""
    import numpy as np

    P = comm.process_count
    me = comm.process_index
    table: dict[tuple[int, int], float] = {}
    for nbytes in sorted(set(sizes_bytes)):
        length = max(int(nbytes) // 4, 1)
        for n in range(2, P + 1):
            participants = tuple(range(n))
            if me >= n:
                continue
            vec = np.zeros(length, np.float32)
            # Warmup compiles the mesh program; then time synced rounds.
            np.asarray(comm.group_sum_device(vec, length, participants))
            t0 = time.perf_counter()
            for _ in range(iters):
                np.asarray(
                    comm.group_sum_device(vec, length, participants)
                )
            table[(int(nbytes), n)] = (
                (time.perf_counter() - t0) / iters * 1e3
            )
    return table


def effective_tag(model_tag: str, execution=None) -> str:
    """Profile cache tag incorporating the execution knobs that change layer
    timing and memory (precision / remat / attention_impl): a bf16 profile
    must never be mistaken for an f32 one when planning memory bounds."""
    if execution is None:
        return model_tag
    parts = [model_tag]
    if getattr(execution, "precision", "bfloat16") != "bfloat16":
        parts.append(execution.precision)
    if not getattr(execution, "remat", True):
        parts.append("noremat")
    impl = getattr(execution, "attention_impl", "auto")
    if impl != "auto":
        parts.append(impl)
    return "+".join(parts)


def job_tag(model_tag: str, seq_len: int | None) -> str:
    """The model tag of a job that states its sequence length
    (`job.seq_len`): two lengths never share a profile. A job that states
    none keeps the tag it always had."""
    return model_tag if seq_len is None else f"{model_tag}+seq{seq_len}"


def profile(model_name: str, model_args: dict, *, model_tag: str = "default",
            microbatch_size: int = 1, seq_len: int | None = None,
            chips_per_host: int = 4, max_hosts: int = 32,
            force: bool = False, execution=None) -> Path:
    """Run all profiles and write the JSON cache; returns the cache dir.

    `execution` (ExecutionArguments, duck-typed) must match what the engine
    trains with: it changes the measured model (dtype/remat/attention) AND
    the cache tag (pass the same object to effective_tag for loading).
    `seq_len` is the length measured at (None: `training_seq_len`'s
    default); a job that states its own passes a `model_tag` that names it
    (`job_tag`).

    File layout matches the reference (profiler.py:290-319) so the planner's
    loader is schema-compatible.
    """
    from oobleck_tpu.models import build_model

    path = get_profile_path(model_name, effective_tag(model_tag, execution))
    files = [f"mb{microbatch_size}.json", "allreduce_in_node.json",
             "allreduce_across_nodes.json", "model_args.json"]
    version = path / "profile_version.json"
    fresh = (version.exists()
             and json.loads(version.read_text()) == PROFILE_VERSION)
    if fresh and all((path / f).exists() for f in files) and not force:
        # Cache hit requires ALL files: a killed run may have written some.
        validate_model_args(path, model_args)
        return path
    path.mkdir(parents=True, exist_ok=True)
    if not fresh:
        # Another microbatch size's rows are of the old version too.
        for stale in path.glob("mb*.json"):
            stale.unlink()
    model = build_model(model_name, model_args, execution=execution)

    contents = {
        f"mb{microbatch_size}.json":
            json.dumps(profile_execution_layers(model, microbatch_size, seq_len)),
        "allreduce_in_node.json":
            json.dumps(profile_allreduce_in_node(model, chips_per_host)),
        "allreduce_across_nodes.json":
            json.dumps(profile_allreduce_across_nodes(model, max_hosts)),
        "model_args.json": json.dumps(model_args),
        "profile_version.json": json.dumps(PROFILE_VERSION),
    }
    # Atomic publish: write temps, then rename — a crash mid-profile never
    # leaves a partial cache that later runs mistake for a hit.
    for fname, text in contents.items():
        tmp = path / (fname + ".tmp")
        tmp.write_text(text)
    for fname in contents:
        (path / (fname + ".tmp")).rename(path / fname)
    return path


def validate_model_args(path: Path, model_args: dict) -> None:
    """Cached profile must match the requested model shape
    (cf. reference validate_model_args, profiler.py:326-340)."""
    f = path / "model_args.json"
    if not f.exists():
        return
    cached = json.loads(f.read_text())
    if cached != model_args:
        raise ValueError(
            f"cached profile at {path} was made with model_args={cached}, "
            f"requested {model_args}; use force=True to re-profile"
        )


def load_profile(model_name: str, model_tag: str, microbatch_size: int
                 ) -> list[LayerProfile]:
    """Load the JSON cache into LayerProfiles (reference get_profile_results,
    pipeline_template.cpp:29-80)."""
    path = get_profile_path(model_name, model_tag)
    mb = json.loads((path / f"mb{microbatch_size}.json").read_text())
    ar_in = json.loads((path / "allreduce_in_node.json").read_text())
    ar_across = json.loads((path / "allreduce_across_nodes.json").read_text())
    profiles = []
    for i, row in enumerate(mb):
        profiles.append(LayerProfile(
            layer_index=i,
            forward=row["forward"],
            backward=row["backward"],
            # Non-numeric keys are annotations (e.g. "measured": true on
            # live-world rows), not host counts.
            allreduce_in_host={int(k): v for k, v in ar_in[i].items()
                               if str(k).isdigit()},
            allreduce_across_hosts={int(k): v for k, v in ar_across[i].items()
                                    if str(k).isdigit()},
            mem_params=row["mem_required"][0],
            mem_activation=row["mem_required"][1],
        ))
    return profiles
