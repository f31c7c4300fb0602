"""Cross-process device collectives for the multi-host MPMD path.

The reference syncs heterogeneous pipelines across nodes with NCCL process
groups (/root/reference/oobleck/execution/engine.py:363-412, per-(layer,
shard) allreduce; pipeline.py:582-617, node-spanning p2p). The TPU-native
equivalent here: every worker joins ONE jax.distributed world, and all
cross-host data-plane traffic rides XLA collectives compiled over small
"process meshes" — one device per participating process — so on real
hardware the bytes move over ICI/DCN, never through the control plane
(which the round-3 GRAD_SYNC TCP relay violated; deleted in favor of this).

Three primitives, all built on the same mechanism
(`jax.make_array_from_single_device_arrays` over a process mesh + a jitted
reduction with replicated out_sharding):

  * `group_sum`:   sum of per-process f32 vectors over any process subset —
                   the grand DP gradient allreduce (all processes) and
                   point-to-point activation transfer (2 processes, receiver
                   contributes zeros) are both this;
  * `group_min`:   element-wise min — used as a "lowest owner" election for
                   layer-state recovery (each process votes its process
                   index where it holds a layer, +inf elsewhere);
  * flat pack/unpack helpers with a deterministic per-layer layout shared by
    every process (layouts derive from model avals, so no metadata protocol
    is needed — shapes are static, as everywhere else on TPU).

Every participating process MUST call the same primitive with the same
(participants, length) in the same relative order; the engine guarantees
this by having every process interpret the same global schedule.
"""

from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from oobleck_tpu.execution.pipeline import PROGRAMS


class ProcessComm:
    """Collectives over jax.distributed processes (cached meshes; the
    jitted programs are in the process's `PROGRAMS`, by the process mesh
    or the flat layout each bakes in)."""

    def __init__(self):
        self._mesh_cache: dict[tuple[int, ...], Mesh] = {}
        self._local_device = jax.local_devices()[0]
        self.process_index = jax.process_index()
        self.process_count = jax.process_count()
        # Observability: bytes THIS process contributed to cross-process
        # collectives (its row of each reduce; single-participant calls are
        # local and count zero). The DP engine snapshots deltas per step so
        # tests can assert the wire carries only what DP actually requires.
        self.wire_bytes = 0

    # -- process meshes ------------------------------------------------- #

    def _mesh(self, participants: tuple[int, ...]) -> Mesh:
        if participants not in self._mesh_cache:
            devs = jax.devices()
            picked = [
                min((d for d in devs if d.process_index == p),
                    key=lambda d: d.id)
                for p in participants
            ]
            self._mesh_cache[participants] = Mesh(np.array(picked), ("proc",))
        return self._mesh_cache[participants]

    def _reduce_device(self, local_vec, length: int,
                       participants: Sequence[int], op: str,
                       dtype=jnp.float32):
        """Shared machinery: stack per-process rows, reduce over `proc`.
        Accepts a host OR device vector (cast to `dtype` — the WIRE dtype:
        bf16 edges ride as bf16, f32 grads as f32); returns the reduced
        vector as a DEVICE array on this process's local device (no host
        round-trip on the receive side)."""
        participants = tuple(sorted(participants))
        assert self.process_index in participants, (
            f"process {self.process_index} is not in {participants}"
        )
        if len(participants) == 1:
            return jax.device_put(
                jnp.asarray(local_vec, dtype), self._local_device
            )
        self.wire_bytes += length * np.dtype(dtype).itemsize
        mesh = self._mesh(participants)
        n = len(participants)
        sharding = NamedSharding(mesh, P("proc"))
        row = jax.device_put(
            jnp.asarray(local_vec, dtype)[None, :], self._local_device
        )
        garr = jax.make_array_from_single_device_arrays(
            (n, length), sharding, [row]
        )
        key = ("reduce", mesh, op)
        if key not in PROGRAMS:
            fn = {"sum": lambda a: a.sum(0), "min": lambda a: a.min(0)}[op]
            PROGRAMS[key] = jax.jit(
                fn, out_shardings=NamedSharding(mesh, P())
            )
        out = PROGRAMS[key](garr)
        return out.addressable_data(0)

    # -- public primitives ---------------------------------------------- #

    def group_sum(self, local_vec, length: int,
                  participants: Sequence[int],
                  dtype=jnp.float32) -> np.ndarray:
        """Element-wise sum of each participant's vector (all get it).
        `dtype` is the wire dtype — int32 lanes keep integer meta (step
        counts, byte counts) exact where f32 would round past 2**24."""
        return np.asarray(
            self._reduce_device(local_vec, length, participants, "sum",
                                dtype)
        )

    def group_min(self, local_vec, length: int,
                  participants: Sequence[int]) -> np.ndarray:
        return np.asarray(
            self._reduce_device(local_vec, length, participants, "min")
        )

    def group_sum_device(self, local_vec, length: int,
                         participants: Sequence[int], dtype=jnp.float32):
        """group_sum whose input AND output stay device arrays on this
        process's local device — the hot-path form (per-step gradient
        allreduce) with no host staging on either side. `dtype` is the
        wire dtype (native grad/activation width, not forced f32)."""
        return self._reduce_device(local_vec, length, participants, "sum",
                                   dtype)

    @property
    def local_device_sharding(self):
        return jax.sharding.SingleDeviceSharding(self._local_device)

    def send(self, value, src: int, dst: int, aval):
        """Point-to-point: move the pytree `value` (on src) to dst; returns
        it on dst (leaves on this process's local device), None on src.
        Compiles to a 2-process collective — the multi-host analog of the
        reference's stage-to-stage NCCL p2p (pipeline.py:288-333). `aval`
        is the static pytree of ShapeDtypeStructs (tuple carries — T5
        bridge, CLIP towers — flatten like any pytree); pack/unpack run on
        device, so the bytes never stage through host numpy. The wire
        carries NATIVE dtypes (one flat vector per distinct leaf dtype):
        bf16 activations cost bf16 bytes, and the receiver's zero
        contribution keeps the sum bit-exact."""
        key = ("send", tuple((tuple(l.shape), str(l.dtype))
                             for l in jax.tree.leaves(aval)))
        if key not in PROGRAMS:
            made = TypedFlatLayout({0: aval})
            PROGRAMS[key] = (
                made,
                jax.jit(lambda ls: made.pack_leaves(0, ls)),
                jax.jit(lambda vs: jax.tree.leaves(made.unpack(vs, 0))),
            )
        layout, pack, unpack = PROGRAMS[key]
        if self.process_index == src:
            # Consolidate onto the local proc-mesh device (D2D within the
            # host), then fuse ravel/cast/concat in one jitted program.
            vecs = pack(jax.device_put(
                jax.tree.leaves(value), self.local_device_sharding))
        else:
            vecs = tuple(jnp.zeros(layout.lengths[dt], dt)
                         for dt in layout.dtypes)
        totals = tuple(
            self._reduce_device(v, layout.lengths[dt], (src, dst), "sum", dt)
            for v, dt in zip(vecs, layout.dtypes)
        )
        if self.process_index == src:
            return None
        return jax.tree.unflatten(jax.tree.structure(aval), unpack(totals))


# ---------------------------------------------------------------------- #
# Flat layouts for layer-keyed pytrees.


class TypedFlatLayout:
    """Native-dtype flat layout for a {layer_index: pytree} mapping: ONE
    flat vector per distinct leaf dtype (bf16 leaves ride a bf16 vector,
    f32 an f32 one — no f32 widening on the wire).
    Derived from abstract shapes only, so every process computes the
    identical layout without communicating. Non-arithmetic leaves (bool)
    map to an int32 wire lane and cast back on unpack.

    The reference keeps native dtypes trivially — NCCL allreduces each
    tensor in place (engine.py:404-412); this is the packed-wire
    equivalent for the flat process-mesh collectives."""

    _WIRE = {np.dtype(np.bool_): np.dtype(np.int32)}

    def __init__(self, avals_by_layer: dict[int, Any]):
        self.layers = sorted(avals_by_layer)
        self.structs: dict[int, Any] = {}
        # li -> [(shape, dtype, wire_dtype, offset_in_wire_vec, size)]
        self.leaf_metas: dict[int, list] = {}
        lengths: dict[Any, int] = {}
        for li in self.layers:
            leaves, struct = jax.tree.flatten(avals_by_layer[li])
            metas = []
            for l in leaves:
                dt = np.dtype(l.dtype)
                wdt = self._WIRE.get(dt, dt)
                n = int(np.prod(l.shape)) if l.shape else 1
                off = lengths.get(wdt, 0)
                metas.append((tuple(l.shape), l.dtype, wdt, off, n))
                lengths[wdt] = off + n
            self.structs[li] = struct
            self.leaf_metas[li] = metas
        self.dtypes = tuple(sorted(lengths, key=lambda d: d.name))
        self.lengths = lengths

    @property
    def wire_bytes(self) -> int:
        """Bytes one process's full contribution occupies on the wire."""
        return sum(n * dt.itemsize for dt, n in self.lengths.items())

    def pack_leaves(self, li: int, leaves: list):
        """Trace-pure: layer li's leaves -> per-dtype flat vectors (tuple
        aligned with self.dtypes). Leaves must be full layers in layout
        order; partial packing is not supported (offsets are cumulative)."""
        segs: dict[Any, list] = {dt: [] for dt in self.dtypes}
        for leaf, (shape, dtype, wdt, off, n) in zip(
            leaves, self.leaf_metas[li], strict=True
        ):
            segs[wdt].append(jnp.ravel(leaf).astype(wdt))
        return tuple(
            jnp.concatenate(segs[dt]) if segs[dt]
            else jnp.zeros(0, dt)
            for dt in self.dtypes
        )

    def unpack(self, vecs, li: int):
        """Layer li's tree out of per-dtype flat vectors (tuple aligned
        with self.dtypes). Trace-pure (works on numpy and under jit)."""
        by_dt = dict(zip(self.dtypes, vecs, strict=True))
        leaves = []
        for shape, dtype, wdt, off, n in self.leaf_metas[li]:
            leaves.append(
                by_dt[wdt][off:off + n].reshape(shape).astype(dtype)
            )
        return jax.tree.unflatten(self.structs[li], leaves)

    def pack_into(self, bufs: dict, li: int, tree) -> None:
        """Host-side: write layer li's leaves into per-dtype numpy buffers
        (keyed by wire dtype, sized self.lengths). Winner-unique packing —
        assignment, not accumulation."""
        for leaf, (shape, dtype, wdt, off, n) in zip(
            jax.tree.leaves(tree), self.leaf_metas[li], strict=True
        ):
            bufs[wdt][off:off + n] = np.asarray(
                jax.device_get(leaf)
            ).ravel().astype(wdt)


def layer_avals(model) -> dict[int, Any]:
    """Abstract param trees per pipeline layer (no device use)."""
    rng = jax.random.PRNGKey(0)
    return {
        li: jax.eval_shape(lambda r, _li=li: model.init_layer(r, _li), rng)
        for li in range(model.num_pipeline_layers)
    }


def activation_avals(model, microbatch_size: int, seq_len: int) -> list:
    """Abstract activation (carry) aval AFTER each non-final layer, chained
    through jax.eval_shape — the static shape contract for cross-host
    stage-to-stage transfers (no metadata handshake, unlike the reference's
    first-transfer header protocol, pipeline.py:288-333)."""
    avals = layer_avals(model)
    batch = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype),
        model.sample_batch(microbatch_size, seq_len),
    )
    out: list = []

    def step(li, carry):
        return jax.eval_shape(
            lambda p, c, b: model.apply_layer(li, p, c, b),
            avals[li], carry, batch,
        )

    carry = None
    for li in range(model.num_pipeline_layers - 1):
        carry = step(li, carry)
        out.append(carry)
    return out
