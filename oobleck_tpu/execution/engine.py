"""The worker-side engine: planning, pipelines, training loop, recovery.

Capability match for the reference OobleckEngine / DataParallelEngine /
ReconfigurationEngine (/root/reference/oobleck/execution/engine.py:39-668).
Two deployment shapes share this code:

  * single-controller (default): one engine process drives every visible
    chip; "hosts" partition the chip list (chips_per_host each);
  * multi-host MPMD (OOBLECK_MULTIHOST=1): every host's worker joins one
    jax.distributed world (coordinator address via the control plane,
    elastic/). Pipelines span hosts with host-local stages; cross-host
    edges and the layer-granularity DP allreduce ride XLA collectives over
    process meshes (parallel/cross_host.py); recovery is respawn + live
    mirror refill (checkpoint-free, matching the reference's in-memory
    recovery, engine.py:238-309).

Key behaviors mirrored from the reference:
  * ctor builds dataset/model/profile/templates without any distributed
    state (engine.py:415-524), including the min-host memory bound
    (engine.py:490-513) from template memory requirements vs HBM;
  * instantiate_pipelines: best plan -> per-pipeline dataloaders (data
    position-aware) -> pipeline instances -> DP engine (engine.py:600-643);
  * train loop: pipeline step + layer-granularity cross-pipeline grad sync +
    optimizer step, step timing and memory logged every 10 steps, loss
    logged every step (the reference accumulates loss but never reports it —
    SURVEY §5 gap, closed here);
  * reconfiguration: host algebra (reconfigure.py) -> template re-match ->
    batch redistribution -> re-instantiation reusing surviving weights and
    optimizer state, dataloader position carried over (engine.py:182-309).
"""

from __future__ import annotations

import json
import logging
import threading
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from oobleck_tpu.config import OobleckArguments, training_seq_len
from oobleck_tpu.elastic.message import JOINED_KEY
from oobleck_tpu.execution.dataloader import (
    DeviceStager,
    OobleckDataLoader,
    OobleckSampler,
    PrefetchingLoader,
)
from oobleck_tpu.execution.dataset import build_dataset
from oobleck_tpu.execution.pipeline import PROGRAMS, PipelineInstance
from oobleck_tpu.execution.reconfigure import (
    fit_host_groups,
    hosts_to_ranks,
    reconfigure_hosts,
)
from oobleck_tpu.models import build_model
from oobleck_tpu.models.base import applied_param_count, passes_of, repeated
from oobleck_tpu.obs import goodput as obs_goodput
from oobleck_tpu.obs import incident as obs_incident
from oobleck_tpu.obs import spans as obs_spans
from oobleck_tpu.obs import telemetry as obs_telemetry
from oobleck_tpu.parallel.train import make_optimizer
from oobleck_tpu.planning.instantiator import HeterogeneousPlan, PipelineInstantiator
from oobleck_tpu.planning.profiler import (
    effective_tag,
    job_tag,
    load_profile,
    profile,
)
from oobleck_tpu.planning.templates import PipelineTemplate, TemplateGenerator
from oobleck_tpu.policy import DECISION_KEY as POLICY_DECISION_KEY
from oobleck_tpu.policy import (
    GROW_MODES,
    MECH_ABSORB,
    MECH_GROW_DP,
    MECH_GROW_RESHAPE,
    MECH_REINSTANTIATE,
    MECH_REROUTE,
    MECH_RESTORE,
    decision_from_payload,
)
from oobleck_tpu.utils import background, metrics, recovery
from oobleck_tpu.utils.compile_cache import compile_unpersisted
from oobleck_tpu.utils.chaos import chaos

logger = logging.getLogger("oobleck.engine")

# Per-device memory the planner assumes on the CPU test backend, which
# reports none. A TPU is asked (compute_min_hosts) and never assumed.
CPU_TEST_HBM_BYTES = 16 * 2**30


class HostSyncCounter:
    """Counts host-blocking device readbacks the engine performs (the
    `float(loss)` family). Test hook for the async-dispatch guarantee:
    with input prefetch on and loss_readback_every > 1, steady-state steps
    must not bump this at all."""

    def __init__(self) -> None:
        self.count = 0


host_sync_counter = HostSyncCounter()


def _host_sync(value, read=float):
    """The engine's ONLY device->host readback funnel (counted). `read`
    is what reads `value`: `float` a loss, `jax.device_get` a step's loads
    (`StepLoad`)."""
    host_sync_counter.count += 1
    # Where the host blocks on the device: the step's work has been
    # enqueued, and this returns when the loss is there.
    with obs_spans.region("engine.loss_readback"):
        return read(value)


class StepLoad:
    """Where a step routed (each pipeline's `load`: its routed chunks'
    loads summed over the microbatches), on the device until `read()`.
    Read only where the step's loss is, and after it: the backward
    programs that wrote these few hundred bytes a routed layer have
    finished by then. Empty (false) where no pipeline hands a load out."""

    def __init__(self, pipelines) -> None:
        self._arrays = []
        self._rows = []        # (label, the tile's rows) a row of them
        for pipe in pipelines:
            for layers, array in pipe.load or ():
                self._arrays.append(array)
                self._rows += [pipe.load_info[li] for li in layers]

    def __bool__(self) -> bool:
        return bool(self._arrays)

    def read(self) -> dict:
        """{layer: (rows of each held expert..., tiles in use, the tile's
        rows)}, pipelines summed: ONE transfer through the counted
        funnel, whatever the number of chunks."""
        arrays = _host_sync(self._arrays, read=jax.device_get)
        out: dict[str, list[int]] = {}
        for (label, tile), row in zip(
                self._rows, (row for array in arrays for row in array)):
            seen = out.setdefault(label, [0] * len(row) + [tile])
            for i, n in enumerate(row.tolist()):
                seen[i] += n
        return {label: tuple(seen) for label, seen in out.items()}


class DeferredLoss:
    """Weighted on-device loss scalars whose host readback is postponed
    (execution.loss_readback_every > 1). Holding the jax arrays keeps them
    alive without forcing a sync; resolve() is the single point where the
    host finally blocks. The step's load rides with them (`StepLoad`, or
    None) and is read when they are."""

    def __init__(self, parts: list[tuple[Any, int]],
                 load: "StepLoad | None" = None) -> None:
        self._parts = parts
        self._load = load

    def resolve(self) -> float:
        total = sum(w for _, w in self._parts)
        return sum(
            _host_sync(l) * w for l, w in self._parts
        ) / max(1, total)

    def resolve_load(self) -> dict | None:
        return self._load.read() if self._load else None


# The data-parallel programs that bake nothing in are jitted once, here (jit
# keys each by its operands' trees and shardings); the others: `PROGRAMS`.


@jax.jit
def pack_flat(trees: list):
    """One flat f32 buffer from same-mesh trees: ONE jitted program
    (`jit_pack_flat`), which reads every leaf and writes a new buffer of
    all their elements at four bytes each. The anchor path's, for owners
    whose meshes differ; congruent owners sum in `dp_sum_program` and pack
    nothing."""
    return jnp.concatenate([
        l.ravel().astype(jnp.float32)
        for t in trees for l in jax.tree.leaves(t)])


def _slices(flat, leaves):
    """`flat` cut into the shapes and dtypes of `leaves`, in their order."""
    off = 0
    for l in leaves:
        yield flat[off:off + l.size].reshape(l.shape).astype(l.dtype)
        off += l.size


@jax.jit
def unpack_add(flat, trees: list) -> list:
    """trees[i] + slices-of-flat, one jitted program on the dst mesh."""
    leaves, struct = jax.tree.flatten(trees)
    return jax.tree.unflatten(
        struct, [l + seg for l, seg in zip(leaves, _slices(flat, leaves))])


def unpack_to(flat, likes: list, shardings: list) -> list:
    """Slices of flat in the trees, shapes and dtypes of `likes`, placed on
    `shardings`: one jitted program with explicit out_shardings on the dst
    mesh, which key it in `PROGRAMS` beside the shapes (identical shapes on
    different destination stages need different out_shardings)."""
    leaves, struct = jax.tree.flatten(jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), likes))
    key = ("unpack_to", tuple((l.shape, l.dtype) for l in leaves), struct,
           tuple(jax.tree.leaves(shardings)))
    if key not in PROGRAMS:
        def unpack(f):
            return jax.tree.unflatten(struct, list(_slices(f, leaves)))
        PROGRAMS[key] = jax.jit(unpack, out_shardings=shardings)
    return PROGRAMS[key](flat)


@jax.jit
def sum_trees(per_tree: list) -> list:
    """Leaf-wise sum of equal lists of leaves."""
    return [sum(g[1:], start=g[0]) for g in zip(*per_tree)]


# The axis of a collective group's mesh that runs over the layer's owners.
DP_AXIS = "dp"


def dp_spec(spec: PartitionSpec, ndim: int) -> PartitionSpec:
    """An owner's `spec` of a leaf, as the spec of all the owners' leaves
    laid end to end along dimension 0 over `DP_AXIS` and then the axes the
    leaf's own dimension 0 is sharded over: a chip's shard of the whole IS
    its shard of its owner's leaf, shape and all. (A leaf of no dimension
    gets one, of the owners.)"""
    if ndim == 0:
        return PartitionSpec(DP_AXIS)
    first = spec[0] if len(spec) else None
    names = (() if first is None
             else first if isinstance(first, tuple) else (first,))
    return PartitionSpec((DP_AXIS, *names), *spec[1:])


def dp_sum_program(shardings: list, operands: list):
    """THE compiled sum over `DP_AXIS` of a list of leaves (`jit_dp_sum` in
    a device trace): `operands` are the whole arrays or their shapes, and
    `shardings` theirs, all on one mesh whose first axis is `DP_AXIS`. One
    all-reduce a leaf among the chips that hold the same shard of it, every
    owner left with the same bits, in the leaves' own dtypes; one program
    for the whole list, so XLA may combine the leaves' all-reduces. The
    operands are NOT donated: they are the owners' own gradient buffers
    (`PipelineInstance.grads`), which callers read after the sum.

    What `PROGRAMS` keeps, by the shardings (the owners' chips in their
    stage meshes' arrangement, each leaf's spec), the shapes and the
    dtypes, is the EXECUTABLE, compiled here and never read back from the
    persistent cache (`compile_cache.compile_unpersisted`: a collective
    over some of the process's chips does not survive it): whoever builds
    it first, the recovery precompiler's walk or a step, the next caller
    in the process compiles nothing."""
    key = ("dp_sum", tuple(shardings),
           tuple((o.shape, jnp.dtype(o.dtype)) for o in operands))
    if key not in PROGRAMS:
        def dp_sum(leaves):
            return [jax.lax.psum(l, DP_AXIS) for l in leaves]

        specs = [sh.spec for sh in shardings]
        PROGRAMS[key] = compile_unpersisted(
            jax.jit(jax.shard_map(dp_sum, mesh=shardings[0].mesh,
                                  in_specs=(specs,), out_specs=specs)),
            [jax.ShapeDtypeStruct(o.shape, o.dtype, sharding=sh)
             for o, sh in zip(operands, shardings)])
    return PROGRAMS[key]


class CollectiveGroup:
    """Layers that the same pipelines hold on congruent stages (one stage
    of each owner: meshes of one shape and axis names over distinct chips,
    every leaf at the same spec), and what sums their gradients in one
    program: a mesh with `DP_AXIS` over the owners and then the stage
    mesh's own axes, each leaf's spec on it (`dp_spec`), the program."""

    def __init__(self, layers, owners, stages):
        self.layers = tuple(layers)
        self.owners = list(owners)
        self.stages = list(stages)
        first = stages[0]
        self.mesh = Mesh(
            np.stack([st.mesh.devices for st in stages]),
            (DP_AXIS, *first.mesh.axis_names))
        self.owner_of_device = {
            d: i for i, st in enumerate(stages) for d in st.mesh.devices.flat}
        # Each owner's shardings of the layers' leaves, in the order
        # `jax.tree.flatten` gives the layers' gradient trees.
        self.leaf_shardings = [
            jax.tree.leaves([st.param_shardings[li] for li in self.layers])
            for st in stages]
        self._program: tuple | None = None

    @staticmethod
    def congruent(stages, layers) -> bool:
        """Whether one collective can sum these layers between `stages`,
        from what the stages are: equal mesh shapes and axis names, no
        chip twice, and every leaf of every layer at one spec."""
        first = stages[0]
        chips = [d for st in stages for d in st.mesh.devices.flat]
        if len(set(chips)) != len(chips):
            return False
        for st in stages[1:]:
            if (st.mesh.devices.shape != first.mesh.devices.shape
                    or st.mesh.axis_names != first.mesh.axis_names):
                return False
            for li in layers:
                a, b = (jax.tree.flatten(
                    s.param_pspecs[li],
                    is_leaf=lambda x: isinstance(x, PartitionSpec))
                    for s in (first, st))
                if a != b:
                    return False
        return True

    def program(self, leaves):
        """(the leaves' shardings on the group's mesh, the shapes of the
        owners' leaves end to end, the compiled sum), made once from one
        owner's `leaves`: arrays, or their shapes (what the recovery
        precompiler has: `parallel/cross_host.layer_avals` of the group's
        layers, a gradient having its parameter's shape and dtype)."""
        if self._program is None:
            n = len(self.owners)
            shardings = [
                NamedSharding(self.mesh, dp_spec(sh.spec, l.ndim))
                for sh, l in zip(self.leaf_shardings[0], leaves)]
            whole = [jax.ShapeDtypeStruct(
                (n * l.shape[0], *l.shape[1:]) if l.ndim else (n,), l.dtype)
                for l in leaves]
            self._program = (shardings, [w.shape for w in whole],
                             dp_sum_program(shardings, whole))
        return self._program

    def summed_grads(self) -> list[list]:
        """The layers' summed gradient trees, a list an owner: every
        owner's on its OWN shardings. Nothing is copied on the way in (the
        owners' shards ARE the whole array's) or out (each owner's arrays
        are made of the result's shards on its chips)."""
        per_owner, structs = zip(*(
            jax.tree.flatten([p.grads[li] for li in self.layers])
            for p in self.owners))
        firsts = per_owner[0]
        shardings, shapes, program = self.program(firsts)
        whole = []
        for like, sharding, shape, *leaves in zip(
                firsts, shardings, shapes, *per_owner):
            shards = [s.data for l in leaves for s in l.addressable_shards]
            if not like.ndim:
                shards = [s.reshape(1) for s in shards]
            whole.append(jax.make_array_from_single_device_arrays(
                shape, sharding, shards))
        sums = program(whole)
        out: list[list] = [[] for _ in self.owners]
        for like, total in zip(firsts, sums):
            pieces: list[list] = [[] for _ in self.owners]
            for s in total.addressable_shards:
                pieces[self.owner_of_device[s.device]].append(
                    s.data if like.ndim else s.data.reshape(()))
            for i, mine in enumerate(pieces):
                out[i].append(jax.make_array_from_single_device_arrays(
                    like.shape, self.leaf_shardings[i][len(out[i])], mine))
        return [jax.tree.unflatten(struct, leaves)
                for struct, leaves in zip(structs, out)]


class DataParallelEngine:
    """Layer-granularity gradient sync across heterogeneous pipelines
    (reference engine.py:363-412): each layer's grads are summed over every
    pipeline that owns it, at whatever sharding each owner uses.

    Shared layers are grouped by who holds them and on which stage, and
    each group takes one of two paths, decided at construction from the
    owners' stage meshes and specs alone (`CollectiveGroup.congruent`):

      * congruent owners (pipelines of one shape: the same stage mesh
        shape, every leaf at the same spec): ONE jitted collective a group
        (`dp_sum_program`), over a mesh made of the owners' chips. The
        owners' gradient shards go in as they lie and the sums come back
        on each owner's own shardings: no packed copy, no anchor, every
        owner given the same bits;
      * anything else (pipelines of different stage counts that hold a
        layer on one chip here and two there): the anchor path. The first
        owner is the anchor; every other owner flattens its shared layers'
        grads into ONE f32 buffer a stage pair (`pack_flat`), all buffers
        ship in one `jax.device_put`, the anchor adds them in per layer
        inside one jitted program (`unpack_add`), and the totals go back
        the same way (`unpack_to`).

    `oobleck_dp_sync_layer_sums_total{path}` counts a shared layer a step
    under the path that summed it."""

    def __init__(self, pipelines: list[PipelineInstance]):
        self.pipelines = pipelines
        self.owners: dict[int, list[PipelineInstance]] = {}
        for p in pipelines:
            for st in p.stages:
                for li in st.layer_ids:
                    self.owners.setdefault(li, []).append(p)
        # Cross-mesh programs the last do_allreduce issued: a group's
        # collective counts one, the anchor path one batched device_put a
        # phase.
        self.last_transfer_count = 0
        by_holders: dict[tuple, list[int]] = {}
        for li, owners in sorted(self.owners.items()):
            if len(owners) > 1:
                by_holders.setdefault(tuple(
                    self._group_key(p, li) for p in owners), []).append(li)
        self.collective_groups: list[CollectiveGroup] = []
        self.anchor_layers: list[int] = []
        for holders, layers in by_holders.items():
            owners = self.owners[layers[0]]
            stages = [p.stages[si] for p, (_, si) in zip(owners, holders)]
            if CollectiveGroup.congruent(stages, layers):
                self.collective_groups.append(
                    CollectiveGroup(layers, owners, stages))
            else:
                self.anchor_layers += layers
        self._m_layer_sums = metrics.registry().counter(
            "oobleck_dp_sync_layer_sums_total",
            "Layers whose gradients were summed between the pipelines "
            "that hold them, a layer a step, by path (collective: one "
            "program over the owners' chips; anchor: packed copies to the "
            "first owner and back)")

    @staticmethod
    def _group_key(pipe: PipelineInstance, li: int) -> tuple:
        """Transfer-group key: the stage (sub-mesh) owning layer li."""
        return (pipe.pipeline_id, pipe.stage_of_layer(li))

    def do_allreduce(self) -> dict[int, dict[int, Any]]:
        """Returns {pipeline_id: {layer: synced_grad_tree}}: every owner's
        tree on its own stage's shardings. A layer with one owner passes
        through; a congruent group's layers are summed by the group's one
        collective (`CollectiveGroup.summed_grads`); the rest take the
        anchor path (`_anchor_sums`). A one-pipeline engine dispatches
        nothing."""
        synced: dict[int, dict[int, Any]] = {p.pipeline_id: {} for p in self.pipelines}
        self.last_transfer_count = 0
        for li, owners in self.owners.items():
            if len(owners) == 1:
                synced[owners[0].pipeline_id][li] = owners[0].grads[li]
        for group in self.collective_groups:
            for pipe, trees in zip(group.owners, group.summed_grads()):
                synced[pipe.pipeline_id].update(zip(group.layers, trees))
            self.last_transfer_count += 1
            self._m_layer_sums.inc(len(group.layers), path="collective")
        if self.anchor_layers:
            self._anchor_sums(synced)
            self._m_layer_sums.inc(len(self.anchor_layers), path="anchor")
        return synced

    def _anchor_sums(self, synced: dict[int, dict[int, Any]]) -> None:
        """`self.anchor_layers` summed on their first owners and handed
        back, into `synced`.

        Transfer granularity is (src stage) -> (anchor stage): one packed
        buffer per stage pair per direction, because a jitted program's
        inputs must share one mesh — a stage IS a mesh here. The
        replicated-flat hop stands in for a collective where the owners'
        meshes have no common shape to build one over."""
        # Group shared layers by (src stage, anchor stage).
        fwd_groups: dict[tuple, list[int]] = {}
        anchors: dict[int, PipelineInstance] = {}
        for li in self.anchor_layers:
            owners = self.owners[li]
            anchor = owners[0]
            anchors[li] = anchor
            for other in owners[1:]:
                key = (self._group_key(other, li), self._group_key(anchor, li))
                fwd_groups.setdefault(key, []).append(li)
        by_id = {p.pipeline_id: p for p in self.pipelines}

        # Phase 1 — sum every remote stage's contribution on the anchor.
        # Pack one buffer per (src stage, anchor stage) pair, then ship ALL
        # buffers in a single jax.device_put: handing the runtime the whole
        # transfer set at once lets the copies ride ICI/DCN concurrently
        # instead of serializing through the Python loop.
        totals: dict[int, Any] = {li: anchors[li].grads[li] for li in anchors}
        fwd = []
        for ((src_id, _), (dst_id, dst_st)), lis in sorted(fwd_groups.items()):
            lis = sorted(lis)
            src, dst = by_id[src_id], by_id[dst_id]
            flat = pack_flat([src.grads[li] for li in lis])
            sharding = NamedSharding(
                dst.stages[dst_st].mesh, jax.sharding.PartitionSpec()
            )
            fwd.append((lis, flat, sharding))
        if fwd:
            group_lis, flats, dst_shardings = zip(*fwd)
            moved = jax.device_put(list(flats), list(dst_shardings))
            self.last_transfer_count += 1
            for lis, flat in zip(group_lis, moved):
                added = unpack_add(flat, [totals[li] for li in lis])
                for li, tree in zip(lis, added):
                    totals[li] = tree

        # Phase 2 — redistribute anchor totals to the other owners.
        bwd_groups: dict[tuple, list[int]] = {}
        for li, anchor in anchors.items():
            synced[anchor.pipeline_id][li] = totals[li]
            for other in self.owners[li][1:]:
                key = (self._group_key(anchor, li), self._group_key(other, li))
                bwd_groups.setdefault(key, []).append(li)
        bwd = []
        for ((_, _), (dst_id, dst_st)), lis in sorted(bwd_groups.items()):
            lis = sorted(lis)
            dst = by_id[dst_id]
            flat = pack_flat([totals[li] for li in lis])
            sharding = NamedSharding(
                dst.stages[dst_st].mesh, jax.sharding.PartitionSpec()
            )
            bwd.append((lis, flat, sharding, dst, dst_st))
        if bwd:
            group_lis, flats, dst_shardings, dsts, dst_sts = zip(*bwd)
            moved = jax.device_put(list(flats), list(dst_shardings))
            self.last_transfer_count += 1
            for lis, flat, dst, dst_st in zip(group_lis, moved, dsts, dst_sts):
                unpacked = unpack_to(
                    flat, [totals[li] for li in lis],
                    [dst.stages[dst_st].param_shardings[li] for li in lis])
                for li, tree in zip(lis, unpacked):
                    synced[dst.pipeline_id][li] = tree


class MultiHostDataParallelEngine:
    """Layer-granularity DP sync when pipelines live across jax.distributed
    processes. The wire carries ONLY what DP requires (the reference's own
    discipline: per-layer groups spanning only that layer's owners,
    engine.py:363-412):

      * layers whose owning (pipeline, stage) processes form a SINGLE
        process never touch the wire — their cross-pipeline sum (if any) is
        a local jitted add;
      * layers with the same multi-process owner set are packed into one
        flat buffer per owner set and psummed over THAT process subset, in
        NATIVE dtypes (one lane per dtype — bf16 grads cost bf16 bytes);
      * the per-pipeline weighted losses ride one tiny f32 psum over all
        processes (every process logs the global loss).

    Each (pipeline, layer) gradient is owned by exactly one process (stages
    are host-local), so summing local contributions before the psum
    double-counts nothing. Groups are issued in ascending first-layer order
    — a total order every process derives identically, so overlapping
    owner-set collectives can never deadlock. A 1-pipeline plan (no DP) has
    no shared layers and transfers ~nothing beyond the loss scalar."""

    def __init__(self, pipelines: list[PipelineInstance], model, comm,
                 participants=None):
        from oobleck_tpu.parallel.cross_host import (
            TypedFlatLayout, layer_avals)

        self.pipelines = pipelines
        self.comm = comm
        # Loss-psum membership. Defaults to the whole world; an in-place
        # degrade (zero-respawn recovery) shrinks it to the survivors so
        # collectives never wait on the drained victim process.
        self.participants = (list(participants) if participants is not None
                            else list(range(comm.process_count)))
        # Union of owners across ALL pipelines (remote included): needed so
        # every process agrees on which layers are DP-shared.
        self.owners: dict[int, list[PipelineInstance]] = {}
        owner_procs: dict[int, set[int]] = {}
        for p in pipelines:
            for st in p.stages:
                for li in st.layer_ids:
                    self.owners.setdefault(li, []).append(p)
                    owner_procs.setdefault(li, set()).add(st.process)
        by_set: dict[tuple[int, ...], list[int]] = {}
        for li, procs in owner_procs.items():
            if len(procs) > 1:
                by_set.setdefault(tuple(sorted(procs)), []).append(li)
        # [(procs, sorted layer ids)] in ascending first-layer order.
        self.groups: list[tuple[tuple[int, ...], list[int]]] = [
            (procs, sorted(lis))
            for procs, lis in sorted(by_set.items(),
                                     key=lambda kv: min(kv[1]))
        ]
        avals = layer_avals(model)
        self.layouts = [
            TypedFlatLayout({li: avals[li] for li in lis})
            for _, lis in self.groups
        ]
        self._wire_layer_group = {
            li: gi for gi, (_, lis) in enumerate(self.groups) for li in lis
        }
        # What the layouts are made from, beside a group's layers: for the
        # keys of the programs that bake one in.
        self._model_key = (type(model), model.config)
        self.last_transfer_count = 0
        self.last_wire_bytes = 0
        self.n_pipelines = len(pipelines)

    # -- device-side pack/sum/unpack ------------------------------------ #

    def _pack_group(self, gi: int, per_layer: dict[int, list]):
        """Per-dtype flat contribution vectors for group gi: local grad
        leaves are consolidated onto the local proc-mesh device (D2D) and a
        single jitted program sums same-layer contributions and
        ravels/concats them into layout order — no host staging, no f32
        widening."""
        _, lis = self.groups[gi]
        layout = self.layouts[gi]
        all_leaves = [
            l for li in lis for t in per_layer[li]
            for l in jax.tree.leaves(t)
        ]
        all_leaves = jax.device_put(
            all_leaves, self.comm.local_device_sharding
        )
        counts = tuple(len(per_layer[li]) for li in lis)
        key = ("pack_group", self._model_key, tuple(lis), counts)
        if key not in PROGRAMS:
            nleaves = {li: len(layout.leaf_metas[li]) for li in lis}

            def pack(leaves):
                it = iter(leaves)
                segs: dict[Any, list] = {dt: [] for dt in layout.dtypes}
                for li, cnt in zip(lis, counts):
                    per_tree = [
                        [next(it) for _ in range(nleaves[li])]
                        for _ in range(cnt)
                    ]
                    summed = [
                        sum(ls[1:], start=ls[0]) for ls in zip(*per_tree)
                    ]
                    for leaf, (shape, dtype, wdt, off, n) in zip(
                        summed, layout.leaf_metas[li]
                    ):
                        segs[wdt].append(jnp.ravel(leaf).astype(wdt))
                return tuple(
                    jnp.concatenate(segs[dt]) for dt in layout.dtypes
                )

            PROGRAMS[key] = jax.jit(pack)
        return PROGRAMS[key](all_leaves)

    def _unpack_layer_device(self, gi: int, totals, li: int):
        """Slice one layer's grad tree out of group gi's reduced vectors,
        on the local device (the subsequent device_put to the stage
        sharding is a D2D placement)."""
        key = ("unpack_layer", self._model_key,
               tuple(self.groups[gi][1]), li)
        if key not in PROGRAMS:
            layout = self.layouts[gi]
            def unpack_layer(vs, _li=li):
                return layout.unpack(vs, _li)

            PROGRAMS[key] = jax.jit(unpack_layer)
        return PROGRAMS[key](totals)

    def _local_sum(self, trees: list):
        """Sum same-layer grads from multiple LOCAL pipelines (no wire)."""
        if len(trees) == 1:
            return trees[0]
        per_tree = jax.device_put(
            [jax.tree.leaves(t) for t in trees],
            self.comm.local_device_sharding)
        return jax.tree.unflatten(
            jax.tree.structure(trees[0]), sum_trees(per_tree))

    def allreduce(self, local_losses: dict[int, tuple[float, int]]
                  ) -> tuple[dict[int, dict[int, Any]], float]:
        """local_losses: {pipeline_id: (loss, weight)} for pipelines whose
        last stage is local. Returns ({pipeline_id: {layer: summed grads}}
        for LOCAL (pipeline, layer) pairs, global weighted mean loss)."""
        me = self.comm.process_index
        wire0 = self.comm.wire_bytes
        per_layer: dict[int, list] = {}
        for pipe in self.pipelines:
            for li in sorted(pipe.grads):
                per_layer.setdefault(li, []).append(pipe.grads[li])

        # Wire phase: one per-dtype psum per owner set this process is in,
        # in the global group order (deadlock-free by construction).
        group_totals: dict[int, tuple] = {}
        self.last_transfer_count = 0
        for gi, ((procs, lis), layout) in enumerate(
            zip(self.groups, self.layouts)
        ):
            if me not in procs:
                continue
            vecs = self._pack_group(gi, per_layer)
            group_totals[gi] = tuple(
                self.comm.group_sum_device(v, layout.lengths[dt], procs, dt)
                for v, dt in zip(vecs, layout.dtypes)
            )
            self.last_transfer_count += len(layout.dtypes)

        # Loss psum (all processes): [weight * loss, weight] per pipeline.
        loss_vec = np.zeros(2 * self.n_pipelines, np.float32)
        for i, pipe in enumerate(self.pipelines):
            if pipe.pipeline_id in local_losses:
                loss, weight = local_losses[pipe.pipeline_id]
                # The multihost loss rides the host-side group_sum;
                # _defer_losses() documents this path cannot defer.
                # oobleck: allow[OBL002] -- multihost loss allreduce
                loss_vec[2 * i] = float(loss) * weight
                loss_vec[2 * i + 1] = weight
        tail = self.comm.group_sum(
            loss_vec, loss_vec.shape[0], self.participants
        )
        self.last_wire_bytes = self.comm.wire_bytes - wire0

        # Local phase: slice wire totals / sum local-only layers, placed on
        # each owning pipeline's stage sharding.
        local_sums: dict[int, Any] = {}
        synced: dict[int, dict[int, Any]] = {}
        for pipe in self.pipelines:
            if not pipe.participates_locally:
                continue
            out: dict[int, Any] = {}
            for li in pipe.params:
                gi = self._wire_layer_group.get(li)
                if gi is not None:
                    tree = self._unpack_layer_device(gi, group_totals[gi], li)
                else:
                    if li not in local_sums:
                        local_sums[li] = self._local_sum(per_layer[li])
                    tree = local_sums[li]
                out[li] = jax.device_put(
                    tree,
                    pipe.stages[pipe.stage_of_layer(li)].param_shardings[li],
                )
            synced[pipe.pipeline_id] = out
        wl = tail[0::2].sum()
        w = tail[1::2].sum()
        return synced, float(wl / w) if w else float("nan")


class ReconfigurationEngine:
    """Listens on the agent pipe for lost-host notifications and drives the
    engine's reconfiguration (reference engine.py:39-89, daemon thread)."""

    def __init__(self, engine: "OobleckEngine", pipe):
        self.engine = engine
        self.pipe = pipe
        self._thread = threading.Thread(
            target=self._listen, name="reconfig-listener", daemon=True
        )
        self._thread.start()

    def _listen(self) -> None:
        # Single reader for the agent pipe: other message kinds (coordinator
        # announcements during multi-host init) are routed to the engine's
        # control queue instead of being dropped — two readers on one pipe
        # would race and eat each other's messages.
        while True:
            try:
                msg = self.pipe.recv()
            except (EOFError, OSError):
                return
            if not isinstance(msg, dict):
                continue
            if msg.get("kind") == "drain":
                # Proactive preemption: flush durable state at the next
                # step boundary and exit cleanly (agent reports JOB_DONE).
                self.engine.request_drain(trace=obs_spans.extract(msg))
            elif (msg.get("kind") == "degrade" and msg.get("inplace")
                    and self.engine.multihost):
                # Multihost zero-respawn reroute: queued separately so every
                # process can agree on ONE apply boundary via the per-step
                # consensus collective (_maybe_inplace_degrade).
                self.engine.request_inplace_degrade(
                    msg["lost_ip"], trace=obs_spans.extract(msg),
                    decision=msg.get(POLICY_DECISION_KEY))
            elif msg.get("kind") in ("reconfigure", "degrade", "restore"):
                # The verbs funnel into the same pending queue: the policy
                # decision riding the payload (or, absent one, the engine's
                # own policy consult) picks the mechanism, so the verb is a
                # control-plane hint (and a distinct wire event for the
                # flight recorder), not a hard dispatch. The incident's
                # trace context rides along (obs/spans).
                self.engine.request_reconfiguration(
                    msg["lost_ip"], trace=obs_spans.extract(msg),
                    decision=msg.get(POLICY_DECISION_KEY))
            elif msg.get("kind") == "grow":
                # JOIN incident: capacity ARRIVING instead of leaving. The
                # grow direction rides the same pending-queue + step-
                # boundary pattern as losses (one correlated incident per
                # boundary), never a mid-step mutation.
                self.engine.request_grow(
                    list(msg.get(JOINED_KEY) or ()),
                    trace=obs_spans.extract(msg),
                    decision=msg.get(POLICY_DECISION_KEY))
            else:
                self.engine._control_msgs.put(msg)


class OobleckEngine:
    @obs_spans.span("engine.build")
    def __init__(self, args: OobleckArguments, agent_ip: str | None = None,
                 agent_pipe=None, devices: list | None = None):
        self.args = args
        self.agent_ip = agent_ip
        self.agent_pipe = agent_pipe
        self._injected_devices = devices

        self.model = build_model(args.model.model_name, args.model.model_args,
                                 execution=args.execution)
        if (args.execution.resolved_path() == "fused"
                and not getattr(self.model, "fused_supported", False)):
            raise ValueError(
                f"{args.model.model_name} ({getattr(self.model, 'data_kind', '?')}) "
                "is not supported by the fused SPMD step (causal LM only); "
                "set execution.engine_path: mpmd"
            )
        cfg = self.model.config
        seq_len = training_seq_len(cfg, args.job.seq_len)
        self.seq_len = seq_len
        logger.info("model %s: %d pipeline layers, hidden %s, seq_len %d",
                    args.model.model_name, self.model.num_pipeline_layers,
                    getattr(cfg, "hidden_size", "?"), seq_len)
        self.dataset = build_dataset(
            args.model.dataset_path, args.model.dataset_name,
            model_name=args.model.model_name,
            # A model that holds a share of the vocabulary says so.
            vocab_size=getattr(cfg, "data_vocab_size",
                               getattr(cfg, "vocab_size", 0)),
            seq_length=seq_len,
            data_kind=getattr(self.model, "data_kind", "causal_lm"),
            mask_token_id=getattr(cfg, "mask_token_id", 103),
            image_size=getattr(cfg, "image_size", 224),
            num_classes=getattr(cfg, "num_classes", 1000),
            num_channels=getattr(cfg, "num_channels", 3),
        )
        # Real validation split when the data source has one; else
        # evaluate() holds out the eval_fraction tail of the train set.
        # Built lazily on first evaluate() — tokenizing a whole extra split
        # at startup would tax exactly the recovery latency BASELINE bounds.
        self._eval_ds_cache: Any = _UNSET
        self._has_val_split: bool | None = None
        self._eval_state = (0, 0)  # rotating (iterations_done, epoch)

        # Planning inputs. Profile-on-miss runs HERE, in the process that
        # owns the chips — never in the agent. The profiled model carries the same execution overrides as the
        # trained one — a bf16 profile must not plan an f32 run.
        with obs_spans.span("engine.profile"):
            profile(args.model.model_name, args.model.model_args,
                    model_tag=job_tag(args.model.model_tag, args.job.seq_len),
                    execution=args.execution,
                    microbatch_size=args.job.microbatch_size, seq_len=seq_len)
            self.profiles = load_profile(
                args.model.model_name, self._profile_tag(),
                args.job.microbatch_size
            )
        # What the largest carry any layer hands the next takes for one
        # microbatch: what a stage edge would ship were the list cut there,
        # and what the planner was charged for it (`mem_activation`: of a
        # layer the model repeats, once a pass).
        metrics.registry().gauge(
            "oobleck_pipeline_carry_bytes_max",
            "Bytes of the largest carry (one microbatch) any layer of the "
            "model hands the next, from the planner's layer profiles",
        ).set(max(p.mem_activation // passes_of(self.model, p.layer_index)
                  for p in self.profiles[:-1]))

        # Cluster geometry: hosts partition the device list. Ranks encode
        # ORIGINAL host indices (rank = original_index * chips_per_host +
        # local), and self.devices never shrinks — so lost-host lookups must
        # use this immutable map, never .index() on the shrinking host_ips
        # list (a second failure would resolve to the wrong host).
        self.host_ips = list(args.dist.node_ips)
        self._host_index = {ip: i for i, ip in enumerate(self.host_ips)}
        self.devices: list | None = None
        self.chips_per_host: int | None = None
        # Multi-host MPMD: one jax.distributed world, host h == process h.
        self.multihost = False
        self.comm = None
        self.templates: list[PipelineTemplate] = []
        self.pipelines: list[PipelineInstance] = []
        self.fused = None                    # FusedPipeline when engine_path=fused
        self._fused_hosts: list[int] = []    # surviving ORIGINAL host indices
        # Wall-clock seconds per completed reconfiguration — the paper's
        # headline recovery metric (BASELINE.md targets <60 s/failure).
        self.recovery_times: list[float] = []
        # Chips left idle by each fused-path recovery (shrink_to_fit drops
        # devices until microbatch divisibility holds); first-class next to
        # recovery_times so silent capacity loss is visible.
        self.stranded_chips: list[int] = []
        self.dataloaders: list[OobleckDataLoader] = []
        self.opt_states: dict[int, dict[int, Any]] = {}
        self.plan: HeterogeneousPlan | None = None
        self.dp_engine: DataParallelEngine | None = None
        self.step = 0
        # Async-dispatch state: device-resident losses awaiting readback
        # (loss_readback_every > 1) and the resolved (step, loss) history —
        # identical in content between deferred and per-step readback, which
        # the parity tests pin down.
        self._pending_losses: list[tuple[int, DeferredLoss]] = []
        self.loss_history: list[tuple[int, float]] = []
        # Warm-recovery precompiler (execution/precompile.py); armed by
        # start_recovery_precompile and re-armed after each reconfigure.
        self._precompiler = None
        # RECOVERY_DEADLINE accounting: set when this engine's state came
        # out of a recovery (in-place reconfigure, or a respawned world
        # restoring live mirrors); cleared by the first completed step,
        # which emits the FIRST_STEP mark.
        self._recovering = False
        self._recovered_at: float | None = None
        # Incident forensics (obs/incident.py): opened by reconfigure(),
        # committed at the first post-recovery step; the digest rides the
        # next metrics push so the master's /status shows the phase
        # breakdown without pulling the full report file.
        self._incident: obs_incident.IncidentBuilder | None = None
        self._incident_record: dict | None = None
        # Live-mirror background writer: snapshots are immutable jax arrays,
        # so the step thread only hands over references; the device_get +
        # pack + npz write happen off-thread (round-4 weak #3).
        self._mirror_thread: threading.Thread | None = None
        self._mirror_skipped = 0
        self.mirror_write_s: list[float] = []
        # Durable-state plane (oobleck_tpu/ckpt): the persistent half of
        # the two-tier recovery story — mirrors refill peers, checkpoints
        # survive whole-slice preemption. Built lazily (needs the resolved
        # process/world identity); env vars can retarget it per deployment.
        args.execution.apply_durable_env_overrides()
        self._durable = None
        self.ckpt_stall_s: list[float] = []
        self._pending_lost: list[tuple[str, dict | None, dict | None]] = []
        # Grow direction (PR 13): JOIN batches waiting for the next step
        # boundary, hosts parked by an absorb_spare verdict (admitted into
        # geometry but not the plan), and chaos spot-lifetime deadlines
        # (monotonic) armed at admit — the priced-in churn actually lands.
        self._pending_joins: list[tuple[list[str], dict | None,
                                        dict | None]] = []
        self._spare_hosts: list[str] = []
        self._spot_deadlines: dict[str, float] = {}
        self._lock = threading.Lock()
        import queue as _queue

        self._control_msgs: _queue.Queue = _queue.Queue()
        # Policy plane (oobleck_tpu/policy): local decision engine for
        # losses the control plane never saw (in-process chaos). A decision
        # attached to the broadcast overrides it, so every process applies
        # the master's verdict. Built lazily.
        self._policy = None
        # Set by a preemption drain request (or by the victim of an
        # in-place degrade): flush durable state at the next step boundary
        # and leave the train loop cleanly.
        self._drain_requested = False
        # Multihost in-place degrade consensus (_maybe_inplace_degrade):
        # the listener thread enqueues under _lock; every process applies
        # entry k only once ALL live processes have seen it.
        self._inplace_queue: list[dict] = []
        self._inplace_applied = 0
        # Processes still in the per-step collectives; None = full world.
        self._live_procs: list[int] | None = None
        # EWMA of wall seconds per step: the policy scorer's unit for
        # converting checkpoint staleness into lost work.
        self._step_s_ewma: float | None = None
        # Fleet-health planes (obs/telemetry.py, obs/goodput.py): one
        # per-step host sample into the process-global ring (the digest
        # rides the agent's heartbeats), and the wall-clock ledger this
        # worker's time is partitioned into. Live-bytes is static leaf
        # metadata cached per plan adoption — summing nbytes every step
        # is wasted host work; ckpt stalls are consumed by cursor so
        # each flush is telemetered exactly once.
        self._ledger = obs_goodput.GoodputLedger()
        self._live_bytes = 0
        self._live_bytes_stale = True
        self._ckpt_stall_seen = 0
        self._data_wait_s = 0.0
        self._last_mfu: float | None = None

        # Training-quality metrics (utils/metrics.py): per-step gauges the
        # master aggregates cluster-wide via the METRICS push.
        reg = metrics.registry()
        self._m_step_seconds = reg.histogram(
            "oobleck_engine_step_seconds", "Wall time per training step")
        self._m_steps = reg.counter(
            "oobleck_engine_steps_total", "Completed training steps")
        self._m_loss = reg.gauge(
            "oobleck_engine_loss", "Training loss of the last step")
        self._m_tokens_per_sec = reg.gauge(
            "oobleck_engine_tokens_per_sec",
            "Global training throughput of the last step")
        self._m_mfu = reg.gauge(
            "oobleck_engine_mfu",
            "Model FLOPs utilization estimate of the last step")
        self._m_bubble = reg.gauge(
            "oobleck_engine_pipeline_bubble_fraction",
            "Pipeline bubble fraction (kind=schedule: closed form "
            "(S-1)/(vM+S-1); kind=measured: dependency replay of measured "
            "per-chunk dispatch times through the schedule graph, falling "
            "back to 1 - busy/(S*step) when no per-op times exist)")
        self._m_input_wait = reg.histogram(
            "oobleck_input_wait_seconds",
            "Blocking time per step waiting on the device-side input "
            "stager (~0 when staging keeps ahead of compute)")
        self._m_dispatch_stall = reg.histogram(
            "oobleck_dispatch_stall_seconds",
            "Time per step spent dispatching batched cross-stage "
            "activation/gradient transfers")
        self._m_reconfigs = reg.counter(
            "oobleck_engine_reconfigurations_total",
            "In-place reconfigurations completed")
        self._m_grows = reg.counter(
            "oobleck_engine_grows_total",
            "Grow incidents applied, by mechanism (absorb_spare / "
            "grow_dp / grow_reshape)")
        self._m_template = reg.gauge(
            "oobleck_engine_pipeline_template_info",
            "Current pipeline layout (labels); value = step when adopted")
        self._m_goodput = reg.gauge(
            "oobleck_goodput_fraction",
            "Fraction of this worker's wall-clock spent in productive "
            "training steps (obs/goodput.py ledger)")
        # (flops_per_token, peak_flops_per_chip|None, n_chips), resolved
        # lazily on the first step; None when the model defies estimation.
        self._flops_cache: Any = _UNSET
        # The engine owns its tracer so reconfigure() can close a mid-window
        # jax.profiler trace before tearing the old topology down.
        self._tracer = None
        # What the host did in each step, kept per step (obs/telemetry.py):
        # the train thread's region seconds and open regions, the watchdog
        # that looks at a step while it is open (one thread per train()
        # call; None outside it and from a reconfiguration to the next
        # step), and the end of the previous step for `between_s`.
        self._step_acc = obs_spans.StepAccumulator()
        self._watchdog: obs_telemetry.StepWatchdog | None = None
        self._last_step_end: float | None = None
        self._between_s = 0.0

        self.optimizer = make_optimizer(
            learning_rate=args.job.learning_rate,
            warmup_steps=args.job.warmup_steps,
            weight_decay=args.job.weight_decay,
            max_grad_norm=args.job.max_grad_norm,
            frozen=getattr(self.model, "frozen_param_names", ()),
        )
        if agent_pipe is not None:
            ReconfigurationEngine(self, agent_pipe)

    # ------------------------------------------------------------------ #

    def _profile_tag(self) -> str:
        """The planner's profile of this job: by model tag, the job's
        sequence length where it states one, and the execution knobs."""
        return effective_tag(
            job_tag(self.args.model.model_tag, self.args.job.seq_len),
            self.args.execution)

    def initialize_distributed(self) -> None:
        """Bind to the visible devices and compute templates.

        Single-controller (default): all chips are local. Multi-host
        (OOBLECK_MULTIHOST=1): initialize the JAX runtime from the control
        plane's coordinator chain — the first host's worker announces
        `<its_ip>:port` through its agent pipe, the master relays it, and
        every worker passes it to jax.distributed.initialize. This is the
        TPU equivalent of the reference's rank-0 TCPStore port chain +
        NCCL world init (engine.py:563-593).
        """
        import os

        if (os.environ.get("OOBLECK_MULTIHOST") == "1"
                and self.agent_pipe is not None
                and not jax.distributed.is_initialized()):
            # Normally worker_main brought the runtime up before the engine
            # was built (backends must not initialize first); this is the
            # embedded-engine path.
            self._initialize_multihost()
        n_hosts = len(self.host_ips)
        multihost_world = (
            jax.process_count() > 1
            # A 1-host survivor world stays on the multihost path (degenerate
            # 1-process collectives) so mirror-based recovery still runs.
            or (os.environ.get("OOBLECK_MULTIHOST") == "1"
                and jax.distributed.is_initialized())
        )
        if (self._injected_devices is None and multihost_world
                and self.args.execution.resolved_path() == "mpmd"):
            # Multi-host MPMD: host h IS jax process h (worker_main passes
            # process_id = node_ips.index(agent_ip)). Order the global
            # device list host-major so rank = host * chips_per_host +
            # local, and bring up the cross-process comm backend.
            from oobleck_tpu.parallel.cross_host import ProcessComm

            if jax.process_count() != n_hosts:
                raise RuntimeError(
                    f"{jax.process_count()} jax processes != {n_hosts} hosts"
                )
            per_host = [
                sorted((d for d in jax.devices() if d.process_index == p),
                       key=lambda d: d.id)
                for p in range(n_hosts)
            ]
            if len({len(l) for l in per_host}) != 1:
                raise RuntimeError(
                    f"uneven chips per host: {[len(l) for l in per_host]}"
                )
            self.devices = [d for l in per_host for d in l]
            self.chips_per_host = len(per_host[0])
            self.multihost = True
            self.comm = ProcessComm()
            self._broadcast_profiles()
            self._measure_cross_host_allreduce()
        else:
            self.devices = (
                list(self._injected_devices)
                if self._injected_devices is not None
                else list(jax.devices())
            )
            if len(self.devices) % n_hosts != 0:
                raise ValueError(
                    f"{len(self.devices)} devices not divisible by "
                    f"{n_hosts} hosts"
                )
            self.chips_per_host = len(self.devices) // n_hosts

        if self.args.execution.resolved_path() == "fused":
            # Fused path: one global mesh instead of per-pipeline templates;
            # geometry comes from ExecutionArguments at instantiation time.
            self._fused_hosts = list(range(n_hosts))
            return

        self.templates = self._generate_templates(n_hosts)
        logger.info("templates for host counts %s",
                    [t.num_hosts for t in self.templates])

    @obs_spans.span("engine.plan")
    def _generate_templates(self, max_hosts: int) -> list[PipelineTemplate]:
        """Pipeline templates for every feasible host count in
        [compute_min_hosts(), max_hosts]. Deterministic in its inputs
        (profiles, chip geometry, execution knobs), which is what lets
        grow re-instantiation regenerate with a LARGER ceiling and get the
        existing templates back bit-for-bit plus the new sizes — plan
        parity with a fresh larger-fleet bring-up holds by construction
        (_ensure_templates_for)."""
        min_hosts = self.compute_min_hosts()
        gen = TemplateGenerator()
        # Interleaving changes the cost model (warmup ramp / v), so the
        # planner must rank stage partitions under the schedule that will
        # actually run them.
        vstages = self.args.execution.resolved_virtual_stages
        tp = self.args.execution.tensor_parallel
        sp = max(1, self.args.execution.sequence_parallel)
        unit = tp * sp
        if unit > 1:
            # TP*SP groups are the planning unit: templates are generated
            # over chips_per_host // (tp*sp) "chip groups" and scaled back,
            # so every stage's chip count factors into its (fsdp, seq,
            # tensor) stage mesh.
            if self.chips_per_host % unit != 0:
                raise ValueError(
                    f"chips_per_host={self.chips_per_host} not divisible by "
                    f"tensor_parallel*sequence_parallel={tp}*{sp}"
                )
            base = gen.create_pipeline_templates(
                self.profiles, (min_hosts, max_hosts),
                self.chips_per_host // unit, virtual_stages=vstages,
            )
            templates = [_scale_template_chips(t, unit) for t in base]
        else:
            templates = gen.create_pipeline_templates(
                self.profiles, (min_hosts, max_hosts), self.chips_per_host,
                virtual_stages=vstages,
            )
        if not templates:
            raise RuntimeError(
                f"no feasible pipeline templates for hosts in "
                f"[{min_hosts}, {max_hosts}] x {self.chips_per_host} chips"
            )
        num_stages = self.args.execution.num_stages
        if num_stages > 0:
            filtered = [t for t in templates
                        if len(t.stages) == num_stages]
            if not filtered:
                raise RuntimeError(
                    f"execution.num_stages={num_stages} matches no feasible "
                    f"template (stage counts available: "
                    f"{sorted({len(t.stages) for t in templates})})"
                )
            templates = filtered
        return templates

    def _ensure_templates_for(self, n_hosts: int) -> None:
        """Raise the template ceiling to cover `n_hosts`. Templates were
        generated only up to the STARTUP fleet size (the reference never
        grows, so neither did the generator call); growing past that
        ceiling re-runs the generator with the same inputs and a larger
        range — the overlapping templates come back identical, so every
        cached plan/executable keyed on them stays valid."""
        if self.templates and max(
                t.num_hosts for t in self.templates) >= n_hosts:
            return
        self.templates = self._generate_templates(n_hosts)
        logger.info("templates extended for host counts %s",
                    [t.num_hosts for t in self.templates])

    def _broadcast_profiles(self) -> None:
        """Adopt process 0's layer profile on every process. Planning is
        cost-driven; per-process timing noise would otherwise produce
        different templates/plans per process and the global schedule (whose
        cross-process collectives rely on identical interpretation order)
        would diverge. One collective, at startup only.

        Timings ride an f32 lane; byte counts (mem_params/mem_activation)
        ride an exact int32 lane as two 31-bit halves — f32 silently rounds
        integers past 2**24 (16 MiB, routine for real layers), quietly
        perturbing the planner's memory-feasibility inputs (round-4
        advisor, low), and a single int32 lane would cap layers at 2 GiB
        (real for wide-vocab embeddings / long-context activations)."""
        import dataclasses

        vec: list[float] = []
        ints: list[int] = []
        for p in self.profiles:
            vec.extend([p.forward, p.backward])
            vec.extend(v for _, v in sorted(p.allreduce_in_host.items()))
            vec.extend(v for _, v in sorted(p.allreduce_across_hosts.items()))
            for v in (p.mem_params, p.mem_activation):
                ints.extend([v & 0x7FFFFFFF, v >> 31])  # lo, hi (< 2**62)
        # Profile broadcast happens once per reconfiguration, off the step
        # loop; the inputs are host floats, not device buffers.
        arr = np.asarray(vec, np.float32)  # oobleck: allow[OBL002] -- cold reconfigure path
        iarr = np.asarray(ints, np.int32)  # oobleck: allow[OBL002] -- cold reconfigure path
        if self.comm.process_index != 0:
            arr = np.zeros_like(arr)
            iarr = np.zeros_like(iarr)
        total = self.comm.group_sum(arr, arr.shape[0],
                                    range(self.comm.process_count))
        itotal = self.comm.group_sum(iarr, iarr.shape[0],
                                     range(self.comm.process_count),
                                     dtype=jnp.int32)
        it = iter(total.tolist())
        iit = iter(itotal.tolist())

        def next_int() -> int:
            lo, hi = next(iit), next(iit)
            return (int(hi) << 31) | int(lo)

        adopted = []
        for p in self.profiles:
            fwd, bwd = next(it), next(it)
            in_host = {k: next(it) for k in sorted(p.allreduce_in_host)}
            across = {k: next(it) for k in sorted(p.allreduce_across_hosts)}
            mp, ma = next_int(), next_int()
            adopted.append(dataclasses.replace(
                p, forward=fwd, backward=bwd,
                mem_params=mp, mem_activation=ma,
                allreduce_in_host=in_host, allreduce_across_hosts=across,
            ))
        self.profiles = adopted

    def _measure_cross_host_allreduce(self) -> None:
        """Replace the profile's modeled DCN allreduce costs with MEASURED
        psums over the live process meshes (the same collectives DP sync
        rides), then adopt process 0's measurements everywhere so plans
        stay identical. The reference feeds its planner measured cross-node
        allreduce latencies (profiler.py:141-234); before this, multi-host
        plan quality rested on hardcoded DCN_BW/DCN_LAT_MS constants
        (round-4 missing #2). The measured table is persisted to
        allreduce_across_nodes.json with a "measured" flag so offline
        planning reuses real numbers."""
        import dataclasses

        from oobleck_tpu.planning.profiler import (
            get_profile_path, measure_allreduce_across_processes)

        P = self.comm.process_count
        if P < 2:
            return
        sizes = sorted({p.mem_params for p in self.profiles})
        path = get_profile_path(
            self.args.model.model_name,
            self._profile_tag(),
        )
        # Reuse a previously MEASURED table when process 0's cache holds
        # one covering this world size — a post-failure respawn re-enters
        # here and must not pay warmup+timed psums at real layer sizes
        # again (recovery latency is the headline metric). Only process 0
        # reads the file (caches are host-local); the flag + table ride
        # the same broadcast every startup cost does.
        flat = np.zeros(len(sizes) * (P - 1) + 1, np.float32)
        if self.comm.process_index == 0:
            cached = self._load_measured_allreduce(path, P)
            if cached is not None:
                flat[0] = 1.0
                for i, nbytes in enumerate(sizes):
                    for n in range(2, P + 1):
                        flat[1 + i * (P - 1) + (n - 2)] = cached[(nbytes, n)]
                logger.info(
                    "reusing measured cross-host allreduce profile from %s "
                    "(respawns skip re-measurement)", path,
                )
        have = self.comm.group_sum(flat[:1], 1, range(P))
        if have[0] < 1.0:
            table = measure_allreduce_across_processes(self.comm, sizes)
            if self.comm.process_index == 0:
                for i, nbytes in enumerate(sizes):
                    for n in range(2, P + 1):
                        flat[1 + i * (P - 1) + (n - 2)] = table[(nbytes, n)]
        flat = self.comm.group_sum(flat, flat.shape[0], range(P))[1:]
        by_size = {
            nbytes: {
                # oobleck: allow[OBL002] -- one-shot startup microbenchmark
                n: float(flat[i * (P - 1) + (n - 2)])
                for n in range(2, P + 1)
            }
            for i, nbytes in enumerate(sizes)
        }
        adopted = []
        for p in self.profiles:
            across = dict(p.allreduce_across_hosts)
            across.update(by_size[p.mem_params])
            across[1] = 0.0
            adopted.append(
                dataclasses.replace(p, allreduce_across_hosts=across)
            )
        self.profiles = adopted
        logger.info(
            "cross-host allreduce profile measured over %d processes "
            "(%d sizes); planner consumes measured DCN costs", P, len(sizes),
        )
        if self.comm.process_index == 0:
            try:
                # "measured_n" records how far the live measurement went:
                # rows keep modeled entries for n > P (offline planning
                # wants full coverage), so the flag alone must never let a
                # LARGER later world mistake those for measurements.
                rows = [
                    {**{str(k): v
                        for k, v in p.allreduce_across_hosts.items()},
                     "measured": True, "measured_n": P}
                    for p in self.profiles
                ]
                tmp = path / "allreduce_across_nodes.json.tmp"
                tmp.write_text(json.dumps(rows))
                tmp.rename(path / "allreduce_across_nodes.json")
            except OSError as e:
                logger.warning("could not persist measured allreduce "
                               "profile: %s", e)

    def _load_measured_allreduce(self, path, P: int
                                 ) -> dict[tuple[int, int], float] | None:
        """Previously MEASURED cross-host allreduce table from the profile
        cache, keyed (mem_params_bytes, n_hosts) — None unless every row is
        flagged "measured" AND its recorded measurement extent covers this
        world ("measured_n" >= P; rows also carry modeled entries for
        larger n, which must never pass as measurements). Modeled (offline)
        tables never short-circuit a live measurement."""
        f = path / "allreduce_across_nodes.json"
        if not f.exists():
            return None
        try:
            rows = json.loads(f.read_text())
        except (OSError, ValueError):
            return None
        if len(rows) != len(self.profiles):
            return None
        out: dict[tuple[int, int], float] = {}
        for p, row in zip(self.profiles, rows):
            if not row.get("measured") or int(row.get("measured_n", 0)) < P:
                return None
            for n in range(2, P + 1):
                if str(n) not in row:
                    return None
                # oobleck: allow[OBL002] -- parses JSON floats, no device value
                out[(p.mem_params, n)] = float(row[str(n)])
        return out

    def _initialize_multihost(self, timeout_s: float = 120.0) -> None:
        """Coordinator chain: host 0 announces, everyone initializes.

        Untested on real multi-host hardware (the chip runs so far are
        one process per machine); the chain mirrors the single-host path
        in elastic/ (worker -> agent -> master -> agents -> workers) that
        the multi-process CPU worlds exercise.
        """
        import socket
        import time as _time

        from oobleck_tpu.elastic.worker import (
            coordinator_address_if_current,
            coordinator_announcement,
        )

        world = len(self.host_ips)
        process_id = self.host_ips.index(self.agent_ip)
        if process_id == 0:
            port = 0
            with socket.socket() as s:
                s.bind(("", 0))
                port = s.getsockname()[1]
            address = f"{self.agent_ip}:{port}"
            self.agent_pipe.send(coordinator_announcement(address, world))
        else:
            # The ReconfigurationEngine thread owns the pipe; coordinator
            # messages arrive via the control queue it feeds.
            import queue as _queue

            deadline = _time.monotonic() + timeout_s
            address = None
            while _time.monotonic() < deadline:
                try:
                    msg = self._control_msgs.get(timeout=1.0)
                except _queue.Empty:
                    continue
                addr = coordinator_address_if_current(msg, world)
                if addr is not None:
                    address = addr
                    break
            if address is None:
                raise TimeoutError("no coordinator address from the agent")
        jax.distributed.initialize(
            coordinator_address=address,
            num_processes=len(self.host_ips),
            process_id=process_id,
        )
        logger.info("jax.distributed initialized: %s (process %d/%d)",
                    address, process_id, len(self.host_ips))

    def compute_min_hosts(self) -> int:
        """Memory lower bound on hosts per pipeline (reference
        engine.py:490-513): 6x param bytes + activations must fit."""
        total_mem = sum(6 * p.mem_params + p.mem_activation for p in self.profiles)
        dev = (self.devices or jax.local_devices())[0]
        if dev.platform == "cpu":
            hbm = CPU_TEST_HBM_BYTES
        else:
            # A plan sized against a guessed memory either wastes hosts or
            # fails at the first allocation: no limit reported is an error.
            stats = dev.memory_stats() or {}
            if "bytes_limit" not in stats:
                raise RuntimeError(
                    f"{dev} reports no memory limit (memory_stats: "
                    f"{sorted(stats)}); cannot bound hosts per pipeline")
            hbm = stats["bytes_limit"]
        per_host = hbm * (self.chips_per_host or 1)
        return max(1, -(-total_mem // per_host))

    # ------------------------------------------------------------------ #

    def _restore_durable_state(self) -> dict | None:
        """ONE restore API over both persistence planes: live-state
        mirrors (peer recovery, freshest) and the durable checkpoint plane
        (survives whole-slice loss). The freshest source wins per the step
        election; checkpoint state fills layers no surviving mirror holds."""
        restored = self.try_restore_checkpoint()
        if self.multihost and self.args.execution.mirror_dir:
            # Collective — every process calls regardless of mirror state.
            mirrored = self._try_restore_mirror()
            if mirrored is not None and (
                restored is None
                or mirrored["meta"]["step"] >= restored["meta"]["step"]
            ):
                if restored is not None:
                    # Layers absent from every mirror keep checkpoint state.
                    for li, v in restored["params"].items():
                        mirrored["params"].setdefault(li, v)
                    for li, v in restored["opt"].items():
                        mirrored["opt"].setdefault(li, v)
                logger.info(
                    "recovered live state from surviving mirrors (step %s, "
                    "checkpoint-free)", mirrored["meta"]["step"],
                )
                restored = mirrored
                # This world exists because a peer died: the first step it
                # completes closes the RECOVERY_DEADLINE chain.
                self._recovering = True
                self._recovered_at = time.monotonic()
        return restored

    @obs_spans.span("engine.instantiate")
    def instantiate_pipelines(self, global_num_microbatch: int,
                              num_iterations_done: int = 0, epoch: int = 0) -> None:
        old_params = old_opt = None
        restored = self._restore_durable_state()
        if restored is not None:
            old_params = restored["params"]
            # Optimizer leaves were stored flat; rebuild the optax structure.
            old_opt = {}
            for li, leaves in restored["opt"].items():
                struct = jax.tree.structure(
                    jax.eval_shape(self.optimizer.init, old_params[li])
                )
                old_opt[li] = jax.tree.unflatten(struct, leaves)
            meta = restored["meta"]
            self.step = int(meta["step"])
            num_iterations_done = int(meta["num_iterations_done"])
            epoch = int(meta["epoch"])

        if self.args.execution.resolved_path() == "fused":
            payload = None
            if restored is not None:
                payload = {"params": old_params, "opt": old_opt,
                           "meta": {"step": self.step}}
            self._materialize_fused(global_num_microbatch,
                                    num_iterations_done, epoch, payload)
            self._set_template_gauge()
            return

        ar_across = [p.allreduce_across_hosts for p in self.profiles]
        self.plan = PipelineInstantiator().get_best_execution_plan(
            self.templates, ar_across, len(self.host_ips), global_num_microbatch
        )
        logger.info("execution plan: %s", self.plan)
        self._materialize_plan(self.plan, num_iterations_done, epoch,
                               old_params=old_params, old_opt=old_opt)
        self._set_template_gauge()

    def _fused_devices(self) -> list:
        return [
            d
            for h in self._fused_hosts
            for d in self.devices[h * self.chips_per_host:
                                  (h + 1) * self.chips_per_host]
        ]

    def _fused_mesh(self, devices: list, *, shrink_to_fit: bool):
        """Resolve ExecutionArguments into a global fused mesh over `devices`.

        fsdp=-1 means "the chips left after stage*tensor*seq" (ZeRO-style
        param sharding, matching the MPMD meaning of -1); data absorbs any
        explicit-fsdp remainder. The fused step shards each microbatch's
        sample dim over (data, fsdp), so microbatch_size must divide by
        their product — a config error at startup, but during recovery
        (`shrink_to_fit`) the mesh drops chips instead of crashing the
        training loop it exists to save."""
        from oobleck_tpu.parallel.mesh import MeshShape, make_mesh

        ex = self.args.execution
        mb = self.args.job.microbatch_size
        stage = ex.num_stages if ex.num_stages > 0 else 1
        base = stage * ex.tensor_parallel * ex.sequence_parallel
        if len(devices) < base:
            raise RuntimeError(
                f"{len(devices)} devices cannot fit stage*tensor*seq={base}"
            )
        if self.seq_len % ex.sequence_parallel != 0:
            raise ValueError(
                f"seq_len={self.seq_len} not divisible by "
                f"sequence_parallel={ex.sequence_parallel}"
            )
        hidden = int(getattr(self.model.config, "hidden_size", 0) or 0)
        if ex.fsdp > 0:
            fsdp = ex.fsdp
            data = len(devices) // (base * fsdp)
            if data < 1:
                raise RuntimeError(
                    f"{len(devices)} devices cannot fit "
                    f"stage*tensor*seq*fsdp={base * fsdp}"
                )
        else:
            # Free fsdp: maximize chips used subject to BOTH divisibility
            # constraints (batch dim over data*fsdp, hidden dim over fsdp),
            # preferring larger fsdp (ZeRO memory savings) on ties. The old
            # "fsdp = all remaining chips" choice produced XLA sharding
            # errors whenever hidden_size wasn't divisible by the remainder.
            data, fsdp = _best_data_fsdp(len(devices) // base, mb, hidden)
            if not shrink_to_fit and data * fsdp * base < len(devices):
                # A config that strands chips must stay a LOUD startup
                # error (recovery is the only time quietly dropping chips
                # beats crashing the run it exists to save).
                raise ValueError(
                    f"no (data, fsdp) split uses all {len(devices)} devices: "
                    f"best uses {data * fsdp * base} "
                    f"(microbatch_size={mb} must divide by data*fsdp and "
                    f"hidden_size={hidden} by fsdp); adjust microbatch_size "
                    "or pin stage/tensor/seq via ExecutionArguments"
                )
        if mb % (data * fsdp) != 0 and not shrink_to_fit:
            raise ValueError(
                f"microbatch_size={mb} not divisible by data*fsdp="
                f"{data * fsdp}: the fused path shards each microbatch's "
                "sample dim over (data, fsdp); raise microbatch_size or "
                "pin more devices to stage/tensor/seq via "
                "ExecutionArguments"
            )
        if shrink_to_fit and (
            mb % (data * fsdp) != 0 or data * fsdp * base < len(devices)
        ):
            # Recovery re-plan: instead of only shrinking `data` (which can
            # strand chips, round-3 weak #7), search every feasible
            # (stage, fsdp, data) — stage must divide the model's blocks AND
            # the microbatch count; data*fsdp must divide microbatch_size —
            # and keep the one using the MOST surviving chips, preferring
            # the configured stage count on ties.
            num_mb = self.fused.num_microbatches if self.fused else 1
            layers = getattr(self.model.config, "num_layers", stage)
            best = None
            for s in range(1, len(devices) // (ex.tensor_parallel
                                               * ex.sequence_parallel) + 1):
                if layers % s or num_mb % s:
                    continue
                s_base = s * ex.tensor_parallel * ex.sequence_parallel
                cap = len(devices) // s_base
                if cap < 1:
                    continue
                if ex.fsdp > 0:
                    if mb % ex.fsdp:
                        continue
                    d = next((d for d in range(cap // ex.fsdp, 0, -1)
                              if mb % (d * ex.fsdp) == 0), 0)
                    if not d:
                        continue
                    cand = (d, ex.fsdp)
                else:
                    cand = _best_data_fsdp(cap, mb, hidden)
                used_chips = cand[0] * cand[1] * s_base
                rank = (used_chips, s == stage, -abs(s - stage))
                if best is None or rank > best[0]:
                    best = (rank, s, cand)
            if best is None:
                raise RuntimeError(
                    f"microbatch_size={mb} admits no runnable recovery mesh "
                    f"over {len(devices)} devices"
                )
            _, new_stage, (data, fsdp) = best
            if new_stage != stage:
                logger.warning(
                    "recovery re-plan: stage %d -> %d to reclaim chips",
                    stage, new_stage,
                )
                stage = new_stage
                base = stage * ex.tensor_parallel * ex.sequence_parallel
        used = data * fsdp * base
        if used < len(devices):
            logger.warning(
                "fused mesh uses %d of %d devices", used, len(devices)
            )
        shape = MeshShape(data=data, stage=stage, fsdp=fsdp,
                          seq=ex.sequence_parallel, tensor=ex.tensor_parallel)
        return make_mesh(shape, devices[:used])

    def _prefetch_enabled(self) -> bool:
        """Device-side input staging (execution/dataloader.DeviceStager):
        a background thread shapes AND device_puts iteration N+1's
        microbatches while step N computes. Default ON single-controller,
        OFF under jax.distributed (a staging thread issuing device_puts
        next to collectives is a hang risk not worth the default);
        OOBLECK_PREFETCH=0/1 overrides either way."""
        import os

        v = os.environ.get("OOBLECK_PREFETCH")
        if v is not None:
            return v.lower() not in ("0", "false", "no")
        return not self.multihost

    def _effective_virtual_stages(self, num_stages: int,
                                  num_microbatches: int,
                                  pipeline_index: int,
                                  record: bool = True) -> int:
        """The virtual-stage degree a pipeline can actually run: the
        configured one when its constraints hold (microbatches divisible by
        stages, enough layers), else 1 — with a flight-recorder event so a
        silent fallback after reconfiguration is diagnosable. The recovery
        precompiler calls this with record=False for PREDICTED plans (same
        decision, hence same program keys, without logging a fallback
        that has not happened)."""
        v = self.args.execution.resolved_virtual_stages
        if v <= 1 or num_stages <= 1:
            return 1
        reason = None
        if num_microbatches % num_stages != 0:
            reason = (f"num_microbatches {num_microbatches} not divisible "
                      f"by num_stages {num_stages}")
        elif self.model.num_pipeline_layers < num_stages * v:
            reason = (f"{self.model.num_pipeline_layers} pipeline layers < "
                      f"num_stages*virtual_stages {num_stages * v}")
        if reason is None:
            return v
        if record:
            logger.warning(
                "pipeline %d: interleaved schedule unavailable (%s); "
                "falling back to 1f1b", pipeline_index, reason,
            )
            metrics.flight_recorder().record(
                "interleave_fallback", pipeline=pipeline_index,
                requested=v, reason=reason, step=self.step,
            )
        return 1

    def _materialize_fused(self, global_num_microbatch: int,
                           num_iterations_done: int, epoch: int,
                           restored: dict | None) -> None:
        from oobleck_tpu.execution.fused import FusedPipeline

        mesh = self._fused_mesh(self._fused_devices(), shrink_to_fit=False)
        logger.info("fused mesh: %s", dict(mesh.shape))
        self.fused = FusedPipeline(
            self.model, mesh, num_microbatches=global_num_microbatch,
            microbatch_size=self.args.job.microbatch_size,
            seq_len=self.seq_len, optimizer=self.optimizer,
            restored=restored,
            overlap=self.args.execution.overlap_config(),
        )
        self.dataloaders = [self._fused_dataloader(
            global_num_microbatch, num_iterations_done, epoch)]
        self.pipelines = []
        self.dp_engine = None

    def _fused_dataloader(self, global_num_microbatch: int,
                          num_iterations_done: int, epoch: int):
        """A loader for the CURRENT self.fused — the stager's place_fn is
        bound to the fused pipeline's mesh, so reconfiguration must rebuild
        it (a batch staged for the old mesh carries the old sharding)."""
        sampler = OobleckSampler(
            num_samples=len(self.dataset) - self._eval_reserve(),
            microbatch_size=self.args.job.microbatch_size,
            pipeline_index=0,
            num_microbatches=[global_num_microbatch],
            num_iterations_done=num_iterations_done,
            epoch=epoch,
        )
        loader = OobleckDataLoader(self.dataset, sampler)
        if self._prefetch_enabled():
            return DeviceStager(loader, self.fused.place_batch)
        return PrefetchingLoader(loader)

    def _materialize_plan(self, plan: HeterogeneousPlan, num_iterations_done,
                          epoch, old_params, old_opt,
                          host_assignment: list[list[int]] | None = None) -> None:
        assignments = plan.assignments(
            ranks=None if host_assignment is None else [
                hosts_to_ranks(hosts, self.chips_per_host)
                for hosts in host_assignment
            ]
        )
        num_mb_list = [a.num_microbatches for a in assignments]
        total_mb = plan.total_num_microbatches
        self.pipelines = []
        for old_dl in self.dataloaders:
            if hasattr(old_dl, "close"):
                old_dl.close()
        self.dataloaders = []
        self.opt_states = {}
        train_samples = len(self.dataset) - self._eval_reserve()
        process_of_rank = (
            [r // self.chips_per_host for r in range(len(self.devices))]
            if self.multihost else None
        )
        for a in assignments:
            pipe = PipelineInstance(
                pipeline_id=a.pipeline_index,
                template=a.template,
                ranks=list(a.ranks),
                model=self.model,
                devices=self.devices,
                num_microbatches=a.num_microbatches,
                total_num_microbatches=total_mb,
                microbatch_size=self.args.job.microbatch_size,
                seq_len=self.seq_len,
                params=old_params,
                tensor_parallel=self.args.execution.tensor_parallel,
                sequence_parallel=self.args.execution.sequence_parallel,
                fsdp=self.args.execution.fsdp,
                process_of_rank=process_of_rank,
                comm=self.comm,
                virtual_stages=self._effective_virtual_stages(
                    a.template.num_stages, a.num_microbatches,
                    a.pipeline_index,
                ),
            )
            self.pipelines.append(pipe)
            # Train over the head split only; the tail is evaluate()'s
            # held-out reserve.
            sampler = OobleckSampler(
                num_samples=train_samples,
                microbatch_size=self.args.job.microbatch_size,
                pipeline_index=a.pipeline_index,
                num_microbatches=num_mb_list,
                num_iterations_done=num_iterations_done,
                epoch=epoch,
            )
            loader = OobleckDataLoader(self.dataset, sampler)
            # Double-buffering only pays where batches are consumed;
            # non-participating pipelines only track position (advance()).
            if not self.multihost or pipe.participates_locally:
                if self._prefetch_enabled():
                    loader = DeviceStager(
                        loader,
                        lambda b, _p=pipe: _p._place_batch(
                            _p._as_batch_dict(b))[0],
                    )
                else:
                    loader = PrefetchingLoader(loader)
            self.dataloaders.append(loader)
            if old_opt is not None:
                # Optimizer state mirrors params: re-place each layer's state
                # on its new stage sharding (surviving state is reused, as the
                # reference reuses surviving ranks' optimizer objects,
                # pipeline.py:509-519).
                self.opt_states[pipe.pipeline_id] = {
                    li: _place_opt_state(
                        self.optimizer, old_opt[li],
                        pipe.stages[pipe.stage_of_layer(li)].param_shardings[li],
                    )
                    for li in pipe.params
                }
            else:
                self.opt_states[pipe.pipeline_id] = pipe.init_opt_state(self.optimizer)
        self.dp_engine = (
            MultiHostDataParallelEngine(self.pipelines, self.model, self.comm)
            if self.multihost else DataParallelEngine(self.pipelines)
        )

    # ------------------------------------------------------------------ #

    def _defer_losses(self) -> bool:
        """Whether steady-state steps keep losses on-device. The multihost
        MPMD step cannot defer: its loss rides the gradient allreduce as a
        host-side collective value (_train_step_multihost)."""
        return (self.args.execution.loss_readback_every > 1
                and not self.multihost)

    def _wait_staged_inputs(self) -> None:
        """Pre-fence handshake with the input stagers: let every
        in-flight DeviceStager grab finish placing before the train
        thread takes the step's device_work fence (the stager needs the
        fence to place, so waiting on its future while holding the fence
        is a deadlock)."""
        for dl in self.dataloaders:
            if isinstance(dl, DeviceStager):
                dl.wait_staged()

    def _staged_batch(self, dl):
        """(host_batch, placed_or_None) from a loader, observing the input
        wait when a DeviceStager fronted it."""
        with obs_spans.region("engine.staging"):
            if isinstance(dl, DeviceStager):
                batch, placed = dl.next_placed()
                self._m_input_wait.observe(dl.last_wait_s)
                self._data_wait_s += dl.last_wait_s
                return batch, placed
            return dl.next_batch(), None

    def _train_step(self) -> "float | DeferredLoss":
        with obs_spans.region("engine.step"):
            if self.fused is not None:
                batch, placed = self._staged_batch(self.dataloaders[0])
                with obs_spans.region("engine.fused_step"):
                    loss = self.fused.train_step(batch, placed=placed)
                self.step += 1
                if self._defer_losses():
                    return DeferredLoss([(loss, 1)])
                return _host_sync(loss)     # no routed experts run fused

            if self.multihost:
                return self._train_step_multihost()

            losses = []
            weights = []
            stall_s = 0.0
            for pipe, dl in zip(self.pipelines, self.dataloaders):
                batch, placed = self._staged_batch(dl)
                losses.append(pipe.train_step(batch, placed=placed))
                weights.append(pipe.num_microbatches)
                stall_s += pipe.last_dispatch_stall_s
            with obs_spans.region("dp.allreduce"):
                synced = self.dp_engine.do_allreduce()
            with obs_spans.region("engine.optimizer"):
                for pipe in self.pipelines:
                    self.opt_states[pipe.pipeline_id] = pipe.apply_updates(
                        self.optimizer, self.opt_states[pipe.pipeline_id],
                        synced[pipe.pipeline_id],
                    )
            self._m_dispatch_stall.observe(stall_s)
            self.step += 1
            load = StepLoad(self.pipelines)
            if self._defer_losses():
                return DeferredLoss(list(zip(losses, weights)), load)
            total = sum(w for w in weights)
            loss = sum(
                _host_sync(l) * w for l, w in zip(losses, weights)) / total
            self._record_load(self.step, load)
            return loss

    def _record_load(self, step: int, load: "StepLoad | None") -> None:
        """Step `step`'s loss has been read: read where it routed, into
        the telemetry ring."""
        if load:
            obs_telemetry.telemetry().record_load(step, load.read())

    def _train_step_multihost(self) -> float:
        """One step across the jax.distributed world: every process
        interprets every pipeline (executing only its own stages and the
        cross-process edges it borders), then ONE flat allreduce syncs all
        layer grads and the per-pipeline losses, then each process steps its
        local layers. The reference's cross-node train step decomposes the
        same way (pipeline.train per rank + DataParallelEngine.do_allreduce,
        engine.py:645-649)."""
        local_losses: dict[int, tuple[float, int]] = {}
        for pipe, dl in zip(self.pipelines, self.dataloaders):
            # EVERY process advances EVERY sampler in lockstep
            # (deterministic positions), but only participants pay for
            # batch materialization — non-owners advance position only.
            if not pipe.participates_locally:
                dl.advance()
                continue
            with obs_spans.region("engine.staging"):
                batch = dl.next_batch()
            loss = pipe.train_step(batch)
            if loss is not None:
                local_losses[pipe.pipeline_id] = (
                    _host_sync(loss), pipe.num_microbatches
                )
        with obs_spans.region("dp.allreduce"):
            synced, global_loss = self.dp_engine.allreduce(local_losses)
        with obs_spans.region("engine.optimizer"):
            for pipe in self.pipelines:
                if pipe.participates_locally:
                    self.opt_states[pipe.pipeline_id] = pipe.apply_updates(
                        self.optimizer, self.opt_states[pipe.pipeline_id],
                        synced[pipe.pipeline_id],
                    )
        self.step += 1
        self._record_load(self.step, StepLoad(self.pipelines))
        return global_loss

    def _set_template_gauge(self) -> None:
        """Current pipeline layout for /status: labels describe the plan,
        the value is the step it was adopted at (the master picks the
        series with the highest value as current)."""
        if self.plan is not None:
            self._m_template.set(
                self.step,
                pipelines=str(self.plan.total_num_pipelines),
                stages="/".join(str(t.num_stages)
                                for t in self.plan.instances),
                microbatches="/".join(str(m)
                                      for m in self.plan.num_microbatches),
                hosts=str(len(self.host_ips)),
            )
            # Refresh the projected reroute-retention gauge for the NEW
            # topology (a representative single-host loss): the master's
            # policy scorer reads it from the next snapshot push, so its
            # decisions price degraded throughput from the live plan, not
            # a prior.
            if self.pipelines and self.host_ips:
                self._projected_degrade_retention([self.host_ips[0]])
        elif self.fused is not None:
            self._m_template.set(
                self.step, path="fused", hosts=str(len(self.host_ips)))
        # Plan adoption changed what lives on-device: refresh the
        # live-bytes telemetry estimate at the next step sample.
        self._live_bytes_stale = True

    def _flops_info(self):
        """(flops_per_token, peak_flops_per_chip|None, n_chips) for the MFU
        gauge; None when the model defies the 6N estimate (cached)."""
        if self._flops_cache is not _UNSET:
            return self._flops_cache
        try:
            from oobleck_tpu.parallel.train import (
                count_params,
                estimate_flops_per_token,
                peak_flops,
            )

            cfg = self.model.config
            # Of a model that repeats layers: the parameters a token is
            # multiplied through and the attention layers it visits, each
            # once a pass. Of every other model, its parameters and layers.
            passes = repeated(self.model)[1]
            fpt = estimate_flops_per_token(
                applied_param_count(self.model) if passes > 1
                else count_params(self.model), self.seq_len,
                num_layers=passes * getattr(cfg, "num_layers", 0),
                hidden_size=getattr(cfg, "hidden_size", 0),
            )
        except Exception as e:  # MFU is best-effort; training never pays
            logger.info("MFU estimate unavailable: %s", e)
            self._flops_cache = None
            return None
        devices = self.devices or jax.devices()
        # Outside the catch: a TPU kind missing from the peak table is a
        # fault to repair, not an MFU to drop quietly.
        peak = (peak_flops(devices[0].device_kind)
                if devices[0].platform == "tpu" else None)
        self._flops_cache = (fpt, peak, len(devices))
        return self._flops_cache

    def _bubble_fractions(self, step_s: float) -> dict[str, float]:
        """kind=schedule: the closed form (S-1)/(vM+S-1), microbatch-
        weighted over pipelines. kind=measured: replay of the measured
        per-(stage, chunk) fwd/bwd dispatch durations through the
        schedule's dependency graph (schedule.simulate_bubble) — this
        isolates the schedule-shape bubble from host serialization, which
        a raw busy/step wall-clock ratio cannot do when one process
        dispatches every stage. Falls back to 1 - busy/(S*step) when no
        per-op times exist."""
        from oobleck_tpu.execution.schedule import (
            Op,
            bubble_fraction,
            simulate_bubble,
        )

        out: dict[str, float] = {}
        sched_num = sched_den = 0.0
        sim_num = sim_den = 0.0
        busy_s = 0.0
        busy_slots = 0
        for pipe in self.pipelines:
            s = pipe.num_stages
            m = pipe.num_microbatches
            v = getattr(pipe, "virtual_stages", 1)
            if m + s > 1:
                sched_num += m * bubble_fraction(s, m, v)
                sched_den += m
            op_times = getattr(pipe, "last_op_times", None)
            if op_times:
                def dur(inst, _t=op_times):
                    kind = "f" if inst.op is Op.FORWARD else "b"
                    tot, n = _t.get((inst.stage, inst.chunk, kind),
                                    (0.0, 0))
                    if n:
                        return tot / n
                    vals = [t / c for (_, _, k), (t, c) in _t.items()
                            if k == kind and c]
                    return sum(vals) / len(vals) if vals else 1.0

                try:
                    sim_num += m * simulate_bubble(s, m, v, dur)
                    sim_den += m
                except RuntimeError:  # replay deadlock: fall through
                    pass
            if pipe.last_stage_busy_s:
                busy_s += sum(pipe.last_stage_busy_s.values())
                busy_slots += s
        if sched_den:
            out["schedule"] = sched_num / sched_den
        if sim_den:
            out["measured"] = sim_num / sim_den
        elif busy_slots and step_s > 0:
            out["measured"] = max(0.0, 1.0 - busy_s / (busy_slots * step_s))
        return out

    def _record_step_metrics(self, loss: "float | None",
                             step_s: float) -> None:
        """Per-step timing/throughput metrics; loss is None while its
        readback is deferred (the gauge updates at drain time)."""
        self._m_steps.inc()
        self._m_step_seconds.observe(step_s)
        if loss is not None:
            self._m_loss.set(loss)
        if step_s > 0:
            tokens = self.args.job.global_microbatch_size * self.seq_len
            tps = tokens / step_s
            self._m_tokens_per_sec.set(tps)
            info = self._flops_info()
            if info is not None:
                from oobleck_tpu.parallel.train import mfu_estimate

                fpt, peak, n_chips = info
                mfu = mfu_estimate(tps, fpt, n_chips, peak)
                if mfu is not None:
                    self._m_mfu.set(mfu)
                    self._last_mfu = mfu
        fracs = self._bubble_fractions(step_s)
        for kind, frac in fracs.items():
            self._m_bubble.set(frac, kind=kind)
        self._record_telemetry(step_s, fracs.get("measured", 0.0))

    def _record_telemetry(self, step_s: float,
                          bubble_frac: float) -> None:
        """Feed the fleet-health planes one step's worth of wall-clock:
        a per-host sample into the telemetry ring (the compact digest
        rides the agent's next heartbeat to the master's FleetTracker)
        and the matching split into the goodput ledger. Everything here
        is host arithmetic over already-host values — no device syncs
        (obs/telemetry.py is under the OBL002 fence)."""
        if self._live_bytes_stale:
            self._live_bytes_stale = False
            self._live_bytes = self._estimate_live_bytes()
        compute_s = comm_s = 0.0
        for pipe in self.pipelines:
            c, m = pipe.op_time_split()
            compute_s += c
            comm_s += m
        # Checkpoint flushes land outside step_s (step-boundary stalls),
        # so they are a separate ledger bucket, not a step subdivision.
        ckpt_s = sum(self.ckpt_stall_s[self._ckpt_stall_seen:])
        self._ckpt_stall_seen = len(self.ckpt_stall_s)
        sample = obs_telemetry.telemetry().record_step(
            self.step, step_s, compute_s=compute_s, comm_s=comm_s,
            data_wait_s=self._data_wait_s, ckpt_s=ckpt_s,
            live_bytes=self._live_bytes, between_s=self._between_s,
            phases=obs_telemetry.phases_of(self._step_acc.seconds),
            hbm=_hbm_sample())
        if self._watchdog is not None:
            self._watchdog.step_recorded(sample)
        self._ledger.account_step(step_s, bubble_frac=bubble_frac,
                                  data_wait_s=self._data_wait_s)
        if ckpt_s > 0:
            self._ledger.account("checkpoint", ckpt_s)
        self._m_goodput.set(self._ledger.goodput_fraction())

    def _estimate_live_bytes(self) -> int:
        """Σ nbytes over this process's live params + optimizer leaves.
        Array.nbytes is shape/dtype metadata, not a device readback."""
        try:
            if self.fused is not None:
                st = self.fused.state
                leaves = (jax.tree.leaves(st.params)
                          + jax.tree.leaves(st.opt_state))
            else:
                leaves = []
                for pipe in self.pipelines:
                    leaves += jax.tree.leaves(pipe.params)
                    leaves += jax.tree.leaves(
                        self.opt_states.get(pipe.pipeline_id, {}))
            return sum(int(getattr(x, "nbytes", 0)) for x in leaves)
        except Exception:  # mid-reconfigure topology: skip this sample
            return 0

    def _drain_pending_losses(self, max_steps: int | None = None) -> None:
        """Resolve every deferred loss (one readback per step, but off the
        steady-state critical path): log each step's line in the classic
        format, update the loss gauge to the newest value, and append to
        loss_history. Resolution can fail after a reconfiguration freed
        the backing devices; those steps report as unavailable rather than
        killing the loop."""
        if not self._pending_losses:
            return
        if max_steps is None:
            max_steps = self.args.job.steps
        # The readbacks are device work: fence them so they can't
        # interleave with a stager placing the next batch (same runtime
        # race class as the precompile x checkpoint flake).
        with background.device_work("loss_drain"):
            for step_i, pending in self._pending_losses:
                try:
                    val = pending.resolve()
                    load = pending.resolve_load()
                except Exception as e:  # backing buffers gone (reconfig)
                    logger.warning(
                        "step %d loss unavailable (deferred readback: %s)",
                        step_i, e,
                    )
                    continue
                self.loss_history.append((step_i, val))
                self._m_loss.set(val)
                if load:
                    obs_telemetry.telemetry().record_load(step_i, load)
                logger.info("step %d/%d loss %.4f", step_i, max_steps, val)
        self._pending_losses.clear()

    def _commit_incident(self) -> None:
        """Close the open incident at the first post-recovery step: stamp
        the first_step mark, commit incident-<n>.json, and stage a digest
        for the next metrics push (the agent relays it to the master's
        /status forensics)."""
        inc = self._incident
        if inc is None:
            return
        self._incident = None
        t = inc.mark("first_step")
        obs_spans.span_recorder().record(
            "incident.first_step", t, t, trace_id=inc.trace_id,
            step=self.step)
        # Goodput attribution: the detect -> first_step window is wall-
        # clock this worker did not train. Charge it to the incident's
        # trace so the ledger, /status, and the committed record all
        # agree on what the incident cost.
        lost_s = inc.phase_breakdown().get("total_s", 0.0)
        if lost_s > 0:
            self._ledger.attribute(inc.trace_id, lost_s,
                                   cause=inc.cause or "")
        inc.goodput_cost = self._ledger.incident_cost(inc.trace_id)
        path = inc.commit()
        digest = {"trace_id": inc.trace_id, "lost_ip": inc.lost_ip,
                  "cause": inc.cause, "marks": dict(inc.marks),
                  **inc.phase_breakdown(), "committed_at": t}
        if path:
            digest["path"] = path
        self._incident_record = digest

    def _publish_metrics(self) -> None:
        """Ship the registry snapshot up the agent pipe (relayed to the
        master's /metrics) and append it to the JSONL sink."""
        snap = metrics.registry().snapshot()
        snap["step"] = self.step
        d = obs_telemetry.telemetry().digest()
        if d is not None:
            # The agent keeps the latest digest and epoch-stamps it onto
            # every heartbeat (TELEMETRY_KEY) — fleet health costs zero
            # extra control-plane messages.
            snap["telemetry"] = d
        snap["goodput"] = self._ledger.snapshot(mfu=self._last_mfu)
        if self._incident_record is not None:
            # One-shot piggyback, consumed only once the relay succeeds:
            # the master dedups by trace_id, so resending after a pipe
            # hiccup is safe while dropping the digest is not.
            snap["incident"] = self._incident_record
        if self.agent_pipe is not None:
            try:
                self.agent_pipe.send({"kind": "metrics", "snapshot": snap})
                self._incident_record = None
            except (OSError, ValueError):
                pass  # agent gone; the digest stays staged for next push
        else:
            self._incident_record = None  # no relay; the JSONL sink has it
        metrics.dump_jsonl(snap)

    def train(self) -> None:
        """Reference train loop (engine.py:651-668) + loss reporting and
        periodic checkpointing (capability the reference lacks)."""
        from oobleck_tpu.utils.tracing import StepTracer

        max_steps = self.args.job.steps
        interval = self.args.execution.checkpoint_interval
        sync_interval = self.args.execution.replica_sync_interval
        self._tracer = StepTracer()
        self._step_acc.install()
        self._last_step_end = time.perf_counter()
        plane = self._durable_plane()
        if plane is not None:
            # SIGTERM (TPU maintenance / preemption notice) drains the
            # in-flight snapshot before the process obeys the signal.
            plane.install_preemption_hook()
        # "engine.bookkeeping": from one step's end to the next step's
        # start (metrics, publish, checkpoint submit, the chaos and
        # reconfigure polls, the stagers' pre-fence handshake). It spans
        # the loop's back edge, so it is opened and closed by hand.
        bookkeeping = None
        try:
            while self.step < max_steps:
                self._tracer.on_step(self.step)
                self._maybe_chaos_kill_stage()
                self._maybe_chaos_kill_hosts()
                self._maybe_chaos_join()
                self._maybe_spot_expire()
                self._maybe_reconfigure()
                self._maybe_grow()
                self._maybe_inplace_degrade()
                if self._drain_requested:
                    # Preemption drain (or in-place-degrade victim): flush
                    # durable state and leave cleanly — the agent reports
                    # JOB_DONE, not a failure.
                    logger.warning(
                        "drain requested: flushing durable state and "
                        "exiting cleanly at step %d", self.step)
                    self.save_checkpoint(wait=True)
                    metrics.flight_recorder().record(
                        "drain_complete", ip=self.agent_ip, step=self.step)
                    break
                # Fault-injection points (utils/chaos.py): the barrier ip/
                # ordinal selectors let a test SIGKILL exactly one worker at
                # exactly one step boundary.
                chaos().barrier("step_start", ip=self.agent_ip)
                # Fence the step dispatch against background XLA work
                # (recovery precompiles, mirror device_get, input staging)
                # — see utils/background.py. The stagers place under their
                # own fence hold, so the in-flight grab must finish BEFORE
                # we take the fence; waiting inside it would deadlock.
                # t0 sits inside the fence so step_s measures the step,
                # not lock contention (the wait is flight-recorded
                # separately as background_work_wait).
                self._wait_staged_inputs()
                self._data_wait_s = 0.0
                if bookkeeping is not None:
                    bookkeeping.__exit__(None, None, None)
                if self._watchdog is None:
                    self._watchdog = self._start_watchdog()
                with background.device_work("train_step"):
                    self._step_acc.begin()
                    self._watchdog.step_opens(self.step + 1)
                    t0 = time.perf_counter()
                    loss = self._train_step()
                    t1 = time.perf_counter()
                    self._watchdog.step_closes()
                step_s = t1 - t0
                self._between_s = t0 - self._last_step_end
                self._last_step_end = t1
                if self._tracer.stall_open:
                    self._tracer.close_stall_window()
                bookkeeping = obs_spans.region("engine.bookkeeping")
                bookkeeping.__enter__()
                factor = chaos().slow_factor(self.agent_ip)
                if factor is not None:
                    # Gray-failure injection: stretch this host's step by
                    # sleeping host-side (no device sync involved), so the
                    # telemetry sample reports the same wall time a
                    # genuinely degraded host would.
                    time.sleep((factor - 1.0) * step_s)
                    step_s *= factor
                self._step_s_ewma = (
                    step_s if self._step_s_ewma is None
                    else 0.8 * self._step_s_ewma + 0.2 * step_s)
                chaos().barrier("step_end", ip=self.agent_ip)
                first_after_recovery = self._recovering
                if first_after_recovery:
                    self._recovering = False
                    recovery.mark(
                        recovery.FIRST_STEP, step=self.step, ip=self.agent_ip,
                        elapsed=None if self._recovered_at is None else round(
                            time.monotonic() - self._recovered_at, 3),
                    )
                    self._commit_incident()
                deferred = isinstance(loss, DeferredLoss)
                if deferred:
                    self._pending_losses.append((self.step, loss))
                self._record_step_metrics(
                    None if deferred else loss, step_s)
                if first_after_recovery:
                    # Push at once: the master resolves the in-flight
                    # recovery in /status on the first worker snapshot, and
                    # must not wait out the periodic publish interval.
                    self._publish_metrics()
                if deferred:
                    every = self.args.execution.loss_readback_every
                    if (self.step % every == 0 or self.step >= max_steps
                            or first_after_recovery):
                        self._drain_pending_losses(max_steps)
                else:
                    self.loss_history.append((self.step, loss))
                    logger.info("step %d/%d loss %.4f",
                                self.step, max_steps, loss)
                if self.step % 10 == 0:
                    wire = (
                        f" | dp wire {self.dp_engine.last_wire_bytes} B/step"
                        if self.multihost and self.dp_engine is not None
                        else ""
                    )
                    hist = self._m_step_seconds.series()
                    n = sum(c["count"] for c in hist)
                    mean_s = sum(c["sum"] for c in hist) / max(n, 1)
                    logger.info(
                        "step timer: n=%d, last=%.1fms, mean=%.1fms | %s%s%s",
                        n, step_s * 1e3, mean_s * 1e3,
                        _device_memory_summary(
                            obs_telemetry.telemetry().last()), wire,
                        _load_summary(obs_telemetry.telemetry()))
                    self._publish_metrics()
                if sync_interval and self.step % sync_interval == 0:
                    self._sync_replicas()
                if interval and self.step % interval == 0:
                    # Async submit: the loop stalls only for drain+capture;
                    # the write happens off-thread (oobleck_tpu/ckpt).
                    self.save_checkpoint(wait=False)
                mirror_every = self.args.execution.mirror_interval
                if (self.multihost and self.args.execution.mirror_dir
                        and mirror_every
                        and self.step % mirror_every == 0):
                    self._write_mirror()
            if interval and self.step % interval != 0:
                self.save_checkpoint()
        finally:
            if bookkeeping is not None:
                bookkeeping.__exit__(None, None, None)
            self._drain_pending_losses(max_steps)
            self._mirror_flush()
            if self._durable is not None:
                self._durable.flush()
            self._publish_metrics()
            # The span ring is written only when the JSONL metrics sink is
            # enabled.
            if metrics.metrics_dir() is not None:
                obs_spans.span_recorder().dump("train_end")
            self._close_step_watch()
            self._tracer = None
            self._step_acc.uninstall()

    def _start_watchdog(self) -> obs_telemetry.StepWatchdog:
        tracer = self._tracer
        return obs_telemetry.StepWatchdog(
            obs_telemetry.telemetry(), self._step_acc,
            open_trace=None if tracer is None else tracer.open_stall_window,
        ).start()

    def _close_step_watch(self) -> None:
        """Stop the watchdog's thread, then close whatever profiler window
        is open (train()'s end, and every change of topology: a trace must
        not straddle one, and the first step after it may compile, which
        the next watchdog skips as its first). The thread first, so that it
        cannot open a stall window behind the close."""
        if self._watchdog is not None:
            self._watchdog.stop()
            self._watchdog = None
        if self._tracer is not None:
            self._tracer.close()

    # ------------------------------------------------------------------ #

    def _collect_layer_state(self):
        params: dict[int, Any] = {}
        opt: dict[int, Any] = {}
        for pipe in self.pipelines:
            for li, p in pipe.params.items():
                params.setdefault(li, p)
                opt.setdefault(li, self.opt_states[pipe.pipeline_id][li])
        return params, opt

    def _sync_replicas(self) -> None:
        """Re-broadcast each DP-replicated layer from its canonical owner
        (the first pipeline holding it) to every other owner, bounding the
        bit-wise replica drift that accumulates from different per-mesh
        reduction orders (reference _copy_model_states broadcasts from an
        owner, engine.py:238-309; here a cross-mesh device_put)."""
        if not self.dp_engine:
            return
        if self.multihost:
            self._sync_replicas_multihost()
            return
        for li, owners in self.dp_engine.owners.items():
            if len(owners) <= 1:
                continue
            anchor = owners[0]
            for other in owners[1:]:
                dst = other.stages[other.stage_of_layer(li)].param_shardings[li]
                other.params[li] = jax.device_put(anchor.params[li], dst)
                self.opt_states[other.pipeline_id][li] = _place_opt_state(
                    self.optimizer,
                    self.opt_states[anchor.pipeline_id][li],
                    dst,
                )

    def _fill_full_state(self) -> dict[int, Any]:
        """COLLECTIVE: elect, per layer, the lowest process holding it
        live, and refill the FULL {layer: {"p": params, "o": opt}} state on
        every process with one native-dtype psum per dtype lane — the
        workhorse behind multi-host replica sync and multi-host checkpoint
        collection (the reference's _copy_model_states broadcast,
        engine.py:238-309)."""
        layout = self._live_layout
        nl = len(layout.layers)
        P = self.comm.process_count
        me = self.comm.process_index
        local_state: dict[int, Any] = {}
        for pipe in self.pipelines:
            if not pipe.participates_locally:
                continue
            for li in pipe.params:
                if li not in local_state:
                    local_state[li] = {
                        "p": pipe.params[li],
                        "o": self.opt_states[pipe.pipeline_id][li],
                    }
        votes = np.full(nl, np.inf, np.float32)
        for i, li in enumerate(layout.layers):
            if li in local_state:
                votes[i] = me
        winners = self.comm.group_min(votes, nl, range(P))
        bufs = {dt: np.zeros(layout.lengths[dt], dt)
                for dt in layout.dtypes}
        for i, li in enumerate(layout.layers):
            if np.isfinite(winners[i]) and winners[i] == me:
                layout.pack_into(bufs, li, local_state[li])
        totals = tuple(
            self.comm.group_sum(bufs[dt], layout.lengths[dt], range(P),
                                dtype=dt)
            for dt in layout.dtypes
        )
        return {
            li: layout.unpack(totals, li)
            for i, li in enumerate(layout.layers) if np.isfinite(winners[i])
        }

    def _sync_replicas_multihost(self) -> None:
        """COLLECTIVE anchor re-broadcast across processes: every local
        owner of a DP-shared layer adopts the elected anchor's replica."""
        shared = {li for li, ow in self.dp_engine.owners.items()
                  if len(ow) > 1}
        if not shared:
            return
        full = self._fill_full_state()
        for pipe in self.pipelines:
            if not pipe.participates_locally:
                continue
            for li in pipe.params:
                if li not in shared or li not in full:
                    continue
                dst = pipe.stages[pipe.stage_of_layer(li)].param_shardings[li]
                pipe.params[li] = jax.device_put(full[li]["p"], dst)
                self.opt_states[pipe.pipeline_id][li] = _place_opt_state(
                    self.optimizer, full[li]["o"], dst,
                )

    def _durable_plane(self):
        """Lazy handle on the durable-state plane (oobleck_tpu/ckpt), or
        None when checkpointing is off. Rebuilt if the process identity or
        target dir changed (a respawned multi-host world resolves its comm
        after __init__)."""
        ckpt_dir = self.args.execution.checkpoint_dir
        if not ckpt_dir:
            return None
        from pathlib import Path

        from oobleck_tpu import ckpt

        pi = ws = None
        if self.multihost and self.comm is not None:
            pi, ws = self.comm.process_index, self.comm.process_count
        else:
            # Fused multi-host worlds have no MPMD comm; their process
            # identity is jax.distributed's (1/1 when uninitialized).
            pi, ws = jax.process_index(), jax.process_count()
        d = self._durable
        if (d is None or str(d.root) != str(Path(ckpt_dir).resolve())
                or d.process_index != pi or d.world_size != ws):
            if d is not None:
                d.close()
            ex = self.args.execution
            self._durable = ckpt.DurableStatePlane(
                ckpt_dir, process_index=pi, world_size=ws,
                keep_last=ex.checkpoint_keep_last,
                asynchronous=ex.checkpoint_async, ip=self.agent_ip)
        return self._durable

    def _elected_local_layer_state(self):
        """Multi-host MPMD, NO collective: every layer's writer is the
        minimum process owning it — derivable from the plan on every
        process identically — so each process contributes a disjoint slice
        of the global layer set and the plane's manifest merge makes the
        checkpoint whole. Replaces the old _fill_full_state collective on
        the save path (which shipped every layer to every host just so
        one of them could write)."""
        me = self.comm.process_index if self.comm is not None else 0
        owner: dict[int, int] = {}
        for pipe in self.pipelines:
            for st in pipe.stages:
                proc = st.process if st.process is not None else 0
                for li in st.layer_ids:
                    owner[li] = min(owner.get(li, 1 << 30), proc)
        params: dict[int, Any] = {}
        opt: dict[int, Any] = {}
        for pipe in self.pipelines:
            if not pipe.participates_locally:
                continue
            for li, p in pipe.params.items():
                if owner.get(li) == me and li not in params:
                    params[li] = p
                    opt[li] = self.opt_states[pipe.pipeline_id][li]
        return params, opt

    def save_checkpoint(self, wait: bool = True) -> None:
        """Snapshot + submit to the durable-state plane. Every process
        calls this (each writes only its elected layers' shards; process 0
        commits the manifest — no collective, no barrier). `wait=False` is
        the train-loop mode: the call returns once the snapshot is staged
        to host and enqueued; the stall is drain + staging, not the
        write."""
        plane = self._durable_plane()
        if plane is None:
            return
        meta = dict(
            num_iterations_done=self.dataloaders[0].num_iterations_done,
            epoch=self.dataloaders[0].epoch,
            extra={"model_name": self.args.model.model_name},
        )
        if self.fused is not None:
            try:
                params, opt = self.fused.layer_state()
            except ValueError:
                # Cross-host-sharded fused state: host-local layer assembly
                # is impossible (to_host_local raises). Write the raw
                # stacked leaves shard-wise instead — restore layerizes
                # them (_layerize_stacked) where model+optimizer live.
                st = self.fused.state
                stall = plane.save_stacked(
                    step=self.step, params=st.params,
                    opt_leaves=jax.tree.leaves(st.opt_state), **meta)
                self.ckpt_stall_s.append(stall)
                if wait:
                    plane.flush()
                return
        elif self.multihost:
            params, opt = self._elected_local_layer_state()
        else:
            self._sync_replicas()
            params, opt = self._collect_layer_state()
        stall = plane.save(step=self.step, params=params, opt_state=opt,
                           **meta)
        self.ckpt_stall_s.append(stall)
        if wait:
            plane.flush()

    def try_restore_checkpoint(self) -> dict | None:
        """Load the newest restorable checkpoint from the durable-state
        plane, if any. Torn/corrupt step dirs are quarantined (by process
        0) and skipped. Returns the payload for instantiate_pipelines-time
        consumption."""
        plane = self._durable_plane()
        if plane is None:
            return None
        res = plane.load_latest()  # shared step-selection (ckpt/restore.py)
        if res is None:
            return None
        step, payload = res
        if payload.get("kind") == "fused_stacked":
            payload = self._layerize_stacked(payload)
        from oobleck_tpu.ckpt import manifest as _mf
        logger.info("restoring from durable checkpoint %s (step %s)",
                    _mf.step_dir_name(step), step)
        return payload

    def _layerize_stacked(self, payload: dict) -> dict:
        """Convert a fused_stacked payload (raw stacked TrainState on
        host) into the layer-keyed checkpoint form — pure host-side tree
        restructuring via the fused path's own converters."""
        from oobleck_tpu.execution.fused import (
            opt_state_to_layers,
            params_to_layers,
        )

        params = payload["params"]
        struct = jax.tree.structure(
            jax.eval_shape(self.optimizer.init, params))
        opt_state = jax.tree.unflatten(struct, payload["opt"])
        p_layers = params_to_layers(self.model, params)
        o_layers = opt_state_to_layers(self.model, self.optimizer, params,
                                       opt_state)
        return {"params": p_layers,
                "opt": {li: jax.tree.leaves(v) for li, v in o_layers.items()},
                "meta": payload["meta"]}

    # -- checkpoint-free live-state mirror (multi-host MPMD) ------------ #

    _MAX_MIRROR_STEP = 2**18 - 1  # election votes must fit f32 exactly

    @property
    def _live_layout(self):
        """TypedFlatLayout over {layer: {"p": params, "o": opt leaves}} —
        the shared NATIVE-dtype wire format for mirrors, recovery fill, and
        replica sync (one lane per leaf dtype; no f32 widening)."""
        if getattr(self, "_live_layout_cache", None) is None:
            from oobleck_tpu.parallel.cross_host import (
                TypedFlatLayout, layer_avals)

            avals = layer_avals(self.model)
            self._live_layout_cache = TypedFlatLayout({
                li: {"p": avals[li],
                     "o": jax.eval_shape(self.optimizer.init, avals[li])}
                for li in avals
            })
        return self._live_layout_cache

    def _mirror_file(self):
        """Mirror path. mirror_dir should be host-local storage; the file
        name still carries the host identity so same-machine test clusters
        (loopback-alias "hosts" sharing a filesystem) don't collide."""
        from pathlib import Path

        d = self.args.execution.mirror_dir
        if not d:
            return None
        tag = (self.agent_ip or "local").replace(":", "_").replace("/", "_")
        return Path(d) / f"live_state_{tag}.npz"

    def _write_mirror(self) -> None:
        """Persist this process's LOCAL layers' live state to host-local
        storage (atomic replace). The failure-time cost this buys: recovery
        needs no checkpoint reload and loses at most mirror_interval-1
        steps (reference in-memory recovery loses none but requires
        survivors' processes to outlive the broken world, which the JAX
        runtime cannot guarantee — respawn + mirror is the TPU-shaped
        equivalent).

        The step thread only snapshots REFERENCES (jax arrays are
        immutable — the optimizer step creates new ones); device_get,
        native-dtype packing, and the npz write run on a background
        thread. A write requested while the previous one is in flight is
        skipped (the next interval supersedes it) so mirroring never backs
        up the training loop."""
        path = self._mirror_file()
        if path is None:
            return
        if self._mirror_thread is not None and self._mirror_thread.is_alive():
            self._mirror_skipped += 1
            return
        params, opt = self._collect_layer_state()
        state = {li: {"p": params[li], "o": opt[li]} for li in params}
        meta = {
            "step": self.step,
            "num_iterations_done": self.dataloaders[0].num_iterations_done,
            "epoch": self.dataloaders[0].epoch,
        }
        t = threading.Thread(
            target=self._mirror_write_worker, args=(path, state, meta),
            daemon=True,
        )
        self._mirror_thread = t
        t.start()

    def _mirror_write_worker(self, path, state: dict[int, Any],
                             meta: dict) -> None:
        import os as _os

        t0 = time.monotonic()
        layout = self._live_layout
        # Per-dtype buffers stored as raw bytes: np.save has no portable
        # descr for ml_dtypes (bf16), so every lane rides uint8 and views
        # back to its wire dtype on load.
        bufs = {dt: np.zeros(layout.lengths[dt], dt)
                for dt in layout.dtypes}
        have = np.zeros(len(layout.layers), bool)
        # pack_into device_gets live jax arrays — fence it against the
        # train thread's dispatch/readback (utils/background.py). The npz
        # write below is pure host I/O and runs outside the fence.
        with background.device_work("mirror"):
            for li, tree in state.items():
                layout.pack_into(bufs, li, tree)
                have[layout.layers.index(li)] = True
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_suffix(".tmp.npz")
        np.savez(tmp, have=have, **meta,
                 **{f"buf_{dt.name}": b.view(np.uint8)
                    for dt, b in bufs.items()})
        _os.replace(tmp, path)
        dur = time.monotonic() - t0
        self.mirror_write_s.append(dur)
        logger.info(
            "mirror write %.3fs (%d B native-dtype, off-thread, "
            "%d skipped)", dur,
            sum(b.nbytes for b in bufs.values()), self._mirror_skipped,
        )

    def _mirror_flush(self) -> None:
        """Join any in-flight mirror write (restore paths + shutdown)."""
        t = self._mirror_thread
        if t is not None and t.is_alive():
            t.join()

    def _try_restore_mirror(self) -> dict | None:
        """COLLECTIVE (every process must call, mirror or not): elect ONE
        GLOBAL step — the minimum of the survivors' mirror steps, i.e. the
        newest step the laggard still holds — then restore every layer from
        a mirror AT exactly that step (ties -> lowest process). Layers no
        step-S mirror holds fall back to the freshest available copy with a
        loud cross-step-mix warning; without the global election first, a
        failure landing between survivors' mirror writes would silently mix
        layer states from different steps while meta claimed the freshest
        (round-4 advisor, medium). Refills ride one native-dtype psum per
        dtype lane; meta rides an exact int32 lane. Returns a payload
        shaped like try_restore_checkpoint's; None when no process holds a
        mirror. Matches the reference's survivor-broadcast recovery
        (engine.py:238-309) with the state moving over DCN collectives."""
        self._mirror_flush()
        layout = self._live_layout
        nl = len(layout.layers)
        P = self.comm.process_count
        me = self.comm.process_index
        path = self._mirror_file()
        local = None
        if path is not None and path.exists():
            try:
                local = np.load(path)
                # Format check BEFORE any collective round: a mirror from
                # an older wire format (e.g. the pre-round-5 single f32
                # 'buf') must count as unreadable here — discovering a
                # missing key mid-election would kill this process while
                # the other survivors block in the next collective.
                needed = {"have", "step", "num_iterations_done", "epoch"}
                needed |= {f"buf_{dt.name}" for dt in layout.dtypes}
                missing_keys = needed - set(local.files)
                if missing_keys:
                    logger.warning(
                        "mirror %s lacks keys %s (stale wire format?); "
                        "treating as absent", path, sorted(missing_keys),
                    )
                    local = None
            except Exception as e:
                logger.warning("unreadable mirror %s: %s", path, e)
        # Vote encoding (MAX-step)*64 + process must stay exact in f32 and
        # decode via % 64: both break past 64 processes (the control plane
        # caps clusters at MAX_NUM_HOSTS=32, master.py).
        if P > 64:
            raise RuntimeError(
                f"mirror election supports <= 64 processes, got {P}"
            )
        INF = np.float32(np.inf)
        step = have = None
        if local is not None:
            step = int(local["step"])
            if step > self._MAX_MIRROR_STEP:
                # Clamped steps tie in the election (lowest process wins
                # regardless of freshness) — keep recovering, but say so.
                logger.warning(
                    "mirror step %d exceeds the election's exact range "
                    "(%d); freshness ties break by process index",
                    step, self._MAX_MIRROR_STEP,
                )
                step = self._MAX_MIRROR_STEP
            have = np.asarray(local["have"], bool)  # oobleck: allow[OBL002] -- recovery path, host mirror
        # Round 0: the global step S = min over survivors' mirror steps.
        svec = np.full(1, INF, np.float32)
        if local is not None:
            svec[0] = step
        smin = self.comm.group_min(svec, 1, range(P))
        if not np.isfinite(smin[0]):
            return None
        S = int(smin[0])
        at_S = local is not None and step == S
        # Round 1: per-layer owner among mirrors AT step S (lowest process).
        votes1 = np.full(nl, INF, np.float32)
        if at_S:
            votes1[have] = me
        w1 = self.comm.group_min(votes1, nl, range(P))
        # Round 2: freshest-any fallback for layers uncovered at step S.
        votes2 = np.full(nl, INF, np.float32)
        if local is not None:
            votes2[have] = np.float32(
                (self._MAX_MIRROR_STEP - step) * 64 + me
            )
        w2 = self.comm.group_min(votes2, nl, range(P))
        covered = np.isfinite(w2)
        mixed = [layout.layers[i] for i in range(nl)
                 if covered[i] and not np.isfinite(w1[i])]
        if mixed:
            logger.warning(
                "layers %s have no surviving mirror at the elected global "
                "step %d; restoring them from fresher mirrors — their "
                "layer/optimizer state mixes steps", mixed, S,
            )
        missing = [layout.layers[i] for i in range(nl) if not covered[i]]
        if missing:
            logger.warning(
                "no surviving mirror holds layers %s; they fall back to "
                "checkpoint or fresh init", missing,
            )
        # Winners pack their raw slices (vote encodings embed the process
        # index, so winners are unique per layer and round).
        bufs = {dt: np.zeros(layout.lengths[dt], dt)
                for dt in layout.dtypes}
        if local is not None:
            # oobleck: allow[OBL002] -- recovery path, host mirror buffers
            raw = {dt: np.asarray(local[f"buf_{dt.name}"]).view(dt)
                   for dt in layout.dtypes}
            for i, li in enumerate(layout.layers):
                won = (w1[i] == np.float32(me)) or (
                    not np.isfinite(w1[i])
                    and np.isfinite(votes2[i]) and votes2[i] == w2[i]
                )
                if won:
                    for _, _, wdt, off, n in layout.leaf_metas[li]:
                        bufs[wdt][off:off + n] = raw[wdt][off:off + n]
        totals = tuple(
            self.comm.group_sum(bufs[dt], layout.lengths[dt], range(P),
                                dtype=dt)
            for dt in layout.dtypes
        )
        # Meta (data position) from the lowest process AT step S, over an
        # exact int32 lane (f32 would round num_iterations_done past 2**24).
        mvote = np.full(1, INF, np.float32)
        if at_S:
            mvote[0] = me
        mwin = self.comm.group_min(mvote, 1, range(P))
        mvec = np.zeros(3, np.int32)
        if at_S and mwin[0] == np.float32(me):
            mvec[:] = (int(local["step"]),
                       int(local["num_iterations_done"]),
                       int(local["epoch"]))
        mtotal = self.comm.group_sum(mvec, 3, range(P), dtype=jnp.int32)
        params = {}
        opt = {}
        for i, li in enumerate(layout.layers):
            if covered[i]:
                tree = layout.unpack(totals, li)
                params[li] = tree["p"]
                opt[li] = jax.tree.leaves(tree["o"])
        return {
            "params": params,
            "opt": opt,
            "meta": {
                "step": int(mtotal[0]),
                "num_iterations_done": int(mtotal[1]),
                "epoch": int(mtotal[2]),
            },
        }

    # ------------------------------------------------------------------ #

    def _has_validation_split(self) -> bool:
        """Whether a USABLE validation split exists.

        The raw split probe is not enough: a split that tokenizes to zero
        full sequences must count as absent, or the reserve is sized 0 and
        evaluate() would score training data. So a present split is
        tokenized here (validation splits are small) and cached for
        evaluate()."""
        if self._has_val_split is None:
            from oobleck_tpu.execution.dataset import (
                build_eval_dataset, has_validation_split)

            present = has_validation_split(
                self.args.model.dataset_path, self.args.model.dataset_name
            )
            if present:
                ds = build_eval_dataset(
                    self.args.model.dataset_path,
                    self.args.model.dataset_name,
                    model_name=self.args.model.model_name,
                    seq_length=self.seq_len,
                    data_kind=getattr(self.model, "data_kind", "causal_lm"),
                    vocab_size=getattr(self.model.config, "vocab_size", 0),
                    mask_token_id=getattr(self.model.config,
                                          "mask_token_id", 103),
                )
                if len(ds) == 0:
                    logger.warning(
                        "validation split tokenizes to 0 sequences at "
                        "seq_length %d; treating it as absent (held-out "
                        "tail reserve applies)", self.seq_len,
                    )
                    present = False
                else:
                    self._eval_ds_cache = ds
            self._has_val_split = present
        return self._has_val_split

    @property
    def eval_dataset(self):
        if self._eval_ds_cache is _UNSET:
            # _has_validation_split tokenizes and caches a usable split.
            if not self._has_validation_split():
                self._eval_ds_cache = None
        return self._eval_ds_cache

    def _eval_reserve(self) -> int:
        if self._has_validation_split():
            return 0  # a real validation split exists; train on everything
        return int(len(self.dataset) * self.args.execution.eval_fraction)

    def evaluate(self, num_batches: int = 8) -> float:
        """Forward-only mean loss over held-out data.

        The pool is a real validation split when the data source has one,
        else the eval_fraction tail reserve — training samplers cover only
        the head split (_materialize_plan), so the tail is genuinely
        unseen. Windows ROTATE: the eval position persists across calls
        (epoch wrap in the sampler), so repeated evaluate() calls sweep the
        whole pool instead of replaying its first window. (The reference
        defines an Evaluation LoaderType but never drives it,
        dataloader.py:101.)"""
        mb_counts = (
            [self.fused.num_microbatches] if self.fused is not None
            else [p.num_microbatches for p in self.pipelines]
        )
        bucket = self.args.job.microbatch_size * sum(mb_counts)
        pool = self.eval_dataset
        if pool is not None and len(pool) == 0:
            # A real validation split can tokenize to zero full sequences
            # (fewer than seq_length tokens); treat it as absent rather than
            # dividing by zero in _CyclicView.
            logger.warning(
                "validation split tokenizes to 0 sequences at seq_length %d; "
                "falling back to the held-out training tail", self.seq_len,
            )
            pool = None
        if pool is None:
            n = len(self.dataset)
            eval_n = self._eval_reserve()
            if eval_n < bucket:
                logger.warning(
                    "eval reserve %d < one bucket %d; eval overlaps the "
                    "training tail (raise execution.eval_fraction)",
                    eval_n, bucket,
                )
                eval_n = bucket
            pool = _TailView(self.dataset, n - eval_n, eval_n)
        elif len(pool) < bucket:
            logger.warning(
                "validation split of %d samples smaller than one eval "
                "bucket (%d); samples repeat within a window",
                len(pool), bucket,
            )
            pool = _CyclicView(pool, bucket)

        it_done, epoch = self._eval_state
        correct_sum = 0.0
        count_sum = 0.0
        samplers = [
            OobleckSampler(
                num_samples=len(pool),
                microbatch_size=self.args.job.microbatch_size,
                pipeline_index=i,
                num_microbatches=mb_counts,
                num_iterations_done=it_done,  # sampler wraps epochs itself
                epoch=epoch,
            )
            for i in range(len(mb_counts))
        ]
        loaders = [OobleckDataLoader(pool, s) for s in samplers]
        # Losses stay on-device through the whole eval sweep (each float()
        # readback would serialize dispatch); the single drain below
        # resolves them after every batch's compute is in flight.
        device_losses: list[tuple[Any, int]] = []
        weight_sum = 0
        for _ in range(max(1, num_batches // len(mb_counts))):
            if self.fused is not None:
                device_losses.append(
                    (self.fused.eval_step(loaders[0].next_batch()), 1))
                weight_sum += 1
            else:
                for pipe, dl in zip(self.pipelines, loaders):
                    if self.multihost and not pipe.participates_locally:
                        # Lockstep position only — no batch materialization
                        # for pipelines with no local stage (mirrors
                        # _train_step_multihost; round-4 advisor, low).
                        dl.advance()
                        continue
                    batch = dl.next_batch()
                    loss = pipe.eval_step(batch)
                    if pipe.last_eval_metrics is not None:
                        correct_sum += pipe.last_eval_metrics[0]
                        count_sum += pipe.last_eval_metrics[1]
                    if loss is None:
                        continue  # last stage lives on another process
                    device_losses.append((loss, pipe.num_microbatches))
                    weight_sum += pipe.num_microbatches
        loss_sum = sum(_host_sync(l) * w for l, w in device_losses)
        self._eval_state = (samplers[0].num_iterations_done, samplers[0].epoch)
        if self.multihost:
            total = self.comm.group_sum(
                # oobleck: allow[OBL002] -- eval sweep, off the step loop
                np.asarray([loss_sum, weight_sum, correct_sum, count_sum],
                           np.float32), 4,
                range(self.comm.process_count),
            )
            # oobleck: allow[OBL002] -- eval sweep, off the step loop
            loss_sum, weight_sum = float(total[0]), float(total[1])
            # oobleck: allow[OBL002] -- eval sweep, off the step loop
            correct_sum, count_sum = float(total[2]), float(total[3])
        mean_loss = loss_sum / weight_sum
        # Task metric alongside the loss (reference builds accuracy via
        # `evaluate` but never reports it, dataset.py:39-54): reported for
        # every non-causal-LM family through accuracy_from_logits.
        self.last_eval_metrics = {"loss": mean_loss}
        if count_sum > 0:
            self.last_eval_metrics["accuracy"] = correct_sum / count_sum
            logger.info("eval loss %.4f accuracy %.4f (%d predictions)",
                        mean_loss, correct_sum / count_sum, int(count_sum))
        else:
            logger.info("eval loss %.4f", mean_loss)
        return mean_loss

    def predict_replan(self, lost_hosts: set[int],
                       current: list[list[int]] | None = None):
        """Host algebra + template re-match for losing `lost_hosts`, WITHOUT
        mutating engine state: returns (plan, host_assignment, idle_hosts).

        reconfigure() applies the prediction at failure time; the recovery
        precompiler (execution/precompile.py) walks the same function AHEAD
        of failure — sharing one code path is what guarantees the
        precompiled executables carry byte-identical cache keys (stage
        ranks included) to the ones recovery will ask for."""
        if current is None:
            current = [
                sorted({r // self.chips_per_host for r in p.ranks})
                for p in self.pipelines
            ]
        min_hosts = min(t.num_hosts for t in self.templates)
        new_hosts = reconfigure_hosts(current, lost_hosts, min_hosts)

        # Match each host group to the largest template it can fill,
        # re-folding surplus hosts instead of silently idling them
        # (fit_host_groups; round-1 advisor finding).
        by_hosts = {t.num_hosts: t for t in self.templates}
        sizes = sorted(by_hosts)
        new_hosts, idle = fit_host_groups(new_hosts, sizes)
        new_instances: dict[PipelineTemplate, int] = {}
        for hosts in new_hosts:
            t = by_hosts[len(hosts)]
            new_instances[t] = new_instances.get(t, 0) + 1

        ar_across = [p.allreduce_across_hosts for p in self.profiles]
        plan = PipelineInstantiator().get_new_execution_plan(
            new_instances, ar_across, self.plan.total_num_microbatches
        )
        # Pair each plan instance with a host group of exactly its size —
        # explicit matching rather than relying on two separate sorts
        # (plan.instances' canonical order vs a host-list sort) agreeing.
        groups_by_size: dict[int, list[list[int]]] = {}
        for g in new_hosts:
            groups_by_size.setdefault(len(g), []).append(g)
        host_assignment = [
            groups_by_size[t.num_hosts].pop(0) for t in plan.instances
        ]
        return plan, host_assignment, idle

    def start_recovery_precompile(self, wait: bool = False):
        """Arm the warm-recovery precompiler: AOT-compile the stage
        executables of the plans `predict_replan` would produce after
        likely failures into the persistent compilation cache, on a
        background thread (execution/precompile.py).

        No-op (returns None) when disabled (`precompile_recovery_depth` 0 /
        OOBLECK_PRECOMPILE=0) or when there is no MPMD plan to predict from
        (fused path recovers by mesh shrink — same program geometry class,
        not a template re-match). Where the persistent cache is off (the
        CPU backend) the walk still fills the process's `PROGRAMS`, which an
        in-place reconfigure reuses; only the respawn path goes cold.
        `wait=True` blocks until warm — tests that inject a failure at
        a fixed early step need the warmth guaranteed, production wants the
        background default."""
        import os

        from oobleck_tpu.utils.compile_cache import ensure_persistent_cache

        depth = self.args.execution.precompile_recovery_depth
        env = os.environ.get("OOBLECK_PRECOMPILE")
        if env is not None:
            try:
                depth = int(env)
            except ValueError:
                logger.warning("ignoring malformed OOBLECK_PRECOMPILE=%r", env)
        if depth <= 0 or self.fused is not None or self.plan is None:
            return None
        ensure_persistent_cache()
        from oobleck_tpu.execution.precompile import RecoveryPrecompiler

        if self._precompiler is not None:
            # Re-arm: stop the previous walk before starting a new one —
            # two threads predicting from different topologies would race
            # each other (and the training thread) on the shared caches.
            self._precompiler.cancel()
        self._precompiler = RecoveryPrecompiler(self, depth=depth)
        self._precompiler.start()
        if wait:
            self._precompiler.wait()
        return self._precompiler

    # -- adaptive fault-tolerance policy (oobleck_tpu/policy) ----------- #

    def _policy_engine(self):
        if self._policy is None:
            from oobleck_tpu.policy import PolicyEngine

            self._policy = PolicyEngine(multihost=self.multihost)
        return self._policy

    def _consult_policy(self, lost_ips: list[str], *, cause: str = ""):
        """Score the recovery arms for an in-process-detected loss with
        the same signals the master would use: planner-projected reroute
        retention, durable-checkpoint staleness, measured step time, and
        the local MTBF history."""
        pol = self._policy_engine()
        for ip in lost_ips:
            pol.observe_failure(ip, cause)
        staleness = None
        plane = self._durable_plane()
        if plane is not None:
            durable = plane.last_durable_step
            if durable is not None and durable >= 0:
                staleness = max(float(self.step - durable), 0.0)
        n = len(self.host_ips)
        survivor_frac = (max(n - len(lost_ips), 0) / n) if n else 1.0
        return pol.decide(
            lost_ips,
            degrade_enabled=(self.args.execution.degrade_enabled
                             and self.fused is None),
            reroute_retention=self._projected_degrade_retention(lost_ips),
            survivor_frac=survivor_frac,
            staleness_steps=staleness,
            step_seconds=self._step_s_ewma,
            cause=cause)

    def _observe_policy_measured(self, mechanism: str,
                                 seconds: float | None) -> None:
        """Close the projected-vs-measured loop on the local policy engine
        (and, via its histogram, on the master's next snapshot scan)."""
        if seconds is not None:
            self._policy_engine().observe_measured(mechanism, seconds)

    def _projected_degrade_retention(self, lost_ips: list[str]
                                     ) -> float | None:
        """Planner-projected survivor throughput retention if `lost_ips`
        were rerouted — the scorer's reroute-retention signal, published
        as a gauge so the master scores from the same number. None when
        the reroute is structurally off the table."""
        if (len(lost_ips) != 1 or not self.pipelines
                or self.fused is not None
                or lost_ips[0] not in self.host_ips):
            return None
        try:
            from oobleck_tpu.degrade.apply import specs_from_pipelines
            from oobleck_tpu.degrade.classify import classify_failure
            from oobleck_tpu.degrade.planner import plan_reroute

            report = classify_failure(
                self._host_index[lost_ips[0]],
                [p.ranks for p in self.pipelines], self.chips_per_host)
            plan = plan_reroute(
                report, specs_from_pipelines(self.pipelines),
                max_slowdown=self.args.execution.degrade_max_slowdown)
        except Exception:
            logger.debug("reroute projection failed", exc_info=True)
            return None
        if not plan.feasible:
            return None
        metrics.registry().gauge(
            "oobleck_degrade_projected_retention",
            "Planner-projected survivor throughput retention of a "
            "single-host reroute from the current topology",
        ).set(plan.throughput_retention)
        return plan.throughput_retention

    def _restore_recover(self, lost_ips: list[str], t0: float) -> bool:
        """Checkpoint-restore recovery: the same survivor re-plan as
        re-instantiation, but the state comes from the last durable
        checkpoint instead of the surviving live arrays — the policy plane
        picks this when a churn storm makes in-memory recovery a losing
        bet (the next failure would eat the replayed work anyway). Returns
        False when no checkpoint is loadable; the caller falls back."""
        restored = self.try_restore_checkpoint()
        if restored is None:
            return False
        rolled_back = self.step
        with obs_spans.span("engine.restore",
                            lost_ips=",".join(lost_ips)):
            old_params = restored["params"]
            old_opt = {}
            for li, leaves in restored["opt"].items():
                struct = jax.tree.structure(
                    jax.eval_shape(self.optimizer.init, old_params[li]))
                old_opt[li] = jax.tree.unflatten(struct, leaves)
            meta = restored["meta"]
            plan, host_assignment, idle = self.predict_replan(
                {self._host_index[ip] for ip in lost_ips})
            if idle:
                logger.warning("hosts %s idle after restore", idle)
            for ip in lost_ips:
                self.host_ips.remove(ip)
            self.step = int(meta["step"])
            self.plan = plan
            self._materialize_plan(
                plan, int(meta["num_iterations_done"]), int(meta["epoch"]),
                old_params, old_opt, host_assignment=host_assignment)
        rolled_back -= self.step
        elapsed = time.perf_counter() - t0
        self.recovery_times.append(elapsed)
        self._recovering = True
        self._recovered_at = time.monotonic()
        self._m_reconfigs.inc(path="restore")
        self._set_template_gauge()
        recovery.observe_latency(elapsed, stage="restore")
        self._observe_policy_measured(MECH_RESTORE, elapsed)
        metrics.flight_recorder().record(
            "engine_restored", lost_ips=lost_ips, path="restore",
            elapsed_s=round(elapsed, 3), step=self.step,
            rolled_back_steps=rolled_back)
        logger.warning(
            "restored from durable checkpoint after losing %s in %.2fs "
            "(rolled back %d step(s)): %s",
            lost_ips, elapsed, rolled_back, plan)
        if self._precompiler is not None:
            self.start_recovery_precompile()
        return True

    # -- multihost zero-respawn degrade --------------------------------- #

    def _maybe_inplace_degrade(self) -> None:
        """Multihost in-place DEGRADE (ROADMAP item 1 remainder): apply a
        queued reroute once EVERY live process has seen it, at the same
        step boundary, via a 1-float group-min each step. The collective
        runs unconditionally on the (multihost, degrade-enabled) path — a
        conditionally-entered collective would deadlock against the step's
        own allreduce when one process enters it and another does not."""
        if (not self.multihost or self.comm is None
                or self.fused is not None
                or not self.args.execution.degrade_enabled):
            return
        if self._live_procs is None:
            self._live_procs = list(range(self.comm.process_count))
        if self.comm.process_index not in self._live_procs:
            return
        with self._lock:
            pending = len(self._inplace_queue) > self._inplace_applied
        ready = np.array([1.0 if pending else 0.0], np.float32)
        agreed = self.comm.group_min(ready, 1, self._live_procs)
        if agreed[0] < 1.0:
            return
        with self._lock:
            entry = self._inplace_queue[self._inplace_applied]
            self._inplace_applied += 1
        lost_ip = entry["lost_ip"]
        if lost_ip not in self.host_ips:
            return
        if self.agent_ip == lost_ip:
            # Victim at the agreed boundary: flush what only this process
            # holds, then leave the train loop cleanly — the survivors
            # drop this process from their collectives at the same step.
            metrics.flight_recorder().record(
                "inplace_drain", ip=self.agent_ip, step=self.step,
                trace_id=(entry["trace"] or {}).get("trace_id"))
            self._mirror_flush()
            self._drain_requested = True
            return
        self.reconfigure(lost_ip, trace=entry["trace"],
                         decision=entry["decision"], inplace=True)

    def _do_inplace_reroute(self, lost_ip: str, decision: dict | None,
                            t0: float) -> None:
        """Survivor side of the multihost zero-respawn DEGRADE. The plan
        is deterministic from shared state, so every survivor computes —
        and applies — the identical reroute without exchanging it; only
        the boundary needed consensus. Infeasibility is equally
        deterministic: every survivor falls back to respawn via its
        agent."""
        from oobleck_tpu.degrade.apply import try_degrade

        self._close_step_watch()
        ddec = try_degrade(self, lost_ip, self._host_index[lost_ip], t0)
        if ddec.mechanism == "reroute":
            self._observe_policy_measured(
                MECH_REROUTE, ddec.measured_recovery_s)
            return
        metrics.flight_recorder().record(
            "degrade_fallback", lost_ip=lost_ip, reason=ddec.reason,
            step=self.step)
        logger.warning("in-place degrade infeasible (%s); requesting "
                       "respawn fallback", ddec.reason)
        if self.agent_pipe is not None:
            try:
                self.agent_pipe.send({"kind": "degrade_fallback",
                                      "lost_ip": lost_ip,
                                      "reason": ddec.reason})
            except (OSError, ValueError):
                pass

    def _maybe_chaos_kill_hosts(self) -> None:
        """Correlated fault injection (OOBLECK_CHAOS=kill_hosts=
        <ip1+ip2+...>): declare several hosts lost in the same detection
        window, exercising the policy plane's correlated-failure path
        (reroute infeasible, one incident covering the whole blast
        radius)."""
        if not chaos().active or not self.pipelines:
            return
        ips = chaos().kill_hosts_target()
        if not ips:
            return
        known = [ip for ip in ips if ip in self.host_ips]
        if not known:
            logger.warning("chaos kill_hosts: no known hosts in %s", ips)
            return
        detected_at = time.time()
        trace = {"trace_id": obs_spans.new_trace_id(),
                 "detected_at": detected_at, "cause": "chaos_kill_hosts"}
        metrics.flight_recorder().record(
            "chaos_kill_hosts_resolved", lost_ips=known, step=self.step)
        obs_spans.span_recorder().record(
            "incident.detect", detected_at, detected_at,
            trace_id=trace["trace_id"], lost_ip=",".join(known),
            cause="chaos_kill_hosts")
        logger.warning("chaos kill_hosts: declaring %s lost together",
                       known)
        for ip in known:
            # Same trace, same drain window -> one correlated incident.
            self.request_reconfiguration(ip, trace=trace)

    def _maybe_chaos_join(self) -> None:
        """Chaos capacity arrival (OOBLECK_CHAOS=join_host=<ip>[@<delay>]
        / join_hosts=<ip1+ip2>): declare freshly provisioned hosts at a
        step boundary — the in-process mirror of a real JOIN handshake,
        so the grow plane is exercisable without a control plane. Hosts
        maturing at the same boundary arrive as ONE batch (the grow
        mirror of kill_hosts' correlated loss)."""
        if not chaos().active or not self.pipelines:
            return
        ips = chaos().join_targets()
        if not ips:
            return
        fresh = [ip for ip in ips
                 if ip not in self.host_ips and ip not in self._spare_hosts]
        if not fresh:
            logger.warning("chaos join: hosts %s already present", ips)
            return
        detected_at = time.time()
        trace = {"trace_id": obs_spans.new_trace_id(),
                 "detected_at": detected_at, "cause": "chaos_join_host"}
        obs_spans.span_recorder().record(
            "incident.detect", detected_at, detected_at,
            trace_id=trace["trace_id"], joined_ips=",".join(fresh),
            cause="chaos_join_host")
        logger.warning("chaos join: hosts %s arriving together", fresh)
        self.request_grow(fresh, trace=trace)

    def _maybe_spot_expire(self) -> None:
        """Spot-lifetime deadlines armed at admit (chaos spot_lifetime
        directive): when a joined host's advertised lifetime runs out,
        the churn the policy's amortization horizon priced in actually
        happens. An active host leaves through the REGULAR loss path
        (one synthetic incident); a parked spare just unparks — it was
        never in the plan, so its departure interrupts nothing."""
        if not self._spot_deadlines:
            return
        now = time.monotonic()
        expired = [ip for ip, t in self._spot_deadlines.items() if now >= t]
        for ip in expired:
            del self._spot_deadlines[ip]
            if ip in self._spare_hosts:
                self._spare_hosts.remove(ip)
                metrics.flight_recorder().record(
                    "spot_lifetime_expired", ip=ip, step=self.step,
                    was_spare=True)
                logger.warning("spare host %s reached its spot lifetime; "
                               "unparked", ip)
                continue
            if ip not in self.host_ips:
                continue
            detected_at = time.time()
            trace = {"trace_id": obs_spans.new_trace_id(),
                     "detected_at": detected_at, "cause": "spot_lifetime"}
            obs_spans.span_recorder().record(
                "incident.detect", detected_at, detected_at,
                trace_id=trace["trace_id"], lost_ip=ip,
                cause="spot_lifetime")
            metrics.flight_recorder().record(
                "spot_lifetime_expired", ip=ip, step=self.step,
                was_spare=False)
            logger.warning("host %s reached its advertised spot lifetime; "
                           "declaring it lost", ip)
            self.request_reconfiguration(ip, trace=trace)

    def _maybe_chaos_kill_stage(self) -> None:
        """Stage-addressed fault injection (OOBLECK_CHAOS=kill_stage=
        <stage>:<replica>): declare the host owning that stage of that
        pipeline lost, in place of an out-of-band SIGKILL — the
        single-controller analog of killing one DP peer, deterministic
        enough for the degraded-mode tests to target a specific peer."""
        if not chaos().active or not self.pipelines:
            return
        target = chaos().kill_stage_target()
        if target is None:
            return
        stage, replica = target
        if replica >= len(self.pipelines):
            logger.warning("chaos kill_stage: no pipeline replica %d "
                           "(have %d); ignoring", replica, len(self.pipelines))
            return
        pipe = self.pipelines[replica]
        if stage >= pipe.num_stages:
            logger.warning("chaos kill_stage: pipeline %d has no stage %d; "
                           "ignoring", replica, stage)
            return
        host = pipe.stages[stage].ranks[0] // self.chips_per_host
        ip = next((p for p in self.host_ips
                   if self._host_index[p] == host), None)
        if ip is None:
            logger.warning("chaos kill_stage: host %d already gone", host)
            return
        logger.warning(
            "chaos kill_stage: stage %d of replica %d lives on host %s; "
            "declaring it lost", stage, replica, ip)
        metrics.flight_recorder().record(
            "chaos_kill_stage_resolved", stage=stage, replica=replica,
            lost_ip=ip, step=self.step)
        # In-process detection: the engine is both detector and responder,
        # so it mints the incident's trace_id itself (the master would on
        # a real host loss).
        detected_at = time.time()
        trace = {"trace_id": obs_spans.new_trace_id(),
                 "detected_at": detected_at, "cause": "chaos_kill_stage"}
        obs_spans.span_recorder().record(
            "incident.detect", detected_at, detected_at,
            trace_id=trace["trace_id"], lost_ip=ip, cause="chaos_kill_stage")
        self.request_reconfiguration(ip, trace=trace)

    def request_reconfiguration(self, lost_ip: str,
                                trace: dict | None = None,
                                decision: dict | None = None) -> None:
        with self._lock:
            self._pending_lost.append((lost_ip, trace, decision))

    def request_grow(self, joined_ips: list[str],
                     trace: dict | None = None,
                     decision: dict | None = None) -> None:
        """Queue a JOIN batch; applied at the next step boundary
        (_maybe_grow), never mid-step."""
        if not joined_ips:
            return
        with self._lock:
            self._pending_joins.append((list(joined_ips), trace, decision))

    def request_drain(self, trace: dict | None = None) -> None:
        """Proactive preemption drain: the host got an advance notice, so
        flush durable state at the next step boundary and exit cleanly
        (the agent reports JOB_DONE, not a failure)."""
        metrics.flight_recorder().record(
            "drain_requested", ip=self.agent_ip, step=self.step,
            trace_id=(trace or {}).get("trace_id"))
        with self._lock:
            self._drain_requested = True

    def request_inplace_degrade(self, lost_ip: str,
                                trace: dict | None = None,
                                decision: dict | None = None) -> None:
        """Multihost zero-respawn reroute request; applied at the next
        step boundary ALL live processes agree on."""
        with self._lock:
            self._inplace_queue.append(
                {"lost_ip": lost_ip, "trace": trace, "decision": decision})

    def _maybe_reconfigure(self) -> None:
        with self._lock:
            lost = list(self._pending_lost)
            self._pending_lost.clear()
        if not lost:
            return
        # Losses pending at the same boundary are ONE correlated incident:
        # recovering them serially would let the first re-plan route work
        # onto hosts the second is about to remove (and the policy plane
        # must see the full blast radius to rule out rerouting).
        seen: dict[str, None] = {}
        for ip, _, _ in lost:
            seen.setdefault(ip)
        ip0, trace, decision = lost[0]
        extra = [ip for ip in seen if ip != ip0]
        self.reconfigure(ip0, trace=trace, decision=decision,
                         extra_lost=extra)

    def _maybe_grow(self) -> None:
        with self._lock:
            pending = list(self._pending_joins)
            self._pending_joins.clear()
        if not pending:
            return
        # Arrivals pending at one step boundary are ONE grow incident:
        # the policy must price the whole batch (three spares vs one
        # 3-host pipeline are different verdicts), mirroring the
        # correlated-loss batching above. First trace/decision wins.
        seen: dict[str, None] = {}
        for ips, _, _ in pending:
            for ip in ips:
                seen.setdefault(ip)
        _, trace, decision = pending[0]
        self.grow(list(seen), trace=trace, decision=decision)

    def reconfigure(self, lost_ip: str, trace: dict | None = None,
                    decision: dict | None = None,
                    extra_lost: tuple | list = (),
                    inplace: bool = False) -> None:
        """Incident-instrumented recovery entry point: opens the incident
        (adopting the upstream detect/broadcast/notified marks the trace
        context carried), pins the trace as the process ambient so every
        span recorded during recovery stitches onto it, and runs the
        actual recovery (_do_reconfigure). When recovery was applied, the
        incident stays open until the first post-recovery step commits
        incident-<n>.json (train loop -> _commit_incident)."""
        incident = obs_incident.IncidentBuilder(
            lost_ip,
            trace_id=(trace or {}).get("trace_id"),
            cause=(trace or {}).get("cause"))
        incident.adopt(trace)
        incident.mark("apply_start")
        obs_spans.set_ambient({"trace_id": incident.trace_id})
        prev_recovered = self._recovered_at
        try:
            with obs_spans.span("engine.reconfigure",
                                trace_id=incident.trace_id, lost_ip=lost_ip,
                                extra_lost=",".join(extra_lost)):
                self._do_reconfigure(lost_ip, decision=decision,
                                     extra_lost=extra_lost, inplace=inplace)
        finally:
            obs_spans.set_ambient(None)
            if self._recovering and self._recovered_at != prev_recovered:
                incident.mark("apply_end")
                self._incident = incident

    def _do_reconfigure(self, lost_ip: str, decision: dict | None = None,
                        extra_lost: tuple | list = (),
                        inplace: bool = False) -> None:
        """Full recovery path (reference on_reconfigure, engine.py:91-180),
        dispatched on the policy verdict: reroute mutates the live topology
        in place (degrade/), reinstantiate runs host algebra -> template
        re-match -> batch redistribution -> re-instantiation reusing
        surviving weights + optimizer state and the data position, restore
        does the same re-plan but from the last durable checkpoint (the
        policy plane picks it when in-memory recovery is a losing bet)."""
        t0 = time.perf_counter()
        # Deferred losses reference arrays on the pre-failure meshes; read
        # them back now, while (most of) the backing buffers still exist.
        self._drain_pending_losses()
        if self.multihost:
            if inplace:
                self._do_inplace_reroute(lost_ip, decision, t0)
                return
            # A lost peer breaks the shared jax.distributed world; the agent
            # respawns the worker over the survivors (live mirrors make the
            # restart checkpoint-free). In-place RECONFIGURATION stays
            # single-controller; an in-place DEGRADE rides the consensus
            # queue (_maybe_inplace_degrade) instead of this path.
            logger.warning(
                "multihost MPMD reconfigures by respawn; ignoring in-place "
                "request for %s", lost_ip,
            )
            return
        lost_ips = [ip for ip in (lost_ip, *extra_lost)
                    if ip in self.host_ips]
        if not lost_ips:
            logger.warning("unknown lost host %s", lost_ip)
            return
        lost_ip = lost_ips[0]
        lost_host = self._host_index[lost_ip]
        correlated = len(lost_ips) > 1
        # A mid-window jax.profiler trace must not straddle the topology
        # change: close it now; the tracer re-arms on its next window.
        self._close_step_watch()
        if self.fused is not None:
            # Fused recovery is a mesh shrink; one host at a time.
            for ip in lost_ips:
                self._reconfigure_fused(ip, self._host_index[ip], t0)
            return

        # Policy verdict: the broadcast decision when the master attached
        # one (every process applies the same verdict), the local policy
        # engine's otherwise (in-process detection never crossed the
        # control plane).
        pdec = decision_from_payload(decision)
        if pdec is None:
            pdec = self._consult_policy(lost_ips, cause="engine_detected")
        mechanism = pdec.mechanism

        if mechanism == MECH_RESTORE:
            if self._restore_recover(lost_ips, t0):
                return
            logger.warning("policy chose restore but no durable checkpoint "
                           "is loadable; re-instantiating instead")
            mechanism = MECH_REINSTANTIATE

        # Degraded-mode fast path (oobleck_tpu/degrade): reroute the dead
        # replica's microbatches into the survivors' bubbles on the same
        # topology — no re-plan, no recompile. try_degrade returns one
        # DegradeDecision either way; on fallback it is recorded below with
        # the measured re-instantiation latency so estimate and actual land
        # in the same flight-recorder event.
        ddec = None
        if (mechanism == MECH_REROUTE and not correlated
                and self.args.execution.degrade_enabled):
            from oobleck_tpu.degrade.apply import try_degrade

            ddec = try_degrade(self, lost_ip, lost_host, t0)
            if ddec.mechanism == "reroute":
                self._observe_policy_measured(
                    MECH_REROUTE, ddec.measured_recovery_s)
                return
        else:
            from oobleck_tpu.degrade.decision import (
                MECH_DISABLED,
                DegradeDecision,
            )

            if not self.args.execution.degrade_enabled:
                reason = "degrade_disabled"
            elif correlated:
                # Correlated loss: the survivors' bubbles cannot absorb
                # several replicas' worth of work (policy marks the reroute
                # arm infeasible); fall straight through to a full re-plan.
                reason = "correlated_failure"
            else:
                reason = f"policy:{pdec.reason}"
            ddec = DegradeDecision(
                lost_ip=lost_ip, lost_host=lost_host,
                mechanism=(MECH_DISABLED
                           if not self.args.execution.degrade_enabled
                           else MECH_REINSTANTIATE),
                reason=reason)

        # Host algebra + template re-match, shared verbatim with the
        # recovery precompiler so its AOT executables hit here.
        plan, host_assignment, idle = self.predict_replan(
            {self._host_index[ip] for ip in lost_ips})
        if idle:
            logger.warning(
                "hosts %s idle after reconfiguration: no template extension "
                "fits them (feasible sizes %s)", idle,
                sorted({t.num_hosts for t in self.templates}),
            )

        # Surviving weights + optimizer state by layer (reference
        # _copy_model_states, engine.py:238-309: broadcast from an owner —
        # single-controller, a device_put from any survivor).
        old_params, old_opt = self._collect_layer_state()

        # Data position carries over (reference engine.py:203-214).
        it_done = self.dataloaders[0].num_iterations_done
        epoch = self.dataloaders[0].epoch

        for ip in lost_ips:
            self.host_ips.remove(ip)
        self.plan = plan
        self._materialize_plan(
            plan, it_done, epoch, old_params, old_opt,
            host_assignment=host_assignment,
        )
        elapsed = time.perf_counter() - t0
        self.recovery_times.append(elapsed)
        self._recovering = True
        self._recovered_at = time.monotonic()
        self._m_reconfigs.inc(path="mpmd")
        self._set_template_gauge()
        recovery.observe_latency(elapsed, stage="reconfigure")
        if ddec is not None:
            ddec.measured_recovery_s = elapsed
            ddec.record()
        self._observe_policy_measured(MECH_REINSTANTIATE, elapsed)
        metrics.flight_recorder().record(
            "engine_reconfigured", lost_ip=lost_ip, path="mpmd",
            lost_ips=lost_ips, correlated=correlated,
            elapsed_s=round(elapsed, 3), step=self.step)
        logger.warning(
            "reconfigured after losing %s in %.2fs: %s", lost_ip, elapsed, plan,
        )
        if self._precompiler is not None:
            # Re-arm for the NEXT failure from the new (smaller) topology.
            self.start_recovery_precompile()

    # -- grow direction (JOIN incidents, PR 13) ------------------------- #

    def grow(self, joined_ips: list[str], trace: dict | None = None,
             decision: dict | None = None) -> None:
        """Incident-instrumented grow entry point, mirroring
        reconfigure(): opens the incident (adopting upstream detect/
        broadcast/notified marks), pins the trace as the process ambient,
        and runs _do_grow. The incident stays open until the first
        post-grow step commits incident-<n>.json — one committed file per
        JOIN batch, with the policy decision (all three arm costs)
        attached."""
        incident = obs_incident.IncidentBuilder(
            "",
            trace_id=(trace or {}).get("trace_id"),
            cause=(trace or {}).get("cause") or "join",
            joined_ips=list(joined_ips), direction="grow")
        incident.adopt(trace)
        incident.mark("apply_start")
        obs_spans.set_ambient({"trace_id": incident.trace_id})
        prev_recovered = self._recovered_at
        pdec = None
        try:
            with obs_spans.span("engine.grow",
                                trace_id=incident.trace_id,
                                joined_ips=",".join(joined_ips)):
                pdec = self._do_grow(joined_ips, decision=decision)
        finally:
            obs_spans.set_ambient(None)
            if self._recovering and self._recovered_at != prev_recovered:
                if pdec is not None:
                    incident.attrs["decision"] = pdec.as_payload()
                incident.mark("apply_end")
                self._incident = incident

    def _do_grow(self, joined_ips: list[str], decision: dict | None = None):
        """Apply one grow incident: bind the arrivals into the engine's
        geometry, resolve the policy verdict (a broadcast-attached grow
        decision wins; anything else consults the local policy engine),
        and execute the chosen arm. Returns the resolved PolicyDecision
        (None when nothing was admitted)."""
        t0 = time.perf_counter()
        if self.multihost or self.fused is not None:
            # Growing a jax.distributed world takes a coordinated restart
            # of every process (world size is baked into the runtime);
            # the fused path would need a mesh re-grow. Both park the
            # arrivals as spares so the capacity is tracked, never lost.
            for ip in joined_ips:
                if ip not in self.host_ips and ip not in self._spare_hosts:
                    self._spare_hosts.append(ip)
            metrics.flight_recorder().record(
                "grow_deferred", joined_ips=joined_ips, step=self.step,
                reason="multihost" if self.multihost else "fused")
            logger.warning("grow deferred (%s path): %s parked as spares",
                           "multihost" if self.multihost else "fused",
                           joined_ips)
            return None
        admitted = self._admit_hosts(joined_ips)
        if not admitted:
            return None
        # Deferred losses reference arrays on the pre-grow meshes; read
        # them back before a re-materialization can drop the buffers.
        self._drain_pending_losses()
        pdec = decision_from_payload(decision)
        if pdec is None or pdec.mechanism not in GROW_MODES:
            pdec = self._consult_policy_grow(admitted,
                                             cause="engine_detected")
        mechanism = pdec.mechanism

        if mechanism == MECH_GROW_DP:
            if self._grow_dp_apply(admitted, t0):
                return pdec
            logger.warning("grow_dp chosen but no template fits the "
                           "arrivals; absorbing %s as spares", admitted)
            mechanism = MECH_ABSORB
        if mechanism == MECH_GROW_RESHAPE:
            self._grow_reshape_apply(admitted, t0)
            return pdec

        # absorb_spare (chosen, or the grow_dp apply-time fallback):
        # park the arrivals in the spare pool — zero interruption, the
        # live pipelines never notice. The incident still commits (the
        # decision and its costs are the forensic record).
        self._spare_hosts.extend(admitted)
        elapsed = time.perf_counter() - t0
        self._recovering = True
        self._recovered_at = time.monotonic()
        self._m_grows.inc(mechanism=MECH_ABSORB)
        self._observe_policy_measured(MECH_ABSORB, elapsed)
        metrics.flight_recorder().record(
            "grow_absorbed", joined_ips=admitted,
            spares=list(self._spare_hosts),
            elapsed_s=round(elapsed, 3), step=self.step)
        logger.warning(
            "absorbed %s into the spare pool in %.3fs (zero interruption; "
            "spares now %s)", admitted, elapsed, self._spare_hosts)
        return pdec

    def _admit_hosts(self, ips: list[str]) -> list[str]:
        """Bind arriving hosts into the engine's immutable geometry: a
        NEW host gets the next ORIGINAL index and chips_per_host fresh
        devices (self.devices only ever grows — the rank encoding
        rank = original_index * chips_per_host + local stays valid);
        a previously-lost host rejoining reuses its original index, whose
        device slice never left self.devices. Arms the chaos
        spot-lifetime deadline when one is advertised. Returns the ips
        actually admitted."""
        admitted = []
        for ip in ips:
            if ip in self.host_ips or ip in self._spare_hosts:
                logger.warning("join: host %s already present; ignoring",
                               ip)
                continue
            if ip not in self._host_index:
                cph = self.chips_per_host or 1
                bound = {id(d) for d in self.devices}
                pool = [d for d in jax.devices() if id(d) not in bound]
                if len(pool) < cph:
                    logger.warning(
                        "join: no %d free devices for %s (have %d); "
                        "refusing", cph, ip, len(pool))
                    metrics.flight_recorder().record(
                        "join_refused", ip=ip, reason="no_free_devices",
                        step=self.step)
                    continue
                self._host_index[ip] = len(self._host_index)
                self.devices.extend(pool[:cph])
            lifetime = chaos().spot_lifetime(ip)
            if lifetime is not None:
                self._spot_deadlines[ip] = time.monotonic() + lifetime
            admitted.append(ip)
        return admitted

    def predict_grow(self, new_hosts: set[int],
                     current: list[list[int]] | None = None):
        """predict_replan's grow-direction mirror: keep every current
        pipeline's host group intact and fold `new_hosts` into
        additional DP pipeline(s) from the existing templates, WITHOUT
        mutating engine state. Returns (plan, host_assignment,
        idle_hosts); plan is None when no template fits the arrivals
        (the caller absorbs them instead). Shared with the recovery
        precompiler so predicted post-grow executables carry
        byte-identical cache keys to the ones a real JOIN will ask
        for."""
        if current is None:
            current = [
                sorted({r // self.chips_per_host for r in p.ranks})
                for p in self.pipelines
            ]
        by_hosts = {t.num_hosts: t for t in self.templates}
        sizes = sorted(by_hosts)
        fitted, idle = fit_host_groups([sorted(new_hosts)], sizes)
        if not fitted:
            return None, None, sorted(new_hosts)
        groups = [list(g) for g in current] + fitted
        new_instances: dict[PipelineTemplate, int] = {}
        for hosts in groups:
            t = by_hosts[len(hosts)]
            new_instances[t] = new_instances.get(t, 0) + 1
        ar_across = [p.allreduce_across_hosts for p in self.profiles]
        plan = PipelineInstantiator().get_new_execution_plan(
            new_instances, ar_across, self.plan.total_num_microbatches
        )
        groups_by_size: dict[int, list[list[int]]] = {}
        for g in groups:
            groups_by_size.setdefault(len(g), []).append(g)
        host_assignment = [
            groups_by_size[t.num_hosts].pop(0) for t in plan.instances
        ]
        return plan, host_assignment, idle

    def _grow_dp_apply(self, admitted: list[str], t0: float) -> bool:
        """grow_dp: keep every surviving pipeline's host group intact and
        add DP pipeline(s) over the arriving hosts from the EXISTING
        templates — no restore, no survivor respawn; the batch
        redistribution and the new replicas materializing from the live
        weights (the DP copy IS the state transfer) are the whole
        interruption. Returns False when no template fits."""
        new_group = {self._host_index[ip] for ip in admitted}
        plan, host_assignment, idle = self.predict_grow(new_group)
        if plan is None:
            return False
        active = {h for g in host_assignment for h in g}
        joined_active = [ip for ip in admitted
                        if self._host_index[ip] in active]
        joined_idle = [ip for ip in admitted if ip not in joined_active]
        if joined_idle:
            logger.warning(
                "hosts %s idle after grow_dp (no template extension fits "
                "them); parked as spares", joined_idle)
            self._spare_hosts.extend(joined_idle)
        old_params, old_opt = self._collect_layer_state()
        it_done = self.dataloaders[0].num_iterations_done
        epoch = self.dataloaders[0].epoch
        self.host_ips.extend(joined_active)
        self.plan = plan
        self._materialize_plan(plan, it_done, epoch, old_params, old_opt,
                               host_assignment=host_assignment)
        self._finish_grow(MECH_GROW_DP, joined_active, t0, rolled_back=0)
        return True

    def _grow_reshape_apply(self, admitted: list[str], t0: float) -> None:
        """grow_reshape: re-instantiate on the larger template set,
        planned exactly as a fresh bring-up at the new fleet size would
        plan — the LIVE promotion of the offline 2->4
        restore-across-reshape path. State comes from the last durable
        checkpoint when one exists (honest rollback, the step counter
        rewinds); else the live layer state reshapes in place (nothing
        replayed)."""
        self._ensure_templates_for(len(self.host_ips) + len(admitted))
        restored = self.try_restore_checkpoint()
        rolled_back = 0
        if restored is not None:
            old_params = restored["params"]
            old_opt = {}
            for li, leaves in restored["opt"].items():
                struct = jax.tree.structure(
                    jax.eval_shape(self.optimizer.init, old_params[li]))
                old_opt[li] = jax.tree.unflatten(struct, leaves)
            meta = restored["meta"]
            it_done = int(meta["num_iterations_done"])
            epoch = int(meta["epoch"])
            rolled_back = self.step - int(meta["step"])
            self.step = int(meta["step"])
        else:
            old_params, old_opt = self._collect_layer_state()
            it_done = self.dataloaders[0].num_iterations_done
            epoch = self.dataloaders[0].epoch
        self.host_ips.extend(admitted)
        ar_across = [p.allreduce_across_hosts for p in self.profiles]
        plan = PipelineInstantiator().get_best_execution_plan(
            self.templates, ar_across, len(self.host_ips),
            self.plan.total_num_microbatches,
        )
        # Contiguous blocks over the sorted available indices — for a
        # never-shrunk fleet this is exactly the assignment a fresh
        # bring-up materializes, which is what the live-grow parity test
        # pins against its uninterrupted twin.
        avail = sorted(self._host_index[ip] for ip in self.host_ips)
        host_assignment = []
        pos = 0
        for t in plan.instances:
            host_assignment.append(avail[pos:pos + t.num_hosts])
            pos += t.num_hosts
        self.plan = plan
        self._materialize_plan(plan, it_done, epoch, old_params, old_opt,
                               host_assignment=host_assignment)
        self._finish_grow(MECH_GROW_RESHAPE, admitted, t0,
                          rolled_back=rolled_back)

    def _finish_grow(self, mechanism: str, admitted: list[str], t0: float,
                     *, rolled_back: int) -> None:
        elapsed = time.perf_counter() - t0
        self.recovery_times.append(elapsed)
        self._recovering = True
        self._recovered_at = time.monotonic()
        self._m_grows.inc(mechanism=mechanism)
        self._set_template_gauge()
        recovery.observe_latency(elapsed, stage="grow")
        self._observe_policy_measured(mechanism, elapsed)
        metrics.flight_recorder().record(
            "engine_grown", joined_ips=admitted, mechanism=mechanism,
            elapsed_s=round(elapsed, 3), step=self.step,
            rolled_back_steps=rolled_back)
        logger.warning(
            "grew onto %s via %s in %.2fs%s: %s", admitted, mechanism,
            elapsed,
            f" (rolled back {rolled_back} step(s))" if rolled_back else "",
            self.plan)
        if self._precompiler is not None:
            # Re-arm for the NEXT incident from the new (larger) topology.
            self.start_recovery_precompile()

    def _grow_dp_feasibility(self, k: int) -> tuple[bool, str]:
        """Whether k arriving hosts can form new DP pipeline(s) from the
        EXISTING templates alone (grow_dp's apply-time requirement)."""
        if not self.templates or self.plan is None:
            return False, "no_plan"
        smallest = min(t.num_hosts for t in self.templates)
        if k >= smallest:
            return True, ""
        return False, f"arrivals({k})<smallest_template({smallest})"

    def _consult_policy_grow(self, joined_ips: list[str], *,
                             cause: str = ""):
        """Score the grow arms for an in-process-detected JOIN with the
        same signals the master would use, plus the chaos spot-lifetime
        hints only this process can see."""
        pol = self._policy_engine()
        staleness = None
        plane = self._durable_plane()
        if plane is not None:
            durable = plane.last_durable_step
            if durable is not None and durable >= 0:
                staleness = max(float(self.step - durable), 0.0)
        hints: dict[str, float] = {}
        for ip in joined_ips:
            lt = chaos().spot_lifetime(ip)
            if lt:
                hints[ip] = lt
        dp_ok, dp_why = self._grow_dp_feasibility(len(joined_ips))
        return pol.decide_grow(
            joined_ips,
            current_hosts=len(self.host_ips),
            dp_feasible=dp_ok,
            dp_reason=dp_why,
            staleness_steps=staleness,
            step_seconds=self._step_s_ewma,
            lifetime_hints=hints,
            cause=cause)

    def _reconfigure_fused(self, lost_ip: str, lost_host: int, t0: float) -> None:
        """Fused-path recovery: shrink the global mesh to the surviving
        chips and re-place the live TrainState on it (the sharded-state
        analog of the reference's template re-match + weight copy)."""
        # Build the new mesh BEFORE mutating host bookkeeping: if the
        # survivors genuinely cannot run (fewer than stage*tensor*seq
        # chips), the raise leaves the engine state consistent.
        survivors = [h for h in self._fused_hosts if h != lost_host]
        devices = [
            d for h in survivors
            for d in self.devices[h * self.chips_per_host:
                                  (h + 1) * self.chips_per_host]
        ]
        mesh = self._fused_mesh(devices, shrink_to_fit=True)
        new_fused = self.fused.replace_mesh(mesh)
        self._fused_hosts = survivors
        self.host_ips.remove(lost_ip)
        self.fused = new_fused
        # Rebuild the loader from the CONSUMED position: any staged batch
        # was placed with the dead mesh's sharding, and the stager's
        # place_fn is bound to the old FusedPipeline.
        old_dl = self.dataloaders[0]
        it_done, ep = old_dl.num_iterations_done, old_dl.epoch
        if hasattr(old_dl, "close"):
            old_dl.close()
        self.dataloaders = [self._fused_dataloader(
            new_fused.num_microbatches, it_done, ep)]
        elapsed = time.perf_counter() - t0
        self.recovery_times.append(elapsed)
        self._m_reconfigs.inc(path="fused")
        self._set_template_gauge()
        recovery.observe_latency(elapsed, stage="reconfigure")
        metrics.flight_recorder().record(
            "engine_reconfigured", lost_ip=lost_ip, path="fused",
            elapsed_s=round(elapsed, 3), step=self.step)
        stranded = len(devices) - mesh.devices.size
        self.stranded_chips.append(stranded)
        logger.warning(
            "reconfigured (fused) after losing %s in %.2fs: mesh %s"
            "%s", lost_ip, elapsed, dict(mesh.shape),
            f" ({stranded} surviving chips STRANDED)" if stranded else "",
        )


_UNSET = object()


class _CyclicView:
    """Repeat a too-small eval pool up to `length` samples (i mod len) so a
    tiny validation split can still fill one iteration bucket."""

    def __init__(self, ds, length: int):
        self.ds = ds
        self.length = length

    def __len__(self) -> int:
        return self.length

    def __getitem__(self, i: int):
        return self.ds[i % len(self.ds)]

    def set_epoch(self, epoch: int) -> None:
        if hasattr(self.ds, "set_epoch"):
            self.ds.set_epoch(epoch)


class _TailView:
    """A length-`length` window of `ds` starting at `offset` (the held-out
    evaluation tail)."""

    def __init__(self, ds, offset: int, length: int):
        self.ds = ds
        self.offset = offset
        self.length = length

    def __len__(self) -> int:
        return self.length

    def __getitem__(self, i: int):
        return self.ds[self.offset + i]

    def set_epoch(self, epoch: int) -> None:
        if hasattr(self.ds, "set_epoch"):
            self.ds.set_epoch(epoch)


def _best_data_fsdp(cap: int, mb: int, hidden: int) -> tuple[int, int]:
    """Pick (data, fsdp) with data*fsdp <= cap maximizing chips used, s.t.
    mb % (data*fsdp) == 0 and (when known) hidden % fsdp == 0; ties prefer
    larger fsdp. (1, 1) always qualifies."""
    best = (0, 1, 1)  # (used, fsdp, data)
    for f in range(cap, 0, -1):
        if hidden and hidden % f:
            continue
        d = next((d for d in range(cap // f, 0, -1)
                  if mb % (d * f) == 0), 0)
        if d and (d * f > best[0] or (d * f == best[0] and f > best[1])):
            best = (d * f, f, d)
    return best[2], best[1]


def _scale_template_chips(t: PipelineTemplate, tp: int) -> PipelineTemplate:
    """Scale a template generated over TP chip-groups back to real chips."""
    import dataclasses

    stages = tuple(
        dataclasses.replace(s, num_chips=s.num_chips * tp) for s in t.stages
    )
    return dataclasses.replace(
        t, stages=stages, chips_per_host=t.chips_per_host * tp
    )


def _hbm_sample() -> tuple:
    """(bytes in use, limit, largest free block) of the fullest local
    device, each None where the platform reports none (the CPU does):
    what the allocator has left at a step's end, for the telemetry ring.
    Called once a step, in bookkeeping; asks the allocator for its
    counters and waits for nothing on the device."""
    # local_devices: on multi-host, devices()[0] is process 0's chip and
    # is non-addressable from other workers.
    stats = max((d.memory_stats() or {} for d in jax.local_devices()),
                key=lambda m: m.get("bytes_in_use", 0))
    return (stats.get("bytes_in_use"), stats.get("bytes_limit"),
            stats.get("largest_free_block_bytes"))


def _load_summary(ring) -> str:
    """Where the ring's window of steps routed, for the step timer's line;
    nothing where no step recorded a load."""
    window = ring.load_window()
    if not window:
        return ""
    stats = obs_telemetry.load_stats(window)
    return f" | moe fill {stats['fill_pct']:.1f}% skew {stats['skew']:.2f}"


def _device_memory_summary(sample: tuple | None) -> str:
    """Device memory at the last step's end, from the telemetry ring's
    sample (reference logs CUDA memory every 10 steps, engine.py:657-659);
    CPU backends report no stats."""
    if sample is None or sample[obs_telemetry.HBM_IN_USE] is None:
        return "mem n/a"
    out = f"mem {sample[obs_telemetry.HBM_IN_USE] / 2**30:.2f}GiB"
    limit = sample[obs_telemetry.HBM_LIMIT]
    if limit:
        out += f" / limit {limit / 2**30:.0f}GiB"
    free = sample[obs_telemetry.HBM_LARGEST_FREE]
    if free is not None:
        out += f", largest free block {free / 2**30:.2f}GiB"
    return out


def _place_opt_state(optimizer, state, param_sharding_tree):
    """Re-place one layer's optimizer state onto new param shardings.

    Adam mu/nu mirror the param tree (placed like the params); scalar
    bookkeeping leaves (count) go replicated on the same mesh."""
    import optax
    from jax.sharding import NamedSharding, PartitionSpec

    mesh = jax.tree.leaves(
        param_sharding_tree, is_leaf=lambda x: hasattr(x, "mesh")
    )[0].mesh
    replicated = NamedSharding(mesh, PartitionSpec())
    return optax.tree_map_params(
        optimizer,
        lambda leaf, sh: jax.device_put(leaf, sh),
        state,
        param_sharding_tree,
        transform_non_params=lambda leaf: jax.device_put(leaf, replicated),
        is_leaf=lambda x: hasattr(x, "mesh"),
    )
