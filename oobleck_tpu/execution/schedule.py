"""1F1B and interleaved-1F1B pipeline instruction schedules.

Capability match for the reference's OobleckPipelineSchedule
(/root/reference/oobleck/execution/pipeline.py:24-84, a deepspeed
TrainSchedule subclass): the schedule is an explicit per-stage instruction
stream with gradient-allreduce and optimizer-step decoupled from it. The
engine interprets these instructions; on TPU each Forward/Backward dispatches
a jitted stage program, and send/recv become cross-mesh device transfers.

Stage i of S with M microbatches runs the canonical 1F1B order:
  warmup  = min(S-1-i, M) forwards,
  steady  = alternating forward/backward,
  cooldown = remaining backwards,
with a pipeline bubble of (S-1)/(M+S-1).

The interleaved schedule (Megatron-LM's virtual-pipeline variant) assigns v
model *chunks* to each physical stage; virtual stage vs = chunk*S + stage, so
activations flow chunk-major through the physical ring (stage S-1 hands chunk
c straight to stage 0's chunk c+1). Each rank's warmup grows to
min((S-1-i)*2 + (v-1)*S, v*M) forward units, but every unit is 1/v of the
model, shrinking the bubble to (S-1)/(v*M+S-1). v=1 degenerates to exactly
the canonical streams above (the interleaved warmup formula does not — it is
special-cased, and the invariant tests pin that down).

A LOOPED model's visits are the same streams. A model that goes through a
range of its layers R times over one set of weights (`models/base.py`) and
whose range a template cuts across stages runs with v = R: chunk c of a
stage is the stage's share of the repeated range, THE SAME layers for every
c (the first also holds the stage's layers in front of the range, the last
those behind it), where the interleaved schedule's v chunks are v different
layer ranges. Nothing here knows the difference: "stage S-1 hands chunk c
straight to stage 0's chunk c+1" is the carry going back into the range's
first layer, and the warm-up arithmetic is the same. The pipeline derives
R from the model, never from `virtual_stages` (`execution/pipeline.py`).
"""

from __future__ import annotations

from dataclasses import dataclass
from enum import Enum


class Op(Enum):
    LOAD_MICROBATCH = "load_microbatch"
    RECV_ACTIVATION = "recv_activation"
    FORWARD = "forward"
    SEND_ACTIVATION = "send_activation"
    RECV_GRAD = "recv_grad"
    BACKWARD = "backward"
    SEND_GRAD = "send_grad"


@dataclass(frozen=True)
class Instruction:
    op: Op
    stage: int
    microbatch: int
    chunk: int = 0


def bubble_fraction(num_stages: int, num_microbatches: int,
                    virtual_stages: int = 1) -> float:
    """Closed-form pipeline bubble: (S-1)/(v*M+S-1)."""
    S, M, v = num_stages, num_microbatches, virtual_stages
    if S <= 1:
        return 0.0
    return (S - 1) / (v * M + S - 1)


def validate_interleaving(num_stages: int, num_microbatches: int,
                          virtual_stages: int) -> None:
    """Raise ValueError when (S, M, v) cannot run interleaved."""
    S, M, v = num_stages, num_microbatches, virtual_stages
    if v < 1:
        raise ValueError(f"virtual_stages must be >= 1, got {v}")
    if v == 1:
        return
    if M % S != 0:
        raise ValueError(
            "interleaved schedule requires num_microbatches to be a "
            f"multiple of num_stages: {M} % {S} != 0"
        )


def send_activation_dest(stage: int, chunk: int, num_stages: int
                         ) -> tuple[int, int]:
    """(stage, chunk) that receives the activation sent by (stage, chunk)."""
    vs = chunk * num_stages + stage + 1
    return vs % num_stages, vs // num_stages


def send_grad_dest(stage: int, chunk: int, num_stages: int
                   ) -> tuple[int, int]:
    """(stage, chunk) that receives the gradient sent by (stage, chunk)."""
    vs = chunk * num_stages + stage - 1
    return vs % num_stages, vs // num_stages


def interleaved_warmup(stage: int, num_stages: int, num_microbatches: int,
                       virtual_stages: int) -> int:
    """Forward units rank `stage` runs before its first backward (v > 1)."""
    S, M, v, i = num_stages, num_microbatches, virtual_stages, stage
    return min((S - 1 - i) * 2 + (v - 1) * S, v * M)


def _interleaved_forward_unit(k: int, stage: int, num_stages: int,
                              virtual_stages: int) -> tuple[int, int]:
    """k-th forward microbatch-chunk unit on this rank -> (chunk, mb).

    Units sweep S microbatches through all v chunks before moving to the
    next group of S microbatches (Megatron's interleaved order)."""
    S, v = num_stages, virtual_stages
    group, within = divmod(k, S * v)
    chunk, offset = divmod(within, S)
    return chunk, group * S + offset


def _interleaved_backward_unit(k: int, stage: int, num_stages: int,
                               virtual_stages: int) -> tuple[int, int]:
    """k-th backward unit on this rank -> (chunk, mb); chunks run in
    reverse order (the last virtual stage backpropagates first)."""
    S, v = num_stages, virtual_stages
    group, within = divmod(k, S * v)
    chunk, offset = divmod(within, S)
    return v - 1 - chunk, group * S + offset


def stage_instructions(stage: int, num_stages: int, num_microbatches: int,
                       virtual_stages: int = 1) -> list[Instruction]:
    """The instruction stream for one physical stage.

    virtual_stages=1 is the canonical 1F1B stream (byte-identical to what
    this module emitted before interleaving existed); v>1 is interleaved
    1F1B and requires num_microbatches % num_stages == 0."""
    if virtual_stages > 1:
        return _interleaved_stage_instructions(
            stage, num_stages, num_microbatches, virtual_stages)

    S, M, i = num_stages, num_microbatches, stage
    first, last = i == 0, i == S - 1
    warmup = min(S - 1 - i, M)

    out: list[Instruction] = []

    def fwd(m):
        if first:
            out.append(Instruction(Op.LOAD_MICROBATCH, i, m))
        else:
            out.append(Instruction(Op.RECV_ACTIVATION, i, m))
        out.append(Instruction(Op.FORWARD, i, m))
        if not last:
            out.append(Instruction(Op.SEND_ACTIVATION, i, m))

    def bwd(m):
        if not last:
            out.append(Instruction(Op.RECV_GRAD, i, m))
        out.append(Instruction(Op.BACKWARD, i, m))
        if not first:
            out.append(Instruction(Op.SEND_GRAD, i, m))

    for m in range(warmup):
        fwd(m)
    for m in range(warmup, M):
        fwd(m)
        bwd(m - warmup)
    for m in range(M - warmup, M):
        bwd(m)
    return out


def _interleaved_stage_instructions(stage: int, num_stages: int,
                                    num_microbatches: int,
                                    virtual_stages: int) -> list[Instruction]:
    validate_interleaving(num_stages, num_microbatches, virtual_stages)
    S, M, v, i = num_stages, num_microbatches, virtual_stages, stage
    last_vs = S * v - 1
    total = v * M
    warmup = interleaved_warmup(i, S, M, v)

    out: list[Instruction] = []

    def fwd(k):
        chunk, m = _interleaved_forward_unit(k, i, S, v)
        vs = chunk * S + i
        if vs == 0:
            out.append(Instruction(Op.LOAD_MICROBATCH, i, m, chunk))
        else:
            out.append(Instruction(Op.RECV_ACTIVATION, i, m, chunk))
        out.append(Instruction(Op.FORWARD, i, m, chunk))
        if vs < last_vs:
            out.append(Instruction(Op.SEND_ACTIVATION, i, m, chunk))

    def bwd(k):
        chunk, m = _interleaved_backward_unit(k, i, S, v)
        vs = chunk * S + i
        if vs < last_vs:
            out.append(Instruction(Op.RECV_GRAD, i, m, chunk))
        out.append(Instruction(Op.BACKWARD, i, m, chunk))
        if vs > 0:
            out.append(Instruction(Op.SEND_GRAD, i, m, chunk))

    for k in range(warmup):
        fwd(k)
    for k in range(warmup, total):
        fwd(k)
        bwd(k - warmup)
    for k in range(total - warmup, total):
        bwd(k)
    return out


def all_instructions(num_stages: int, num_microbatches: int,
                     virtual_stages: int = 1) -> list[list[Instruction]]:
    return [stage_instructions(i, num_stages, num_microbatches,
                               virtual_stages)
            for i in range(num_stages)]


def replay_schedule(num_stages: int, num_microbatches: int,
                    virtual_stages: int = 1,
                    duration_fn=None,
                    streams: "list[list[Instruction]] | None" = None,
                    on_op=None,
                    ) -> tuple[float, float]:
    """Dependency replay of per-unit compute durations: (makespan, busy).

    FORWARD(vs, m) waits for FORWARD(vs-1, m), BACKWARD(vs, m) waits for
    FORWARD(vs, m) and BACKWARD(vs+1, m), each physical stage is serial.
    Transfers are modeled as free (the interpreter overlaps them), so this
    isolates the schedule-shape component from dispatch/input stalls,
    which the engine reports separately. duration_fn(instruction) ->
    seconds; defaults to fwd=1, bwd=2 (the classic cost model). `streams`
    overrides the canonical per-stage instruction streams — the degrade
    planner replays rerouted streams through the same dependency rules,
    which is what makes its makespan estimate and the test-side replay of
    the emitted schedule one computation instead of two. `on_op(stage,
    inst, start, end)` observes every scheduled compute unit.
    """
    S, M, v = num_stages, num_microbatches, virtual_stages
    if duration_fn is None:
        duration_fn = lambda inst: 2.0 if inst.op is Op.BACKWARD else 1.0

    if streams is None:
        streams = all_instructions(S, M, v)
    ptr = [0] * S
    clock = [0.0] * S
    done: dict[tuple[str, int, int], float] = {}
    busy = 0.0
    last_vs = S * v - 1

    def deps_ready(inst: Instruction) -> float | None:
        """Latest dependency finish time, or None if not yet computable."""
        vs = inst.chunk * S + inst.stage
        t = 0.0
        if inst.op is Op.FORWARD:
            if vs > 0:
                key = ("f", vs - 1, inst.microbatch)
                if key not in done:
                    return None
                t = done[key]
        elif inst.op is Op.BACKWARD:
            key = ("f", vs, inst.microbatch)
            if key not in done:
                return None
            t = done[key]
            if vs < last_vs:
                key = ("b", vs + 1, inst.microbatch)
                if key not in done:
                    return None
                t = max(t, done[key])
        return t

    remaining = sum(len(s) for s in streams)
    while remaining:
        progressed = False
        for i in range(S):
            while ptr[i] < len(streams[i]):
                inst = streams[i][ptr[i]]
                if inst.op not in (Op.FORWARD, Op.BACKWARD):
                    ptr[i] += 1
                    remaining -= 1
                    progressed = True
                    continue
                ready = deps_ready(inst)
                if ready is None:
                    break
                d = float(duration_fn(inst))
                start = max(clock[i], ready)
                end = start + d
                clock[i] = end
                busy += d
                if on_op is not None:
                    on_op(i, inst, start, end)
                vs = inst.chunk * S + inst.stage
                kind = "f" if inst.op is Op.FORWARD else "b"
                done[(kind, vs, inst.microbatch)] = end
                ptr[i] += 1
                remaining -= 1
                progressed = True
        if not progressed:
            raise RuntimeError(
                f"schedule deadlock in replay: S={S} M={M} v={v}")
    makespan = max(clock) if clock else 0.0
    return makespan, busy


def simulate_bubble(num_stages: int, num_microbatches: int,
                    virtual_stages: int = 1,
                    duration_fn=None) -> float:
    """Measured-schedule bubble via dependency replay (replay_schedule):
    1 - busy/(S * makespan)."""
    makespan, busy = replay_schedule(
        num_stages, num_microbatches, virtual_stages, duration_fn)
    if makespan <= 0 or busy <= 0:
        return 0.0
    return max(0.0, 1.0 - busy / (num_stages * makespan))
