"""Invariants of the 1F1B / interleaved-1F1B instruction schedules.

Sweeps every (S <= 6, M <= 8, v <= 3) combination the schedule admits and
pins down: per-(microbatch, chunk) forward-before-backward ordering,
send/recv matching across neighbor streams, the closed-form warmup/steady/
cooldown phase structure, exact degeneration of v=1 to the canonical 1F1B
streams, rejection of invalid (S, M, v), and the dependency-replay bubble
reproducing the closed forms under the uniform fwd=1/bwd=2 cost model,
with the replay's observed slices adding up to the same bubble.
"""

import pytest

from oobleck_tpu.execution.schedule import (
    Instruction,
    Op,
    all_instructions,
    bubble_fraction,
    interleaved_warmup,
    replay_schedule,
    send_activation_dest,
    send_grad_dest,
    simulate_bubble,
    stage_instructions,
    validate_interleaving,
)


def _valid_combos():
    for S in range(1, 7):
        for M in range(1, 9):
            for v in range(1, 4):
                if v > 1 and M % S != 0:
                    continue
                yield S, M, v


COMBOS = list(_valid_combos())


def _reference_1f1b(stage: int, S: int, M: int) -> list[Instruction]:
    """The canonical 1F1B stream, restated independently so a refactor of
    stage_instructions cannot silently drift the v=1 behavior."""
    first, last = stage == 0, stage == S - 1
    warmup = min(S - 1 - stage, M)
    out: list[Instruction] = []

    def fwd(m):
        out.append(Instruction(
            Op.LOAD_MICROBATCH if first else Op.RECV_ACTIVATION, stage, m))
        out.append(Instruction(Op.FORWARD, stage, m))
        if not last:
            out.append(Instruction(Op.SEND_ACTIVATION, stage, m))

    def bwd(m):
        if not last:
            out.append(Instruction(Op.RECV_GRAD, stage, m))
        out.append(Instruction(Op.BACKWARD, stage, m))
        if not first:
            out.append(Instruction(Op.SEND_GRAD, stage, m))

    for m in range(warmup):
        fwd(m)
    for m in range(warmup, M):
        fwd(m)
        bwd(m - warmup)
    for m in range(M - warmup, M):
        bwd(m)
    return out


def _warmup(stage: int, S: int, M: int, v: int) -> int:
    if v == 1:
        return min(S - 1 - stage, M)
    return interleaved_warmup(stage, S, M, v)


@pytest.mark.parametrize("S,M,v", COMBOS)
def test_unit_coverage_and_fwd_before_bwd(S, M, v):
    """Every (chunk, microbatch) unit runs FORWARD exactly once and
    BACKWARD exactly once on its owning stage, forward first."""
    for stage, stream in enumerate(all_instructions(S, M, v)):
        fwd_pos = {}
        bwd_pos = {}
        for n, ins in enumerate(stream):
            assert ins.stage == stage
            if ins.op is Op.FORWARD:
                assert (ins.chunk, ins.microbatch) not in fwd_pos
                fwd_pos[(ins.chunk, ins.microbatch)] = n
            elif ins.op is Op.BACKWARD:
                assert (ins.chunk, ins.microbatch) not in bwd_pos
                bwd_pos[(ins.chunk, ins.microbatch)] = n
        expect = {(c, m) for c in range(v) for m in range(M)}
        assert set(fwd_pos) == expect
        assert set(bwd_pos) == expect
        for unit, nf in fwd_pos.items():
            assert nf < bwd_pos[unit], f"backward before forward for {unit}"


@pytest.mark.parametrize("S,M,v", COMBOS)
def test_send_recv_matching(S, M, v):
    """Every SEND has exactly one matching RECV on the destination stream
    (and vice versa), with the destination given by the ring helpers."""
    streams = all_instructions(S, M, v)

    def ops(stage, op):
        return {(i.chunk, i.microbatch) for i in streams[stage] if i.op is op}

    for stage in range(S):
        for ins in streams[stage]:
            if ins.op is Op.SEND_ACTIVATION:
                ds, dc = send_activation_dest(stage, ins.chunk, S)
                assert (dc, ins.microbatch) in ops(ds, Op.RECV_ACTIVATION)
            elif ins.op is Op.SEND_GRAD:
                ds, dc = send_grad_dest(stage, ins.chunk, S)
                assert (dc, ins.microbatch) in ops(ds, Op.RECV_GRAD)
            elif ins.op is Op.RECV_ACTIVATION:
                vs = ins.chunk * S + stage
                src_s, src_c = (vs - 1) % S, (vs - 1) // S
                assert (src_c, ins.microbatch) in ops(src_s, Op.SEND_ACTIVATION)
            elif ins.op is Op.RECV_GRAD:
                vs = ins.chunk * S + stage
                src_s, src_c = (vs + 1) % S, (vs + 1) // S
                assert (src_c, ins.microbatch) in ops(src_s, Op.SEND_GRAD)
    # global conservation: sends == recvs per edge type
    n_sa = sum(1 for s in streams for i in s if i.op is Op.SEND_ACTIVATION)
    n_ra = sum(1 for s in streams for i in s if i.op is Op.RECV_ACTIVATION)
    n_sg = sum(1 for s in streams for i in s if i.op is Op.SEND_GRAD)
    n_rg = sum(1 for s in streams for i in s if i.op is Op.RECV_GRAD)
    assert n_sa == n_ra == (S * v - 1) * M
    assert n_sg == n_rg == (S * v - 1) * M


@pytest.mark.parametrize("S,M,v", COMBOS)
def test_phase_structure_matches_closed_form(S, M, v):
    """Warmup/steady/cooldown counts: `warmup` forwards precede the first
    backward (one more in steady state), totals are v*M each."""
    for stage, stream in enumerate(all_instructions(S, M, v)):
        total = v * M
        warmup = _warmup(stage, S, M, v)
        compute = [i.op for i in stream if i.op in (Op.FORWARD, Op.BACKWARD)]
        assert compute.count(Op.FORWARD) == total
        assert compute.count(Op.BACKWARD) == total
        first_b = compute.index(Op.BACKWARD)
        fwd_before = compute[:first_b].count(Op.FORWARD)
        # steady state leads each fwd/bwd pair with the forward
        assert fwd_before == (warmup + 1 if warmup < total else total)
        # steady phase strictly alternates; cooldown is all backwards
        n_steady = 2 * (total - warmup) - 1 if warmup < total else 0
        steady = compute[first_b:first_b + n_steady]
        assert all(op is Op.BACKWARD for n, op in enumerate(steady)
                   if n % 2 == 0)
        assert all(op is Op.FORWARD for n, op in enumerate(steady)
                   if n % 2 == 1)
        cooldown = compute[first_b + n_steady:]
        assert all(op is Op.BACKWARD for op in cooldown)


@pytest.mark.parametrize("S", range(1, 7))
@pytest.mark.parametrize("M", range(1, 9))
def test_v1_degenerates_to_canonical_1f1b(S, M):
    """virtual_stages=1 must emit EXACTLY the canonical 1F1B streams —
    instruction for instruction, chunk 0 everywhere."""
    for stage in range(S):
        got = stage_instructions(stage, S, M, virtual_stages=1)
        want = _reference_1f1b(stage, S, M)
        assert got == want
        assert all(i.chunk == 0 for i in got)
        # the 3-arg legacy call is the same stream
        assert stage_instructions(stage, S, M) == want


@pytest.mark.parametrize("S,M,v", [(2, 3, 2), (3, 4, 2), (4, 6, 3),
                                   (5, 8, 2), (2, 5, 3)])
def test_invalid_interleaving_rejected(S, M, v):
    with pytest.raises(ValueError, match="multiple of num_stages"):
        validate_interleaving(S, M, v)
    with pytest.raises(ValueError, match="multiple of num_stages"):
        stage_instructions(0, S, M, virtual_stages=v)


def test_nonpositive_virtual_stages_rejected():
    with pytest.raises(ValueError, match="virtual_stages"):
        validate_interleaving(2, 4, 0)


@pytest.mark.parametrize("S,M,v", COMBOS)
def test_simulated_bubble_matches_closed_form_uniform_costs(S, M, v):
    """Dependency replay under the uniform fwd=1/bwd=2 cost model must
    reproduce the closed form (S-1)/(v*M+S-1) for both schedules — this is
    what licenses simulate_bubble as the 'measured' bubble estimator."""
    got = simulate_bubble(S, M, v)
    want = bubble_fraction(S, M, v)
    assert got == pytest.approx(want, abs=1e-9)


def test_interleaving_strictly_shrinks_closed_form_bubble():
    for S in (2, 3, 4):
        for M in (S, 2 * S, 4 * S):
            assert bubble_fraction(S, M, 2) < bubble_fraction(S, M, 1)
            assert bubble_fraction(S, M, 3) < bubble_fraction(S, M, 2)


def test_simulated_bubble_tracks_interleaving_gain():
    """Under uniform costs the replay, like the closed form, must show the
    interleaved schedule strictly below 1F1B for the same (S, M)."""
    for S, M in ((2, 4), (2, 8), (4, 8)):
        assert simulate_bubble(S, M, 2) < simulate_bubble(S, M, 1)


@pytest.mark.parametrize("S,M,v", [(2, 8, 1), (2, 8, 2), (4, 8, 1)])
def test_replayed_gap_matches_simulate_bubble(S, M, v):
    """What `on_op` observes is the replay itself: every scheduled unit
    once, no two units of a stage overlapping, and the gap between the
    observed slices equal to simulate_bubble's."""
    slices = []

    def on_op(stage, inst, start, end):
        assert inst.stage == stage
        slices.append((inst, start, end))

    makespan, busy = replay_schedule(S, M, v, on_op=on_op)
    # S*v forward + S*v backward units per microbatch
    assert len(slices) == S * v * M * 2
    assert len({(i.op, i.stage, i.chunk, i.microbatch)
                for i, _, _ in slices}) == len(slices)
    for stage in range(S):
        own = sorted((start, end) for inst, start, end in slices
                     if inst.stage == stage)
        assert all(a_end <= b_start for (_, a_end), (b_start, _)
                   in zip(own, own[1:]))
    assert sum(end - start for _, start, end in slices) == pytest.approx(busy)
    assert max(end for _, _, end in slices) == pytest.approx(makespan)
    gap = 1.0 - busy / (S * makespan)
    assert gap == pytest.approx(simulate_bubble(S, M, v), rel=1e-12)
