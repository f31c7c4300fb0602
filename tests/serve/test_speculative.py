"""Speculative decode end-to-end invariants, driven synchronously through
the real batcher (no scheduler thread — deterministic step order):

  * greedy parity: speculative output is BYTE-IDENTICAL to the
    non-speculative greedy stream, across page-boundary crossings,
    prefix-cache hits, and 100% misdrafting;
  * rollback hygiene: after a speculative run, the allocator state
    (refcounts, prefix registrations, free list) and lane tables are
    IDENTICAL to a never-drafted twin's — rejected drafts leave no
    trace the prefix cache could ever serve;
  * multi-token accounting: eos cuts mid-acceptance, max_tokens clamps
    the advance, deadlines fire on the first token past expiry;
  * k-adaptation: sustained rejection (spec_misdraft=1.0) collapses a
    lane to k=0, the probe path reopens it.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oobleck_tpu.models import build_model
from oobleck_tpu.serve.batcher import ContinuousBatcher, GenRequest
from oobleck_tpu.serve.engine import PagedDecodeEngine
from oobleck_tpu.serve.speculative import (
    LookupDrafter,
    ModelDrafter,
    SpecConfig,
    build_controller,
)
from oobleck_tpu.utils import chaos as chaos_mod
from oobleck_tpu.utils import metrics

PAGE = 4
MAX_SEQ = 64
PROMPT = [5, 6, 7, 8, 5, 6, 7, 8, 5, 6]


@pytest.fixture(autouse=True)
def _clean_chaos():
    # Fresh chaos plan AND a fresh metrics registry per test: the spec
    # counters are process-global, so per-test assertions on .value()
    # need a clean slate.
    chaos_mod.reset("")
    metrics.registry().clear()
    yield
    chaos_mod.reset("")


@pytest.fixture(scope="module", params=["gpt2-tiny", "llama-tiny"])
def model_and_params(request):
    model = build_model(request.param, {"dtype": jnp.float32})
    return model, model.init_params(jax.random.PRNGKey(0))


def _mk_batcher(model, params, *, mode, k=4, lanes=2, num_pages=64,
                min_accept=0.25, probe_every=32, drafter=None):
    engine = PagedDecodeEngine(model, lanes=lanes, max_seq=MAX_SEQ,
                               page_size=PAGE, num_pages=num_pages)
    engine.set_params(engine.stage_params(params), 0)
    spec = None
    if mode != "off":
        spec = build_controller(SpecConfig(
            mode=mode, k=k, min_accept=min_accept, probe_every=probe_every),
            draft_model=drafter)
    return ContinuousBatcher(engine, max_queue=8, spec=spec)


def _drive(b, reqs, max_iters=400):
    """Runs the scheduler by hand until every request is done; returns the
    number of decode / verify steps it took."""
    for r in reqs:
        b.submit(r)
    steps = 0
    for _ in range(max_iters):
        b._admit()
        if b.slots_active:
            if b.spec is not None:
                b._spec_step()
            else:
                b._decode_step()
            steps += 1
        if all(r.done.is_set() for r in reqs):
            return steps
    raise AssertionError("requests did not finish")


def _allocator_state(engine):
    a = engine.allocator
    return {
        "ref": list(a._ref),
        "chains": dict(a._chain_to_page),
        "pages": dict(a._page_to_chain),
        "free": list(a._free),
        "tables": engine.tables.tolist(),
        "lane_pages": [list(p) for p in engine._lane_pages],
    }


# -- drafters ------------------------------------------------------------ #

def test_lookup_drafter_proposes_cycle_continuation():
    d = LookupDrafter(max_ngram=3)
    assert d.propose([1, 2, 3, 4, 1, 2, 3, 4, 1, 2, 3], 4) == [4, 1, 2, 3]


def test_lookup_drafter_prefers_longest_ngram():
    # Trailing [9, 1] matched as a 2-gram beats the later 1-gram [1].
    ctx = [9, 1, 7, 7, 1, 5, 9, 1]
    assert LookupDrafter(max_ngram=3).propose(ctx, 2) == [7, 7]


def test_lookup_drafter_short_and_missing_contexts():
    d = LookupDrafter(max_ngram=3)
    assert d.propose([], 4) == []
    assert d.propose([7], 4) == []
    assert d.propose([1, 2, 3, 4, 5], 4) == []   # no repetition
    assert d.propose([1, 2, 3], 0) == []


def test_model_drafter_matches_greedy_continuation(model_and_params):
    model, params = model_and_params
    drafter = ModelDrafter(model, params)
    got = drafter.propose(PROMPT, 3)
    toks = list(PROMPT)
    want = []
    for _ in range(3):
        logits = model.forward(params, jnp.asarray(toks, jnp.int32)[None])
        nxt = int(jnp.argmax(logits[0, -1]))
        want.append(nxt)
        toks.append(nxt)
    assert got == want


# -- greedy parity ------------------------------------------------------- #

def _greedy_run(model, params, *, mode, n_new=24, prompt=None, **kw):
    b = _mk_batcher(model, params, mode=mode, **kw)
    req = GenRequest(list(prompt or PROMPT), max_tokens=n_new)
    _drive(b, [req])
    state = _allocator_state(b.engine)
    return req, state, b


def test_spec_greedy_parity_across_pages(model_and_params):
    """24 generated tokens at page=4 cross several page boundaries; the
    speculative stream must equal the non-speculative one byte for
    byte."""
    model, params = model_and_params
    off, _, _ = _greedy_run(model, params, mode="off")
    on, _, b = _greedy_run(model, params, mode="lookup")
    assert on.out_tokens == off.out_tokens
    assert on.finish_reason == off.finish_reason == "length"
    assert b.spec is not None  # the spec path actually ran


def test_spec_parity_with_model_drafter(model_and_params):
    """Draft-model mode (here: the target model drafting for itself —
    perfect drafts) must also be byte-identical, with full acceptance."""
    model, params = model_and_params
    off, _, _ = _greedy_run(model, params, mode="off", n_new=12)
    on, _, b = _greedy_run(model, params, mode="draft", n_new=12,
                           drafter=ModelDrafter(model, params))
    assert on.out_tokens == off.out_tokens
    drafted = b.spec.m_drafted.value()
    assert drafted > 0
    # Self-drafting is always right: every drafted token accepted.
    assert b.spec.m_accepted.value() == drafted


@pytest.mark.parametrize("mode", ["draft", "lookup"])
def test_verify_steps_count_what_drafts_saved(model_and_params, mode):
    """What speculation buys, as counts: after the prefill's token every
    verify step emits one token plus the drafts it accepted, so steps +
    accepted is the k = 0 run's step count at the same output; perfect
    drafts take ceil(n / (k + 1)) steps, and no drafter takes more steps
    than decoding one token at a time."""
    model, params = model_and_params
    n_new, k = 24, 4
    b_off = _mk_batcher(model, params, mode="off")
    off = GenRequest(list(PROMPT), max_tokens=n_new)
    steps_off = _drive(b_off, [off])
    assert steps_off == n_new - 1          # the prefill gave the first

    drafter = ModelDrafter(model, params) if mode == "draft" else None
    b = _mk_batcher(model, params, mode=mode, k=k, drafter=drafter)
    on = GenRequest(list(PROMPT), max_tokens=n_new)
    steps = _drive(b, [on])
    assert on.out_tokens == off.out_tokens
    accepted = int(b.spec.m_accepted.value())
    assert steps + accepted == steps_off
    assert steps <= steps_off
    if mode == "draft":
        assert accepted == int(b.spec.m_drafted.value())
        assert steps == -(-(n_new - 1) // (k + 1))
        assert b.spec.m_rollbacks.value() == 0


def test_spec_parity_on_prefix_cache_hit(model_and_params):
    """Second request with the same prompt rides cached prefix pages;
    speculation on top of a prefix hit must stay byte-identical and must
    not perturb the shared pages."""
    model, params = model_and_params

    def twice(mode):
        b = _mk_batcher(model, params, mode=mode)
        r1 = GenRequest(list(PROMPT), max_tokens=16)
        _drive(b, [r1])
        hits0 = b.engine.m_prefix_hits.value()
        r2 = GenRequest(list(PROMPT), max_tokens=16)
        _drive(b, [r2])
        assert b.engine.m_prefix_hits.value() == hits0 + 1
        return r1, r2, _allocator_state(b.engine)

    off1, off2, st_off = twice("off")
    on1, on2, st_on = twice("lookup")
    assert on1.out_tokens == off1.out_tokens
    assert on2.out_tokens == off2.out_tokens
    # Same prompt, same weights: both requests produce the same stream.
    assert off1.out_tokens == off2.out_tokens
    assert st_on == st_off


def test_spec_parity_and_state_under_full_misdraft(model_and_params):
    """spec_misdraft=1.0 makes every draft token wrong: acceptance
    collapses, the rollback path runs on every drafting step — and the
    output AND the allocator/prefix-cache/table state must still be
    identical to the never-drafted twin's."""
    model, params = model_and_params
    off, st_off, _ = _greedy_run(model, params, mode="off")

    chaos_mod.reset("spec_misdraft=1.0")
    on, st_on, b = _greedy_run(model, params, mode="lookup",
                               min_accept=0.0)  # keep drafting through it
    assert on.out_tokens == off.out_tokens
    assert st_on == st_off
    assert b.spec.m_rollbacks.value() > 0


def test_spec_run_leaves_state_of_never_drafted_run(model_and_params):
    """Baseline hygiene: even with ACCEPTED drafts, the end state
    (refcounts, registrations, free-list order, tables) matches the
    non-speculative twin — speculation is invisible to the allocator."""
    model, params = model_and_params
    _, st_off, _ = _greedy_run(model, params, mode="off")
    _, st_on, _ = _greedy_run(model, params, mode="lookup")
    assert st_on == st_off


# -- multi-token accounting (S1 edges) ----------------------------------- #

def test_eos_truncates_mid_acceptance(model_and_params):
    """An eos landing inside an accepted draft run must cut the stream AT
    the eos — tokens the draft would have continued with are never
    emitted."""
    model, params = model_and_params
    off, _, _ = _greedy_run(model, params, mode="off", n_new=24)
    cut = 10
    eos = off.out_tokens[cut]

    b = _mk_batcher(model, params, mode="lookup")
    req = GenRequest(list(PROMPT), max_tokens=24, eos_token=eos)
    _drive(b, [req])
    assert req.finish_reason == "eos"
    assert req.out_tokens == off.out_tokens[:cut + 1]
    assert b.slots_active == 0  # lane freed, pages returned


def test_max_tokens_clamps_multi_token_advance(model_and_params):
    """max_tokens smaller than one full acceptance run: the request must
    finish with EXACTLY max_tokens tokens (prefix of the greedy
    stream)."""
    model, params = model_and_params
    off, _, _ = _greedy_run(model, params, mode="off", n_new=24)
    b = _mk_batcher(model, params, mode="lookup", k=8)
    req = GenRequest(list(PROMPT), max_tokens=5)
    _drive(b, [req])
    assert req.finish_reason == "length"
    assert req.out_tokens == off.out_tokens[:5]


def test_deadline_fires_on_first_token_past_expiry(model_and_params):
    """A deadline that expires mid-generation finishes the request on the
    next emitted token — a multi-token step must not keep emitting past
    the cut."""
    model, params = model_and_params
    b = _mk_batcher(model, params, mode="lookup")
    req = GenRequest(list(PROMPT), max_tokens=40, deadline_s=30.0)
    b.submit(req)
    b._admit()                       # prefill emits the first token
    assert not req.done.is_set()
    n_before = len(req.out_tokens)
    req.deadline = time.monotonic() - 0.01   # force-expire mid-generation
    b._spec_step()
    assert req.finish_reason == "deadline"
    assert len(req.out_tokens) == n_before + 1


# -- k adaptation -------------------------------------------------------- #

def test_k_collapses_to_zero_under_full_misdraft():
    chaos_mod.reset("spec_misdraft=1.0")
    ctrl = build_controller(SpecConfig(mode="lookup", k=4, min_accept=0.25,
                                       probe_every=8))
    ctx = [1, 2, 3, 4] * 8
    lane = 0
    for _ in range(16):
        k = ctrl.k_for(lane, mode="lookup", temperature=0.0, remaining=100)
        if k <= 0:
            break
        d = ctrl.draft(lane, ctx, k, "lookup", 1)
        # Misdrafted tokens never match the true continuation -> 0 accepted.
        ctrl.observe(lane, drafted=len(d), matched=0)
    ks = [ctrl.k_for(lane, mode="lookup", temperature=0.0, remaining=100)
          for _ in range(8)]
    assert ks.count(0) == 7 and ks.count(1) == 1  # collapsed + one probe


def test_misdraft_tokens_are_wrong():
    chaos_mod.reset("spec_misdraft=1.0")
    ctrl = build_controller(SpecConfig(mode="lookup", k=4))
    ctx = [1, 2, 3, 4, 1, 2, 3, 4, 1, 2, 3]
    clean = LookupDrafter(max_ngram=3).propose(ctx, 4)
    poisoned = ctrl.draft(0, ctx, 4, "lookup", 1)
    assert len(poisoned) == len(clean)
    assert all(p != c for p, c in zip(poisoned, clean))


def test_sampled_requests_never_draft():
    ctrl = build_controller(SpecConfig(mode="lookup", k=4))
    assert ctrl.k_for(0, mode="lookup", temperature=0.7, remaining=100) == 0


def test_request_mode_narrows_plane_mode():
    ctrl = build_controller(SpecConfig(mode="lookup", k=4))
    assert ctrl.mode_for(None) == "lookup"
    assert ctrl.mode_for("off") == "off"
    # "draft" without a draft model falls back to lookup.
    assert ctrl.mode_for("draft") == "lookup"


def test_spec_off_is_exactly_the_classic_path(model_and_params):
    """mode="off" never builds a controller; the batcher runs the
    classic decode step (spec attribute None)."""
    model, params = model_and_params
    assert build_controller(SpecConfig(mode="off")) is None
    b = _mk_batcher(model, params, mode="off")
    assert b.spec is None
