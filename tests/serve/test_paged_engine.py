"""PagedDecodeEngine + ContinuousBatcher integration: prefix reuse on the
real engine (cached pages survive their owner, skip prefill compute, and
produce dense-identical logits), page-pool exhaustion surfacing as bounded
-queue backpressure (the HTTP 429 path), and weight hot-reload mid-decode
with live block tables. Batcher tests drive the scheduler methods directly
(thread never started) so every assertion is deterministic."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oobleck_tpu.models import build_model
from oobleck_tpu.serve.batcher import ContinuousBatcher, GenRequest, QueueFull
from oobleck_tpu.serve.engine import DecodeEngine, PagedDecodeEngine

PAGE = 4
MAX_SEQ = 32


@pytest.fixture(scope="module")
def model_and_params():
    model = build_model("gpt2-tiny", {"dtype": jnp.float32})
    return model, model.init_params(jax.random.PRNGKey(0))


def _paged_engine(model, params, *, lanes=2, num_pages=16):
    eng = PagedDecodeEngine(model, lanes=lanes, max_seq=MAX_SEQ,
                            page_size=PAGE, num_pages=num_pages)
    eng.set_params(eng.stage_params(params), 1)
    return eng


def test_prefix_reuse_matches_dense_and_counts(model_and_params):
    """B shares A's first 2 pages after A finished: the hit is counted,
    the cached tokens skip prefill, and the logits equal a dense-slot
    prefill of the same prompt."""
    model, params = model_and_params
    eng = _paged_engine(model, params)
    hits0 = eng.m_prefix_hits.value()
    cached0 = eng.m_cached_tokens.value()

    prompt_a = [3, 7, 1, 9, 4, 2, 8, 6, 11, 5, 10, 12]   # 3 full pages
    eng.prefill(prompt_a, 0, max_tokens=4)
    assert eng.m_prefix_hits.value() == hits0              # cold: no hit
    assert eng.allocator.pages_in_use == 4                 # 16-token span
    eng.release(0)
    assert eng.allocator.pages_in_use == 0                 # freed...

    prompt_b = prompt_a[:8] + [30, 29, 28, 27]             # shared 2-page head
    logits_b = eng.prefill(prompt_b, 0, max_tokens=4)
    assert eng.m_prefix_hits.value() == hits0 + 1          # ...but still cached
    assert eng.m_cached_tokens.value() == cached0 + 8
    assert eng.allocator.pages_in_use == 4                 # 2 pinned + 2 fresh

    dense = DecodeEngine(model, slots=1, max_seq=MAX_SEQ)
    dense.set_params(dense.stage_params(params), 1)
    logits_dense = dense.prefill(prompt_b, 0)
    assert int(np.argmax(logits_b)) == int(np.argmax(logits_dense))
    np.testing.assert_allclose(logits_b, logits_dense, atol=1e-4)


def test_pool_exhaustion_is_queue_backpressure(model_and_params):
    """One request spanning the whole pool starves admission by PAGES while
    lanes sit free; waiting line + bounded queue absorb arrivals until the
    queue bound rejects (server.py maps QueueFull to HTTP 429). When the
    hog finishes, its pages free incrementally and everyone drains FIFO."""
    model, params = model_and_params
    eng = _paged_engine(model, params, lanes=2, num_pages=9)  # 8 usable pages
    b = ContinuousBatcher(eng, max_queue=2)  # scheduler NOT started
    hog = b.submit(GenRequest([3, 1, 4, 1], max_tokens=28))   # 32 tok = 8 pages
    b._admit()
    assert b.slots_active == 1
    assert eng.allocator.free_pages == 0

    extras = [b.submit(GenRequest([5 + i, 2, 7, i], max_tokens=4))
              for i in range(2)]                               # 2 pages each
    b._admit()                                  # pulls both into waiting; no pages
    assert b.slots_active == 1                  # a free LANE is not capacity
    extras += [b.submit(GenRequest([15 + i, 2, 7, i], max_tokens=4))
               for i in range(2)]               # refill the bounded queue
    assert b.queue_depth == 4
    with pytest.raises(QueueFull):
        b.submit(GenRequest([9, 9, 9, 9], max_tokens=4))

    for _ in range(200):
        if all(r.done.is_set() for r in [hog, *extras]):
            break
        b._admit()
        if b.slots_active:
            b._decode_step()
    assert hog.finish_reason == "length" and len(hog.out_tokens) == 28
    for r in extras:
        assert r.finish_reason == "length" and len(r.out_tokens) == 4
    assert eng.allocator.free_pages == 8
    b.stop()


@pytest.mark.parametrize("page", [4, 8])
def test_paged_pool_admits_more_than_dense_slots_at_equal_bytes(
        model_and_params, page):
    """The paged cache's claim, as a count: a pool of the SAME bytes as
    `slots x max_seq` dense rows holds a burst of short requests by their
    true span (8 tokens), not by a max_seq reservation each. One admission
    pass puts usable_pages // pages_per_request of them in flight where the
    dense engine holds `slots`, and both finish the whole burst."""
    model, params = model_and_params
    slots, span = 2, 8                         # 4 prompt + 4 generated
    num_pages = slots * MAX_SEQ // page        # the same token budget
    dense = DecodeEngine(model, slots=slots, max_seq=MAX_SEQ)
    dense.set_params(dense.stage_params(params), 1)
    paged = PagedDecodeEngine(model, lanes=num_pages - 1, max_seq=MAX_SEQ,
                              page_size=page, num_pages=num_pages)
    paged.set_params(paged.stage_params(params), 1)
    nbytes = lambda cache: sum(int(x.nbytes) for x in jax.tree.leaves(cache))
    assert nbytes(paged.cache) == nbytes(dense.cache)

    burst = num_pages + 4                      # oversubscribes both
    in_flight = {}
    for name, eng in (("dense", dense), ("paged", paged)):
        b = ContinuousBatcher(eng, max_queue=burst)  # scheduler NOT started
        reqs = [b.submit(GenRequest(
            [1 + (4 * i + j) % 97 for j in range(4)], max_tokens=4))
            for i in range(burst)]
        b._admit()
        in_flight[name] = b.slots_active
        for _ in range(50 * burst):
            if all(r.done.is_set() for r in reqs):
                break
            b._admit()
            if b.slots_active:
                b._decode_step()
            assert b.slots_active <= in_flight[name]
        assert all(r.finish_reason == "length" and len(r.out_tokens) == 4
                   for r in reqs)
        b.stop()
    assert in_flight["dense"] == slots
    usable = num_pages - 1                     # page 0 is the garbage page
    assert in_flight["paged"] == usable // (span // page)
    assert in_flight["paged"] > in_flight["dense"]
    assert paged.allocator.free_pages == usable


def test_hot_reload_mid_decode_keeps_block_tables(model_and_params):
    """Weights swap at the decode-step barrier while a paged request is
    mid-generation: the request keeps its pages and finishes under the new
    step, with the full token budget generated."""
    model, params = model_and_params
    eng = _paged_engine(model, params, lanes=1)
    b = ContinuousBatcher(eng)                  # scheduler NOT started
    req = b.submit(GenRequest([3, 7, 1, 9, 4], max_tokens=6))
    b._admit()
    b._decode_step()
    b._decode_step()
    assert not req.done.is_set()
    pages_mid = list(eng._lane_pages[0])
    assert pages_mid

    params2 = jax.tree.map(lambda x: x * 1.01, params)
    b.post_swap(5, eng.stage_params(params2))
    b._maybe_swap()
    assert eng.params_step == 5
    assert eng._lane_pages[0] == pages_mid      # tables untouched by the swap

    for _ in range(20):
        if req.done.is_set():
            break
        b._decode_step()
    assert req.finish_reason == "length"
    assert req.step == 5
    assert len(req.out_tokens) == 6
    assert eng.allocator.pages_in_use == 0      # freed at finish
    b.stop()
