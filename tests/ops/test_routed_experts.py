"""`ops/moe.routed_experts`: dropless top-k sigmoid routing over a held
range of the experts, and the grouped-matmul kernels under it.

The op against a loop over tokens (uneven loads, an expert that gets no
token, every pick of a token held here), the shares' partial results
against the uncut layer, gradients against a dense formulation that
autodiff differentiates, and the kernels in interpreter mode against one
`jnp.einsum` per group; a running gradient sum handed to the dW kernel
against the sum plus what the kernel gives without it.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from oobleck_tpu.ops import kernel, moe

T, D, F, NE, K = 48, 32, 64, 8, 2


@pytest.fixture(scope="module")
def layer():
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    bias = jax.random.normal(ks[2], (NE,)) * 0.3
    return {
        "x": jax.random.normal(ks[0], (T, D)),
        "router": jax.random.normal(ks[1], (D, NE)) * 0.5,
        # Expert 3 never wins a place; expert 5 nearly always does: the
        # loads are uneven and one group is empty.
        "bias": bias.at[3].set(-100.0).at[5].set(2.0),
        "w1": jax.random.normal(ks[3], (NE, D, F)) * 0.2,
        "w3": jax.random.normal(ks[4], (NE, D, F)) * 0.2,
        "w2": jax.random.normal(ks[5], (NE, F, D)) * 0.2,
    }


TOP_KS = pytest.mark.parametrize("top_k", [K, 1], ids=["top2", "top1"])


def _share(layer, offset, held, top_k=K, **kw):
    sl = slice(offset, offset + held)
    return moe.routed_experts(
        layer["x"], layer["router"], layer["bias"], layer["w1"][sl],
        layer["w3"][sl], layer["w2"][sl], num_experts=NE, top_k=top_k,
        expert_offset=offset, **kw)


@functools.partial(jax.jit, static_argnames=("held", "top_k", "kw"))
def _share_by_one_program(layer, offset, *, held, top_k, kw=()):
    """The share of experts `offset` .. `offset + held - 1`, by ONE program
    a (layer's shapes, `held`, `top_k`, `kw`) whatever the offset, which is
    an operand: the router's columns (and the bias) rolled so that the
    share's experts are experts 0 .. `held - 1`, their matrices sliced at
    the offset, the call made at `expert_offset=0`. Every token picks the
    experts it picked, under other numbers, so the share is the one
    `expert_offset=offset` gives: what the tests that add shares up need,
    without an eager call a share (each re-traces and compiles its tile
    loops, a third of a second). What an offset does inside the call is
    the loop-over-tokens tests', at offsets of their own."""
    roll = lambda a: None if a is None else jnp.roll(a, -offset, axis=-1)
    cut = lambda a: None if a is None else jax.lax.dynamic_slice_in_dim(
        a, offset, held)
    num_experts = layer["router"].shape[-1]
    return moe.routed_experts(
        layer["x"], roll(layer["router"]), roll(layer.get("bias")),
        cut(layer["w1"]), cut(layer.get("w3")), cut(layer["w2"]),
        num_experts=num_experts, top_k=top_k, expert_offset=0,
        router_x=layer.get("router_x"), **dict(kw))


def _shares_total(layer, parts, top_k=K, **kw):
    """The sum of the `parts` equal shares of `layer`'s experts."""
    held = layer["router"].shape[-1] // parts
    return sum(np.asarray(_share_by_one_program(
        layer, jnp.int32(i * held), held=held, top_k=top_k,
        kw=tuple(sorted(kw.items())))) for i in range(parts))


def _loop_over_tokens(layer, offset, held, top_k=K):
    """The layer as its definition reads, one token and one pick at a
    time, in float64 on the host."""
    f64 = lambda a: np.asarray(a, np.float64)
    x, rw, b = f64(layer["x"]), f64(layer["router"]), f64(layer["bias"])
    scores = 1.0 / (1.0 + np.exp(-(x @ rw)))
    y = np.zeros_like(x)
    picks = []
    for t in range(x.shape[0]):
        chosen = np.argsort(-(scores[t] + b), kind="stable")[:top_k]
        picks.append(chosen)
        g = scores[t, chosen] / (scores[t, chosen].sum() + 1e-6)
        for weight, e in zip(g, chosen):
            if offset <= e < offset + held:
                gate = x[t] @ f64(layer["w1"][e])
                up = x[t] @ f64(layer["w3"][e])
                y[t] += weight * ((gate / (1.0 + np.exp(-gate)) * up)
                                  @ f64(layer["w2"][e]))
    return y, np.stack(picks)


@pytest.mark.parametrize("offset,held", [(0, NE), (2, 4), (4, 2), (3, 1)],
                         ids=["all", "middle4", "two", "empty_expert_only"])
@TOP_KS
def test_routed_experts_matches_a_loop_over_tokens(layer, offset, held, top_k):
    want, picks = _loop_over_tokens(layer, offset, held, top_k)
    got, chosen = jax.jit(
        lambda: _share(layer, offset, held, top_k, return_routing=True))()
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    assert (np.sort(np.asarray(chosen), -1) == np.sort(picks, -1)).all()
    if (offset, held) == (3, 1):
        assert not np.asarray(got).any()      # its one expert got no token


@TOP_KS
def test_no_token_is_dropped_and_loads_are_uneven(layer, top_k):
    _, picks = _loop_over_tokens(layer, 0, NE, top_k)
    loads = np.bincount(picks.reshape(-1), minlength=NE)
    pairs = T * top_k
    assert loads.sum() == pairs and loads[3] == 0 and loads.max() > 2 * pairs / NE
    rows, tile = moe.buffer_rows(T, top_k, NE, NE)
    plan = moe.plan_routing(jnp.asarray(picks.reshape(-1), jnp.int32), NE,
                            rows, tile)
    np.testing.assert_array_equal(np.asarray(plan.group_sizes), loads)
    # Every pair has a row of its own, inside its expert's tiles: walk the
    # tiles in use as the loops do.
    order = np.asarray(plan.order)
    first, valid_rows = np.asarray(plan.tile_first), np.asarray(plan.tile_rows)
    group = np.asarray(plan.tile_group)
    used = int(plan.num_tiles[0])
    seen = []
    for i in range(used):
        pairs_of_tile = order[first[i]:first[i] + valid_rows[i]]
        assert (picks.reshape(-1)[pairs_of_tile] == group[i]).all()
        assert (np.diff(pairs_of_tile) > 0).all()      # the pairs' own order
        seen.extend(pairs_of_tile.tolist())
    assert sorted(seen) == list(range(pairs))
    assert not valid_rows[used:].any()
    assert used * tile == int(np.asarray(plan.padded_sizes).sum())
    # An expert with no token still has its one (empty) tile.
    assert (group[:used] == 3).sum() == 1 and valid_rows[group == 3][0] == 0


def test_all_picks_of_a_token_can_be_held_here(layer):
    """Experts 4..5: expert 5 is nearly everyone's pick, so some tokens
    have BOTH picks in the held range; the buffer's worst case holds."""
    _, picks = _loop_over_tokens(layer, 4, 2)
    both = ((picks >= 4) & (picks < 6)).all(axis=1)
    assert both.any()
    want, _ = _loop_over_tokens(layer, 4, 2)
    np.testing.assert_allclose(np.asarray(_share(layer, 4, 2)), want,
                               atol=2e-5)


@TOP_KS
def test_the_shares_add_up_to_the_uncut_layer(layer, top_k):
    whole = np.asarray(_share(layer, 0, NE, top_k))
    for parts in (8, 4):
        np.testing.assert_allclose(_shares_total(layer, parts, top_k), whole,
                                   atol=2e-5)
    # Two halves by the call's own `expert_offset`, and by the one program.
    halves = sum(np.asarray(_share(layer, i * NE // 2, NE // 2, top_k))
                 for i in range(2))
    np.testing.assert_allclose(halves, whole, atol=2e-5)
    np.testing.assert_allclose(_shares_total(layer, 2, top_k), halves,
                               atol=1e-6)


def _dense(x, router, bias, w1, w3, w2, offset, top_k):
    """The same function, dense over the held experts, for autodiff."""
    scores = jax.nn.sigmoid(x @ router)
    _, chosen = jax.lax.top_k(scores + bias, top_k)
    picked = jax.nn.one_hot(chosen, NE).sum(-2)
    g = picked * scores
    g = g / (g.sum(-1, keepdims=True) + 1e-6)
    g = g[:, offset:offset + w1.shape[0]]
    h = jax.nn.silu(jnp.einsum("td,edf->tef", x, w1)) \
        * jnp.einsum("td,edf->tef", x, w3)
    return jnp.einsum("tef,efd,te->td", h, w2, g)


@pytest.mark.parametrize("offset,held", [(0, NE), (2, 4)], ids=["all", "share"])
@TOP_KS
def test_gradients_match_the_dense_formulation(layer, offset, held, top_k):
    sl = slice(offset, offset + held)
    args = (layer["x"], layer["router"], layer["w1"][sl], layer["w3"][sl],
            layer["w2"][sl])
    target = jax.random.normal(jax.random.PRNGKey(1), (T, D))

    def routed(x, router, w1, w3, w2):
        y = moe.routed_experts(x, router, layer["bias"], w1, w3, w2,
                               num_experts=NE, top_k=top_k,
                               expert_offset=offset)
        return jnp.sum(y * target)

    def dense(x, router, w1, w3, w2):
        return jnp.sum(_dense(x, router, layer["bias"], w1, w3, w2, offset,
                              top_k) * target)

    got = jax.jit(jax.grad(routed, argnums=range(5)))(*args)
    want = jax.grad(dense, argnums=range(5))(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=3e-5)
    # The empty expert's matrices get an exact zero, not garbage.
    if offset == 0:
        assert not np.asarray(got[2][3]).any()


def test_the_bias_selects_and_takes_no_gradient(layer):
    grad = jax.grad(lambda b: jnp.sum(moe.routed_experts(
        layer["x"], layer["router"], b, layer["w1"], layer["w3"],
        layer["w2"], num_experts=NE, top_k=K)))(layer["bias"])
    assert not np.asarray(grad).any()
    unbiased = moe.routed_experts(
        layer["x"], layer["router"], None, layer["w1"], layer["w3"],
        layer["w2"], num_experts=NE, top_k=K)
    assert not np.allclose(np.asarray(unbiased), np.asarray(_share(layer, 0, NE)))


def test_forced_experts_replace_the_selection_only(layer):
    forced = jnp.tile(jnp.asarray([[1, 6]], jnp.int32), (T, 1))
    got, chosen = _share(layer, 0, NE, forced_experts=forced,
                         return_routing=True)
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(forced))
    scores = jax.nn.sigmoid(layer["x"] @ layer["router"])
    g = scores[:, jnp.asarray([1, 6])]
    g = g / (g.sum(-1, keepdims=True) + 1e-6)
    want = 0
    for slot, e in enumerate((1, 6)):
        h = jax.nn.silu(layer["x"] @ layer["w1"][e]) * (layer["x"] @ layer["w3"][e])
        want = want + g[:, slot:slot + 1] * (h @ layer["w2"][e])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# --------------------------------------------------------------------- #
# the kernels, interpreted                                               #
# --------------------------------------------------------------------- #

SIZES = [(37, 0, 70, 5, 16), (0, 0, 0, 128), (16, 16, 16, 16, 16, 16)]


def _layout(sizes, tile=16, seed=0):
    """A plan for groups of the given sizes (pairs in shuffled order)."""
    held = len(sizes)
    local = np.repeat(np.arange(held), sizes).astype(np.int32)
    extra = np.full(11, held, np.int32)                  # routed elsewhere
    local = np.random.default_rng(seed).permutation(np.concatenate([local, extra]))
    rows = -(-len(local) // tile) * tile + held * tile
    return moe.plan_routing(jnp.asarray(local), held, rows, tile), rows, tile


@pytest.mark.parametrize("sizes", SIZES, ids=["uneven", "one_full", "even"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_gmm_kernel_equals_einsum_per_group(sizes, dtype):
    plan, rows, tile = _layout(sizes)
    held, k, n = len(sizes), 32, 48
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    lhs = jax.random.normal(ks[0], (rows, k)).astype(dtype)
    rhs = jax.random.normal(ks[1], (held, k, n))          # float32 as stored
    d_out = jax.random.normal(ks[2], (rows, n)).astype(dtype)
    out = moe.gmm_call(lhs, rhs, plan.tile_group, plan.num_tiles, tile=tile)
    back = moe.gmm_call(d_out, rhs, plan.tile_group, plan.num_tiles,
                        tile=tile, transpose_rhs=True)
    dw = moe.tgmm_call(lhs, d_out, plan.tile_group, plan.num_tiles, tile=tile,
                       num_groups=held, out_dtype=jnp.float32)
    assert out.dtype == dtype and dw.dtype == jnp.float32
    padded = np.asarray(plan.padded_sizes)
    starts = np.cumsum(padded) - padded
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    w = rhs.astype(dtype).astype(jnp.float32)
    for g, (s, p) in enumerate(zip(starts, padded)):
        rows_g = slice(int(s), int(s + p))
        a = lhs[rows_g].astype(jnp.float32)
        d = d_out[rows_g].astype(jnp.float32)
        np.testing.assert_allclose(
            np.asarray(out[rows_g], np.float32),
            np.asarray(jnp.einsum("mk,kn->mn", a, w[g])), atol=tol * 8)
        np.testing.assert_allclose(
            np.asarray(back[rows_g], np.float32),
            np.asarray(jnp.einsum("mn,kn->mk", d, w[g])), atol=tol * 8)
        np.testing.assert_allclose(
            np.asarray(dw[g]), np.asarray(jnp.einsum("mk,mn->kn", a, d)),
            atol=tol * 8)


@pytest.mark.parametrize("sizes", SIZES, ids=["uneven", "one_full", "even"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_tgmm_kernel_starts_from_a_sum_it_is_handed(sizes, dtype):
    """Experts with only their pad tile (a size of 0) included: that
    tile's rows are the kernel's to multiply like any other's."""
    plan, rows, tile = _layout(sizes)
    held, k, n = len(sizes), 32, 48
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    lhs = jax.random.normal(ks[0], (rows, k)).astype(dtype)
    d_out = jax.random.normal(ks[1], (rows, n)).astype(dtype)
    start = jax.random.normal(ks[2], (held, k, n)) * 3.0
    call = lambda **kw: moe.tgmm_call(
        lhs, d_out, plan.tile_group, plan.num_tiles, tile=tile,
        num_groups=held, out_dtype=jnp.float32, **kw)
    got, alone = call(start=start), call()
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(start + alone),
                               atol=1e-5)


def _weights_and_sums(layer, offset, held, gated):
    sl = slice(offset, offset + held)
    names = ("w1", "w3", "w2") if gated else ("w1", "w2")
    ws = {n: layer[n][sl] for n in names}
    keys = jax.random.split(jax.random.PRNGKey(13), len(names))
    sums = {n: jax.random.normal(k, ws[n].shape) for n, k in zip(names, keys)}
    return ws, sums


def _weight_grads(layer, offset, ws, sums, names=None):
    """d sum(y^2) / d the held experts' matrices, each `sums` entry handed
    down under its own name (or under `names`' entry for it; the names
    opened are `names`' values)."""
    names = {n: n for n in sums} if names is None else names

    @jax.jit
    def grads(ws, sums):
        handed = {n: moe.GradSum(s, names[n]) for n, s in sums.items()}

        def loss(ws):
            y = moe.routed_experts(
                layer["x"], layer["router"], layer["bias"], ws["w1"],
                ws.get("w3"), ws["w2"], num_experts=NE, top_k=K,
                expert_offset=offset,
                dw_sums=tuple(handed.get(n) for n in ("w1", "w3", "w2")))
            return jnp.sum(y ** 2)

        with moe.handing_sums(set(names.values())):
            return jax.grad(loss)(ws)

    return grads(ws, sums)


@pytest.mark.parametrize("gated", [True, False], ids=["swiglu", "ungated"])
@pytest.mark.parametrize("offset,held", [(0, NE), (2, 4), (3, 1)],
                         ids=["all", "share", "empty_expert_only"])
def test_gradients_with_sums_handed_are_the_sums_plus_the_gradients(
        layer, kernels_interpreted, offset, held, gated):
    ws, sums = _weights_and_sums(layer, offset, held, gated)
    plain = _weight_grads(layer, offset, ws, {})
    got = _weight_grads(layer, offset, ws, sums)
    assert sorted(got) == sorted(ws)
    for n in ws:
        np.testing.assert_allclose(np.asarray(got[n]),
                                   np.asarray(sums[n] + plain[n]), atol=2e-5)


WRONGLY_HANDED = {
    # w3's sum rides under w1's name: one name taken twice, one never.
    "taken_twice": ({"w1": "w1", "w3": "w1", "w2": "w2"}, "'w1': 2"),
    # A name opened and handed to nothing.
    "not_taken": ({"w1": "w1", "w3": "w3", "w2": "w2", "lost": "lost"},
                  "'lost': 0"),
}


@pytest.mark.parametrize("case", sorted(WRONGLY_HANDED))
def test_a_sum_not_taken_exactly_once_fails_the_trace(layer, kernels_interpreted,
                                                      case):
    names, said = WRONGLY_HANDED[case]
    ws, sums = _weights_and_sums(layer, 2, 4, True)
    with pytest.raises(ValueError, match=said):
        _weight_grads(layer, 2, ws, sums, names)


def test_a_sum_cannot_be_handed_outside_its_ledger_or_off_the_kernels(layer,
                                                                      request):
    ws, sums = _weights_and_sums(layer, 2, 4, True)
    handed = tuple(moe.GradSum(sums[n], n) for n in ("w1", "w3", "w2"))
    loss = lambda ws: jnp.sum(moe.routed_experts(
        layer["x"], layer["router"], layer["bias"], ws["w1"], ws["w3"],
        ws["w2"], num_experts=NE, top_k=K, expert_offset=2, dw_sums=handed))
    with pytest.raises(AssertionError, match="off the kernels' path"):
        jax.grad(loss)(ws)              # `lax.ragged_dot` takes no sum
    request.getfixturevalue("kernels_interpreted")
    with pytest.raises(ValueError, match="outside `handing_sums`"):
        jax.grad(loss)(ws)


# (tokens, top k, experts) -> the tile: `lfm2-24b-a2b.steady`'s call (512
# rows expected an expert: two tiles of 384 from 385 to 768 rows, 16 tiles a
# layer), `moonlight-16b-a3b.steady`'s (384 expected: ONE tile of 512, where
# the rule before gave 384 and put the expected rows on its edge), its
# fallback's (2048 tokens: 192 expected, one tile of 384), 256 expected, a
# lane's worth and the tests'.
ROW_TILES = {
    "lfm2_cell": ((8192, 4, 64), 384),
    "moonlight_cell": ((4096, 6, 64), 512),
    "moonlight_fallback": ((2048, 6, 64), 384),
    "expects_256": ((8192, 8, 256), 384),
    "under_a_lane": ((1024, 4, 64), 96),
    # `qwen3-next-80b-a3b.steady`'s: 80 rows expected an expert, the first
    # call under a lane at a benchmark's size: one bfloat16-sublane multiple
    # that holds 120 rows (half as many again), not a lane for its own sake.
    "qwen3_next_cell": ((4096, 10, 512), 128),
    "tests_sizes": ((48, 2, 8), 32),
}


@pytest.mark.parametrize("case", sorted(ROW_TILES))
def test_row_tile_keeps_the_expected_rows_off_its_edges(case):
    (tokens, top_k, experts), want = ROW_TILES[case]
    tile = moe.choose_row_tile(tokens * top_k, experts)
    assert tile == want
    expected = tokens * top_k / experts
    tiles_at = lambda rows: max(-(-int(rows) // tile), 1)
    # Loads a quarter either way fill the tiles the expected load fills.
    assert {tiles_at(expected * f) for f in (0.76, 0.9, 1.0, 1.1, 1.25)} == {
        tiles_at(expected)}
    unit = moe.LANE if 1.5 * expected >= moe.LANE else moe.SUBLANE
    assert tile % unit == 0 and tile <= moe.MAX_ROW_TILE


def test_tiles_follow_the_shapes():
    # lfm2's cell: 16 tiles a layer, as before the rule looked at edges.
    assert 8 * -(-512 // moe.choose_row_tile(8192 * 4, 64)) == 16
    # Many tiles an expert: no tile keeps 8192 rows off an edge by much;
    # 384 leaves 128 rows below and 256 above where 512 sits on one.
    assert moe.choose_row_tile(8192 * 8, 8) == 384
    assert moe._col_tile(1536, moe.MAX_COL_TILE) == 512
    assert moe._col_tile(2048, moe.MAX_COL_TILE) == 512
    assert moe._col_tile(2048, moe.MAX_TGMM_ROWS) == 1024
    assert moe._col_tile(1536, moe.MAX_TGMM_ROWS) == 768
    assert moe._col_tile(48, moe.MAX_COL_TILE) == 48
    # 1408 = 11 x 128 (moonlight-16b-a3b's experts): whole, not in lanes.
    assert moe._col_tile(1408, moe.MAX_COL_TILE) == 1408
    assert moe._col_tile(1408, moe.MAX_TGMM_ROWS) == 1408
    assert moe._col_tile(128, moe.MAX_COL_TILE) == 128
    assert moe._col_tile(384, moe.MAX_COL_TILE) == 384
    assert moe._col_tile(128 * 17, moe.MAX_COL_TILE) == 128   # over the cap
    rows, tile = moe.buffer_rows(8192, 4, 8, 64)
    assert (rows, tile) == (86 * 384 + 8 * 384, 384)


# --------------------------------------------------------------------- #
# experts without a gate: W2 relu(W1 x)^2 (`w3=None`)                    #
# --------------------------------------------------------------------- #

F_ODD = 40          # no multiple of any tile: the width is taken whole


@pytest.fixture(scope="module")
def ungated(layer):
    ks = jax.random.split(jax.random.PRNGKey(11), 2)
    return dict(layer, w1=jax.random.normal(ks[0], (NE, D, F_ODD)) * 0.2,
                w2=jax.random.normal(ks[1], (NE, F_ODD, D)) * 0.2)


def _ungated_share(layer, offset, held, **kw):
    sl = slice(offset, offset + held)
    return moe.routed_experts(
        layer["x"], layer["router"], layer["bias"], layer["w1"][sl], None,
        layer["w2"][sl], num_experts=NE, top_k=K, expert_offset=offset, **kw)


def _ungated_loop(layer, offset, held):
    """The layer as its definition reads, one held expert at a time, in
    float64 on the host."""
    f64 = lambda a: np.asarray(a, np.float64)
    x, rw, b = f64(layer["x"]), f64(layer["router"]), f64(layer["bias"])
    scores = 1.0 / (1.0 + np.exp(-(x @ rw)))
    y = np.zeros_like(x)
    for t in range(x.shape[0]):
        chosen = np.argsort(-(scores[t] + b), kind="stable")[:K]
        g = scores[t, chosen] / (scores[t, chosen].sum() + 1e-6)
        for weight, e in zip(g, chosen):
            if offset <= e < offset + held:
                pre = np.maximum(x[t] @ f64(layer["w1"][e]), 0.0)
                y[t] += weight * (pre ** 2 @ f64(layer["w2"][e]))
    return y


@pytest.mark.parametrize("offset,held", [(0, NE), (2, 4), (4, 2), (3, 1)],
                         ids=["all", "share_4", "share_2", "empty_expert"])
def test_ungated_experts_match_a_loop_over_tokens(ungated, offset, held):
    np.testing.assert_allclose(
        np.asarray(_ungated_share(ungated, offset, held)),
        _ungated_loop(ungated, offset, held), atol=2e-5)


@pytest.mark.parametrize("parts", [8, 4, 2])
def test_the_ungated_shares_add_up_to_the_uncut_layer(ungated, parts):
    total = _shares_total({**ungated, "w3": None}, parts)
    np.testing.assert_allclose(
        total, np.asarray(_ungated_share(ungated, 0, NE)), atol=2e-5)


def _ungated_dense(x, router, bias, w1, w2, offset):
    """The same function, dense over the held experts, for autodiff."""
    scores = jax.nn.sigmoid(x @ router)
    _, chosen = jax.lax.top_k(scores + bias, K)
    g = jax.nn.one_hot(chosen, NE).sum(-2) * scores
    g = g / (g.sum(-1, keepdims=True) + 1e-6)
    g = g[:, offset:offset + w1.shape[0]]
    h = jnp.square(jax.nn.relu(jnp.einsum("td,edf->tef", x, w1)))
    return jnp.einsum("tef,efd,te->td", h, w2, g)


UNGATED_OPERANDS = ("x", "router", "w1", "w2")


_UNGATED_GRADS = {}


def _ungated_grads(ungated, offset, held):
    """(the routed call's, the dense form's) gradients by EVERY operand of
    one share: compiled once a share, whichever operand's case asks
    first."""
    if (offset, held) in _UNGATED_GRADS:
        return _UNGATED_GRADS[offset, held]
    sl = slice(offset, offset + held)
    args = (ungated["x"], ungated["router"], ungated["w1"][sl],
            ungated["w2"][sl])
    target = jax.random.normal(jax.random.PRNGKey(1), (T, D))

    def routed(x, router, w1, w2):
        y = moe.routed_experts(x, router, ungated["bias"], w1, None, w2,
                               num_experts=NE, top_k=K, expert_offset=offset)
        return jnp.sum(y * target)

    def dense(x, router, w1, w2):
        return jnp.sum(_ungated_dense(x, router, ungated["bias"], w1, w2,
                                      offset) * target)

    both = _UNGATED_GRADS[offset, held] = tuple(
        jax.jit(jax.grad(fn, argnums=range(4)))(*args)
        for fn in (routed, dense))
    return both


@pytest.mark.parametrize("wrt", range(4), ids=UNGATED_OPERANDS)
@pytest.mark.parametrize("offset,held", [(0, NE), (2, 4)], ids=["all", "share"])
def test_ungated_gradients_match_the_dense_formulation(ungated, offset, held,
                                                       wrt):
    got, want = (g[wrt] for g in _ungated_grads(ungated, offset, held))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)
    # The empty expert's matrices get an exact zero, not garbage.
    if offset == 0 and UNGATED_OPERANDS[wrt] in ("w1", "w2"):
        assert not np.asarray(got[3]).any()


def test_an_ungated_call_holds_two_products_forward_and_four_backward(
        ungated, kernels_interpreted):
    """`grouped_matmul`'s own backward: dX and dW of each of the two; and
    the two sums of rows into tokens, the combine forward and the
    dispatch's dx backward. Counted on the kernels' path (interpreted)."""
    from tests.ops.programs import pallas_calls

    sl = slice(2, 6)

    def loss(x, w1, w2):
        return jnp.sum(moe.routed_experts(
            x, ungated["router"], ungated["bias"], w1, None, w2,
            num_experts=NE, top_k=K, expert_offset=2))

    args = (ungated["x"], ungated["w1"][sl], ungated["w2"][sl])
    forward = [n for n, _ in pallas_calls(jax.make_jaxpr(loss)(*args).jaxpr)]
    assert forward == ["moe_gmm"] * 2 + ["moe_token_sum"]
    both = [n for n, _ in pallas_calls(jax.make_jaxpr(
        jax.grad(loss, argnums=(0, 1, 2)))(*args).jaxpr)]
    assert sorted(both) == (["moe_gmm"] * 4 + ["moe_tgmm"] * 2
                            + ["moe_token_sum"] * 2)


def test_ungated_calls_are_counted_where_they_are_built(ungated):
    from oobleck_tpu.utils import metrics

    built = metrics.registry().counter("oobleck_moe_ungated_calls_total")
    before = built.value()
    fn = jax.jit(lambda x: _ungated_share(dict(ungated, x=x), 0, NE))
    fn(ungated["x"])
    fn(ungated["x"])                # a cache hit traces nothing
    assert built.value() - before == 1
    jax.jit(lambda x: _share(dict(ungated, w1=ungated["w3"][..., :F_ODD],
                                  w3=ungated["w3"][..., :F_ODD], x=x),
                             0, NE))(ungated["x"])
    assert built.value() - before == 1          # a SwiGLU call counts nothing


def test_the_swiglu_call_lowers_to_the_text_it_lowered_to_before():
    """Experts without a gate went in beside the SwiGLU path, not through
    it: value-and-gradient of the SwiGLU call lowers to the text it lowered
    to at the parent of the PR that added them (sha256 of the module's
    text and its length, taken there). Moved once since, by PR 51: the row
    buffers the tile loops start from are `lax.empty` (`moe._unwritten`),
    which off a TPU lowers to the zero broadcast `jnp.zeros` lowered to;
    the text differs in the numbers of its private functions alone
    (`@_where_78` where `@_where_79` stood), so the length stayed and the
    hash did not."""
    import hashlib

    s = jax.ShapeDtypeStruct
    f32 = jnp.float32

    def f(x, router, bias, w1, w3, w2):     # the module is named after it
        return jnp.sum(moe.routed_experts(
            x, router, bias, w1, w3, w2, num_experts=NE, top_k=K,
            expert_offset=2))

    text = jax.jit(jax.grad(f, argnums=(0, 1, 3, 4, 5))).lower(
        s((T, D), f32), s((D, NE), f32), s((NE,), f32), s((4, D, F), f32),
        s((4, D, F), f32), s((4, F, D), f32)).as_text()
    assert (hashlib.sha256(text.encode()).hexdigest()[:16], len(text)) == (
        "2257fd6ad66ffeda", 76341)


# --------------------------------------------------------------------- #
# the row buffers are allocated, not filled (`moe._unwritten`)           #
# --------------------------------------------------------------------- #
#
# On a TPU a loop over the row tiles in use starts from whatever the memory
# held. Here the buffers are handed out full of NaN instead: a consumer that
# read a row of a tile past `num_tiles` would carry it into the value or a
# gradient (NaN times zero is NaN), and an exact comparison with the run
# from zeros would fail.

# activation -> (the call's own arguments, buffers a value-and-gradient
# trace hands out: the dispatch and the activation forward; d rows of the
# combine, the activation's gradients and, gated, the sum of the two d rows)
UNWRITTEN_CALLS = {
    "swiglu": ({}, 6),
    "reglu": ({"score": "softmax", "activation": "reglu"}, 6),
    "ungated": ({}, 4),
}
# Experts 2..5 of 8 are held. As routed, expert 3 gets no token: its one
# tile holds no pair. Forced onto experts 0 and 7, no pick lands here:
# `num_tiles` at its least, an empty tile an expert.
UNWRITTEN_ROUTINGS = {"an_expert_without_a_token": None,
                      "no_pick_lands_here": (0, 7)}


def _value_and_grads(layer, activation, forced, load=False):
    """Value and every gradient of one call; with `load`, also what the
    call says beside them: (the chosen experts, its load)."""
    kw, _ = UNWRITTEN_CALLS[activation]
    names = ("x", "router", "w1", "w2") + (
        () if activation == "ungated" else ("w3",))
    sl = slice(2, 6)
    operands = {n: layer[n] if n in ("x", "router") else layer[n][sl]
                for n in names}
    if forced is not None:
        forced = jnp.tile(jnp.asarray([forced], jnp.int32), (T, 1))

    def loss(ops):
        out = moe.routed_experts(
            ops["x"], ops["router"], layer["bias"], ops["w1"], ops.get("w3"),
            ops["w2"], num_experts=NE, top_k=K, expert_offset=2,
            forced_experts=forced, return_routing=load, return_load=load,
            **kw)
        y, *said = out if load else (out,)
        return jnp.sum(y ** 2) + jnp.sum(y), tuple(said)

    return jax.jit(jax.value_and_grad(loss, has_aux=True))(operands)


_UNWRITTEN_RUNS = {}


def _unwritten_run(layer, ungated, path, activation, routing):
    """One case of the matrix below, run once a module whichever test asks
    first: the call as it is (value and gradients), and the call with every
    unwritten buffer handed out full of NaN AND its load handed back
    (value, gradients, the chosen experts, the load); what the first built
    by the two counters, the buffers the second was handed."""
    from oobleck_tpu.utils import metrics

    key = (path, activation, routing)
    if key in _UNWRITTEN_RUNS:
        return _UNWRITTEN_RUNS[key]
    operands = ungated if activation == "ungated" else layer
    forced = UNWRITTEN_ROUTINGS[routing]
    built = metrics.registry().counter("oobleck_moe_unfilled_buffers_total")
    sums = metrics.registry().counter("oobleck_moe_token_sum_kernels_total")
    handed = []

    def poisoned(shape, dtype):
        assert jnp.issubdtype(dtype, jnp.floating), dtype
        handed.append(shape)
        return jnp.full(shape, jnp.nan, dtype)

    with pytest.MonkeyPatch.context() as mp:
        if path == "kernels":
            mp.setattr(kernel, "on_tpu", lambda: True)
            mp.setattr(kernel, "interpret", lambda: True)
        before, sums_before = built.value(), sums.value()
        (value, _), grads = _value_and_grads(operands, activation, forced)
        counted = built.value() - before, sums.value() - sums_before
        mp.setattr(moe, "_unwritten", poisoned)
        (got_value, (chosen, load)), got_grads = _value_and_grads(
            operands, activation, forced, load=True)
    run = _UNWRITTEN_RUNS[key] = {
        "want": (value, grads), "got": (got_value, got_grads),
        "counted": counted, "handed": handed,
        "chosen": np.asarray(chosen), "load": np.asarray(load)}
    return run


UNWRITTEN_MATRIX = [
    pytest.mark.parametrize("routing", sorted(UNWRITTEN_ROUTINGS)),
    pytest.mark.parametrize("activation", sorted(UNWRITTEN_CALLS)),
    pytest.mark.parametrize("path", ["kernels", "fallback"]),
]


def _matrix(test):
    for mark in reversed(UNWRITTEN_MATRIX):
        test = mark(test)
    return test


@_matrix
def test_nothing_reads_a_row_that_no_tile_loop_wrote(
        layer, ungated, path, activation, routing):
    """Value and every gradient with the unwritten buffers full of NaN (and
    the load handed back beside them) are bit for bit the plain call's."""
    run = _unwritten_run(layer, ungated, path, activation, routing)
    assert run["counted"][0] == UNWRITTEN_CALLS[activation][1]
    # On the kernels' path both sums (the combine, the dispatch's dx) are
    # `moe_token_sum` calls: the dispatch's reads a buffer handed out here.
    assert run["counted"][1] == (2 if path == "kernels" else 0)
    assert len(run["handed"]) == UNWRITTEN_CALLS[activation][1]
    rows = moe.buffer_rows(T, K, 4, NE)[0]
    assert {shape[0] for shape in run["handed"]} == {rows}
    got, want = run["got"], run["want"]
    for (path_, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                             jax.tree.leaves(want)):
        assert np.isfinite(np.asarray(g)).all(), path_
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=str(path_))
    if UNWRITTEN_ROUTINGS[routing] is not None:   # nothing is held here
        assert not float(got[0]) and not np.asarray(got[1]["w1"]).any()


@_matrix
def test_the_load_is_the_count_of_the_chosen_experts(
        layer, ungated, path, activation, routing):
    """`return_load`: each held expert's rows are the picks `return_routing`
    shows on it, exactly, and the last number the row tiles they fill (one
    at least an expert); kernels interpreted and fallback, SwiGLU, ReGLU
    and no gate. The same run as the test above, so the value and every
    gradient beside this load are bit for bit what they are without it."""
    run = _unwritten_run(layer, ungated, path, activation, routing)
    held, offset = 4, 2
    local = run["chosen"] - offset
    assert run["chosen"].shape == (T, K)
    counts = np.bincount(local[(local >= 0) & (local < held)],
                         minlength=held)
    load = run["load"]
    assert load.dtype == np.int32 and load.shape == (held + 1,)
    np.testing.assert_array_equal(load[:held], counts)
    tile = moe.buffer_rows(T, K, held, NE)[1]
    assert load[held] == sum(max(-(-int(n) // tile), 1) for n in counts)
    if UNWRITTEN_ROUTINGS[routing] is None:
        assert counts[1] == 0 and counts.sum() > 0    # expert 3 gets none
    else:
        assert not counts.any() and load[held] == held


# --------------------------------------------------------------------- #
# a token's sum over its rows as one kernel (`moe_token_sum`)            #
# --------------------------------------------------------------------- #
#
# The kernel in the interpreter against the loop of scatter-adds it stands
# in for, bit for bit. Experts 2..5 of 8 are held, 64 tokens pick 2. The
# rows and the weights are bfloat16 values (held in float32 where the case
# says so): a weight times a row is then exact in float32, and XLA:CPU,
# which contracts a multiply and an add of ONE program into a fused
# multiply-add, cannot round the interpreter's sum otherwise than the
# loop's (the chip's VPU has no such instruction; unrounded weights agree
# to one unit in the last place, below).
SUM_T, SUM_D = 64, 32
_one = lambda a, b: np.tile(np.asarray([[a, b]], np.int32), (SUM_T, 1))
def _off_a_boundary():
    t = np.arange(SUM_T)
    picks, on_two = _one(0, 7), (t < 5) | (t >= 16)
    picks[on_two] = (2, 0)
    picks[on_two & (t % 2 == 1), 1] = 5
    return picks


# routing -> (picks [T, 2] or None for the router's own, tokens a block,
# rows a chunk). As routed, a router that never picks expert 3 (an expert
# with no row) leaves about half the tokens with no held pick.
TOKEN_SUM_ROUTINGS = {
    "as_routed": (None, 16, None),
    # Every token of every block on expert 4: a run of 32 rows, two chunks
    # and, from the second block on, three (the run starts mid-chunk).
    "a_block_on_one_expert": (_one(4, 7), 32, 16),
    # Tokens 0..4 and 16.. pick expert 2: the second block's run starts at
    # row 5 of the expert's region, off every sublane tile's edge; expert 5
    # takes the odd ones of them, and tokens 5..15 pick nothing held.
    "a_run_off_a_sublane_boundary": (_off_a_boundary(), 16, None),
    # No pick lands here: `num_tiles` at its least, every run empty.
    "nothing_held": (_one(0, 7), 64, None),
}


def _sum_case(routing, dtype):
    picks, block, chunk = TOKEN_SUM_ROUTINGS[routing]
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    if picks is None:
        scores = jax.random.normal(ks[0], (SUM_T, NE)).at[:, 3].set(-100.0)
        picks = np.asarray(jax.lax.top_k(scores, K)[1])
    bf16_values = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
    weights = bf16_values(jax.random.uniform(ks[1], (SUM_T, K), minval=0.1))
    local = np.where((picks >= 2) & (picks < 6), picks - 2, 4).astype(np.int32)
    m_rows, tile = moe.buffer_rows(SUM_T, K, 4, NE)
    plan = moe.plan_routing(jnp.asarray(local.reshape(-1)), 4, m_rows, tile)
    plan = moe.token_runs(plan, jnp.asarray(local), weights, block)
    used = int(plan.num_tiles[0]) * tile
    rows = bf16_values(jax.random.normal(ks[2], (m_rows, SUM_D))).astype(dtype)
    # Rows of tiles past the last one in use hold NOTHING (`_unwritten`).
    return plan, rows.at[used:].set(jnp.nan), weights, tile, chunk, local


@pytest.mark.parametrize("weighted", [False, True], ids=["plain", "weighted"])
@pytest.mark.parametrize("routing", sorted(TOKEN_SUM_ROUTINGS))
def test_the_token_sum_kernel_is_the_loop_bit_for_bit(kernels_interpreted, routing,
                                                      weighted):
    from oobleck_tpu.utils import metrics

    dtype = jnp.bfloat16 if routing == "as_routed" else jnp.float32
    plan, rows, weights, tile, chunk, local = _sum_case(routing, dtype)
    built = metrics.registry().counter("oobleck_moe_token_sum_kernels_total")
    before = built.value()
    got = moe.token_sum_call(rows, plan, tile=tile, weighted=weighted,
                             chunk=chunk)
    assert built.value() - before == 1
    want = moe._tokens_from_rows(rows, plan, tile, K, SUM_T,
                                 weights if weighted else None)
    assert got.dtype == rows.dtype and np.isfinite(
        np.asarray(got, np.float32)).all()
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want.astype(dtype), np.float32))
    held_picks = (local < 4).sum(axis=1)
    assert not np.asarray(got, np.float32)[held_picks == 0].any()
    if routing == "as_routed":      # what the case is there for
        assert (held_picks == 0).any() and not (local == 1).any()
    if routing == "a_block_on_one_expert":
        assert (np.asarray(plan.run_len).reshape(-1, 4)[:, 2] == 32).all()
    if routing == "a_run_off_a_sublane_boundary":
        assert np.asarray(plan.run_first).reshape(-1, 4)[1, 0] % moe.SUBLANE == 5


def test_unrounded_weights_agree_to_the_last_place(kernels_interpreted):
    """The same sum with float32 weights as `route` gives them: the loop
    rounds weight x row and then the sum, the interpreter's one XLA:CPU
    program may fuse the two (one rounding): a unit in the last place of a
    partial sum, never more."""
    plan, rows, weights, tile, chunk, _ = _sum_case("as_routed", jnp.float32)
    weights = weights * (1 + jnp.float32(2.0 ** -12))
    plan = plan._replace(w_held=plan.w_held * (1 + jnp.float32(2.0 ** -12)))
    got = moe.token_sum_call(rows, plan, tile=tile, weighted=True)
    want = moe._tokens_from_rows(rows, plan, tile, K, SUM_T, weights)
    scale = np.abs(np.asarray(rows[:int(plan.num_tiles[0]) * tile])).max()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=2 * K * scale * 2.0 ** -24)


@pytest.mark.parametrize("routing", sorted(TOKEN_SUM_ROUTINGS))
def test_the_plan_s_rows_by_token_are_the_rows_the_tiles_hold(routing):
    """`token_runs` against a walk over the tiles in use, as the loops make
    it: `src_row[t, e]` is the row that holds token t's pair on held expert
    e, `w_held` its weight, and the rows an expert gives a block of tokens
    are `run_len` from `run_first` on, ascending with the tokens. That
    rests on a token's picks being DISTINCT (`lax.top_k`'s are;
    `forced_experts` are a reference's own top-k): with a pick named twice
    the plan has one row where the tiles hold two, which is the caller's to
    rule out and is not checked inside the traced program."""
    plan, _, weights, tile, _, local = _sum_case(routing, jnp.float32)
    _, block, _ = TOKEN_SUM_ROUTINGS[routing]
    assert all(len(set(row[row < 4])) == (row < 4).sum() for row in local)
    order, first = np.asarray(plan.order), np.asarray(plan.tile_first)
    src_row = np.full((SUM_T, 4), -1)
    w_held = np.zeros((SUM_T, 4), np.float32)
    for i in range(int(plan.num_tiles[0])):
        for r in range(int(plan.tile_rows[i])):
            pair, e = order[first[i] + r], int(plan.tile_group[i])
            assert src_row[pair // K, e] == -1
            src_row[pair // K, e] = i * tile + r
            w_held[pair // K, e] = np.asarray(weights).reshape(-1)[pair]
    np.testing.assert_array_equal(np.asarray(plan.src_row), src_row)
    np.testing.assert_array_equal(np.asarray(plan.w_held), w_held)
    run_first = np.asarray(plan.run_first).reshape(-1, 4)
    run_len = np.asarray(plan.run_len).reshape(-1, 4)
    for b in range(SUM_T // block):
        for e in range(4):
            mine = src_row[b * block:(b + 1) * block, e]
            np.testing.assert_array_equal(
                mine[mine >= 0], run_first[b, e] + np.arange(run_len[b, e]))


# cell -> its call (tokens a microbatch, D, picks, experts), the block of
# tokens and of columns a grid step of `moe_token_sum` owns there, and the
# rows a block EXPECTS of a held expert, in a chunk of 128.
CELL_TOKEN_BLOCKS = {
    "lfm2-24b-a2b": ((8192, 2048, 4, 64), (1024, 1024), 64),
    "moonlight-16b-a3b": ((4096, 2048, 6, 64), (512, 2048), 48),
    "nemotron-3-nano-30b-a3b": ((4096, 2688, 6, 128), (1024, 896), 48),
    "qwen3-next-80b-a3b": ((4096, 2048, 10, 512), (1024, 1024), 20),
    "smallthinker-21b-a3b": ((16384, 2560, 6, 64), (512, 2560), 48),
}


@pytest.mark.parametrize("cell", sorted(CELL_TOKEN_BLOCKS))
def test_token_blocks_follow_the_shapes(cell):
    (tokens, d, top_k, experts), blocks, expected = CELL_TOKEN_BLOCKS[cell]
    tile = CELL_TILES[cell][1]
    block = moe.choose_token_block(tokens, top_k, experts, tile)
    assert (block, moe._sum_col_tile(d, block)) == blocks
    assert block * top_k / experts == expected <= moe._sum_chunk(tile) / 2
    # The tests' sizes: a block the tokens divide, or all of them.
    assert moe.choose_token_block(48, 2, 8, 32) == 48
    assert moe.choose_token_block(50, 2, 8, 32) == 50
    assert moe._sum_col_tile(40, 48) == 40


def test_odd_widths_are_taken_whole_by_the_kernels():
    # 1856 = 14.5 x 128 (nemotron-3-nano-30b-a3b's experts): no tile
    # divides it; 2688 = 21 x 128 gets 384 columns and 896 rows of dW.
    assert moe._col_tile(1856, moe.MAX_COL_TILE) == 1856
    assert moe._col_tile(1856, moe.MAX_TGMM_ROWS) == 1856
    assert moe._col_tile(2688, moe.MAX_COL_TILE) == 384
    assert moe._col_tile(2688, moe.MAX_TGMM_ROWS) == 896
    # The cell's call: 192 rows expected an expert, one tile of 384.
    assert moe.choose_row_tile(4096 * 6, 128) == 384


# --------------------------------------------------------------------- #
# softmax scores (`score="softmax"`): the top k of a softmax over ALL     #
# --------------------------------------------------------------------- #

NE_S, K_S = 32, 10      # as the Qwen3-Next family: ten of many, no bias


@pytest.fixture(scope="module")
def softmaxed():
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    return {
        "x": jax.random.normal(ks[0], (T, D)),
        "router": jax.random.normal(ks[1], (D, NE_S)) * 0.5,
        "w1": jax.random.normal(ks[2], (NE_S, D, F)) * 0.2,
        "w3": jax.random.normal(ks[3], (NE_S, D, F)) * 0.2,
        "w2": jax.random.normal(ks[4], (NE_S, F, D)) * 0.2,
    }


def _softmax_share(layer, offset, held, **kw):
    sl = slice(offset, offset + held)
    return moe.routed_experts(
        layer["x"], layer["router"], None, layer["w1"][sl], layer["w3"][sl],
        layer["w2"][sl], num_experts=NE_S, top_k=K_S, expert_offset=offset,
        score="softmax", **kw)


def _softmax_loop(layer, offset, held):
    """The layer as its definition reads: p = softmax(x Wr) over ALL the
    experts, its top ten, weights p / sum of the ten; one token and one
    pick at a time, in float64 on the host."""
    f64 = lambda a: np.asarray(a, np.float64)
    x, logits = f64(layer["x"]), f64(layer["x"]) @ f64(layer["router"])
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    y = np.zeros_like(x)
    picks = []
    for t in range(x.shape[0]):
        chosen = np.argsort(-probs[t], kind="stable")[:K_S]
        picks.append(chosen)
        g = probs[t, chosen] / probs[t, chosen].sum()
        for weight, e in zip(g, chosen):
            if offset <= e < offset + held:
                gate = x[t] @ f64(layer["w1"][e])
                up = x[t] @ f64(layer["w3"][e])
                y[t] += weight * ((gate / (1.0 + np.exp(-gate)) * up)
                                  @ f64(layer["w2"][e]))
    return y, np.stack(picks)


@pytest.mark.parametrize("offset,held", [(0, NE_S), (8, 8), (30, 2), (5, 1)],
                         ids=["all", "8_to_15", "30_to_31", "one"])
def test_softmax_top_ten_matches_a_loop_over_tokens(softmaxed, offset, held):
    y, chosen = jax.jit(lambda: _softmax_share(
        softmaxed, offset, held, return_routing=True))()
    want, picks = _softmax_loop(softmaxed, offset, held)
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-5)
    np.testing.assert_array_equal(np.sort(np.asarray(chosen), -1),
                                  np.sort(picks, -1))


@pytest.mark.parametrize("parts", [32, 8, 2],
                         ids=["thirty_two_chips", "eight_chips", "two_chips"])
def test_the_softmax_shares_add_up_to_the_uncut_layer(softmaxed, parts):
    """Every share routes over all 32 and gives its own experts' part; the
    parts add up to the uncut layer (the shared expert is the model's to
    add, once: tests/models/test_qwen3_next.py)."""
    total = _shares_total(softmaxed, parts, K_S, score="softmax")
    np.testing.assert_allclose(np.asarray(total),
                               np.asarray(_softmax_share(softmaxed, 0, NE_S)),
                               atol=3e-6)


def _softmax_dense(x, router, w1, w3, w2, offset):
    """The same function, dense over the held experts, for autodiff."""
    probs = jax.nn.softmax(x @ router, -1)
    _, chosen = jax.lax.top_k(probs, K_S)
    g = jax.nn.one_hot(chosen, NE_S).sum(-2) * probs
    g = g / g.sum(-1, keepdims=True)
    g = g[:, offset:offset + w1.shape[0]]
    h = jax.nn.silu(jnp.einsum("td,edf->tef", x, w1)) \
        * jnp.einsum("td,edf->tef", x, w3)
    return jnp.einsum("tef,efd,te->td", h, w2, g)


SOFTMAX_OPERANDS = ("x", "router", "w1", "w3", "w2")


_SOFTMAX_GRADS = {}


def _softmax_grads(softmaxed, offset, held):
    """(the routed call's, the dense form's) gradients by EVERY operand of
    one share: compiled once a share, whichever operand's case asks
    first."""
    if (offset, held) in _SOFTMAX_GRADS:
        return _SOFTMAX_GRADS[offset, held]
    sl = slice(offset, offset + held)
    args = (softmaxed["x"], softmaxed["router"], softmaxed["w1"][sl],
            softmaxed["w3"][sl], softmaxed["w2"][sl])
    target = jax.random.normal(jax.random.PRNGKey(1), (T, D))

    def routed(x, router, w1, w3, w2):
        y = moe.routed_experts(x, router, None, w1, w3, w2, num_experts=NE_S,
                               top_k=K_S, expert_offset=offset,
                               score="softmax")
        return jnp.sum(y * target)

    def dense(x, router, w1, w3, w2):
        return jnp.sum(_softmax_dense(x, router, w1, w3, w2, offset) * target)

    both = _SOFTMAX_GRADS[offset, held] = (
        jax.jit(jax.grad(routed, argnums=range(5)))(*args),
        jax.grad(dense, argnums=range(5))(*args))
    return both


@pytest.mark.parametrize("wrt", range(5), ids=SOFTMAX_OPERANDS)
@pytest.mark.parametrize("offset,held", [(0, NE_S), (8, 8)],
                         ids=["all", "share"])
def test_softmax_gradients_match_the_dense_formulation(softmaxed, offset,
                                                       held, wrt):
    got, want = (g[wrt] for g in _softmax_grads(softmaxed, offset, held))
    scale = max(float(jnp.max(jnp.abs(want))), 1e-3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5 * scale + 1e-6)


def test_softmax_weights_are_normalised_over_the_chosen_without_an_epsilon(
        softmaxed):
    x, router = softmaxed["x"], softmaxed["router"]
    experts, weights = moe.route(x, router, None, top_k=K_S, score="softmax")
    np.testing.assert_allclose(np.asarray(weights.sum(-1)), 1.0, atol=1e-6)
    probs = np.asarray(jax.nn.softmax(x @ router, -1))
    picked = np.take_along_axis(probs, np.asarray(experts), -1)
    np.testing.assert_allclose(np.asarray(weights),
                               picked / picked.sum(-1, keepdims=True),
                               atol=1e-6)
    # The chosen are the ten largest of the softmax (of the logits alike).
    assert (picked.min(-1) >= np.sort(probs, -1)[:, -K_S] - 1e-7).all()
    # Forced choices replace the selection; the weights still come from
    # this call's own softmax, over the forced ten.
    forced = (np.asarray(experts) + 1) % NE_S
    again, w_forced = moe.route(x, router, None, top_k=K_S, score="softmax",
                                forced_experts=jnp.asarray(forced))
    np.testing.assert_array_equal(np.asarray(again), forced)
    f_picked = np.take_along_axis(probs, forced, -1)
    np.testing.assert_allclose(np.asarray(w_forced),
                               f_picked / f_picked.sum(-1, keepdims=True),
                               atol=1e-6)
    with pytest.raises(AssertionError):
        moe.route(x, router, None, top_k=K_S, score="tanh")


def test_softmax_calls_are_counted_where_they_are_built(softmaxed, layer):
    from oobleck_tpu.utils import metrics

    built = metrics.registry().counter(
        "oobleck_moe_softmax_routed_calls_total")
    before = built.value()
    fn = jax.jit(lambda x: _softmax_share(dict(softmaxed, x=x), 0, NE_S))
    fn(softmaxed["x"])
    fn(softmaxed["x"])              # a cache hit traces nothing
    assert built.value() - before == 1
    jax.jit(lambda x: _share(dict(layer, x=x), 0, NE))(layer["x"])
    assert built.value() - before == 1          # a sigmoid call counts nothing


def test_the_qwen3_next_cell_s_call_is_sized_for_every_pick_held_here():
    """80 rows expected an expert in tiles of 128, one a held expert; the
    buffer is the dropless worst case (every pick of every token held
    here) plus a tile an expert, and the kernels visit the tiles in use."""
    assert moe.choose_row_tile(4096 * 10, 512) == 128
    rows, tile = moe.buffer_rows(4096, 10, 16, 512)
    assert (rows, tile) == (4096 * 10 + 16 * 128, 128)
    assert moe._col_tile(512, moe.MAX_COL_TILE) == 512
    assert moe._col_tile(512, moe.MAX_TGMM_ROWS) == 512


# --------------------------------------------------------------------- #
# ReGLU experts, and a router that reads rows of its own                 #
# --------------------------------------------------------------------- #
#
# `smallthinker-21b-a3b`'s call: `activation="reglu"` (W2 (relu(W1 y) * W3
# y): the XLA between the grouped products differs, the kernels do not) and
# `router_x` (the router scores the block's input, the experts are handed
# the normed residual stream after the attention).

def _reglu_dense(r, y, router, w1, w3, w2, offset):
    """Dense over the held experts, for autodiff: a softmax over all, its
    top k renormalised, scored on `r`; ReGLU experts on `y`."""
    scores = jax.nn.softmax(r @ router, -1)
    _, chosen = jax.lax.top_k(scores, K)
    g = jax.nn.one_hot(chosen, NE).sum(-2) * scores
    g = g / g.sum(-1, keepdims=True)
    g = g[:, offset:offset + w1.shape[0]]
    h = jax.nn.relu(jnp.einsum("td,edf->tef", y, w1)) \
        * jnp.einsum("td,edf->tef", y, w3)
    return jnp.einsum("tef,efd,te->td", h, w2, g)


def _reglu_share(layer, r, offset, held, **kw):
    sl = slice(offset, offset + held)
    return moe.routed_experts(
        layer["x"], layer["router"], None, layer["w1"][sl], layer["w3"][sl],
        layer["w2"][sl], num_experts=NE, top_k=K, expert_offset=offset,
        score="softmax", activation="reglu", router_x=r, **kw)


@pytest.fixture(scope="module")
def router_rows():
    return jax.random.normal(jax.random.PRNGKey(11), (T, D))


@pytest.mark.parametrize("offset,held", [(0, NE), (2, 4), (7, 1)],
                         ids=["all", "share", "one_expert"])
def test_reglu_experts_with_a_router_of_their_own_match_the_dense_form(
        layer, router_rows, offset, held):
    sl = slice(offset, offset + held)
    args = (router_rows, layer["x"], layer["router"], layer["w1"][sl],
            layer["w3"][sl], layer["w2"][sl])
    target = jax.random.normal(jax.random.PRNGKey(1), (T, D))

    def routed(r, y, router, w1, w3, w2):
        out = moe.routed_experts(
            y, router, None, w1, w3, w2, num_experts=NE, top_k=K,
            expert_offset=offset, score="softmax", activation="reglu",
            router_x=r)
        return jnp.sum(out * target), out

    def dense(r, y, router, w1, w3, w2):
        out = _reglu_dense(r, y, router, w1, w3, w2, offset)
        return jnp.sum(out * target), out

    got, out = jax.jit(jax.grad(routed, argnums=range(6), has_aux=True))(*args)
    want, ref_out = jax.grad(dense, argnums=range(6), has_aux=True)(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               atol=2e-5)
    for name, g, w in zip(("r", "y", "router", "w1", "w3", "w2"), got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=3e-5,
                                   err_msg=name)
    # The router's rows take a gradient (through the weights) and the
    # experts' rows one of their own: neither is the other's.
    assert np.asarray(got[0]).any() and np.asarray(got[1]).any()


def test_the_router_s_rows_choose_and_the_experts_rows_are_computed_on(
        layer, router_rows):
    out, chosen = _reglu_share(layer, router_rows, 0, NE, return_routing=True)
    _, want = jax.lax.top_k(router_rows @ layer["router"], K)
    np.testing.assert_array_equal(np.sort(np.asarray(chosen), -1),
                                  np.sort(np.asarray(want), -1))
    # Other rows for the experts: the same choices, another result.
    moved = dict(layer, x=layer["x"] + 1.0)
    out2, chosen2 = _reglu_share(moved, router_rows, 0, NE,
                                 return_routing=True)
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(chosen2))
    assert float(jnp.max(jnp.abs(out - out2))) > 1e-3
    # `router_x` left out is the rows handed over; handing them over again
    # changes nothing.
    np.testing.assert_array_equal(
        np.asarray(_reglu_share(layer, None, 0, NE)),
        np.asarray(_reglu_share(layer, layer["x"], 0, NE)))
    with pytest.raises(AssertionError):
        _reglu_share(layer, router_rows[:-1], 0, NE)
    with pytest.raises(AssertionError):
        moe.routed_experts(layer["x"], layer["router"], None, layer["w1"],
                           layer["w3"], layer["w2"], num_experts=NE, top_k=K,
                           activation="gelu")


def test_the_reglu_shares_add_up_to_the_uncut_layer(layer, router_rows):
    whole = np.asarray(_reglu_share(layer, router_rows, 0, NE))
    call = {**layer, "bias": None, "router_x": router_rows}
    for parts in (8, 4, 2):
        total = _shares_total(call, parts, score="softmax",
                              activation="reglu")
        np.testing.assert_allclose(total, whole, atol=2e-5)


def test_a_reglu_call_holds_swiglu_s_nine_products(layer, router_rows,
                                                   kernels_interpreted):
    """Three grouped products forward, three dX and three dW backward: the
    kernels are SwiGLU's, under the same names, and so are the two sums of
    rows into tokens."""
    from tests.ops.programs import pallas_calls

    fn = lambda y: jnp.sum(_reglu_share(dict(layer, x=y), router_rows, 2, 4))
    names = [n for n, _ in pallas_calls(jax.make_jaxpr(fn)(layer["x"]).jaxpr)]
    assert names == ["moe_gmm"] * 3 + ["moe_token_sum"]
    grads = jax.grad(lambda y, w1: jnp.sum(moe.routed_experts(
        y, layer["router"], None, w1, layer["w3"][2:6], layer["w2"][2:6],
        num_experts=NE, top_k=K, expert_offset=2, score="softmax",
        activation="reglu", router_x=router_rows)), argnums=(0, 1))
    names = [n for n, _ in pallas_calls(
        jax.make_jaxpr(grads)(layer["x"], layer["w1"][2:6]).jaxpr)]
    # (The jaxpr also holds `_gate_and_up`'s forward products again, which
    # its backward rule traces to pull through and XLA drops.)
    assert set(names) == {"moe_gmm", "moe_tgmm", "moe_token_sum"}
    assert names.count("moe_tgmm") == 3
    assert names.count("moe_token_sum") == 2


def test_reglu_and_early_router_calls_are_counted_where_they_are_built(
        layer, router_rows):
    from oobleck_tpu.utils import metrics

    reg = metrics.registry()
    reglu = reg.counter("oobleck_moe_reglu_calls_total")
    early = reg.counter("oobleck_moe_early_router_calls_total")
    before = (reglu.value(), early.value())
    fn = jax.jit(lambda y: _reglu_share(dict(layer, x=y), router_rows, 0, NE))
    fn(layer["x"])
    fn(layer["x"])                  # a cache hit traces nothing
    assert (reglu.value() - before[0], early.value() - before[1]) == (1, 1)
    jax.jit(lambda y: _reglu_share(dict(layer, x=y), None, 0, NE))(layer["x"])
    assert (reglu.value() - before[0], early.value() - before[1]) == (2, 1)
    jax.jit(lambda x: _share(dict(layer, x=x), 0, NE))(layer["x"])
    assert (reglu.value() - before[0], early.value() - before[1]) == (2, 1)


# Each benchmark cell's call (tokens a microbatch, picks a token, experts):
# the tile `choose_row_tile` gives it. A change to the rule keeps the five
# accepted cells' tiles (ISSUE 45, fallback (c)).
CELL_TILES = {
    "lfm2-24b-a2b": ((8192, 4, 64), 384),
    "moonlight-16b-a3b": ((4096, 6, 64), 512),
    "nemotron-3-nano-30b-a3b": ((4096, 6, 128), 384),
    "qwen3-next-80b-a3b": ((4096, 10, 512), 128),
    "smallthinker-21b-a3b": ((16384, 6, 64), 1024),
}


@pytest.mark.parametrize("cell", sorted(CELL_TILES))
def test_every_cell_keeps_its_row_tile(cell):
    (tokens, top_k, experts), want = CELL_TILES[cell]
    assert moe.choose_row_tile(tokens * top_k, experts) == want


# --------------------------------------------------------------------- #
# the grouped kernels' grids end at the tiles in use (PR 56)             #
# --------------------------------------------------------------------- #

# Each routed cell's call: tokens a microbatch, picks a token, experts held,
# experts, D, F (`benchmarks/configs/*.json`'s published widths, the runners'
# microbatches), and the row steps a grid ran over the worst-case buffer
# before its row axis took the plan's bound: buffer rows / tile. In use, with
# even loads: one tile an expert (`choose_row_tile`), two in `lfm2-24b-a2b`
# and `smallthinker-21b-a3b`: 4.8 to 17.0 % of them.
CELL_CALLS = {
    "lfm2-24b-a2b": ((8192, 4, 8, 64, 2048, 1536), 94),
    "moonlight-16b-a3b": ((4096, 6, 8, 64, 2048, 1408), 56),
    "nemotron-3-nano-30b-a3b": ((4096, 6, 8, 128, 2688, 1856), 72),
    "qwen3-next-80b-a3b": ((4096, 10, 16, 512, 2048, 512), 336),
    "smallthinker-21b-a3b": ((16384, 6, 8, 64, 2560, 768), 104),
}


def _grouped_call(kernel, rows, held, tile, d, f):
    """(a function of `tile_group` and `num_tiles` that makes the cell's
    call of `kernel`, its other operands): `moe_gmm` as the forward's first
    product, `moe_tgmm` as that product's dW on top of a handed sum."""
    s, bf16, f32 = jax.ShapeDtypeStruct, jnp.bfloat16, jnp.float32
    if kernel == "gmm":
        return (lambda tg, nt, lhs, rhs: moe.gmm_call(
            lhs, rhs, tg, nt, tile=tile)), (
            s((rows, d), bf16), s((held, d, f), f32))
    return (lambda tg, nt, lhs, rhs, start: moe.tgmm_call(
        lhs, rhs, tg, nt, tile=tile, num_groups=held, out_dtype=f32,
        start=start)), (
        s((rows, d), bf16), s((rows, f), bf16), s((held, d, f), f32))


@pytest.mark.parametrize("kernel", ["gmm", "tgmm"])
@pytest.mark.parametrize("cell", sorted(CELL_CALLS))
def test_a_grouped_kernel_s_row_axis_ends_at_the_plan_s_tiles(cell, kernel):
    """The traced `pallas_call` carries ONE dynamic grid bound, its row
    axis's (the last: the expert's block stays in VMEM over it), and the
    other axes' bounds are the shape's. Read from the jaxpr: no kernel is
    run. Counted where it is built."""
    from oobleck_tpu.utils import metrics
    from tests.ops.programs import all_eqns

    (tokens, top_k, held, experts, d, f), row_steps = CELL_CALLS[cell]
    rows, tile = moe.buffer_rows(tokens, top_k, held, experts)
    assert (tile, rows // tile) == (CELL_TILES[cell][1], row_steps)
    call, operands = _grouped_call(kernel, rows, held, tile, d, f)
    built = metrics.registry().counter("oobleck_moe_plan_bounded_grids_total")
    before = built.value(kernel=kernel)
    jaxpr = jax.make_jaxpr(call)(
        jax.ShapeDtypeStruct((rows // tile,), jnp.int32),
        jax.ShapeDtypeStruct((1,), jnp.int32), *operands)
    assert built.value(kernel=kernel) - before == 1
    (eqn,) = [e for e in all_eqns(jaxpr.jaxpr)
              if e.primitive.name == "pallas_call"]
    assert eqn.params["name"] == f"moe_{kernel}"
    mapping = eqn.params["grid_mapping"]
    assert mapping.num_dynamic_grid_bounds == 1
    *static, row_axis = mapping.grid
    assert not isinstance(row_axis, int), mapping.grid
    columns = f // moe._col_tile(f, moe.MAX_COL_TILE)
    assert static == {
        "gmm": [columns],
        "tgmm": [d // moe._col_tile(d, moe.MAX_TGMM_ROWS), columns]}[kernel]
    # The bound is the call's first operand; `moe_tgmm` also keeps
    # `num_tiles` as a prefetched table (its body's test of the last tile).
    assert mapping.num_index_operands == (2 if kernel == "tgmm" else 1)
    assert eqn.invars[0].aval.shape == ()


@pytest.mark.parametrize("kernel", ["gmm", "tgmm"])
def test_a_plan_at_its_least_in_a_long_buffer_is_the_buffer_cut_to_it(kernel):
    """No pick lands here: `num_tiles` == the experts held, a tile each,
    in a buffer of five times as many. The kernels' results are those of a
    buffer cut to the tiles in use, bit for bit (the interpreter runs the
    same dynamic bound): the rows past them are no grid step."""
    held, tile, k, n = 4, 16, 32, 48
    rows = 5 * held * tile
    plan = moe.plan_routing(jnp.full((23,), held, jnp.int32), held, rows, tile)
    used = int(plan.num_tiles[0])
    assert used == held < rows // tile
    ks = jax.random.split(jax.random.PRNGKey(56), 4)
    lhs = jax.random.normal(ks[0], (rows, k)).astype(jnp.bfloat16)
    if kernel == "gmm":
        rhs = jax.random.normal(ks[1], (held, k, n))
        call = lambda lhs, tg: moe.gmm_call(lhs, rhs, tg, plan.num_tiles,
                                            tile=tile)
        got = call(lhs, plan.tile_group)[:used * tile]
        want = call(lhs[:used * tile], plan.tile_group[:used])
    else:
        d_out = jax.random.normal(ks[2], (rows, n)).astype(jnp.bfloat16)
        start = jax.random.normal(ks[3], (held, k, n))
        call = lambda lhs, d, tg: moe.tgmm_call(
            lhs, d, tg, plan.num_tiles, tile=tile, num_groups=held,
            out_dtype=jnp.float32, start=start)
        got = call(lhs, d_out, plan.tile_group)
        want = call(lhs[:used * tile], d_out[:used * tile],
                    plan.tile_group[:used])
    assert np.abs(np.asarray(want, np.float32)).max() > 1.0
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_the_smallthinker_cell_s_expected_rows_sit_on_every_tile_s_edge():
    """1,536 rows expected an expert = 3 x 512 = 4 x 384 = 6 x 256 = 12 x
    128: no tile up to MAX_ROW_TILE keeps them off an edge (at 512 an
    expert at 1,537 rows fills a fourth tile, and the cell's rate followed
    the tiles from seed to seed: PERF.md section 6, PR 45). Only then are
    tiles up to twice MAX_ROW_TILE looked at: 1024, two tiles an expert
    with 512 rows of room either way."""
    expected = 16384 * 6 / 64
    assert expected == 1536
    assert all(expected % tile == 0
               for tile in range(moe.LANE, moe.MAX_ROW_TILE + 1, moe.LANE))
    tile = moe.choose_row_tile(16384 * 6, 64)
    assert tile == 1024 == 2 * moe.MAX_ROW_TILE
    tiles_at = lambda rows: -(-rows // tile)
    assert {tiles_at(r) for r in (1025, 1400, 1536, 1700, 2048)} == {2}
    rows, buffer_tile = moe.buffer_rows(16384, 6, 8, 64)
    assert (rows, buffer_tile) == (16384 * 6 + 8 * 1024, 1024)
    assert moe._col_tile(768, moe.MAX_COL_TILE) == 384
    assert moe._col_tile(2560, moe.MAX_COL_TILE) == 512
    # A call with any room at all within MAX_ROW_TILE never looks further:
    # 1,535 and 1,537 expected rows keep a tile of their own within it.
    for pairs in (1535 * 64, 1537 * 64, 3000 * 64):
        assert moe.choose_row_tile(pairs, 64) <= moe.MAX_ROW_TILE
