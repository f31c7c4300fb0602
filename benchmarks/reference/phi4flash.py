"""Plain reference for the Phi-4-mini-flash family (`model_type: phi4flash`:
Phi-4-mini-flash-reasoning; the SambaY decoder-hybrid-decoder,
arXiv:2507.06607).

Every layer is two residual branches, each behind a LayerNorm `LN` with
scale and bias (eps 1e-5):

    x = x + Mixer_l(LN1(x));  x = x + W2 (silu(u W1) * (u W3)), u = LN2(x)
    logits = Head(LN_f(x_last))

`[W1 | W3]` is the published fused 2560 x 20480; no bias in the feed-forward.
The mixer, by the layer's kind (`kinds`, one a layer; `published_kinds` is
the public modelling code's rule), `u` = LN1(x), [B, S, E]:

    mamba, mamba_source
        Mamba-1. [x | z] = u W_in (2 x 5120). x = silu(conv(x) + b_c), conv
        depthwise and causal with 4 taps: conv(v)_t = sum_{j<4} w_j v_{t-j},
        zeros before position 0. [d | B | C] = x W_x (160 + 16 + 16).
        dt = softplus(d W_dt + b_dt). A = -exp(A_log), [5120, 16]. State
        h [5120, 16] a sequence, h_{-1} = 0:
            h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t
            y_t = h_t C_t + D x_t
        computed HERE AS WRITTEN, one position after another (`lax.scan`).
        Output W_out (y * silu(z)). A `mamba_source` also hands its `y`
        (before the gate) to every `gmu` after it, as the memory `m`.
    gmu
        W_out (m * silu(u W_in)), `m` the source's `y`, same position.
    swa, full_source, cross
        differential attention. [q | k | v] = u W_qkv + b (40, 20 and 20
        heads of 64); consecutive heads pair up: query pair j = (q[2j],
        q[2j+1]) reads key-value pair j // 2 = (k[2i], k[2i+1]), (v[2i],
        v[2i+1]), v = [v1 | v2] (128 wide). a_r = softmax(q_r k_r^T / 8 +
        mask) v, r = 1, 2: two full softmaxes of queries against all keys,
        the mask from positions (key s visible to query t iff s <= t; `swa`:
        and t - s < 512). lambda = exp(lq1 . lk1) - exp(lq2 . lk2) +
        lambda_init, lambda_init = 0.8 - 0.6 exp(-0.3 i) at layer i of the
        published numbering. Output W_o concat_j[(1 - lambda_init)
        RMSNorm_128(a_1 - lambda a_2) * g] + b_o, `g` the norm's learned
        scale. A `full_source` also hands its k and v (after the bias,
        before the pairing) to every `cross` after it; a `cross` layer has
        W_q and W_o only and attends to those.

The memory and the keys and values go from layer to layer as plain
variables of `forward`. What the published config does not give is taken
as the configuration file's `assumed` states it.

Plain `jax.numpy`, float32, every contraction at `Precision.HIGHEST`.
Nothing is imported from `oobleck_tpu`; the modes of arithmetic (`highest`,
`bfloat16`, `fp8`) are `reference/gpt.py`'s and apply to every contraction,
the recurrence's two products included (x (x) B and h C; the state itself
stays float32).

Four things are here for size and change no value. The recurrence runs as
a scan over blocks of `SCAN_BLOCK` positions around a scan over the
positions of a block, the inner one a `jax.checkpoint`. Attention runs over
blocks of head pairs and queries (`attend`), each a `jax.checkpoint`. Each
layer is a `jax.checkpoint`. The head and the loss run over blocks of
`LOSS_BLOCK` positions.

`fault` plants one of two faults, for the control
(`control_phi4flash.py`): `no_lambda` leaves the differential term out
(lambda = 0), `gmu_gated` feeds the Gated Memory Units the source's gated
`y * silu(z)` in `m`'s place.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.gpt import MODES, _contract  # noqa: F401

MAMBA, MAMBA_SOURCE, GMU = "mamba", "mamba_source", "gmu"
SWA, FULL_SOURCE, CROSS = "swa", "full_source", "cross"
FAULTS = (None, "no_lambda", "gmu_gated")
SCAN_BLOCK = 128
H_BLOCK, Q_BLOCK = 4, 512
LOSS_BLOCK = 1024
NEG_INF = -1e30


def published_kinds(num_layers: int) -> list[str]:
    """Layer i is a state-space layer iff i is even, else attention; the
    window iff i < N/2; N/2 the memory's source, N/2 + 1 the keys' and
    values'; from N/2 + 2 on `gmu` and `cross`."""
    half = num_layers // 2
    return [(MAMBA if i < half else MAMBA_SOURCE if i == half else GMU)
            if i % 2 == 0 else
            (SWA if i < half else FULL_SOURCE if i == half + 1 else CROSS)
            for i in range(num_layers)]


@dataclass(frozen=True)
class RefConfig:
    vocab_size: int                    # the rows of the vocabulary held
    hidden_size: int
    kinds: tuple[str, ...]             # a kind a layer, as run
    layer_offset: int                  # the published index of layer 0
    num_heads: int
    num_kv_heads: int
    head_dim: int
    intermediate_size: int
    sliding_window: int
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    layer_norm_eps: float = 1e-5
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    initializer_range: float = 0.02
    lambda_range: float = 0.1

    @property
    def num_layers(self) -> int:
        return len(self.kinds)

    @property
    def d_inner(self) -> int:
        return self.expand * self.hidden_size

    @property
    def dt_rank(self) -> int:
        return -(-self.hidden_size // 16)

    def lambda_init(self, layer: int) -> float:
        return 0.8 - 0.6 * math.exp(-0.3 * (self.layer_offset + layer))

    @classmethod
    def from_config(cls, config: dict) -> "RefConfig":
        """From a file under benchmarks/configs/: the sizes as they are
        run, under the published keys."""
        assert len(config["layer_kinds"]) == config["num_hidden_layers"]
        return cls(
            vocab_size=config["vocab_rows_held"],
            hidden_size=config["hidden_size"],
            kinds=tuple(config["layer_kinds"]),
            layer_offset=config["model_args"].get("layer_offset", 0),
            num_heads=config["num_attention_heads"],
            num_kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            intermediate_size=config["intermediate_size"],
            sliding_window=config["sliding_window"],
            layer_norm_eps=config["layer_norm_eps"])

    def block_params(self, layer: int) -> dict[str, int]:
        """Parameters of one layer by part (for sizes and FLOP counts)."""
        e, f, inner = self.hidden_size, self.intermediate_size, self.d_inner
        n, r, d = self.d_state, self.dt_rank, self.head_dim
        parts = {"ff": 3 * e * f, "norms": 4 * e}
        kind = self.kinds[layer]
        if kind in (MAMBA, MAMBA_SOURCE):
            parts.update(w_in=2 * e * inner, w_out=inner * e,
                         conv=(self.d_conv + 1) * inner,
                         w_x=inner * (r + 2 * n), w_dt=(r + 1) * inner,
                         scalars=inner * n + inner)
        elif kind == GMU:
            parts.update(w_in=e * inner, w_out=inner * e)
        else:
            wide = self.num_heads * d + (
                0 if kind == CROSS else 2 * self.num_kv_heads * d)
            parts.update(attention=(e + 1) * wide
                         + self.num_heads * d * e + e,
                         lambdas=4 * d + 2 * d)
        return parts

    def num_params(self) -> int:
        blocks = sum(sum(self.block_params(b).values())
                     for b in range(self.num_layers))
        return (2 * self.vocab_size * self.hidden_size
                + 2 * self.hidden_size + blocks)


# --------------------------------------------------------------------- #
# weights from a seed, in the program's layout                           #
# --------------------------------------------------------------------- #

def _block(key, c: RefConfig, layer: int):
    ks = jax.random.split(key, 16)
    f32 = jnp.float32
    std = c.initializer_range
    res_std = std / (2 * c.num_layers) ** 0.5
    e, f, inner = c.hidden_size, c.intermediate_size, c.d_inner
    normal = lambda k, shape, s: jax.random.normal(k, shape, f32) * s
    uniform = lambda k, shape, lo, hi: jax.random.uniform(
        k, shape, f32, lo, hi)
    # Every bias and the attention norm's scale are drawn, not 0 and 1: a
    # bias that is zero hides where it is added.
    ln = lambda k: {"scale": jnp.ones((e,), f32), "bias": normal(k, (e,), std)}
    p = {"ln_op": ln(ks[13]), "ln_ff": ln(ks[14]),
         "ff": {"w1": normal(ks[0], (e, f), std),
                "w3": normal(ks[1], (e, f), std),
                "w2": normal(ks[2], (f, e), res_std)}}
    kind = c.kinds[layer]
    if kind == GMU:
        p["gmu"] = {"w_in": normal(ks[3], (e, inner), std),
                    "w_out": normal(ks[4], (inner, e), res_std)}
    elif kind in (SWA, FULL_SOURCE, CROSS):
        d = c.head_dim
        wide = c.num_heads * d + (
            0 if kind == CROSS else 2 * c.num_kv_heads * d)
        first = "q" if kind == CROSS else "qkv"
        p["attn"] = {
            f"w_{first}": normal(ks[3], (e, wide), std),
            f"b_{first}": normal(ks[10], (wide,), std),
            "w_o": normal(ks[4], (c.num_heads * d, e), res_std),
            "b_o": normal(ks[11], (e,), std),
            **{name: normal(k, (d,), c.lambda_range) for name, k in zip(
                ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"),
                ks[5:9])},
            "subln": 1.0 + normal(ks[12], (2 * d,), std)}
    else:
        n, r = c.d_state, c.dt_rank
        step = jnp.exp(uniform(ks[8], (inner,), math.log(c.time_step_min),
                               math.log(c.time_step_max)))
        bound = c.d_conv ** -0.5
        p["mamba"] = {
            "w_in": normal(ks[3], (e, 2 * inner), std),
            "conv_taps": uniform(ks[4], (c.d_conv, inner), -bound, bound),
            "conv_bias": uniform(ks[5], (inner,), -bound, bound),
            "w_x": normal(ks[6], (inner, r + 2 * n), std),
            "w_dt": normal(ks[7], (r, inner), std),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),  # inverse softplus
            "A_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, n + 1, dtype=f32)), (inner, n)),
            "D": jnp.ones((inner,), f32),
            "w_out": normal(ks[9], (inner, e), res_std)}
    return p


def init_params(seed: int, c: RefConfig):
    """Seeded float32 weights, made on the device in ONE jitted call:
    {"embed": {wte}, "blocks": [per-layer trees], "head": {ln_f, w}}, each
    tree in the layout of `oobleck_tpu/models/phi4flash.py`'s layer."""

    @jax.jit
    def make(key):
        k_e, k_b, k_h, k_f = jax.random.split(key, 4)
        e, v = c.hidden_size, c.vocab_size
        keys = jax.random.split(k_b, c.num_layers)
        return {
            "embed": {"wte": jax.random.normal(k_e, (v, e), jnp.float32)
                      * c.initializer_range},
            "blocks": [_block(keys[i], c, i) for i in range(c.num_layers)],
            "head": {"ln_f": {"scale": jnp.ones((e,), jnp.float32),
                              "bias": jax.random.normal(
                                  k_f, (e,), jnp.float32)
                              * c.initializer_range},
                     "w": jax.random.normal(k_h, (e, v), jnp.float32)
                     * c.initializer_range},
        }

    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return make(key)


# --------------------------------------------------------------------- #
# arithmetic                                                             #
# --------------------------------------------------------------------- #

def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _causal_conv(v, taps, bias):
    """v [B, S, D], taps [L, D]: sum_j taps[j] * v_{t-j} + bias, zeros
    before position 0."""
    s = v.shape[1]
    out = bias
    for j in range(taps.shape[0]):
        out = out + taps[j] * jnp.pad(v, ((0, 0), (j, 0), (0, 0)))[:, :s]
    return out


def recurrence(x, dt, a_neg, b, c, d_skip, mode: str):
    """The selective scan, one position after another. x, dt [B, S, C];
    a_neg [C, N]; b, c [B, S, N]; d_skip [C]. Returns y [B, S, C]."""
    bsz, s, channels = x.shape
    n = a_neg.shape[1]
    block = SCAN_BLOCK if s % SCAN_BLOCK == 0 else s

    def position(state, row):
        x_t, dt_t, b_t, c_t = row
        state = (jnp.exp(dt_t[..., None] * a_neg) * state
                 + _contract("bc,bn->bcn", dt_t * x_t, b_t, mode))
        y_t = _contract("bcn,bn->bc", state, c_t, mode)
        return state, y_t + d_skip * x_t

    @jax.checkpoint
    def positions(state, rows):
        return lax.scan(position, state, rows)

    by_block = lambda t: jnp.moveaxis(t, 1, 0).reshape(
        s // block, block, bsz, t.shape[-1])
    _, y = lax.scan(positions, jnp.zeros((bsz, channels, n), jnp.float32),
                    tuple(by_block(t) for t in (x, dt, b, c)))
    return jnp.moveaxis(y.reshape(s, bsz, channels), 0, 1)


def _mamba(p, u, c: RefConfig, mode: str):
    """(the mixer's output, y before the gate, y after it)."""
    inner, n, r = c.d_inner, c.d_state, c.dt_rank
    xz = _contract("bse,ef->bsf", u, p["w_in"], mode)
    x, z = xz[..., :inner], xz[..., inner:]
    x = jax.nn.silu(_causal_conv(x, p["conv_taps"], p["conv_bias"]))
    dbc = _contract("bsc,cr->bsr", x, p["w_x"], mode)
    dt = jax.nn.softplus(
        _contract("bsr,rc->bsc", dbc[..., :r], p["w_dt"], mode)
        + p["dt_bias"])
    y = recurrence(x, dt, -jnp.exp(p["A_log"]), dbc[..., r:r + n],
                   dbc[..., r + n:], p["D"], mode)
    gated = y * jax.nn.silu(z)
    return _contract("bsf,fe->bse", gated, p["w_out"], mode), y, gated


def _gmu(p, u, m, mode: str):
    gate = jax.nn.silu(_contract("bse,ef->bsf", u, p["w_in"], mode))
    return _contract("bsf,fe->bse", m * gate, p["w_out"], mode)


def attend(q, k, v, mode: str, window: int | None):
    """One sequence. q, k [H, S, D], v [H, S, Dv] -> [H, S, Dv]: softmax of
    every query against ALL keys under the mask, `H_BLOCK` heads and
    `Q_BLOCK` queries at a time (the whole of either where the block does
    not divide it)."""
    h, s, d = q.shape
    hb = H_BLOCK if h % H_BLOCK == 0 else h
    bq = Q_BLOCK if s % Q_BLOCK == 0 else s
    nh, nq = h // hb, s // bq
    qg = q.reshape(nh, hb, nq, bq, d)
    kg, vg = k.reshape(nh, hb, s, d), v.reshape(nh, hb, s, -1)

    @jax.checkpoint
    def block(g, b):
        t = b * bq + jnp.arange(bq)[:, None]               # query position
        j = jnp.arange(s)[None, :]                         # key position
        seen = j <= t
        if window is not None:
            seen = seen & (t - j < window)
        scores = _contract("hqd,hkd->hqk", qg[g, :, b], kg[g], mode) * d ** -0.5
        probs = jax.nn.softmax(jnp.where(seen, scores, NEG_INF), -1)
        return _contract("hqk,hkd->hqd", probs, vg[g], mode)

    out = lax.map(lambda g: lax.map(lambda b: block(g, b), jnp.arange(nq)),
                  jnp.arange(nh))                          # [nh, nq, hb, bq, Dv]
    return out.transpose(0, 2, 1, 3, 4).reshape(h, s, -1)


def _attention(p, u, kv, c: RefConfig, layer: int, mode: str, fault):
    """(the mixer's output, (k, v) [B, S, 20 x 64] of this layer or, of a
    `cross` layer, those handed in)."""
    bsz, s, _ = u.shape
    h, g, d = c.num_heads, c.num_kv_heads, c.head_dim
    kind = c.kinds[layer]
    if kind == CROSS:
        q = _contract("bse,ef->bsf", u, p["w_q"], mode) + p["b_q"]
    else:
        qkv = _contract("bse,ef->bsf", u, p["w_qkv"], mode) + p["b_qkv"]
        q = qkv[..., :h * d]
        kv = (qkv[..., h * d:(h + g) * d], qkv[..., (h + g) * d:])
    k, v = kv
    heads = lambda t, n: t.reshape(bsz, s, n, d).transpose(0, 2, 1, 3)
    q, k, v = heads(q, h), heads(k, g), heads(v, g)        # [B, heads, S, d]
    q1, q2 = q[:, 0::2], q[:, 1::2]                        # [B, h/2, S, d]
    of_pair = jnp.arange(h // 2) // ((h // 2) // (g // 2))
    k1, k2 = k[:, 0::2][:, of_pair], k[:, 1::2][:, of_pair]
    values = jnp.concatenate([v[:, 0::2], v[:, 1::2]], -1)[:, of_pair]
    window = c.sliding_window if kind == SWA else None
    a1, a2 = (jnp.stack([attend(qr[i], kr[i], values[i], mode, window)
                         for i in range(bsz)])
              for qr, kr in ((q1, k1), (q2, k2)))          # [B, h/2, S, 2d]
    init = c.lambda_init(layer)
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
           - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + init)
    if fault == "no_lambda":
        lam = 0.0
    a = a1 - lam * a2
    a = a * lax.rsqrt(jnp.mean(jnp.square(a), -1, keepdims=True)
                      + c.layer_norm_eps) * p["subln"] * (1.0 - init)
    a = a.transpose(0, 2, 1, 3).reshape(bsz, s, h * d)
    return _contract("bsf,fe->bse", a, p["w_o"], mode) + p["b_o"], kv


def _swiglu(p, u, mode: str):
    hidden = (jax.nn.silu(_contract("bse,ef->bsf", u, p["w1"], mode))
              * _contract("bse,ef->bsf", u, p["w3"], mode))
    return _contract("bsf,fe->bse", hidden, p["w2"], mode)


def _block_forward(p, x, m, kv, c: RefConfig, layer: int, mode: str, fault):
    """(x, m, kv) after layer `layer`: the memory and the keys and values
    as they stand for the layers after it."""
    kind = c.kinds[layer]
    u = _layer_norm(x, p["ln_op"], c.layer_norm_eps)
    if kind in (MAMBA, MAMBA_SOURCE):
        out, y, gated = _mamba(p["mamba"], u, c, mode)
        if kind == MAMBA_SOURCE:
            m = gated if fault == "gmu_gated" else y
    elif kind == GMU:
        out = _gmu(p["gmu"], u, m, mode)
    else:
        out, own = _attention(p["attn"], u, kv, c, layer, mode, fault)
        if kind == FULL_SOURCE:
            kv = own
    x = x + out
    x = x + _swiglu(p["ff"], _layer_norm(x, p["ln_ff"], c.layer_norm_eps),
                    mode)
    return x, m, kv


def hidden(params, tokens, c: RefConfig, mode: str = "highest", fault=None):
    """tokens [B, S] -> the last layer's x [B, S, E], before LN_f."""
    assert fault in FAULTS, fault
    x = params["embed"]["wte"][tokens]
    m = kv = None
    for layer, p in enumerate(params["blocks"]):
        x, m, kv = jax.checkpoint(functools.partial(
            _block_forward, c=c, layer=layer, mode=mode, fault=fault))(
            p, x, m, kv)
    return x


def _logits(head, x, c: RefConfig, mode: str):
    x = _layer_norm(x, head["ln_f"], c.layer_norm_eps)
    return _contract("bse,ev->bsv", x, head["w"], mode)


def forward(params, tokens, c: RefConfig, mode: str = "highest", fault=None):
    """tokens [B, S] -> logits [B, S, vocab] float32."""
    return _logits(params["head"], hidden(params, tokens, c, mode, fault), c,
                   mode)


def loss(params, tokens, c: RefConfig, mode: str = "highest", fault=None):
    """Mean next-token cross entropy, the head `LOSS_BLOCK` positions at a
    time (each a `jax.checkpoint`: the logits of 8192 positions never
    exist whole)."""
    x = hidden(params, tokens, c, mode, fault)
    bsz, s, _ = x.shape
    # Position t's target is token t + 1; the last position has none.
    gold = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))
    counts = jnp.broadcast_to(jnp.arange(s) < s - 1, (bsz, s))
    block = LOSS_BLOCK if s % LOSS_BLOCK == 0 else s

    @jax.checkpoint
    def part(head, x_b, gold_b, counts_b):
        logits = _logits(head, x_b, c, mode)
        picked = jnp.take_along_axis(logits, gold_b[..., None], -1)[..., 0]
        return jnp.sum(jnp.where(
            counts_b, jax.nn.logsumexp(logits, axis=-1) - picked, 0.0))

    by_block = lambda t: jnp.moveaxis(
        t.reshape(bsz, s // block, block, *t.shape[2:]), 1, 0)
    parts = lax.map(lambda rows: part(params["head"], *rows),
                    (by_block(x), by_block(gold), by_block(counts)))
    return jnp.sum(parts) / (bsz * (s - 1))


def loss_and_grads(params, tokens, c: RefConfig, mode: str = "highest",
                   fault=None):
    """(loss, gradients of every parameter)."""
    return jax.value_and_grad(
        functools.partial(loss, c=c, mode=mode, fault=fault))(params, tokens)
