"""Plain reference for the DeepSeek-V3 family (`model_type: deepseek_v3`:
Moonlight-16B-A3B).

Block `l`, input `x` [B, S, E], `N` = RMSNorm (learned scale, eps 1e-5):

    x = x + Attn(N(x));   x = x + FF_l(N(x));   logits = Head(N_f(x_last))

    Attn    latent attention, the same in every block, H heads:
            q = h Wq, per head [q_nope (Dn) | q_rope (Dr)] (`q_lora_rank`
            null: no query latent). [c | k_rope] = h Wkv_a: `c` the latent
            of `kv_lora_rank`, `k_rope` ONE Dr-vector a position that all
            heads share. c' = RMSNorm(c) with a scale of its own;
            [k_nope (Dn) | v (Dv)] = c' Wkv_b per head. Rotary (theta
            50000, no scaling) on q_rope and k_rope over all Dr dimensions;
            k = [k_nope | k_rope]. Scores q k^T / sqrt(Dn + Dr), key j
            visible to query i iff j <= i; a = softmax x v (Dv wide);
            output concat_h(a_h) Wo. No bias anywhere.
    FF_l    l < first_k_dense_replace: W2 (silu(W1 h) * W3 h), 11264 wide.
            Else: s = sigmoid(h Wr) over all 64 experts; I = the 6 largest
            of s + b (`b` the selection bias: it selects and never weighs;
            `n_group` = `topk_group` = 1, so the group step is the
            identity); w_e = s_e / sum_{j in I} s_j x 2.446;
            FF = sum_{e in I, e HELD} w_e SwiGLU_e(h) + SwiGLU_shared(h),
            the 2 shared experts as ONE SwiGLU of 2 x 1408, weight 1.

The published config fixes every size. It is silent on six things, taken
here as the configuration file's `assumed` states them:
  (1) rotary pairing: rotate-half; the family's interleaved pairing is a
      fixed permutation of the 64 rotary columns of Wq and Wkv_a, which
      seeded weights do not distinguish;
  (2) eps of the latent's RMSNorm: 1e-6, as the family's public modelling
      code builds it without the config's eps;
  (3) no auxiliary or sequence balance loss (`seq_aux` has no coefficient
      in the config);
  (4) the selection bias is not trained by the gradient and has no update
      rule here; the seeded weights carry the bias that balances the seed's
      router on uniform ids (`_balance`, as `reference/lfm2.py` does);
  (5) the weight normaliser's epsilon is 1e-6 where the family's code has
      1e-20 (a relative 3e-7);
  (6) initialiser as `lfm2-24b-a2b`'s: normal 0.02, residual outputs (Wo,
      every W2) 0.02 / sqrt(2 x layers).

`held` is the contiguous range of experts the share holds (`expert_offset`,
`num_experts_held`); with all of them it is the published layer. The shared
experts are on every token whatever is held. The vocabulary is the rows
held. Plain `jax.numpy`, float32, every contraction at `Precision.HIGHEST`;
the mask is built from indices; the routed layer runs EVERY held expert on
every token and weights the results with a dense [tokens, experts] matrix:
no sort, no kernel. Nothing is imported from `oobleck_tpu`; the modes of
arithmetic (`highest`, `bfloat16`, `fp8`) are `reference/gpt.py`'s.

Two things are here for size and change no value. Attention runs over
blocks of `H_BLOCK` heads and `Q_BLOCK` queries: 16 heads x 4096 x 4096
float32 scores are 1.07 GB a layer whole, and the check runs beside the
engine's state. Each such block and each layer is a `jax.checkpoint`, so
the gradient keeps a layer's input and recomputes the rest. (In `fp8` mode
a contraction's one scale is then a block's, not the whole tensor's.)

Departure, as `reference/lfm2.py`: `forward` can be handed, per routed
block, the expert indices to use (`forced`); what this file would have
selected is returned beside it (`own`), and `mismatch_share` counts the
(token, block) pairs whose top-k SET differs.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.gpt import MODES, _contract  # noqa: F401
from benchmarks.reference.lfm2 import (  # noqa: F401
    balanced_bias,
    mismatch_share,
)

NEG_INF = -1e30
H_BLOCK = 4
Q_BLOCK = 1024
BALANCE_TOKENS = (2, 4096)     # sequences x length the bias is balanced on


@dataclass(frozen=True)
class RefConfig:
    vocab_size: int                    # the rows of the vocabulary held
    hidden_size: int
    num_layers: int
    num_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    intermediate_size: int
    moe_intermediate_size: int
    first_k_dense_replace: int
    num_experts: int
    num_experts_per_tok: int
    n_shared_experts: int
    num_experts_held: int
    expert_offset: int = 0
    routed_scaling_factor: float = 2.446
    norm_eps: float = 1e-5
    latent_norm_eps: float = 1e-6
    rope_theta: float = 50000.0
    initializer_range: float = 0.02
    expert_bias_range: float = 0.01

    @property
    def routed_blocks(self) -> tuple[int, ...]:
        return tuple(range(self.first_k_dense_replace, self.num_layers))

    @property
    def shared_intermediate_size(self) -> int:
        return self.n_shared_experts * self.moe_intermediate_size

    @classmethod
    def from_config(cls, config: dict) -> "RefConfig":
        """From a file under benchmarks/configs/: the sizes as they are
        run, under the published keys."""
        return cls(
            vocab_size=config["vocab_rows_held"],
            hidden_size=config["hidden_size"],
            num_layers=config["num_hidden_layers"],
            num_heads=config["num_attention_heads"],
            kv_lora_rank=config["kv_lora_rank"],
            qk_nope_head_dim=config["qk_nope_head_dim"],
            qk_rope_head_dim=config["qk_rope_head_dim"],
            v_head_dim=config["v_head_dim"],
            intermediate_size=config["intermediate_size"],
            moe_intermediate_size=config["moe_intermediate_size"],
            first_k_dense_replace=config["first_k_dense_replace"],
            num_experts=config["n_routed_experts"],
            num_experts_per_tok=config["num_experts_per_tok"],
            n_shared_experts=config["n_shared_experts"],
            num_experts_held=config["num_experts_held"],
            expert_offset=config.get("expert_offset", 0),
            routed_scaling_factor=config["routed_scaling_factor"],
            norm_eps=config["rms_norm_eps"],
            rope_theta=config["rope_theta"])

    def block_params(self, block: int) -> dict[str, int]:
        """Parameters of one block by part (for sizes and FLOP counts)."""
        e, h, r = self.hidden_size, self.num_heads, self.kv_lora_rank
        dn, dr, dv = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                      self.v_head_dim)
        attn = (e * h * (dn + dr) + e * (r + dr) + r + r * h * (dn + dv)
                + h * dv * e)
        if block < self.first_k_dense_replace:
            ff, shared, router = 3 * e * self.intermediate_size, 0, 0
        else:
            ff = self.num_experts_held * 3 * e * self.moe_intermediate_size
            shared = 3 * e * self.shared_intermediate_size
            router = e * self.num_experts + self.num_experts
        return {"attention": attn, "ff": ff, "shared": shared,
                "router": router, "norms": 2 * e}

    def num_params(self) -> int:
        blocks = sum(sum(self.block_params(b).values())
                     for b in range(self.num_layers))
        return (2 * self.vocab_size * self.hidden_size + self.hidden_size
                + blocks)


# --------------------------------------------------------------------- #
# weights from a seed, in the program's layout                           #
# --------------------------------------------------------------------- #

def _block(key, c: RefConfig, block: int):
    ks = jax.random.split(key, 16)
    f32 = jnp.float32
    std = c.initializer_range
    res_std = std / (2 * c.num_layers) ** 0.5
    e, h, r = c.hidden_size, c.num_heads, c.kv_lora_rank
    dn, dr, dv = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
    normal = lambda k, shape, s: jax.random.normal(k, shape, f32) * s
    swiglu = lambda k1, k3, k2, lead, f: {
        "w1": normal(k1, (*lead, e, f), std),
        "w3": normal(k3, (*lead, e, f), std),
        "w2": normal(k2, (*lead, f, e), res_std)}
    p = {"ln_op": {"scale": jnp.ones((e,), f32)},
         "ln_ff": {"scale": jnp.ones((e,), f32)},
         "attn": {"wq": normal(ks[0], (e, h, dn + dr), std),
                  "wkv_a": normal(ks[1], (e, r + dr), std),
                  "kv_norm": jnp.ones((r,), f32),
                  "wkv_b": normal(ks[2], (r, h, dn + dv), std),
                  "wo": normal(ks[3], (h, dv, e), res_std)}}
    if block < c.first_k_dense_replace:
        p["ff"] = swiglu(ks[4], ks[5], ks[6], (), c.intermediate_size)
    else:
        p["ff"] = {
            "router": normal(ks[7], (e, c.num_experts), std),
            "expert_bias": normal(ks[8], (c.num_experts,),
                                  c.expert_bias_range),
            **swiglu(ks[9], ks[10], ks[11], (c.num_experts_held,),
                     c.moe_intermediate_size),
            "shared": swiglu(ks[12], ks[13], ks[14], (),
                             c.shared_intermediate_size)}
    return p


def _balance(params, key, c: RefConfig, balance_tokens):
    """Replace every routed block's seeded bias by the one that balances
    its router on seeded uniform token ids, block after block (a block's
    input depends on the routing before it)."""
    tokens = jax.random.randint(key, balance_tokens, 0, c.vocab_size)
    x = params["embed"]["wte"][tokens]
    for block, p in enumerate(params["blocks"]):
        if block in c.routed_blocks:
            h = _rms_norm(_attention_half(p, x, c, "highest"),
                          p["ln_ff"]["scale"], c.norm_eps)
            scores = jax.nn.sigmoid(
                _contract("bse,en->bsn", h, p["ff"]["router"], "highest"))
            p["ff"]["expert_bias"] = balanced_bias(
                scores.reshape(-1, c.num_experts), c.num_experts_per_tok)
        x, _ = _block_forward(p, x, c, block, "highest", None)
    return params


def init_params(seed: int, c: RefConfig, balance_tokens=BALANCE_TOKENS):
    """Seeded float32 weights, made on the device in ONE jitted call:
    {"embed": {wte}, "blocks": [per-block trees], "head": {ln_f, w}}, each
    tree in the layout of `oobleck_tpu/models/deepseek_v3.py`'s layer.

    The selection bias is what its own rule would have made of it:
    balanced, for the seed's router, on `balance_tokens` (sequences, length)
    uniform token ids (`reference/lfm2.py::init_params` says why)."""

    @jax.jit
    def make(key):
        k_e, k_b, k_h, k_t = jax.random.split(key, 4)
        e, v = c.hidden_size, c.vocab_size
        keys = jax.random.split(k_b, c.num_layers)
        params = {
            "embed": {"wte": jax.random.normal(k_e, (v, e), jnp.float32)
                      * c.initializer_range},
            "blocks": [_block(keys[i], c, i) for i in range(c.num_layers)],
            "head": {"ln_f": {"scale": jnp.ones((e,), jnp.float32)},
                     "w": jax.random.normal(k_h, (e, v), jnp.float32)
                     * c.initializer_range},
        }
        return _balance(params, k_t, c, tuple(balance_tokens))

    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return make(key)


# --------------------------------------------------------------------- #
# arithmetic                                                             #
# --------------------------------------------------------------------- #

def _rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * scale


def _rope(x, theta):
    """Rotate-half rotary embedding over the whole last dimension.
    x [..., S, D], positions 0..S-1."""
    d, s = x.shape[-1], x.shape[-2]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    half = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
    return x * cos + half * sin


def attend(q, k, v, mode: str, h_block: int = H_BLOCK,
           q_block: int = Q_BLOCK):
    """One sequence. q, k [H, S, D], v [H, S, Dv] -> [H, S, Dv]: causal
    softmax attention, `h_block` heads and `q_block` queries at a time
    (the whole of either where the block does not divide it)."""
    h, s, d = q.shape
    hb = h_block if h % h_block == 0 else h
    bq = q_block if s % q_block == 0 else s
    nh, nq = h // hb, s // bq
    qg = q.reshape(nh, hb, nq, bq, d)
    kg, vg = k.reshape(nh, hb, s, d), v.reshape(nh, hb, s, -1)

    @jax.checkpoint
    def block(g, b):
        i = b * bq + jnp.arange(bq)[:, None]               # query position
        j = jnp.arange(s)[None, :]                         # key position
        scores = _contract("hqd,hkd->hqk", qg[g, :, b], kg[g], mode) * d ** -0.5
        probs = jax.nn.softmax(jnp.where(j <= i, scores, NEG_INF), -1)
        return _contract("hqk,hkd->hqd", probs, vg[g], mode)

    out = lax.map(lambda g: lax.map(lambda b: block(g, b), jnp.arange(nq)),
                  jnp.arange(nh))                          # [nh, nq, hb, bq, Dv]
    return out.transpose(0, 2, 1, 3, 4).reshape(h, s, -1)


def _attention(p, h, c: RefConfig, mode: str):
    dn, r = c.qk_nope_head_dim, c.kv_lora_rank
    q = _contract("bse,ehd->bhsd", h, p["wq"], mode)
    kv_a = _contract("bse,ed->bsd", h, p["wkv_a"], mode)
    latent = _rms_norm(kv_a[..., :r], p["kv_norm"], c.latent_norm_eps)
    kv = _contract("bsr,rhd->bhsd", latent, p["wkv_b"], mode)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], c.rope_theta)], -1)
    k_rope = _rope(kv_a[..., r:], c.rope_theta)            # [B, S, Dr]
    k = jnp.concatenate(
        [kv[..., :dn],
         jnp.broadcast_to(k_rope[:, None], (*kv.shape[:3], k_rope.shape[-1]))],
        -1)
    a = jnp.stack([attend(q[b], k[b], kv[b, ..., dn:], mode)
                   for b in range(q.shape[0])])            # [B, H, S, Dv]
    return _contract("bhsd,hde->bse", a, p["wo"], mode)


def _swiglu(p, h, mode: str):
    return _contract(
        "bsf,fe->bse",
        jax.nn.silu(_contract("bse,ef->bsf", h, p["w1"], mode))
        * _contract("bse,ef->bsf", h, p["w3"], mode), p["w2"], mode)


def _routed(p, h, c: RefConfig, mode: str, forced):
    """h [B, S, E] -> (held experts' part + shared experts [B, S, E], own
    choice [B, S, k]). `forced` [B, S, k] replaces the selection."""
    scores = jax.nn.sigmoid(_contract("bse,en->bsn", h, p["router"], mode))
    _, own = lax.top_k(lax.stop_gradient(scores + p["expert_bias"]),
                       c.num_experts_per_tok)
    chosen = own if forced is None else forced
    picked = jnp.sum(jax.nn.one_hot(chosen, c.num_experts, dtype=scores.dtype),
                     axis=-2)                              # [B, S, NE] 0/1
    w = picked * scores
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6) * c.routed_scaling_factor
    w = w[..., c.expert_offset:c.expert_offset + c.num_experts_held]
    gate = _contract("bse,xef->xbsf", h, p["w1"], mode)
    up = _contract("bse,xef->xbsf", h, p["w3"], mode)
    out = _contract("xbsf,xfe->xbse", jax.nn.silu(gate) * up, p["w2"], mode)
    routed = jnp.einsum("xbse,bsx->bse", out, w,
                        precision=lax.Precision.HIGHEST)
    return routed + _swiglu(p["shared"], h, mode), own


def _attention_half(p, x, c: RefConfig, mode: str):
    return x + _attention(
        p["attn"], _rms_norm(x, p["ln_op"]["scale"], c.norm_eps), c, mode)


def _block_forward(p, x, c: RefConfig, block: int, mode: str, forced):
    x = _attention_half(p, x, c, mode)
    h = _rms_norm(x, p["ln_ff"]["scale"], c.norm_eps)
    if block < c.first_k_dense_replace:
        return x + _swiglu(p["ff"], h, mode), None
    y, own = _routed(p["ff"], h, c, mode, forced)
    return x + y, own


def forward(params, tokens, c: RefConfig, mode: str = "highest",
            forced=None):
    """tokens [B, S] -> (logits [B, S, vocab] float32, own), `own` the
    experts this file would choose in every routed block, a list of
    [B, S, k] in `routed_blocks` order; `forced`, a list like it, replaces
    the selection."""
    x = params["embed"]["wte"][tokens]
    own = []
    for block, p in enumerate(params["blocks"]):
        routed_index = block - c.first_k_dense_replace
        f = (forced[routed_index]
             if forced is not None and routed_index >= 0 else None)
        x, chose = jax.checkpoint(
            functools.partial(_block_forward, c=c, block=block, mode=mode)
        )(p, x, forced=f)
        if chose is not None:
            own.append(chose)
    x = _rms_norm(x, params["head"]["ln_f"]["scale"], c.norm_eps)
    return _contract("bse,ev->bsv", x, params["head"]["w"], mode), own


def loss(params, tokens, c: RefConfig, mode: str = "highest", forced=None):
    """(mean next-token cross entropy, own choices)."""
    logits, own = forward(params, tokens, c, mode, forced)
    logits = logits[:, :-1]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(logz - gold), own


def loss_and_grads(params, tokens, c: RefConfig, mode: str = "highest",
                   forced=None):
    """((loss, own choices), gradients of every parameter; the selection
    bias's is zero: it selects and is not trained)."""
    return jax.value_and_grad(
        functools.partial(loss, c=c, mode=mode, forced=forced),
        has_aux=True)(params, tokens)
