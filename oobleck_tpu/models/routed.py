"""What the decoders with routed experts share (`models/lfm2.py`,
`models/deepseek_v3.py`, `models/nemotron_h.py`, `models/qwen3_next.py`,
`models/smallthinker.py`): a
layer list whose blocks DIFFER, one chip's share of an expert-parallel
deployment as a model of its own, and the probe that reads where a sequence
was routed.

A block is one or two residual branches, each behind an RMSNorm `N` of its
own: `x + Op(N(x))` (`OP`) and `x + FF(N(x))` (`FF`). A family says which
a block has (`branches`: both, in that order, unless it says otherwise),
what `Op` is (`operator_out`), which blocks are routed (`is_routed`), what
its router scores with (`router_score`), what its router READS
(`router_reads`: `FF`'s normed input as the experts do, or `OP`'s, a router
placed before the block's operator), its gated experts' activation
(`expert_activation`), what its norm is (`norm`: the
plain RMSNorm unless it says otherwise) and what stands between a branch's
output and the residual sum (`branch_out`: nothing, unless it says
otherwise; `models/ouro.py` norms the output too, a sandwich); the feed-forwards (SwiGLU, or
`W2 relu(W1 h)^2` for an entry without `w3`), the routed call
(`ops/moe.routed_experts`), the shared expert added to the routed sum
(times `sigmoid(h w_g)` where the entry has a gate `w_g`), the embedding
and the untied head over the vocabulary rows held, the loss and the
chaining of layers are here.

The share: `num_experts_held` experts from `expert_offset` (the router still
scores all `num_experts`; the layer gives the part its held experts give),
and the first `vocab_rows_held` rows of the vocabulary (embedding and head;
token ids, logits and the loss are over those rows, and the engine draws
its data from them: `data_vocab_size`). Nothing stands in for the absent
chips.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from oobleck_tpu.models.gpt import cross_entropy_loss
from oobleck_tpu.models.llama import _rms_norm as rms_norm


class HeldShare:
    """Properties of a config dataclass with `vocab_size`,
    `vocab_rows_held`, `vocab_pad_multiple`, `num_experts`,
    `num_experts_held`, `expert_offset`, `intermediate_size`."""

    @property
    def data_vocab_size(self) -> int:
        """Rows of the vocabulary this model holds: what token ids range
        over (execution/engine.py draws its data from it)."""
        return (self.vocab_size if self.vocab_rows_held is None
                else self.vocab_rows_held)

    @property
    def padded_vocab_size(self) -> int:
        m = self.vocab_pad_multiple
        return (self.data_vocab_size + m - 1) // m * m

    @property
    def ffn_dim(self) -> int:
        return self.intermediate_size

    @property
    def experts_held(self) -> int:
        return (self.num_experts if self.num_experts_held is None
                else self.num_experts_held)

    def check_share(self) -> None:
        if not 0 < self.data_vocab_size <= self.vocab_size:
            raise ValueError(
                f"vocab_rows_held {self.vocab_rows_held} of {self.vocab_size}")
        if self.expert_offset + self.experts_held > self.num_experts:
            raise ValueError(
                f"experts {self.expert_offset}..+{self.experts_held} of "
                f"{self.num_experts}")


# --------------------------------------------------------------------- #
# rotary embedding, rotate-half form                                     #
# --------------------------------------------------------------------- #

def _half_turn(d: int, dtype) -> jax.Array:
    """The signed permutation `P` with `x @ P = [-x2, x1]`: `P[j + d/2, j]
    = -1`, `P[j, j + d/2] = +1`. From an iota, so a lowered module holds no
    [d, d] literal; the compiler folds it."""
    j = jnp.arange(d)
    col_less_row = j[None, :] - j[:, None]
    return ((col_less_row == d // 2).astype(dtype)
            - (col_less_row == -(d // 2)).astype(dtype))


def _rotate(x: jax.Array, theta: float, sign: float) -> jax.Array:
    """`x cos + [-x2, x1] (sign sin)`, products and sum in float32, one
    rounding to x's dtype. `[-x2, x1]` is `x @ P`: every output is ONE
    operand element, plus or minus, beside zeros, so the product is exact
    (float32 operands at HIGHEST: the default would round them to
    bfloat16), and XLA makes the whole rotation one pass that reads x and
    writes the result: no float32 or half-width copy of x goes to HBM."""
    d, s = x.shape[-1], x.shape[-2]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)       # [S, D]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)
    rotated = jnp.einsum(
        "...d,de->...e", x, _half_turn(d, x.dtype),
        preferred_element_type=jnp.float32,
        precision=(jax.lax.Precision.HIGHEST if x.dtype.itemsize > 2
                   else None))
    return (x.astype(jnp.float32) * cos + rotated * (sign * sin)).astype(
        x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _rotary(x: jax.Array, theta: float) -> jax.Array:
    return _rotate(x, theta, 1.0)


# The transpose is the rotation by the negated angle, in the same one pass
# (what autodiff would build multiplies the float32 cotangent by sin BEFORE
# the permutation: a float32 operand the size of x).
_rotary.defvjp(lambda x, theta: (_rotate(x, theta, 1.0), None),
               lambda theta, _, g: (_rotate(g, theta, -1.0),))


def rotate_half(x: jax.Array, theta: float) -> jax.Array:
    """Rotate-half rotary embedding at positions 0..S-1 over the whole last
    dimension. x: [..., S, D]. `oobleck_rotary_calls_total{width}` counts
    the rotations built into traced programs (not once a step)."""
    from oobleck_tpu.utils import metrics

    metrics.registry().counter(
        "oobleck_rotary_calls_total",
        "Rotary embeddings built into traced programs, by the width "
        "rotated").inc(width=str(x.shape[-1]))
    return _rotary(x, theta)


def short_conv(bu: jax.Array, taps: jax.Array) -> jax.Array:
    """Depthwise causal convolution over the sequence, as shifted
    multiply-adds: z_t = sum_j taps[j] * bu_{t-j}. bu [B, S, D], taps
    [L, D]."""
    s = bu.shape[1]
    z = bu * taps[0]
    for j in range(1, taps.shape[0]):
        shifted = jnp.pad(bu, ((0, 0), (j, 0), (0, 0)))[:, :s]
        z = z + shifted * taps[j]
    return z


# --------------------------------------------------------------------- #
# the layer list                                                         #
# --------------------------------------------------------------------- #

# A block's residual branches, by the norm and the parameters they read:
# `ln_op` and the operator's, `ln_ff` and `ff`.
OP, FF = "op", "ff"


class RoutedShareModel:
    """Layer list [embed, block_0 .. block_{L-1}, head], as every family's,
    so the planner profiles and the MPMD pipeline splits it unchanged;
    generic stage path only (no manual-collective contract)."""

    data_kind = "causal_lm"
    fused_supported = False
    # What the family's router scores with (`ops/moe.route`).
    router_score = "sigmoid"
    # The branch whose normed input the router scores: `FF`'s, the rows
    # the experts read, or `OP`'s, the operator's input (`apply_block`).
    router_reads = FF
    # The gated experts' activation (`ops/moe.routed_experts`).
    expert_activation = "swiglu"

    def __init__(self, config):
        self.config = config

    # ---- what a family says ----

    def is_routed(self, block: int) -> bool:
        raise NotImplementedError

    def operator_out(self, block: int, p, h):
        """Op(h) of block `block`, `p` the block's parameters."""
        raise NotImplementedError

    def branches(self, block: int) -> tuple[str, ...]:
        """The residual branches of block `block`, in order."""
        return (OP, FF)

    def norm(self, x, scale):
        """The RMSNorm in front of every branch and of the head."""
        return rms_norm(x, scale, self.config.norm_eps)

    def branch_out(self, branch: str, p, out):
        """What branch `branch` (`OP` or `FF`) of a block with parameters
        `p` adds to the residual stream, given the branch's output."""
        return out

    def _init_block(self, rng, block: int):
        raise NotImplementedError

    # ---- layer list ----

    @property
    def num_pipeline_layers(self) -> int:
        return self.config.num_layers + 2

    def layer_name(self, index: int) -> str:
        if index == 0:
            return "embed"
        if index == self.num_pipeline_layers - 1:
            return "head"
        return f"block_{index - 1}"

    @property
    def routed_blocks(self) -> tuple[int, ...]:
        return tuple(b for b in range(self.config.num_layers)
                     if self.is_routed(b))

    def init_layer(self, rng: jax.Array, index: int):
        ks = jax.random.split(rng, 3)
        if index == 0:
            return self._init_embed(ks[0])
        if index == self.num_pipeline_layers - 1:
            return self._init_head(ks[2])
        return self._init_block(jax.random.fold_in(ks[1], index), index - 1)

    def apply_layer(self, index: int, params, carry, batch, ctx=None,
                    grad_sums=None, return_load: bool = False):
        """`return_load` (a layer of `load_layers` only): (the carry, the
        routed call's load, `ops/moe.load_of`)."""
        if index == 0:
            return self.embed(params, batch["input_ids"])
        if index == self.num_pipeline_layers - 1:
            return self.head(params, carry)
        return self.apply_block(index - 1, params, carry,
                                grad_sums=grad_sums, return_load=return_load)

    def load_layers(self, num_tokens: int) -> dict[int, tuple[str, int]]:
        """The layers that can hand out their load (`apply_layer(...,
        return_load=True)`), the routed blocks: {layer index: (the label
        the block's counters carry, the rows of one of its row tiles at
        `num_tokens` tokens a call)}. The tile's rows are static, so they
        travel here and not in the load."""
        from oobleck_tpu.ops import moe

        c = self.config
        tile = moe.buffer_rows(num_tokens, c.num_experts_per_tok,
                               c.experts_held, c.num_experts)[1]
        return {b + 1: (str(b), tile) for b in self.routed_blocks}

    def sums_in_kernel(self, index: int, params):
        """Which leaves' running gradient sums layer `index` takes down
        into the dW kernel (`ops/moe.GradSum`), as a tree of booleans over
        `params` (any tree of the layer's structure), or None for none:
        the held experts' matrices of a routed block, where the expert
        kernels run. They go from the parameter tree into the grouped
        products with nothing between (`routed_ff`), so such a leaf's
        cotangent comes back as sum + gradient and `apply_layer` wants the
        sums of the marked leaves in `grad_sums`."""
        from oobleck_tpu.ops import kernel

        block = index - 1
        if not (0 <= block < self.config.num_layers and self.is_routed(block)
                and kernel.on_tpu()):
            return None
        marks = jax.tree.map(lambda _: False, params)
        marks["ff"].update(
            {w: True for w in ("w1", "w3", "w2") if w in params["ff"]})
        return marks

    @jax.named_scope("lm_head")
    def loss_from_logits(self, logits, batch):
        return cross_entropy_loss(logits, batch["input_ids"],
                                  self.config.data_vocab_size)

    def sample_batch(self, batch_size: int, seq_len: int):
        tokens = jax.random.randint(
            jax.random.PRNGKey(0), (batch_size, seq_len), 0,
            self.config.data_vocab_size, dtype=jnp.int32)
        return {"input_ids": tokens}

    # ---- init ----

    def _init_embed(self, rng):
        c = self.config
        return {"wte": jax.random.normal(
            rng, (c.padded_vocab_size, c.hidden_size), c.param_dtype
        ) * c.initializer_range}

    def _init_head(self, rng):
        c = self.config
        return {
            "ln_f": {"scale": jnp.ones((c.hidden_size,), c.param_dtype)},
            "w": jax.random.normal(
                rng, (c.hidden_size, c.padded_vocab_size), c.param_dtype
            ) * c.initializer_range,
        }

    # ---- forward ----

    @jax.named_scope("embed")
    def embed(self, p, tokens):
        return p["wte"][tokens].astype(self.config.dtype)

    def dense_ff(self, p, h):
        """SwiGLU, W2 (silu(W1 h) * W3 h); an entry without `w3` has no
        gate: W2 relu(W1 h)^2."""
        dt = self.config.dtype
        if "w3" in p:
            g = jax.nn.silu(h @ p["w1"].astype(dt)) * (h @ p["w3"].astype(dt))
        else:
            g = jnp.square(jax.nn.relu(
                (h @ p["w1"].astype(dt)).astype(jnp.float32))).astype(dt)
        return g @ p["w2"].astype(dt)

    def routed_ff(self, p, h, *, forced_experts=None,
                  return_routing: bool = False, grad_sums=None,
                  router_in=None, return_load: bool = False):
        """The part of the routed layer that the experts held here give.
        With `return_routing` and / or `return_load`, a tuple: that, the
        chosen experts [B, S, k], the call's load (`ops/moe.load_of`).
        `grad_sums`: `p`'s tree with the experts' running gradient sums
        (`sums_in_kernel`). `router_in` [B, S, E]: what the router scores
        where that is not `h`."""
        from oobleck_tpu.ops.moe import routed_experts

        c = self.config
        b, s, e = h.shape
        sums = {} if grad_sums is None else grad_sums
        out = routed_experts(
            h.reshape(b * s, e), p["router"], p.get("expert_bias"),
            p["w1"], p.get("w3"), p["w2"],
            num_experts=c.num_experts, top_k=c.num_experts_per_tok,
            expert_offset=c.expert_offset, norm_topk_prob=c.norm_topk_prob,
            routed_scaling_factor=c.routed_scaling_factor,
            forced_experts=(None if forced_experts is None
                            else forced_experts.reshape(b * s, -1)),
            return_routing=return_routing,
            dw_sums=tuple(sums.get(w) for w in ("w1", "w3", "w2")),
            score=self.router_score, activation=self.expert_activation,
            router_x=(None if router_in is None
                      else router_in.reshape(b * s, e)),
            return_load=return_load)
        if not (return_routing or return_load):
            return out.reshape(b, s, e)
        y, *extras = out
        if return_routing:
            extras[0] = extras[0].reshape(b, s, -1)
        return (y.reshape(b, s, e), *extras)

    @jax.named_scope("mlp")
    def feed_forward(self, block: int, p, h, *, forced_experts=None,
                     return_routing: bool = False, grad_sums=None,
                     router_in=None, return_load: bool = False):
        """The dense feed-forward or the routed experts, by the block: of
        a routed block the part its held experts give, plus, where the
        entry has them, the `shared` experts (on every token, weight 1:
        every chip of an expert-parallel group computes them alike, so the
        parts the shares give add up to the layer with them counted once).
        With `return_routing` a routed block also returns its chosen
        experts [B, S, k], with `return_load` its load, in that order."""
        if not self.is_routed(block):
            return self.dense_ff(p, h)
        shared = self.dense_ff(p["shared"], h) if "shared" in p else None
        if shared is not None and "w_g" in p["shared"]:
            # A shared expert behind a gate of its own, sigmoid(h w_g).
            shared = shared * jax.nn.sigmoid(jnp.einsum(
                "bse,e->bs", h, p["shared"]["w_g"].astype(h.dtype),
                preferred_element_type=jnp.float32))[..., None].astype(h.dtype)
        out = self.routed_ff(p, h, forced_experts=forced_experts,
                             return_routing=return_routing,
                             grad_sums=grad_sums, router_in=router_in,
                             return_load=return_load)
        if shared is None:
            return out
        if return_routing or return_load:
            return (out[0] + shared, *out[1:])
        return out + shared

    def apply_block(self, block: int, p, x, *, forced_experts=None,
                    return_routing: bool = False, grad_sums=None,
                    return_load: bool = False):
        """A routed block asked for them returns (x, the chosen experts,
        its load): those asked for, in that order."""
        extras = (return_routing or return_load) and self.is_routed(block)
        router_in, rest = None, []
        for branch in self.branches(block):
            if branch == OP:
                h = self.norm(x, p["ln_op"]["scale"])
                if self.router_reads == OP:
                    router_in = h
                x = x + self.branch_out(OP, p, self.operator_out(block, p, h))
                continue
            h = self.norm(x, p["ln_ff"]["scale"])
            out = self.feed_forward(
                block, p["ff"], h, forced_experts=forced_experts,
                return_routing=return_routing, router_in=router_in,
                grad_sums=None if grad_sums is None else grad_sums["ff"],
                return_load=return_load)
            if extras:
                out, *rest = out
            x = x + self.branch_out(FF, p, out)
        return (x, *rest) if extras else x

    @jax.named_scope("lm_head")
    def head(self, p, x):
        c = self.config
        x = self.norm(x, p["ln_f"]["scale"])
        return (x @ p["w"].astype(c.dtype)).astype(jnp.float32)

    # Forward for one device: chain the layers as the pipeline does.
    def forward(self, params_list, tokens, *, return_routing: bool = False):
        """Logits [B, S, padded vocab]; with `return_routing` also the
        experts every routed block chose, one [B, S, k] per block in
        `routed_blocks` order."""
        x = self.embed(params_list[0], tokens)
        routing = []
        for block in range(self.config.num_layers):
            p = params_list[block + 1]
            if return_routing and self.is_routed(block):
                x, experts = self.apply_block(block, p, x,
                                              return_routing=True)
                routing.append(experts)
            else:
                x = self.apply_block(block, p, x)
        logits = self.head(params_list[-1], x)
        return (logits, routing) if return_routing else logits

    def loss(self, params_list, batch):
        return self.loss_from_logits(
            self.forward(params_list, batch["input_ids"]), batch)


def routing_probe(model: RoutedShareModel, params_list, tokens):
    """The experts every routed block chooses for `tokens` [B, S], read out
    on demand: one jitted forward chaining the model's own layers on the
    given per-layer parameters. Returns a list of host arrays [B, S, k] in
    `routed_blocks` order, and counts what it saw: the probed tokens and,
    per block, the (token, slot) pairs whose expert is held here.

    On demand: which expert each token chose, for a comparison that needs
    the choices themselves (the benchmark's reference is handed them).
    HOW MANY rows each held expert got, and the row tiles they filled, a
    training step says itself, every step: the routed layers' loads ride
    out of the pipeline's backward programs beside the loss
    (`apply_layer(return_load=True)`, `execution/pipeline.py`) into the
    telemetry ring (`obs/telemetry.TelemetryRing.loads`)."""
    from oobleck_tpu.obs import spans
    from oobleck_tpu.utils import metrics

    c = model.config
    reg = metrics.registry()
    pairs = reg.counter(
        "oobleck_moe_routed_pairs_total",
        "(token, slot) pairs a routing probe saw routed to experts held "
        "here, by routed block")
    probed = reg.counter(
        "oobleck_moe_probed_tokens_total",
        "Tokens a routing probe read the routing of")
    held_rows = reg.gauge(
        "oobleck_moe_held_rows",
        "(token, slot) pairs of the LAST probed tokens routed to experts "
        "held here, by routed block")
    with spans.span("moe.routing_probe"):
        probe = _probe_program(model)
        routing = [np.asarray(r)  # oobleck: allow[OBL002] -- on-demand probe
                   for r in probe(tuple(params_list), tokens)]
    probed.inc(int(routing[0].shape[0] * routing[0].shape[1]) if routing
               else 0)
    for block, chosen in zip(model.routed_blocks, routing):
        local = chosen - c.expert_offset
        held = int(((local >= 0) & (local < c.experts_held)).sum())
        pairs.inc(held, layer=str(block))
        held_rows.set(held, layer=str(block))
    return routing


def _probe_program(model: RoutedShareModel):
    fn = getattr(model, "_routing_probe_fn", None)
    if fn is None:
        def routing_probe_forward(params_list, tokens):
            return model.forward(list(params_list), tokens,
                                 return_routing=True)[1]

        fn = model._routing_probe_fn = jax.jit(routing_probe_forward)
    return fn
