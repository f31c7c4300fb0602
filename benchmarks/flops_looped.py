"""Required FLOPs a trained token of a LOOPED model: blocks gone through
`passes` times over one set of weights, an exit (one head product) after
every pass (`ouro-2.6b`; Zhu et al. 2025, arXiv:2510.25741).

`flops.train_flops_per_token` counts 6 N over the parameters HELD and is
right where every parameter is applied once. Here a block's matrices are
applied `passes` times and the head's `passes` times (one exit a pass), so
the count is over the parameters APPLIED:

    6 x passes x (blocks x matrices of a block + vocabulary x hidden)
    + causal attention, 6 x hidden x seq a block VISIT (`flops.py`'s term:
      2 S E forward halved by causality, times 3 for forward + backward)

Not counted, as in `flops.py`: operations made again under remat (a
block's forward in the backward, an exit's logits in its backward), the
embedding's lookup, norms, the gate (hidden + 1 parameters), softmaxes.
Nothing here imports the program.
"""

from __future__ import annotations


def block_matrices(hidden: int, heads: int, kv_heads: int, head_dim: int,
                   intermediate: int) -> int:
    """Matrix parameters of one block: W_q, W_o at `heads`, W_k, W_v at
    `kv_heads`, the SwiGLU's three."""
    return hidden * (2 * heads + 2 * kv_heads) * head_dim + (
        3 * hidden * intermediate)


def applied_params(*, passes: int, blocks: int, hidden: int, heads: int,
                   kv_heads: int, head_dim: int, intermediate: int,
                   vocab: int) -> int:
    """Matrix parameters a token is multiplied through, once a use."""
    return passes * (blocks * block_matrices(
        hidden, heads, kv_heads, head_dim, intermediate) + vocab * hidden)


def train_flops_per_token(*, passes: int, blocks: int, hidden: int,
                          heads: int, kv_heads: int, head_dim: int,
                          intermediate: int, vocab: int, seq_len: int
                          ) -> float:
    matrices = 6.0 * applied_params(
        passes=passes, blocks=blocks, hidden=hidden, heads=heads,
        kv_heads=kv_heads, head_dim=head_dim, intermediate=intermediate,
        vocab=vocab)
    attention = 6.0 * passes * blocks * heads * head_dim * seq_len
    return matrices + attention


def from_config(config: dict, seq_len: int) -> float:
    """Of a configuration file under `configs/`, under the published
    keys. A configuration without them: KeyError (the reader asks first)."""
    return train_flops_per_token(
        passes=config["total_ut_steps"], blocks=config["num_hidden_layers"],
        hidden=config["hidden_size"], heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        intermediate=config["intermediate_size"], vocab=config["vocab_size"],
        seq_len=seq_len)
