"""Plain training steps for the GPT family's cells: which rows of the corpus
a step trains on, AdamW as the job states it, and the measures a training
cell's first steps are compared by. With `reference/gpt.py`'s loss and
gradients this follows a training job from its seed, step by step, in
float32 at HIGHEST, a block of rows at a time so that it fits beside
nothing else on one chip. It imports nothing from `oobleck_tpu` and takes
nothing the program has made.

The corpus and its order are COPIES of the program's
(`oobleck_tpu/execution/dataset.py::SyntheticTextDataset`: sample i is an
arithmetic progression mod the vocabulary with a tenth of its positions
replaced; `execution/dataloader.py::OobleckSampler`: step k of an epoch
takes rows [k * global_batch, (k + 1) * global_batch) of that epoch's
permutation of the rows that are not held out for evaluation; the cell's
file states the three numbers, `traffic.corpus`). A step's
loss and gradient are means over its rows, so their order inside a step
changes nothing. `tests/benchmarks/test_bench_hostloss.py` holds the copy
to what the program's loader hands its pipelines.

The optimizer is the job's, stated in the cell's file (`traffic.optimizer`;
`oobleck_tpu/parallel/train.py::make_optimizer` at `JobArguments`'
defaults): the gradient clipped to norm `clip_norm`, AdamW
(b1, b2, eps, decoupled weight decay on every leaf), learning rate
`learning_rate * min(1, (count + 1) / lr_warmup_steps)`. The program steps
each PIPELINE LAYER by itself (the embedding, each block, the head:
`execution/pipeline.py::apply_updates`), so the norm a gradient is clipped
by is its own layer's; `adamw_step` does the same, and the cell's file
states it (`optimizer.clip_norm_over`).
"""

from __future__ import annotations

import functools
import statistics

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import gpt as ref

# --------------------------------------------------------------------- #
# the corpus and its order                                               #
# --------------------------------------------------------------------- #

def corpus_row(seed: int, index: int, vocab: int, seq: int) -> np.ndarray:
    rng = np.random.default_rng(seed * 1_000_003 + index)
    start = rng.integers(0, vocab)
    stride = rng.integers(1, min(vocab, 17))
    ids = (start + stride * np.arange(seq)) % vocab
    noise = rng.random(seq) < 0.1
    ids = np.where(noise, rng.integers(0, vocab, seq), ids)
    return ids.astype(np.int32)


def step_rows(step: int, global_batch: int, corpus: dict) -> np.ndarray:
    """The corpus rows that training step `step` (1, 2, ...) takes, of a
    corpus of `rows` rows whose last `held_out_share` no step trains on and
    whose order in epoch e is the permutation of seed `order_seed` + e."""
    trained = corpus["rows"] - int(corpus["rows"] * corpus["held_out_share"])
    per_epoch = trained // global_batch
    epoch, k = divmod(step - 1, per_epoch)
    order = np.random.default_rng(
        corpus["order_seed"] + epoch).permutation(trained)
    return order[k * global_batch:(k + 1) * global_batch]


def step_tokens(seed: int, step: int, job: dict, vocab: int) -> np.ndarray:
    """int32 [global_batch, seq_len]: the rows of step `step`, in the order
    the pipelines take them (the first pipeline's microbatches first). The
    runner hands the engine the corpus of `seed % 2**31`."""
    return np.stack([
        corpus_row(seed % (1 << 31), int(i), vocab, job["seq_len"])
        for i in step_rows(step, job["global_batch"], job["corpus"])])


# --------------------------------------------------------------------- #
# AdamW                                                                  #
# --------------------------------------------------------------------- #

def by_layer(tree: dict) -> list:
    """A parameter tree as the program's pipeline layers: the embedding,
    each block, the head."""
    return [tree["embed"], *tree["blocks"], tree["head"]]


def from_layers(layers: list) -> dict:
    return {"embed": layers[0], "blocks": list(layers[1:-1]),
            "head": layers[-1]}


def _sq(tree):
    return sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(tree))


def clip(grads: dict, opt: dict) -> dict:
    """The gradient as AdamW gets it."""
    if opt["clip_norm_over"] != "pipeline_layer":
        raise ValueError(opt["clip_norm_over"])
    out = []
    for g in by_layer(grads):
        norm = jnp.sqrt(_sq(g))
        scale = jnp.where(norm < opt["clip_norm"], 1.0, opt["clip_norm"] / norm)
        out.append(jax.tree.map(lambda x: x * scale, g))
    return from_layers(out)


def learning_rate(count, job: dict):
    return job["learning_rate"] * jnp.minimum(
        1.0, (count + 1) / max(job["lr_warmup_steps"], 1))


@functools.partial(jax.jit, static_argnames=("job_key",), donate_argnums=(0, 2, 3))
def _adamw(params, grads, m, v, count, *, job_key):
    job, opt = dict(job_key[0]), dict(job_key[1])
    g = clip(grads, opt)
    t = count + 1
    m = jax.tree.map(lambda m, g: opt["b1"] * m + (1 - opt["b1"]) * g, m, g)
    v = jax.tree.map(lambda v, g: opt["b2"] * v + (1 - opt["b2"]) * g * g, v, g)
    lr = learning_rate(count, job)

    def step(p, m, v):
        m_hat = m / (1 - opt["b1"] ** t)
        v_hat = v / (1 - opt["b2"] ** t)
        return p - lr * (m_hat / (jnp.sqrt(v_hat) + opt["eps"])
                         + opt["weight_decay"] * p)

    return jax.tree.map(step, params, m, v), m, v


def adamw_step(params, grads, m, v, count: int, job: dict):
    """One step: (params, m, v) after it. `count` steps were taken before."""
    key = (tuple(sorted((k, job[k]) for k in
                        ("learning_rate", "lr_warmup_steps"))),
           tuple(sorted(job["optimizer"].items())))
    return _adamw(params, grads, m, v, jnp.asarray(count, jnp.float32),
                  job_key=key)


# --------------------------------------------------------------------- #
# a job followed from its seed                                           #
# --------------------------------------------------------------------- #

@functools.partial(jax.jit, static_argnames=("rc", "mode"),
                   donate_argnums=(0,))
def _accumulate(acc, loss_sum, params, tokens, share, *, rc, mode):
    loss, grads = ref.loss_and_grads(params, tokens, rc, mode)
    return (jax.tree.map(lambda a, g: a + share * g, acc, grads),
            loss_sum + share * loss)


def batch_loss_and_grads(params, tokens: np.ndarray, rc, mode: str,
                         rows_per_block: int, share: float | None = None):
    """Mean loss and gradient over `tokens`' rows, a block of rows at a
    time (`share`: each block's weight, 1 / blocks unless a fault says
    otherwise)."""
    blocks = len(tokens) // rows_per_block
    assert blocks * rows_per_block == len(tokens), (len(tokens), rows_per_block)
    share = 1.0 / blocks if share is None else share
    acc = jax.tree.map(jnp.zeros_like, params)
    loss = jnp.zeros((), jnp.float32)
    for b in range(blocks):
        rows = tokens[b * rows_per_block:(b + 1) * rows_per_block]
        acc, loss = _accumulate(acc, loss, params, jnp.asarray(rows),
                                jnp.float32(share), rc=rc, mode=mode)
    return loss, acc


# What a data-parallel step can get wrong, as (the share of a step's rows,
# from the first, that reach the optimizer; the weight their mean gets).
FAULTS = {
    # Half of the batch left out, the mean taken over the rest.
    "half_batch_left_out": (0.5, 1.0),
    # The gradient sum between the pipelines left out: a pipeline steps on
    # its own half, each microbatch weighed by the whole step's count.
    "exchange_left_out": (0.5, 0.5),
}


def follow(params, tokens_of_step, steps: int, rc, job: dict,
           mode: str = "highest", rows_per_block: int = 1,
           fault: str | None = None):
    """The job from `params` (consumed) through its first `steps` steps.
    After each step yields `(step, loss, params, m)`: the step's loss
    (before its update), the parameters after it and AdamW's first moment,
    which after step 1 is (1 - b1) times the first gradient as AdamW got
    it. What is yielded is the next step's to consume: read it, or copy it,
    before asking for the next. With a `fault` of `FAULTS` this is the
    reference in the place of a program that has it."""
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    rows_share, weight = FAULTS[fault] if fault else (1.0, 1.0)
    for step in range(1, steps + 1):
        tokens = tokens_of_step(step)
        tokens = tokens[:int(len(tokens) * rows_share)]
        loss, grads = batch_loss_and_grads(
            params, tokens, rc, mode, rows_per_block,
            weight * rows_per_block / len(tokens))
        loss = loss / weight
        params, m, v = adamw_step(params, grads, m, v, step - 1, job)
        del grads
        yield step, float(loss), params, m


# --------------------------------------------------------------------- #
# the measures                                                           #
# --------------------------------------------------------------------- #

def _compared_leaves(tree):
    """`tree` with the attention's stacked projections apart: the query's,
    the key's and the value's weights and biases are leaves of their own
    (`reference/gpt.py` keeps them as `wqkv` [E, 3, H, D] and `bqkv`
    [3, H, D]). A key's bias has no gradient under softmax, and moves under
    Adam by round-off alone: inside one leaf with the query's and the
    value's it could be neither compared nor left out."""
    def apart(path, x):
        name = getattr(path[-1], "key", None)
        if name == "bqkv":
            return {"q": x[0], "k": x[1], "v": x[2]}
        if name == "wqkv":
            return {"q": x[:, 0], "k": x[:, 1], "v": x[:, 2]}
        return x

    return jax.tree_util.tree_map_with_path(apart, tree)


@jax.jit
def _leaf_norms(tree):
    return jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))),
        _compared_leaves(tree))


@jax.jit
def _leaf_change_norms(tree, start):
    return jax.tree.map(
        lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
            a.astype(jnp.float32) - b.astype(jnp.float32)))),
        _compared_leaves(tree), _compared_leaves(start))


def _named(layer: int, norms) -> dict[str, float]:
    flat, _ = jax.tree_util.tree_flatten_with_path(norms)
    return {f"{layer}{jax.tree_util.keystr(path)}": float(x)
            for path, x in flat}


def leaf_norms(layer: int, tree) -> dict[str, float]:
    """Each leaf's norm, by `<pipeline layer><path>`."""
    return _named(layer, _leaf_norms(tree))


def leaf_change_norms(layer: int, tree, start) -> dict[str, float]:
    """Each leaf's norm of `tree - start`."""
    return _named(layer, _leaf_change_norms(tree, start))


def tree_leaf_norms(tree: dict) -> dict[str, float]:
    out = {}
    for li, layer in enumerate(by_layer(tree)):
        out.update(leaf_norms(li, layer))
    return out


def tree_leaf_change_norms(tree: dict, start: dict) -> dict[str, float]:
    out = {}
    for li, (a, b) in enumerate(zip(by_layer(tree), by_layer(start))):
        out.update(leaf_change_norms(li, a, b))
    return out


def worst_norm_gap(got: dict[str, float], want: dict[str, float],
                   leaves=None) -> tuple[float, str]:
    """Over the leaves (all of `want`, or `leaves`): the gap between the
    program's norm and the reference's, against the reference's norm of
    that leaf or of the median leaf, whichever is larger. A leaf the
    program lacks reads 1."""
    leaves = sorted(want) if leaves is None else sorted(leaves)
    floor = statistics.median(want[k] for k in leaves)
    worst, at = 0.0, ""
    for k in leaves:
        gap = abs(got.get(k, 0.0) - want[k]) / max(want[k], floor, 1e-30)
        if not np.isfinite(gap):
            gap = float("inf")
        if gap >= worst:
            worst, at = gap, k
    return worst, at


def moved_leaves(first_grad_norms: dict[str, float]) -> list[str]:
    """The leaves whose change is compared: those whose first gradient in
    the reference is not nought to rounding (a thousandth of the median
    leaf's and over). Under Adam the others move by round-off alone."""
    floor = 1e-3 * statistics.median(first_grad_norms.values())
    return [k for k, n in first_grad_norms.items() if n >= floor]


@jax.jit
def _sq_diff_and_sq(got, want):
    diff = jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), got, want)
    return _sq(diff), _sq(jax.tree.map(lambda x: x.astype(jnp.float32), want))


def rel_err(got_layers: list, want_layers: list, device) -> float:
    """|| got - want || / || want || over every leaf of every layer, a
    layer at a time, each pair brought to `device`."""
    diff = want = 0.0
    for g, w in zip(got_layers, want_layers):
        d, n = _sq_diff_and_sq(jax.device_put(g, device),
                               jax.device_put(w, device))
        diff, want = diff + float(d), want + float(n)
    return (diff / want) ** 0.5
