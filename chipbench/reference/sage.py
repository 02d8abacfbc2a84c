"""GraphSAGE with mean aggregation, its loss, gradients and Adam, as the
published description has them, in plain ``jax.numpy``.

Per layer ``h_i' = W_neigh . mean_{j in N(i)} h_j + b + W_self . h_i`` over
the sampled neighbours (Hamilton et al. 2017; PyG ``SAGEConv``), ReLU
between layers, log-softmax head, mean negative log-likelihood over the
seed nodes; Adam as Kingma & Ba state it. No kernels, no dense fanout
layout, no batching tricks: an edge list and a segment sum.

``param_dtype`` and ``compute_dtype`` select the precision. The reference
runs float32 throughout with ``highest`` matmul precision; the control runs
the same code in bfloat16.

What the harness calls of a model's plain side: ``layer_dims``,
``make_weights``, ``train``, ``leaf_norms`` and ``step_flops``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .graph import Block

__all__ = ["layer_dims", "make_weights", "step_flops", "loss_and_grads",
           "adam_init", "adam_update", "train", "leaf_norms"]


def layer_dims(cfg: dict) -> list[tuple[int, int]]:
    """(in, out) width of each SAGE layer, input layer first."""
    dims, d_in = [], int(cfg["feature_dim"])
    for i in range(int(cfg["layers"])):
        last = i == int(cfg["layers"]) - 1
        d_out = int(cfg["classes"] if last else cfg["hidden"])
        dims.append((d_in, d_out))
        d_in = d_out
    return dims


def make_weights(cfg: dict, rng: np.random.Generator) -> list[dict]:
    """Initial weights, one dict per layer (input layer first):
    ``w_neigh`` and ``w_self`` of shape (in, out) drawn N(0, 1/in), ``b``
    zero."""
    layers = []
    for d_in, d_out in layer_dims(cfg):
        std = 1.0 / np.sqrt(d_in)
        layers.append({
            "w_neigh": (rng.standard_normal((d_in, d_out), dtype=np.float32)
                        * np.float32(std)),
            "b": np.zeros((d_out,), np.float32),
            "w_self": (rng.standard_normal((d_in, d_out), dtype=np.float32)
                       * np.float32(std)),
        })
    return layers


def step_flops(counts: dict) -> float:
    """Forward and backward of the SAGE layers over the valid rows, nothing
    recomputed (``counts`` as ``work.py`` describes them). Layer ``i``
    (input layer first) has the targets of hop ``L-1-i``: two matmuls
    forward (neighbour mean and self), their two weight gradients, and
    their two input gradients except at the input layer, whose inputs are
    data; the mean adds one flop per edge and feature each way."""
    hops = counts["hops"][::-1]  # input layer first
    total = 0.0
    for i, ((d_in, d_out), hop) in enumerate(zip(counts["layer_dims"], hops)):
        matmul = 2.0 * hop["targets"] * d_in * d_out
        total += 2 * matmul                    # forward
        total += 2 * matmul                    # weight gradients
        total += 2 * matmul if i else 0.0      # input gradients
        total += 2.0 * hop["edges"] * d_in     # mean, forward and backward
    return total


def forward(weights, x, layers, compute_dtype):
    h = x.astype(compute_dtype)
    for i, (w, (src, dst, n_dst)) in enumerate(zip(weights, layers)):
        valid = src >= 0
        msg = jnp.where(valid[:, None], h[jnp.clip(src, 0)], 0)
        seg = jnp.where(valid, dst, n_dst)
        total = jax.ops.segment_sum(msg, seg, num_segments=n_dst + 1)[:n_dst]
        count = jax.ops.segment_sum(valid.astype(compute_dtype), seg,
                                    num_segments=n_dst + 1)[:n_dst]
        mean = total / jnp.maximum(count, 1)[:, None]
        h = (mean @ w["w_neigh"].astype(compute_dtype)
             + w["b"].astype(compute_dtype)
             + h[:n_dst] @ w["w_self"].astype(compute_dtype))
        if i != len(weights) - 1:
            h = jax.nn.relu(h)
    return jax.nn.log_softmax(h.astype(jnp.float32), axis=-1)


@functools.partial(jax.jit, static_argnames=("n_dsts", "compute_dtype"))
def _loss_and_grads(weights, x, srcs, dsts, labels, mask, n_dsts,
                    compute_dtype):
    layers = list(zip(srcs, dsts, n_dsts))

    def loss_fn(w):
        logp = forward(w, x, layers, compute_dtype)[:labels.shape[0]]
        picked = jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
        m = mask.astype(jnp.float32)
        return -(picked * m).sum() / jnp.maximum(m.sum(), 1.0)

    return jax.value_and_grad(loss_fn)(weights)


def loss_and_grads(weights, features, labels, block: Block,
                   compute_dtype=jnp.float32, seed_mask=None):
    """Loss and gradients of one block. ``features`` and ``labels`` are the
    harness's own device arrays; rows are gathered by ``n_id`` here.
    ``seed_mask`` (bool, per seed) leaves seeds out of the mean: a planted
    fault, never the reference."""
    n_id = jnp.asarray(block.n_id)
    x = jnp.where((n_id >= 0)[:, None], features[jnp.clip(n_id, 0)], 0)
    seeds = n_id[:block.num_seeds]
    mask = jnp.ones((block.num_seeds,), bool) if seed_mask is None \
        else jnp.asarray(seed_mask)
    srcs = tuple(jnp.asarray(s) for s, _, _ in block.layers)
    dsts = tuple(jnp.asarray(d) for _, d, _ in block.layers)
    n_dsts = tuple(int(n) for _, _, n in block.layers)
    with jax.default_matmul_precision("highest"):
        return _loss_and_grads(weights, x, srcs, dsts, labels[seeds], mask,
                               n_dsts, jnp.dtype(compute_dtype))


def adam_init(weights):
    zeros = jax.tree_util.tree_map(jnp.zeros_like, weights)
    return {"m": zeros, "v": zeros, "t": 0}


def adam_update(weights, grads, state, opt: dict):
    lr, b1, b2, eps = (opt[k] for k in ("lr", "b1", "b2", "eps"))
    t = state["t"] + 1
    tm = jax.tree_util.tree_map
    m = tm(lambda m_, g: b1 * m_ + (1 - b1) * g.astype(m_.dtype),
           state["m"], grads)
    v = tm(lambda v_, g: b2 * v_ + (1 - b2) * jnp.square(g.astype(v_.dtype)),
           state["v"], grads)
    new = tm(
        lambda w, m_, v_: w - (lr * (m_ / (1 - b1 ** t))
                               / (jnp.sqrt(v_ / (1 - b2 ** t)) + eps)
                               ).astype(w.dtype),
        weights, m, v,
    )
    return new, {"m": m, "v": v, "t": t}


def train(weights, features, labels, steps: list, opt: dict,
          param_dtype=jnp.float32, compute_dtype=jnp.float32,
          seed_mask=None, workers=None):
    """Follow ``steps`` (each a list of one Block per worker): the mean of
    the workers' losses and gradients, then Adam. Returns the losses, the
    first step's mean gradient and the weights after the last step.
    ``workers`` keeps only those workers' blocks (a planted fault)."""
    w = jax.tree_util.tree_map(lambda a: jnp.asarray(a, param_dtype), weights)
    state = adam_init(w)
    losses, first_grads = [], None
    for blocks in steps:
        if workers is not None:
            blocks = [blocks[i] for i in workers]
        outs = [loss_and_grads(w, features, labels, b, compute_dtype,
                               seed_mask) for b in blocks]
        loss = sum(o[0] for o in outs) / len(outs)
        grads = jax.tree_util.tree_map(lambda *g: sum(g) / len(g),
                                       *[o[1] for o in outs])
        if first_grads is None:
            first_grads = grads
        w, state = adam_update(w, grads, state, opt)
        losses.append(float(loss))
    return losses, first_grads, w


def leaf_norms(tree) -> dict:
    """``layer<i>.<name>`` -> Euclidean norm, in float64 on the host."""
    out = {}
    for i, layer in enumerate(tree):
        for name, leaf in layer.items():
            a = np.asarray(leaf, np.float64)
            out[f"layer{i}.{name}"] = float(np.sqrt((a * a).sum()))
    return out
