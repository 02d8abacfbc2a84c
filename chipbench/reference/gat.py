"""ogbn-products GAT as PyTorch Geometric's example trains it, its loss,
gradients and Adam, in plain ``jax.numpy``.

Source: https://github.com/pyg-team/pytorch_geometric/blob/master/examples/ogbn_products_gat.py
(``GATConv`` is Velickovic et al. 2018, v1). Per layer, with ``H`` heads of
width ``F``, source rows ``h_j`` and targets ``i`` (the first ``n_dst``
sources):

    z_j   = W h_j                         s_j = <a_src, z_j>,  d_i = <a_dst, z_i>
    L(i)  = {sampled edges (j -> i) with j != i} + {(i -> i)}
    e_ij  = LeakyReLU_0.2(s_j + d_i)      alpha_ij = softmax over j in L(i), per head
    o_i   = sum_j alpha_ij z_j
    y_i   = concat_heads(o_i) + b         (output layer: mean_heads(o_i) + b)
    h'_i  = y_i + W_skip h_i + b_skip     ELU between layers

log-softmax head, mean negative log-likelihood over the seed nodes; Adam as
Kingma & Ba state it. ``GATConv``'s self loop is made as PyG makes it: the
edges whose two ends are one node are taken out of the edge list and one
self edge per target is appended to it. No kernels, no dense fanout layout,
no batching tricks: an edge list, ``segment_max`` and ``segment_sum`` by
target.

Departures from the source, each for the harness's sake: ``dropout`` is 0.0
(the source trains with 0.5; the reference cannot follow a mask drawn inside
the program's RNG path), and the graph is the harness's synthetic one.

``param_dtype`` and ``compute_dtype`` select the precision. The reference
runs float32 throughout with ``highest`` matmul precision; the control runs
the same code in bfloat16.

What the harness calls of a model's plain side: ``layer_dims``,
``make_weights``, ``train``, ``leaf_norms``, ``step_flops``, and for
``attn_roofline`` ``attention_bytes``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .graph import Block

__all__ = ["layer_dims", "make_weights", "step_flops", "attention_bytes",
           "loss_and_grads", "adam_init", "adam_update", "train",
           "leaf_norms"]

NEGATIVE_SLOPE = 0.2


def layer_dims(cfg: dict) -> list[tuple[int, int, int, int]]:
    """``(in, out, heads, head_width)`` of each GAT layer, input layer
    first. A hidden layer concatenates its heads (``out = heads *
    head_width``), the output layer averages them (``out = head_width =
    classes``); the projected width is ``heads * head_width`` in both."""
    dims, d_in = [], int(cfg["feature_dim"])
    heads = int(cfg["heads"])
    for i in range(int(cfg["layers"])):
        last = i == int(cfg["layers"]) - 1
        width = int(cfg["classes"] if last else cfg["hidden"])
        d_out = width if last else heads * width
        dims.append((d_in, d_out, heads, width))
        d_in = d_out
    return dims


def make_weights(cfg: dict, rng: np.random.Generator) -> list[dict]:
    """Initial weights, one dict per layer (input layer first): ``w`` (in,
    heads * head_width) and ``w_skip`` (in, out) drawn N(0, 1/in), ``a_src``
    and ``a_dst`` (heads, head_width) drawn N(0, 1/head_width) so that the
    attention is not uniform, ``b`` and ``b_skip`` (out,) zero."""
    def normal(shape, fan_in):
        return (rng.standard_normal(shape, dtype=np.float32)
                * np.float32(1.0 / np.sqrt(fan_in)))

    layers = []
    for d_in, d_out, heads, width in layer_dims(cfg):
        layers.append({
            "w": normal((d_in, heads * width), d_in),
            "a_src": normal((heads, width), width),
            "a_dst": normal((heads, width), width),
            "b": np.zeros((d_out,), np.float32),
            "w_skip": normal((d_in, d_out), d_in),
            "b_skip": np.zeros((d_out,), np.float32),
        })
    return layers


def _layer_counts(counts: dict):
    """Per layer, input layer first: its dims, its source rows ``N`` (the
    distinct nodes of the frontier its hop reached), its targets ``T`` and
    its lanes ``L``: the hop's valid sampled edges and one self lane per
    target (a sampled edge from a target to itself, which the self lane
    replaces, is one edge in a few thousand and is counted as a lane)."""
    for dims, hop in zip(counts["layer_dims"], counts["hops"][::-1]):
        yield dims, hop["unique"], hop["targets"], hop["edges"] + hop["targets"]


def step_flops(counts: dict) -> float:
    """Forward and backward of the GAT layers over the valid rows and lanes,
    nothing recomputed (``counts`` as ``work.py`` describes them). Per
    layer: the projection over the ``N`` source rows and the skip over the
    ``T`` targets (forward, weight gradient, and input gradient except at
    the input layer, whose inputs are data); ``s`` over the sources and
    ``d`` over the targets (a multiply and an add per element forward, twice
    that backward: towards ``z`` and towards ``a``); per lane and head the
    logit (add, LeakyReLU) and the softmax (subtract, exp, add, divide),
    forward and as many again backward; per lane and element of the
    projected row the weighting and the sum (2 forward; 4 backward: the
    weight's gradient is a product and a sum over the row, the row's a
    product and its accumulation into ``z``'s cotangent)."""
    total = 0.0
    for i, ((d_in, d_out, heads, width), n, t, lanes) in enumerate(
            _layer_counts(counts)):
        passes = 3 if i else 2
        wide = heads * width
        total += passes * 2.0 * n * d_in * wide      # projection
        total += passes * 2.0 * t * d_in * d_out     # skip
        total += 3 * 2.0 * (n + t) * wide            # s and d
        total += 2 * (2 + 4) * lanes * heads         # logits and softmax
        total += (2 + 4) * lanes * wide              # weighting and sum
    return total


def attention_bytes(counts: dict) -> float:
    """The bytes the attention must move, per layer over valid lanes and
    rows only, float32: forward each lane's ``z`` row and its ``s`` read and
    each target's aggregated row written; backward the same again (the
    target's cotangent row read in place of written) and the ``z``
    cotangent of every source row written. No padding, no cap."""
    total = 0.0
    for (_, _, heads, width), n, t, lanes in _layer_counts(counts):
        wide = heads * width
        one_way = lanes * (wide + heads) + t * wide
        total += 4.0 * (2 * one_way + n * wide)
    return total


def forward(weights, x, layers, compute_dtype):
    h = x.astype(compute_dtype)
    for i, (w, (src, dst, n_dst)) in enumerate(zip(weights, layers)):
        heads, width = w["a_src"].shape
        z = (h @ w["w"].astype(compute_dtype)).reshape(-1, heads, width)
        s = (z * w["a_src"].astype(compute_dtype)).sum(-1)
        d = (z * w["a_dst"].astype(compute_dtype)).sum(-1)
        # PyG's remove_self_loops, then add_self_loops: one (i -> i) per
        # target at the end of the edge list
        own = jnp.arange(n_dst, dtype=src.dtype)
        keep = jnp.concatenate([(src >= 0) & (src != dst),
                                jnp.ones((n_dst,), bool)])
        src_all = jnp.concatenate([jnp.clip(src, 0), own])
        dst_all = jnp.where(keep, jnp.concatenate([dst, own]), 0)
        e = jax.nn.leaky_relu(s[src_all] + d[dst_all], NEGATIVE_SLOPE)
        # an entry that is no edge weighs exp(-inf) = 0; every target has
        # its self edge, so every maximum is finite
        e = jnp.where(keep[:, None], e, -jnp.inf)
        top = jax.ops.segment_max(e, dst_all, num_segments=n_dst)
        p = jnp.exp(e - top[dst_all])
        alpha = p / jax.ops.segment_sum(p, dst_all, num_segments=n_dst)[dst_all]
        o = jax.ops.segment_sum(alpha[:, :, None] * z[src_all], dst_all,
                                num_segments=n_dst)
        if i != len(weights) - 1:
            y = o.reshape(n_dst, heads * width)
        else:
            y = o.mean(axis=1)
        h = (y + w["b"].astype(compute_dtype)
             + h[:n_dst] @ w["w_skip"].astype(compute_dtype)
             + w["b_skip"].astype(compute_dtype))
        if i != len(weights) - 1:
            h = jax.nn.elu(h)
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
