"""R-GraphSAGE as OGB-LSC's MAG240M baseline trains it (``rgnn.py --model
rgraphsage``), its loss, gradients and Adam, in plain ``jax.numpy``.

Layer ``i`` over the targets ``x_t`` of a sampled block whose every lane
carries its edge's relation (``Block.lane_data[i]["relation"]``)::

    out = x_t W_skip + b_skip + sum_r [r on a valid lane] (mean_r W_r + b_r)

``mean_r`` is a target's mean over its lanes of relation ``r``, 0 where it
has none (PyG ``SAGEConv(root_weight=False)`` over the relation's edges); a
relation that no valid lane of the layer carries adds nothing (the script's
``if subadj_t.nnz() > 0``). Then batch normalisation in training mode over
the layer's valid targets (``target_counts``; biased variance, eps 1e-5) and
ReLU. Head: ``Linear -> BatchNorm over the seeds -> ReLU -> Linear``,
log-softmax, mean negative log-likelihood over the seeds; Adam as
``reference/sage.py`` has it. Rows are gathered by ``n_id`` in the dtype
the harness holds them (float16) and widened at once. An edge list and
segment sums: no dense fanout layout, no kernels.

What the harness calls of a model's plain side: ``layer_dims``,
``make_weights``, ``train``, ``leaf_norms``, ``step_flops``, and the bytes
of the relational aggregation, ``rel_aggregate_bytes``, which
``metrics/rel_roofline.json`` names.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .graph import Block
from .sage import adam_init, adam_update, leaf_norms

__all__ = ["layer_dims", "make_weights", "step_flops", "rel_aggregate_bytes",
           "loss_and_grads", "train", "leaf_norms"]

EPS = 1e-5  # BatchNorm1d's


def layer_dims(cfg: dict) -> list[tuple[int, int, int]]:
    """``(in, out, relations)`` of each layer, input layer first: the
    relational layers, then the head's two linear layers (relations 0)."""
    hidden, relations = int(cfg["hidden"]), int(cfg["relations"])
    dims, d_in = [], int(cfg["feature_dim"])
    for _ in range(int(cfg["layers"])):
        dims.append((d_in, hidden, relations))
        d_in = hidden
    return dims + [(hidden, hidden, 0), (hidden, int(cfg["classes"]), 0)]


def make_weights(cfg: dict, rng: np.random.Generator) -> list[dict]:
    """One dict per relational layer (``w_rel{r}`` / ``b_rel{r}`` per
    relation, ``w_skip`` / ``b_skip``, the batch norm's ``gamma`` /
    ``beta``), then the head's (``w0``, ``b0``, ``gamma``, ``beta``, ``w1``,
    ``b1``). Matrices N(0, 1/in), biases N(0, 0.01): not zero, so that a
    bias added where it must not be shows; ``gamma`` 1, ``beta`` 0."""
    def matrix(d_in, d_out):
        return (rng.standard_normal((d_in, d_out), dtype=np.float32)
                * np.float32(1.0 / np.sqrt(d_in)))

    def bias(d):
        return rng.standard_normal((d,), dtype=np.float32) * np.float32(0.1)

    *convs, (h, _, _), (_, classes, _) = layer_dims(cfg)
    out = []
    for d_in, d_out, relations in convs:
        layer = {}
        for r in range(relations):
            layer[f"w_rel{r}"], layer[f"b_rel{r}"] = (matrix(d_in, d_out),
                                                      bias(d_out))
        layer.update(w_skip=matrix(d_in, d_out), b_skip=bias(d_out),
                     gamma=np.ones((d_out,), np.float32),
                     beta=np.zeros((d_out,), np.float32))
        out.append(layer)
    out.append({"w0": matrix(h, h), "b0": bias(h),
                "gamma": np.ones((h,), np.float32),
                "beta": np.zeros((h,), np.float32),
                "w1": matrix(h, classes), "b1": bias(classes)})
    return out


def _split(counts: dict):
    """The relational layers' dims with their hops (input layer first), and
    the head's dims with the seeds' hop."""
    hops = counts["hops"][::-1]
    dims = counts["layer_dims"]
    return list(zip(dims[:len(hops)], hops)), dims[len(hops):], hops[-1]


def step_flops(counts: dict) -> float:
    """Forward and backward over the valid rows, nothing recomputed
    (``counts`` as ``work.py`` describes them). A relational layer over
    ``T`` targets has ``relations + 1`` products of ``2 T in out``
    operations forward (every relation's, as the program computes them; the
    script skips a relation that no lane carries), their weight gradients,
    and their input gradients except at the input layer, whose inputs are
    data; each relation's mean adds one operation an edge and feature
    forward, and again backward above the input layer. The head's two
    products over the seeds: forward, weight and input gradients."""
    total = 0.0
    layers, head, seeds = _split(counts)
    for i, ((d_in, d_out, relations), hop) in enumerate(layers):
        products = (relations + 1) * 2.0 * hop["targets"] * d_in * d_out
        total += products * (3 if i else 2)
        total += hop["edges"] * d_in * (2 if i else 1)
    for d_in, d_out, _ in head:
        total += 3 * 2.0 * seeds["targets"] * d_in * d_out
    return total


def rel_aggregate_bytes(counts: dict) -> float:
    """The least bytes of the per-relation means: each valid lane's source
    row read (the stored rows at the input layer, ``feature_itemsize``
    bytes a value; float32 above it) with its one-byte relation, and
    ``relations x in`` float32 sums a target written. Above the input layer
    the same again backward; the input layer's rows are data and have no
    gradient."""
    total = 0.0
    layers, _, _ = _split(counts)
    for i, ((d_in, _, relations), hop) in enumerate(layers):
        item = counts["feature_itemsize"] if i == 0 else 4
        once = (hop["edges"] * (d_in * item + 1)
                + hop["targets"] * relations * d_in * 4)
        total += once * (2 if i else 1)
    return float(total)


def _norm(h, valid, gamma, beta):
    v = valid[:, None]
    n = jnp.maximum(valid.sum().astype(h.dtype), 1)
    mean = jnp.where(v, h, 0).sum(axis=0) / n
    var = jnp.where(v, jnp.square(h - mean), 0).sum(axis=0) / n
    y = (h - mean) / jnp.sqrt(var + EPS) * gamma + beta
    return jnp.where(v, y, 0)


def forward(weights, x, layers, relations, targets_valid, seeds_valid,
            compute_dtype):
    cd = compute_dtype
    h = x.astype(cd)
    *convs, head = [{k: v.astype(cd) for k, v in w.items()} for w in weights]
    for w, (src, dst, n_dst), rel, valid_t in zip(convs, layers, relations,
                                                  targets_valid):
        valid = src >= 0
        msg = jnp.where(valid[:, None], h[jnp.clip(src, 0)], 0)
        out = h[:n_dst] @ w["w_skip"] + w["b_skip"]
        r = 0
        while f"w_rel{r}" in w:
            picked = valid & (rel == r)
            seg = jnp.where(picked, dst, n_dst)
            total = jax.ops.segment_sum(
                jnp.where(picked[:, None], msg, 0), seg,
                num_segments=n_dst + 1)[:n_dst]
            count = jax.ops.segment_sum(picked.astype(cd), seg,
                                        num_segments=n_dst + 1)[:n_dst]
            mean = total / jnp.maximum(count, 1)[:, None]
            out = out + jnp.where(picked.any(),
                                  mean @ w[f"w_rel{r}"] + w[f"b_rel{r}"], 0)
            r += 1
        h = jax.nn.relu(_norm(out, valid_t, w["gamma"], w["beta"]))
    h = h @ head["w0"] + head["b0"]
    h = jax.nn.relu(_norm(h, seeds_valid, head["gamma"], head["beta"]))
    h = h @ head["w1"] + head["b1"]
    return jax.nn.log_softmax(h.astype(jnp.float32), axis=-1)


def target_counts(block: Block) -> list[int]:
    """How many of each layer's target slots hold a node, input layer
    first: the seeds at the last layer; below it, the frontier that the
    layer above sampled from (its targets and every source it named, a
    prefix of ``n_id``)."""
    counts = [block.num_seeds]
    for src, _, _ in block.layers[:0:-1]:
        counts.append(max(counts[-1], int(np.max(src, initial=-1)) + 1))
    return counts[::-1]


@functools.partial(jax.jit, static_argnames=("n_dsts", "compute_dtype"))
def _loss_and_grads(weights, x, srcs, dsts, rels, counts, labels, mask,
                    n_dsts, compute_dtype):
    layers = list(zip(srcs, dsts, n_dsts))
    targets_valid = [jnp.arange(n) < c for n, c in zip(n_dsts, counts)]
    seeds_valid = targets_valid[-1]

    def loss_fn(w):
        logp = forward(w, x, layers, rels, targets_valid, seeds_valid,
                       compute_dtype)[:labels.shape[0]]
        picked = jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
        m = mask.astype(jnp.float32)
        return -(picked * m).sum() / jnp.maximum(m.sum(), 1.0)

    return jax.value_and_grad(loss_fn)(weights)


def loss_and_grads(weights, features, labels, block: Block,
                   compute_dtype=jnp.float32, seed_mask=None):
    """Loss and gradients of one block; the relation of every lane is read
    from ``block.lane_data``. ``seed_mask`` (bool, per seed) leaves seeds
    out of the loss's mean: a planted fault, never the reference."""
    n_id = jnp.asarray(block.n_id)
    x = jnp.where((n_id >= 0)[:, None], features[jnp.clip(n_id, 0)], 0)
    seeds = n_id[:block.num_seeds]
    mask = jnp.ones((block.num_seeds,), bool) if seed_mask is None \
        else jnp.asarray(seed_mask)
    srcs = tuple(jnp.asarray(s) for s, _, _ in block.layers)
    dsts = tuple(jnp.asarray(d) for _, d, _ in block.layers)
    # a layer whose lanes carry no relation (a fault that ``lane_faults``
    # counts) is read as all of relation 0, so that there are numbers to
    # compare
    lanes = block.lane_data + [{}] * (len(block.layers) - len(block.lane_data))
    rels = tuple(jnp.asarray(lane.get("relation", np.zeros(np.shape(s))))
                 for lane, (s, _, _) in zip(lanes, block.layers))
    n_dsts = tuple(int(n) for _, _, n in block.layers)
    with jax.default_matmul_precision("highest"):
        return _loss_and_grads(weights, x, srcs, dsts, rels,
                               jnp.asarray(target_counts(block)),
                               labels[seeds], mask, n_dsts,
                               jnp.dtype(compute_dtype))


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
