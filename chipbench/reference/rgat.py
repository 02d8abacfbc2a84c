"""R-GAT as OGB-LSC's MAG240M baseline trains it (``rgnn.py --model rgat``),
its loss, gradients and Adam, in plain ``jax.numpy``.

Layer ``i`` over the targets ``x_t`` of a sampled block whose every lane
carries its edge's relation (``Block.lane_data[i]["relation"]``), with ``H``
heads of width ``C`` (PyG ``GATConv(in, C, heads=H, add_self_loops=False)``
per relation, one weight for sources and targets)::

    out   = x_t W_skip + b_skip + sum_{r on a valid lane} GAT_r(x, x_t)
    z_j   = x_j W_r,   z_t = x_t W_r                       (H, C) each
    e_tjh = LeakyReLU_0.2(a_src[r,h] . z_jh + a_dst[r,h] . z_th)
    alpha = softmax_j(e) per (t, r, h),  j over t's lanes of relation r
    GAT_r(t) = concat_h sum_j alpha_tjh z_jh + b_r    (b_r alone where t has
                                                       no lane of r)

then batch normalisation in training mode over the layer's valid targets
(biased variance, eps 1e-5) and ELU. Head: ``Linear -> BatchNorm over the
seeds -> ReLU -> Linear``, log-softmax, mean negative log-likelihood over
the seeds; Adam as ``reference/sage.py`` has it.

It follows the equations literally: each edge's source row is projected by
its own relation's weight ``W_r``, and each target's row by every ``W_r``,
then the logits are formed, one softmax over every relation's edges keyed
by ``target * R + relation``, and the messages summed by target. A relation
that no valid lane of the layer carries adds nothing, not even its bias
(the script's ``if subadj_t.nnz() > 0``). A layer's edges are its lanes
as the block lists them (``src`` -1 on a masked one) with the relation
each carries, so that every block of a configuration compiles to one
program (edge lists cut to each relation's count would change shape from
block to block, and each shape is compiled again: about a minute for a
v5e at MAG240M's widths). The products run one relation after the other over that list
(``lax.scan``), each keeping its own relation's edges, so that the
comparison holds one ``(edges, out)`` float32 array of projections and not
one per relation, nor a per-node projection by every weight (at
MAG240M's input layer 425,984 x 5 x 1,024 float32 values, which would not
fit beside the rows). No dense fanout layout, no kernels.

Departures from the source, each for the harness's sake: ``dropout`` is 0.0
(the source trains with 0.5 between layers and none inside the attention;
the reference cannot follow a mask drawn inside the program's RNG path);
batch norms keep no running statistics (only evaluation reads them); the
graph is the harness's synthetic one; rows are gathered by ``n_id`` in the
dtype the harness holds them (float16) and widened at once, as the script
widens its batch after the gather.

What the harness calls of a model's plain side: ``layer_dims``,
``make_weights``, ``train``, ``leaf_norms``, ``step_flops``, and the bytes
of the attention, ``rgat_attention_bytes``, which
``metrics/rgat_roofline.json`` names.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .graph import Block
from .rsage import _norm, target_counts
from .sage import adam_init, adam_update, leaf_norms

__all__ = ["layer_dims", "make_weights", "step_flops", "rgat_attention_bytes",
           "loss_and_grads", "train", "leaf_norms"]

NEGATIVE_SLOPE = 0.2


def layer_dims(cfg: dict) -> list[tuple[int, int, int, int]]:
    """``(in, out, relations, heads)`` of each layer, input layer first: the
    relational layers, then the head's two linear layers (no relations, no
    heads)."""
    hidden, relations = int(cfg["hidden"]), int(cfg["relations"])
    heads = int(cfg["heads"])
    dims, d_in = [], int(cfg["feature_dim"])
    for _ in range(int(cfg["layers"])):
        dims.append((d_in, hidden, relations, heads))
        d_in = hidden
    return dims + [(hidden, hidden, 0, 0), (hidden, int(cfg["classes"]), 0, 0)]


def make_weights(cfg: dict, rng: np.random.Generator) -> list[dict]:
    """One dict per relational layer (``w_rel{r}`` ``(in, out)``,
    ``a_src{r}`` / ``a_dst{r}`` ``(heads, out // heads)`` and ``b_rel{r}``
    per relation, ``w_skip`` / ``b_skip``, the batch norm's ``gamma`` /
    ``beta``), then the head's (``w0``, ``b0``, ``gamma``, ``beta``, ``w1``,
    ``b1``). Matrices N(0, 1/in), attention vectors N(0, 1/width) (a logit
    term of unit scale), biases N(0, 0.01): not zero, so that a bias added
    where it must not be shows; ``gamma`` 1, ``beta`` 0."""
    def normal(shape, fan_in):
        return (rng.standard_normal(shape, dtype=np.float32)
                * np.float32(1.0 / np.sqrt(fan_in)))

    def bias(d):
        return rng.standard_normal((d,), dtype=np.float32) * np.float32(0.1)

    *convs, (h, _, _, _), (_, classes, _, _) = layer_dims(cfg)
    out = []
    for d_in, d_out, relations, heads in convs:
        width = d_out // heads
        layer = {}
        for r in range(relations):
            layer[f"w_rel{r}"] = normal((d_in, d_out), d_in)
            layer[f"a_src{r}"] = normal((heads, width), width)
            layer[f"a_dst{r}"] = normal((heads, width), width)
            layer[f"b_rel{r}"] = bias(d_out)
        layer.update(w_skip=normal((d_in, d_out), d_in), b_skip=bias(d_out),
                     gamma=np.ones((d_out,), np.float32),
                     beta=np.zeros((d_out,), np.float32))
        out.append(layer)
    out.append({"w0": normal((h, h), h), "b0": bias(h),
                "gamma": np.ones((h,), np.float32),
                "beta": np.zeros((h,), np.float32),
                "w1": normal((h, classes), h), "b1": bias(classes)})
    return out


def _split(counts: dict):
    """The relational layers' dims with their hops (input layer first), and
    the head's dims with the seeds' hop."""
    hops = counts["hops"][::-1]
    dims = counts["layer_dims"]
    return list(zip(dims[:len(hops)], hops)), dims[len(hops):], hops[-1]


def step_flops(counts: dict) -> float:
    """Forward and backward over the valid rows, nothing recomputed, in the
    cheaper of the two exact orders (aggregate, then transform), so that no
    order the program may take reads over 100 % (``counts`` as ``work.py``
    describes them). A relational layer over ``T`` targets and ``E`` valid
    lanes: ``relations + 1`` products of ``2 T in out`` (each relation's
    ``(target, head)`` sums times its weight, and the skip); per lane
    ``2 in heads`` for its logit terms (its row against ``W_r,h a_src``) and
    again for its weighted sums; per target ``2 in heads`` a relation for
    the targets' logit terms. Backward the weight gradients of all of it,
    and above the input layer the input gradients too (the input layer's
    rows are data). The head's two products over the seeds: forward,
    weight and input gradients."""
    total = 0.0
    layers, head, seeds = _split(counts)
    for i, ((d_in, d_out, relations, heads), hop) in enumerate(layers):
        forward = ((relations + 1) * 2.0 * hop["targets"] * d_in * d_out
                   + 2 * hop["edges"] * 2.0 * d_in * heads
                   + hop["targets"] * relations * 2.0 * d_in * heads)
        total += forward * (3 if i else 2)
    for d_in, d_out, _, _ in head:
        total += 3 * 2.0 * seeds["targets"] * d_in * d_out
    return total


def rgat_attention_bytes(counts: dict) -> float:
    """The least bytes of the attention: each valid lane's source row read
    once (the stored rows at the input layer, ``feature_itemsize`` bytes a
    value; float32 above it) with its one-byte relation, and ``relations x
    out`` float32 values a target written. Above the input layer the same
    again backward; the input layer's rows are data and have no
    gradient."""
    total = 0.0
    layers, _, _ = _split(counts)
    for i, ((d_in, d_out, relations, _), hop) in enumerate(layers):
        item = counts["feature_itemsize"] if i == 0 else 4
        once = (hop["edges"] * (d_in * item + 1)
                + hop["targets"] * relations * d_out * 4)
        total += once * (2 if i else 1)
    return float(total)


def _attend(h, w, src, dst, rel, n_dst, heads, cd):
    """Every relation's messages to the ``n_dst`` targets, (n_dst, H C), and
    which relations have a valid edge. ``src`` / ``dst`` / ``rel`` list the
    layer's edges (``src`` -1 on a masked one). Each edge's source row is
    projected by its own relation's weight, one relation after the other
    (``lax.scan``: the products are not all held at once), then the
    logits, one softmax keyed by ``target * R + relation`` and the sum by
    target."""
    relations = sum(name.startswith("w_rel") for name in w)
    stack = lambda name: jnp.stack([w[f"{name}{r}"] for r in range(relations)])
    weight, a_src, a_dst = stack("w_rel"), stack("a_src"), stack("a_dst")
    valid = src >= 0
    rows = h[jnp.clip(src, 0)]          # widened in each product
    L = src.shape[0]

    def project(carry, r):
        z, s = carry
        mine = valid & (rel == r)
        zr = (rows.astype(cd) @ weight[r]).reshape(L, heads, -1)
        return (z + jnp.where(mine[:, None, None], zr, 0),
                s + jnp.where(mine[:, None], (zr * a_src[r]).sum(-1), 0)), None

    zero = (jnp.zeros((L, heads, weight.shape[-1] // heads), cd),
            jnp.zeros((L, heads), cd))
    # the products again in the backward, not one held per relation
    (z, s), _ = jax.lax.scan(jax.checkpoint(project), zero,
                             jnp.arange(relations))
    z_t = jnp.einsum("tf,rfo->rto", h[:n_dst].astype(cd), weight)
    d = (z_t.reshape(relations, n_dst, heads, -1) * a_dst[:, None]).sum(-1)
    r_safe, t_safe = jnp.clip(rel, 0, relations - 1), jnp.clip(dst, 0)
    e = jax.nn.leaky_relu(s + d[r_safe, t_safe], NEGATIVE_SLOPE)
    key = jnp.where(valid, t_safe * relations + r_safe, n_dst * relations)
    groups = n_dst * relations + 1
    v = valid[:, None]
    # each group's max: the weights do not depend on it
    top = jax.lax.stop_gradient(jax.ops.segment_max(
        jnp.where(v, e, -jnp.inf), key, num_segments=groups))
    expv = jnp.where(v, jnp.exp(jnp.where(v, e - top[key], 0)), 0)
    denom = jax.ops.segment_sum(expv, key, num_segments=groups)
    alpha = expv / jnp.where(v, denom[key], 1)
    msg = jax.ops.segment_sum(alpha[..., None] * z,
                              jnp.where(valid, t_safe, n_dst),
                              num_segments=n_dst + 1)[:n_dst]
    present = jnp.stack([(valid & (rel == r)).any()
                         for r in range(relations)])
    return msg.reshape(n_dst, -1), present


def forward(weights, x, layers, targets_valid, seeds_valid, heads,
            compute_dtype):
    """``layers``: per relational layer ``(n_dst, (src, dst, rel))``."""
    cd = compute_dtype
    h = x
    *convs, head = [{k: v.astype(cd) for k, v in w.items()} for w in weights]
    for w, (n_dst, (src, dst, rel)), valid_t in zip(convs, layers,
                                                    targets_valid):
        msg, present = _attend(h, w, src, dst, rel, n_dst, heads, cd)
        biases = jnp.stack([w[f"b_rel{r}"] for r in range(present.shape[0])])
        out = (h[:n_dst].astype(cd) @ w["w_skip"] + w["b_skip"] + msg
               + jnp.where(present[:, None], biases, 0).sum(axis=0))
        h = jax.nn.elu(_norm(out, valid_t, w["gamma"], w["beta"]))
    h = h @ head["w0"] + head["b0"]
    h = jax.nn.relu(_norm(h, seeds_valid, head["gamma"], head["beta"]))
    h = h @ head["w1"] + head["b1"]
    return jax.nn.log_softmax(h.astype(jnp.float32), axis=-1)


@functools.partial(jax.jit, static_argnames=("n_dsts", "heads",
                                             "compute_dtype"))
def _loss_and_grads(weights, x, edges, counts, labels, mask, n_dsts, heads,
                    compute_dtype):
    layers = list(zip(n_dsts, edges))
    targets_valid = [jnp.arange(n) < c for n, c in zip(n_dsts, counts)]
    seeds_valid = targets_valid[-1]

    def loss_fn(w):
        logp = forward(w, x, layers, targets_valid, seeds_valid, heads,
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
    heads = weights[0]["a_src0"].shape[0]
    # a layer whose lanes carry no relation (a fault that ``lane_faults``
    # counts) is read as all of relation 0, so that there are numbers to
    # compare
    lanes = block.lane_data + [{}] * (len(block.layers) - len(block.lane_data))
    edges = tuple(
        tuple(jnp.asarray(a, jnp.int32) for a in (
            src, dst, lane.get("relation", np.zeros(np.shape(src)))))
        for lane, (src, dst, _) in zip(lanes, block.layers))
    n_dsts = tuple(int(n) for _, _, n in block.layers)
    with jax.default_matmul_precision("highest"):
        return _loss_and_grads(weights, x, edges,
                               jnp.asarray(target_counts(block)),
                               labels[seeds], mask, n_dsts, heads,
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
