"""GAT model tests (BASELINE config 4: attention aggregation).

Checks: padding-lane invariance (extra -1 edges change nothing), forward
shapes, the self lane, that end-to-end training on the synthetic labeled
graph learns — the same acceptance pattern as the SAGE tests — and that the
model is the published recipe: its loss and every leaf's gradient against
the benchmark's plain reference (``chipbench/reference/gat.py``), the dense
fanout path against the segment path, the output layer's heads averaged,
the parameter tree through ``chipbench/models/gat.py`` and back."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
import optax

from chipbench import spec

from quiver_tpu import CSRTopo, GraphSageSampler
from quiver_tpu.feature.feature import Feature
from quiver_tpu.models.gat import GAT, GATConv
from quiver_tpu.parallel.train import init_model, make_train_step

from test_models_train import _labeled_graph


def _tiny_block(num_src=8, num_dst=4, e=16, seed=0):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, num_src, e).astype(np.int32)
    dst = rng.integers(0, num_dst, e).astype(np.int32)
    return np.stack([src, dst])


def test_gatconv_forward_shapes_and_finite():
    ei = _tiny_block()
    x = jnp.asarray(np.random.default_rng(1).normal(size=(8, 6)).astype(np.float32))
    conv = GATConv(features=5, heads=3, concat=True)
    params = conv.init(jax.random.PRNGKey(0), x, jnp.asarray(ei), 4)
    out = conv.apply(params, x, jnp.asarray(ei), 4)
    assert out.shape == (4, 15)
    assert np.all(np.isfinite(np.asarray(out)))

    conv_avg = GATConv(features=5, heads=3, concat=False)
    params = conv_avg.init(jax.random.PRNGKey(0), x, jnp.asarray(ei), 4)
    out = conv_avg.apply(params, x, jnp.asarray(ei), 4)
    assert out.shape == (4, 5)


def test_gatconv_padding_invariance():
    """Appending -1 sentinel edges must not change the output."""
    ei = _tiny_block()
    x = jnp.asarray(np.random.default_rng(1).normal(size=(8, 6)).astype(np.float32))
    conv = GATConv(features=4, heads=2)
    params = conv.init(jax.random.PRNGKey(0), x, jnp.asarray(ei), 4)
    out1 = conv.apply(params, x, jnp.asarray(ei), 4)

    pad = np.full((2, 7), -1, np.int32)
    ei_padded = np.concatenate([ei, pad], axis=1)
    out2 = conv.apply(params, x, jnp.asarray(ei_padded), 4)
    assert np.allclose(np.asarray(out1), np.asarray(out2), atol=1e-6)


def test_gatconv_isolated_dst_attends_to_itself_alone():
    """A destination with no incoming edges receives its own projected row
    (the self lane, weight 1), the bias and its skip."""
    # all 6 edges target dst 0; dst 1 is isolated
    ei = np.stack([np.arange(6, dtype=np.int32), np.zeros(6, np.int32)])
    x = jnp.asarray(np.random.default_rng(2).normal(size=(6, 3)).astype(np.float32))
    conv = GATConv(features=4, heads=2)
    variables = conv.init(jax.random.PRNGKey(0), x, jnp.asarray(ei), 2)
    out = np.asarray(conv.apply(variables, x, jnp.asarray(ei), 2))
    p = variables["params"]
    own = np.asarray(x)[1] @ np.asarray(p["lin"]["kernel"]) + np.asarray(p["bias"])
    own += (np.asarray(x)[1] @ np.asarray(p["skip"]["kernel"])
            + np.asarray(p["skip"]["bias"]))
    assert np.allclose(out[1], own, atol=1e-6)


def test_gat_end_to_end_learns():
    ei, feat, labels = _labeled_graph()
    topo = CSRTopo(edge_index=ei)
    n = topo.node_count
    sampler = GraphSageSampler(topo, [5, 5], seed=1)
    feature = Feature(device_cache_size="1G").from_cpu_tensor(feat[:n])

    model = GAT(hidden=8, num_classes=4, num_layers=2, heads=4)
    tx = optax.adam(5e-3)

    out0 = sampler.sample(np.arange(128) % n)
    x0 = feature[out0.n_id]
    params = init_model(model, jax.random.PRNGKey(0), x0, out0.adjs)
    opt_state = tx.init(params)
    train_step = jax.jit(make_train_step(model, tx))

    rng = np.random.default_rng(0)
    losses = []
    for step in range(30):
        seeds = rng.integers(0, n, 128)
        out = sampler.sample(seeds)
        x = feature[out.n_id]
        cap = out.adjs[-1].size[1]
        lab = np.full(cap, -1, np.int32)
        lab[:128] = labels[seeds]
        mask = np.zeros(cap, bool)
        mask[:128] = True
        params, opt_state, loss = train_step(
            params, opt_state, x, out.adjs,
            jnp.asarray(lab), jnp.asarray(mask), jax.random.PRNGKey(step),
        )
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.8, losses


# -- the published recipe -----------------------------------------------------

REFERENCE = spec.load_model("gat", "reference")
PROGRAM = spec.load_model("gat", "models")
CFG = {"feature_dim": 12, "classes": 5, "layers": 3, "hidden": 6, "heads": 4,
       "dropout": 0.0}


def _fanout_block(rng, num_dst, num_src, fanout, edit=None):
    """A block in the sampler's regular layout (lane ``t * fanout + k``
    targets ``t``), a third of its lanes invalid; ``edit(src)`` bends the
    ``(num_dst, fanout)`` sources before they are flattened."""
    src = rng.integers(0, num_src, (num_dst, fanout)).astype(np.int32)
    src[rng.random((num_dst, fanout)) < 0.33] = -1
    if edit is not None:
        edit(src)
    dst = np.repeat(np.arange(num_dst, dtype=np.int32), fanout)
    dst = np.where(src.reshape(-1) >= 0, dst, -1).astype(np.int32)
    return src.reshape(-1), dst


def _all_invalid(src):
    src[1] = -1
    src[4] = -1


def _self_lanes(src):
    src[2, 0] = 2   # a sampled lane j == i, which the self lane replaces
    src[3, :] = 3   # a target whose every sampled lane is itself


BLOCKS = {
    "random": dict(fanouts=(3, 2, 4)),
    "all-invalid targets": dict(fanouts=(3, 2, 4), edit=_all_invalid),
    "a sampled lane j == i": dict(fanouts=(3, 2, 4), edit=_self_lanes),
    "fanout 1": dict(fanouts=(1, 1, 1)),
}


def _recipe_case(fanouts, edit=None, seed=0):
    """Seeded weights, rows and one three-layer block (input layer first)
    in the regular layout: the reference's ``Block`` and the program's
    ``Adj``s with their fanout, and with it stripped."""
    from chipbench.reference.graph import Block
    from quiver_tpu.sampling.sampler import Adj

    rng = np.random.default_rng(seed)
    sizes = [40, 20, 10, 6]  # sources of layer 0, then each layer's targets
    layers, dense, segment = [], [], []
    for i, k in enumerate(fanouts):
        src, dst = _fanout_block(rng, sizes[i + 1], sizes[i], k, edit)
        layers.append((src, dst, sizes[i + 1]))
        ei = jnp.asarray(np.stack([src, dst]))
        dense.append(Adj(ei, None, (sizes[i], sizes[i + 1]), fanout=k))
        segment.append(Adj(ei, None, (sizes[i], sizes[i + 1])))
    n_id = rng.permutation(100)[:sizes[0]].astype(np.int32)
    features = rng.normal(size=(100, CFG["feature_dim"])).astype(np.float32)
    labels = rng.integers(0, CFG["classes"], 100).astype(np.int32)
    weights = REFERENCE.make_weights(CFG, rng)
    return (Block(n_id, layers, sizes[-1]), dense, segment, features, labels,
            weights)


def _program_loss_and_grads(weights, features, labels, block, adjs):
    model = PROGRAM.build(CFG)
    x = jnp.asarray(features[block.n_id])
    want = jnp.asarray(labels[block.n_id[:block.num_seeds]])

    def loss_fn(params):
        logp = model.apply({"params": params}, x, adjs)[:block.num_seeds]
        return -jnp.take_along_axis(logp, want[:, None], axis=1).mean()

    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(loss_fn)(
            PROGRAM.to_program_tree(weights))
    return float(loss), PROGRAM.from_program_tree(grads, CFG["layers"])


@pytest.mark.parametrize("path", ["dense", "segment"])
@pytest.mark.parametrize("case", list(BLOCKS))
def test_gat_is_the_published_recipe(case, path):
    """Loss and every leaf's gradient of ``GAT`` against the plain reference
    on seeded random weights, to 1e-5 at ``highest``, through the dense
    fanout path and through the segment path: the self lane, sampled lanes
    ``j == i`` dropped, targets with no valid lane, fanout 1."""
    block, dense, segment, features, labels, weights = _recipe_case(
        **BLOCKS[case])
    ref_loss, ref_grads = REFERENCE.loss_and_grads(
        weights, jnp.asarray(features), jnp.asarray(labels), block)
    loss, grads = _program_loss_and_grads(
        weights, features, labels, block, dense if path == "dense" else segment)
    assert abs(loss - float(ref_loss)) <= 1e-5 * abs(float(ref_loss))
    for i, (got, want) in enumerate(zip(grads, ref_grads)):
        assert set(got) == set(want) == {
            "w", "a_src", "a_dst", "b", "w_skip", "b_skip"}
        for name in want:
            w = np.asarray(want[name])
            assert np.abs(w).max() > 0, (i, name)
            np.testing.assert_allclose(
                got[name], w, rtol=1e-5, atol=1e-5 * np.abs(w).max(),
                err_msg=f"layer{i}.{name}")


# the module's dtype, and how closely its dense path follows its segment
# path: float32 at ``highest`` to round-off; in bfloat16 both paths read the
# same rounded rows and weights and sum the messages in another order (read:
# one ulp of a bfloat16 output, 7.8e-3, and 7.9e-3 of the largest gradient)
DTYPES = {None: dict(rtol=1e-5, atol=1e-6),
          "bfloat16": dict(rtol=2e-2, atol=2e-2)}


@pytest.mark.parametrize("dtype", list(DTYPES), ids=lambda d: d or "float32")
@pytest.mark.parametrize("case", list(BLOCKS))
def test_gatconv_dense_path_is_the_segment_path(case, dtype):
    """One ``GATConv`` on the same block with its fanout and without: the
    self term as a separate operand of the max, the denominator and the sum
    gives what the self edges appended to the edge list give, and a lane's
    logit read off the gathered ``z`` row and broadcast over the fanout
    axis is the one gathered from the per-node halves. Values, and every
    parameter's gradient (``att_l`` and ``att_r`` among them, which the two
    paths sum over lanes and over rows)."""
    rng = np.random.default_rng(3)
    k = BLOCKS[case]["fanouts"][0]
    src, dst = _fanout_block(rng, 10, 24, k, BLOCKS[case].get("edit"))
    ei = jnp.asarray(np.stack([src, dst]))
    x = jnp.asarray(rng.normal(size=(24, 7)).astype(np.float32))
    conv = GATConv(features=5, heads=3, dtype=dtype)
    params = conv.init(jax.random.PRNGKey(0), x, ei, 10)
    weight = jnp.asarray(rng.normal(size=(10, 15)).astype(np.float32))

    def value_and_grads(fanout):
        def scalar(p):
            y = conv.apply(p, x, ei, 10, fanout)
            return (y.astype(jnp.float32) * weight).sum(), y

        with jax.default_matmul_precision("highest"):
            (_, y), grads = jax.value_and_grad(scalar, has_aux=True)(params)
        return y, grads["params"]

    y_dense, g_dense = value_and_grads(k)
    y_segment, g_segment = value_and_grads(None)
    assert y_dense.shape == (10, 15)
    tol = DTYPES[dtype]
    np.testing.assert_allclose(
        np.asarray(y_dense, np.float32), np.asarray(y_segment, np.float32),
        **tol)
    flat = jax.tree_util.tree_leaves_with_path(g_segment)
    assert {jax.tree_util.keystr(path) for path, _ in flat} >= {
        "['att_l']", "['att_r']"}
    for (path, want), got in zip(flat, jax.tree_util.tree_leaves(g_dense)):
        want = np.asarray(want, np.float32)
        assert np.abs(want).max() > 0, path
        np.testing.assert_allclose(
            np.asarray(got, np.float32), want, rtol=tol["rtol"],
            atol=tol["rtol"] * np.abs(want).max(),
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("chunk", [2, 1024])
@pytest.mark.parametrize("dtype", list(DTYPES), ids=lambda d: d or "float32")
@pytest.mark.parametrize("case", list(BLOCKS))
def test_gatconv_dense_backward_is_the_segment_paths(
        case, dtype, chunk, monkeypatch):
    """The dense path's backward goes through ``layers.gather_lane_rows``'s
    rule (a row's cotangent gathered from one of its lanes, the repeats
    added in chunks of ``chunk``, the masked lanes dropped) and projects the
    targets' rows on their own; the segment path keeps the plain transposed
    gather. Both give the same gradient of every parameter and of the
    input rows ``x``: sources below ``num_dst``, sources that several lanes
    name (30 lanes over 24 rows), rows that none does."""
    from quiver_tpu.models import layers

    monkeypatch.setattr(layers, "_REPEAT_CHUNK", chunk)
    rng = np.random.default_rng(7)
    k = BLOCKS[case]["fanouts"][0]
    src, dst = _fanout_block(rng, 10, 24, k, BLOCKS[case].get("edit"))
    ei = jnp.asarray(np.stack([src, dst]))
    x = jnp.asarray(rng.normal(size=(24, 7)).astype(np.float32))
    conv = GATConv(features=5, heads=3, dtype=dtype)
    params = conv.init(jax.random.PRNGKey(0), x, ei, 10)
    weight = jnp.asarray(rng.normal(size=(10, 15)).astype(np.float32))

    def grads(fanout):
        def scalar(p, x):
            y = conv.apply(p, x, ei, 10, fanout)
            return (y.astype(jnp.float32) * weight).sum()

        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.grad(scalar, argnums=(0, 1)))(params, x)

    tol = DTYPES[dtype]["rtol"]
    dense, segment = grads(k), grads(None)
    flat = jax.tree_util.tree_leaves_with_path(segment)
    assert len(flat) == 7        # six parameters and x
    for (path, want), got in zip(flat, jax.tree_util.tree_leaves(dense)):
        want = np.asarray(want, np.float32)
        assert np.abs(want).max() > 0, path
        np.testing.assert_allclose(
            np.asarray(got, np.float32), want, rtol=tol,
            atol=tol * np.abs(want).max(),
            err_msg=jax.tree_util.keystr(path))


def test_a_self_lane_is_not_counted_twice():
    """A target whose every sampled lane is itself attends to itself once:
    its output is its own projected row, as if it had no lane at all."""
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(8, 5)).astype(np.float32))
    own = np.stack([np.full(3, 2, np.int32), np.full(3, 2, np.int32)])
    none = np.full((2, 3), -1, np.int32)
    conv = GATConv(features=4, heads=2)
    params = conv.init(jax.random.PRNGKey(0), x, jnp.asarray(own), 3)
    for fanout in (None, 1):
        a = conv.apply(params, x, jnp.asarray(own), 3, fanout)
        b = conv.apply(params, x, jnp.asarray(none), 3, fanout)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_the_output_layer_averages_its_heads():
    """``GAT``'s last layer has the configured heads, not one: its
    attention leaves are ``(heads, classes)``, its output is ``classes``
    wide, and it is the mean over the heads of what the same weights give
    with the heads concatenated."""
    block, dense, _, features, _, weights = _recipe_case((3, 2, 4))
    model = PROGRAM.build(CFG)
    x = jnp.asarray(features[block.n_id])
    params = model.init(jax.random.PRNGKey(0), x, dense)["params"]
    heads, classes = CFG["heads"], CFG["classes"]
    for i in range(CFG["layers"]):
        last = i == CFG["layers"] - 1
        width = classes if last else CFG["hidden"]
        conv = params[f"conv{i}"]
        assert conv["att_l"].shape == conv["att_r"].shape == (heads, width)
        assert conv["skip"]["kernel"].shape[1] == conv["bias"].shape[0] == (
            width if last else heads * width)
    assert model.apply({"params": params}, x, dense).shape == (6, classes)

    rng = np.random.default_rng(5)
    h = jnp.asarray(rng.normal(size=(10, 9)).astype(np.float32))
    ei = dense[-1].edge_index
    mean = GATConv(features=classes, heads=heads, concat=False)
    cat = GATConv(features=classes, heads=heads, concat=True)
    p = mean.init(jax.random.PRNGKey(1), h, ei, 6)["params"]
    p_cat = dict(p, bias=jnp.zeros((heads * classes,)),
                 skip=jax.tree_util.tree_map(
                     lambda leaf: jnp.zeros(
                         leaf.shape[:-1] + (heads * classes,)), p["skip"]))
    y_cat = cat.apply({"params": p_cat}, h, ei, 6).reshape(6, heads, classes)
    skip = h[:6] @ p["skip"]["kernel"] + p["skip"]["bias"]
    np.testing.assert_allclose(
        np.asarray(mean.apply({"params": p}, h, ei, 6)),
        np.asarray(y_cat.mean(axis=1) + p["bias"] + skip),
        rtol=1e-5, atol=1e-6)


def test_the_parameter_tree_round_trips_through_the_benchmarks_model_file():
    """``chipbench/models/gat.py`` puts the harness's leaves where the
    module's own ``init`` puts its parameters, shape for shape, and reads
    them back as they were."""
    block, dense, _, features, _, weights = _recipe_case((3, 2, 4))
    model = PROGRAM.build(CFG)
    x = jnp.asarray(features[block.n_id])
    own = model.init(jax.random.PRNGKey(0), x, dense)["params"]
    tree = PROGRAM.to_program_tree(weights)
    shapes = jax.tree_util.tree_map(np.shape, tree)
    assert shapes == jax.tree_util.tree_map(np.shape, dict(own))
    back = PROGRAM.from_program_tree(tree, CFG["layers"])
    for got, want in zip(back, weights):
        assert set(got) == set(want)
        for name in want:
            assert np.array_equal(got[name], want[name])
    assert all(np.abs(w[a]).min() > 0 for w in weights
               for a in ("a_src", "a_dst"))
    assert REFERENCE.layer_dims(dict(CFG)) == [
        (12, 24, 4, 6), (24, 24, 4, 6), (24, 5, 4, 5)]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_gat_reference_in_bfloat16_comes_out_not_correct(seed):
    """The benchmark's control for this model at a tiny size: the plain
    reference computed in bfloat16 and put in the program's place fails at
    least one compared number; computed in float32 it passes all."""
    from chipbench import check, inputs
    from chipbench.tests import tiny
    from chipbench.tests.test_chipbench import host_block

    cfg = dict(tiny.tiny_config("products-gat"), hidden=16, batch=32)
    data = inputs.make_inputs(cfg, seed)
    weights0 = inputs.make_weights(cfg, seed)
    feed = inputs.Feed(cfg["graph"]["nodes"], cfg["batch"], seed)
    rng = np.random.default_rng(seed)
    steps = [[host_block(data, feed.seeds(i), cfg["fanout"], rng)]
             for i in range(2)]
    feats, labels = jnp.asarray(data.features), jnp.asarray(data.labels)
    ref = REFERENCE.train(weights0, feats, labels, steps, cfg["optimizer"])
    for dtype, passes in ((jnp.float32, True), (jnp.bfloat16, False)):
        side = REFERENCE.train(weights0, feats, labels, steps,
                               cfg["optimizer"], param_dtype=dtype,
                               compute_dtype=dtype)
        values = check.numbers(REFERENCE, *side, *ref, weights0)
        ok, table = check.verdict(values, tiny.LIMITS)
        assert ok is passes, table
