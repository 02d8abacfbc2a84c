"""Feature store tests: gather-vs-dense differential (the reference's oracle
pattern, test_features.py:338-339 `np.array_equal(res, tensor[indices])`),
budget parsing, reorder integration, cold-tier correctness."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from quiver_tpu import CSRTopo
from quiver_tpu.feature.feature import Feature
from quiver_tpu.utils.graphgen import generate_pareto_graph


def _table(n=200, f=8, seed=0):
    return np.random.default_rng(seed).normal(size=(n, f)).astype(np.float32)


def test_all_hot_matches_dense():
    t = _table()
    feat = Feature(device_cache_size="1G").from_cpu_tensor(t)
    assert feat.hot_rows == 200 and feat.cold is None
    ids = np.random.default_rng(1).integers(0, 200, 64)
    out = np.asarray(feat[jnp.asarray(ids)])
    assert np.allclose(out, t[ids])


def test_all_cold_matches_dense():
    t = _table()
    feat = Feature(device_cache_size=0).from_cpu_tensor(t)
    assert feat.hot is None and feat.cold is not None
    ids = np.random.default_rng(2).integers(0, 200, 50)
    out = np.asarray(feat[jnp.asarray(ids)])
    assert np.allclose(out, t[ids])


def test_mixed_tiers_match_dense():
    t = _table()
    row_bytes = 8 * 4
    feat = Feature(device_cache_size=60 * row_bytes).from_cpu_tensor(t)
    assert feat.hot_rows == 60
    assert feat.hot.shape == (60, 8) and feat.cold.shape == (140, 8)
    ids = np.random.default_rng(3).integers(0, 200, 100)
    out = np.asarray(feat[jnp.asarray(ids)])
    assert np.allclose(out, t[ids])


def test_invalid_ids_zero_rows():
    t = _table()
    feat = Feature(device_cache_size="1M").from_cpu_tensor(t)
    ids = jnp.array([3, -1, 7, -1])
    out = np.asarray(feat[ids])
    assert np.allclose(out[0], t[3]) and np.allclose(out[2], t[7])
    assert np.all(out[1] == 0) and np.all(out[3] == 0)


def test_degree_reorder_transparent():
    # with csr_topo, Feature reorders rows hot-first but lookups by original
    # id must still return the original rows (feature_order translation,
    # reference feature.py:184-195)
    ei = generate_pareto_graph(200, 6.0, seed=5)
    topo = CSRTopo(edge_index=ei)
    t = _table(topo.node_count, 8)
    row_bytes = 8 * 4
    feat = Feature(device_cache_size=50 * row_bytes, csr_topo=topo).from_cpu_tensor(t)
    assert topo.feature_order is not None
    ids = np.random.default_rng(4).integers(0, topo.node_count, 80)
    out = np.asarray(feat[jnp.asarray(ids)])
    assert np.allclose(out, t[ids])
    # hot tier actually holds the high-degree nodes
    deg = topo.degree
    hot_nodes = np.where(np.asarray(feat.feature_order) < feat.hot_rows)[0]
    cold_nodes = np.where(np.asarray(feat.feature_order) >= feat.hot_rows)[0]
    assert deg[hot_nodes].min() >= deg[cold_nodes].max()


def test_lookup_inside_jit():
    t = _table()
    feat = Feature(device_cache_size=100 * 8 * 4).from_cpu_tensor(t)

    @jax.jit
    def f(feat, ids):
        return feat[ids].sum(axis=1)

    ids = jnp.array([1, 5, 150, -1])
    out = np.asarray(f(feat, ids))
    expect = t[[1, 5, 150]].sum(axis=1)
    assert np.allclose(out[:3], expect, rtol=1e-5)
    assert out[3] == 0


def test_feature_delete_frees_buffers():
    """shard_tensor.delete parity (SURVEY §2.5): buffers freed, object inert."""
    import pytest as _pytest

    feat = np.random.default_rng(0).normal(size=(100, 8)).astype(np.float32)
    f = Feature(device_cache_size=50 * 8 * 4).from_cpu_tensor(feat)
    hot = f.hot
    f.delete()
    assert f.hot is None and f.cold is None and f.hot_rows == 0
    with _pytest.raises(RuntimeError):
        _ = np.asarray(hot)  # buffer really gone


def _store(kind, t, **kw):
    row_bytes = t.shape[1] * 4
    if kind == "Feature":
        return Feature(device_cache_size=100 * row_bytes, **kw
                       ).from_cpu_tensor(t)
    from quiver_tpu.feature.shard import ShardedFeature
    from quiver_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(n_devices=2, data=1, feature=2)
    return ShardedFeature(mesh, device_cache_size=50 * row_bytes, **kw
                          ).from_cpu_tensor(t)


@pytest.mark.parametrize("kind", ["Feature", "ShardedFeature"])
def test_kernel_auto_and_xla_are_one_path(kind):
    """The keyword names no second gather: the default, "auto" and "xla"
    trace to the same program, and it is the dense take (mixed hot/cold
    tier split and -1 lanes included)."""
    t = _table(n=300, f=16, seed=3)
    ids = jnp.asarray(
        np.concatenate([np.random.default_rng(4).integers(0, 300, 60), [-1, -1]])
    )
    stores = [_store(kind, t), _store(kind, t, kernel="auto"),
              _store(kind, t, kernel="xla")]
    programs = {str(jax.make_jaxpr(lambda i, f=f: f[i])(ids)) for f in stores}
    assert len(programs) == 1
    out = np.asarray(stores[0][ids])
    assert np.array_equal(out[:60], t[np.asarray(ids)[:60]])
    assert np.all(out[60:] == 0)
    assert not hasattr(stores[0], "kernel")


@pytest.mark.parametrize("kind", ["Feature", "ShardedFeature"])
def test_kernel_pallas_is_refused(kind):
    with pytest.raises(ValueError, match="removed"):
        _store(kind, _table(), kernel="pallas")
    with pytest.raises(ValueError, match="kernel"):
        _store(kind, _table(), kernel="cuda")


def test_bf16_storage_doubles_cache_rows_and_stays_close():
    """dtype="bfloat16": half the row bytes => twice the hot rows for the
    same budget; gathered values match f32 within bf16 precision."""
    t = _table(n=400, f=16, seed=5)
    row_bytes_f32 = 16 * 4
    budget = 100 * row_bytes_f32
    f32 = Feature(device_cache_size=budget).from_cpu_tensor(t)
    bf16 = Feature(device_cache_size=budget, dtype="bf16").from_cpu_tensor(t)
    assert f32.hot_rows == 100 and bf16.hot_rows == 200
    ids = jnp.asarray(np.random.default_rng(6).integers(0, 400, 64))
    out = np.asarray(bf16[ids], dtype=np.float32)
    assert out.dtype == np.float32
    np.testing.assert_allclose(out, t[np.asarray(ids)], rtol=1e-2, atol=1e-2)


def test_bf16_model_learns():
    """Mixed-precision GraphSAGE (bf16 compute, f32 params) must train: the
    TPU recipe the fp32-only reference has no analogue of."""
    import optax

    from quiver_tpu import GraphSageSampler
    from quiver_tpu.models.sage import GraphSAGE
    from quiver_tpu.parallel.train import init_model, make_train_step
    from quiver_tpu.utils.graphgen import generate_pareto_graph

    ei = generate_pareto_graph(600, 8.0, seed=7)
    topo = CSRTopo(edge_index=ei)
    feat = _table(n=600, f=12, seed=8)
    labels = np.random.default_rng(9).integers(0, 4, 600)
    feat[np.arange(600), labels % 12] += 2.0  # learnable signal
    feature = Feature(device_cache_size="1G", dtype="bf16").from_cpu_tensor(feat)
    sampler = GraphSageSampler(topo, [5, 3], seed=0)
    model = GraphSAGE(hidden=32, num_classes=4, num_layers=2, dtype="bfloat16")
    out = sampler.sample(np.arange(128))
    x = feature[out.n_id]
    assert x.dtype == jnp.bfloat16
    params = init_model(model, jax.random.PRNGKey(0), x, out.adjs)
    # params stay f32 (mixed precision, not half-precision weights)
    assert all(
        p.dtype == jnp.float32 for p in jax.tree_util.tree_leaves(params)
    )
    tx = optax.adam(1e-2)
    opt_state = tx.init(params)
    step = jax.jit(make_train_step(model, tx))
    labels_all = jnp.asarray(labels)
    losses = []
    for i in range(15):
        seeds = np.random.default_rng(i).integers(0, 600, 128)
        out = sampler.sample(seeds)
        seed_ids = out.n_id[:128]
        params, opt_state, loss = step(
            params, opt_state, feature[out.n_id], out.adjs,
            labels_all[jnp.clip(seed_ids, 0)], seed_ids >= 0,
            jax.random.PRNGKey(i),
        )
        losses.append(float(loss))
    assert losses[-1] < losses[0] and np.isfinite(losses).all()


def test_int8_quantized_storage_accuracy_and_budget():
    """dtype="int8": ~4x the rows of f32 per budget (the WHOLE (N,) f32
    scale array is HBM-resident — both tiers dequantize on device — so all
    N*4 scale bytes are charged up front); every gathered element within
    the absmax/254 quantization bound; -1 lanes still zero."""
    t = _table(n=400, f=16, seed=10)
    row_bytes_f32 = 16 * 4
    budget = 100 * row_bytes_f32
    q = Feature(device_cache_size=budget, dtype="int8").from_cpu_tensor(t)
    assert q.hot_rows == (budget - 4 * 400) // 16  # 300
    assert q.cold is not None  # mixed tiers exercised
    ids = np.concatenate(
        [np.random.default_rng(11).integers(0, 400, 80), [-1, -1]]
    )
    out = np.asarray(q[jnp.asarray(ids)])
    assert out.dtype == np.float32
    bound = (np.abs(t).max(axis=1) / 254.0 + 1e-7)[ids[:80]][:, None]
    assert np.all(np.abs(out[:80] - t[ids[:80]]) <= bound)
    assert np.all(out[80:] == 0)


def test_int8_zero_rows_exact():
    t = _table(n=50, f=8, seed=12)
    t[7] = 0.0
    q = Feature(device_cache_size="1G", dtype="int8").from_cpu_tensor(t)
    out = np.asarray(q[jnp.asarray([7])])
    assert np.all(out == 0)
