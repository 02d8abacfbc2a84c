"""Sharded tensor/feature tests on the 8-device virtual mesh.

Oracle: gather-vs-dense differential, exactly like the reference's
multi-GPU ShardTensor tests (test_shard_tensor.py:70-71) but on a simulated
mesh the reference never had (SURVEY §4 closing note)."""

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
import pytest

from quiver_tpu import CSRTopo
from quiver_tpu.feature.shard import ShardedFeature, ShardedTensor
from quiver_tpu.parallel.mesh import MeshTopo, make_mesh, can_device_access_peer
from quiver_tpu.utils.graphgen import generate_pareto_graph


def _mesh(data=4, feature=2):
    return make_mesh(data=data, feature=feature)


def test_sharded_tensor_matches_dense():
    mesh = _mesh()
    t = np.random.default_rng(0).normal(size=(1000, 32)).astype(np.float32)
    st = ShardedTensor(mesh).from_cpu_tensor(t)
    assert st.rows_per_shard == 500
    ids = np.random.default_rng(1).integers(0, 1000, 64)
    out = np.asarray(st[jnp.asarray(ids)])
    assert np.allclose(out, t[ids])


def test_sharded_tensor_data_sharded_ids():
    mesh = _mesh()
    t = np.random.default_rng(0).normal(size=(640, 16)).astype(np.float32)
    st = ShardedTensor(mesh).from_cpu_tensor(t)
    ids = np.random.default_rng(2).integers(0, 640, 128)
    ids_sharded = jax.device_put(
        jnp.asarray(ids), NamedSharding(mesh, P("data"))
    )
    out = np.asarray(st[ids_sharded])
    assert np.allclose(out, t[ids])


def test_sharded_tensor_uneven_rows():
    mesh = _mesh(data=2, feature=4)
    t = np.random.default_rng(3).normal(size=(37, 8)).astype(np.float32)
    st = ShardedTensor(mesh).from_cpu_tensor(t)
    ids = np.arange(37)
    out = np.asarray(st[jnp.asarray(ids)])
    assert np.allclose(out, t)


def test_sharded_feature_hot_only():
    mesh = _mesh()
    t = np.random.default_rng(4).normal(size=(500, 16)).astype(np.float32)
    feat = ShardedFeature(mesh, device_cache_size="1G").from_cpu_tensor(t)
    assert feat.hot_rows == 500 and feat.cold is None
    ids = np.random.default_rng(5).integers(0, 500, 64)
    out = np.asarray(feat[jnp.asarray(ids)])
    assert np.allclose(out, t[ids])


def test_sharded_feature_mixed_tiers():
    mesh = _mesh()
    t = np.random.default_rng(6).normal(size=(400, 8)).astype(np.float32)
    row_bytes = 8 * 4
    # per-device budget of 30 rows x 2 shards = 60 hot rows
    feat = ShardedFeature(mesh, device_cache_size=30 * row_bytes).from_cpu_tensor(t)
    assert feat.hot_rows == 60
    ids = np.random.default_rng(7).integers(0, 400, 100)
    out = np.asarray(feat[jnp.asarray(ids)])
    assert np.allclose(out, t[ids])


def test_sharded_feature_int8_quantized():
    """int8 over the mesh: psum'd int8 gather + on-device dequant must land
    within the per-row quantization bound; budget charges the replicated
    scale array first."""
    mesh = _mesh()
    n, f = 400, 8
    t = np.random.default_rng(8).normal(size=(n, f)).astype(np.float32)
    budget = 4 * n + 30 * f  # scale bytes + 30 int8 rows per device
    feat = ShardedFeature(
        mesh, device_cache_size=budget, dtype="int8"
    ).from_cpu_tensor(t)
    assert feat.hot_rows == 60  # 30 rows x 2 feature shards
    assert feat.cold is not None
    ids = np.concatenate(
        [np.random.default_rng(9).integers(0, n, 80), [-1, -1]]
    )
    out = np.asarray(feat[jnp.asarray(ids)])
    assert out.dtype == np.float32
    bound = (np.abs(t).max(axis=1) / 254.0 + 1e-7)[ids[:80]][:, None]
    assert np.all(np.abs(out[:80] - t[ids[:80]]) <= bound)
    assert np.all(out[80:] == 0)


def test_sharded_feature_bf16():
    mesh = _mesh()
    t = np.random.default_rng(10).normal(size=(300, 8)).astype(np.float32)
    feat = ShardedFeature(
        mesh, device_cache_size="1G", dtype="bf16"
    ).from_cpu_tensor(t)
    ids = np.random.default_rng(11).integers(0, 300, 64)
    out = np.asarray(feat[jnp.asarray(ids)], dtype=np.float32)
    np.testing.assert_allclose(out, t[ids], rtol=1e-2, atol=1e-2)


def test_sharded_feature_reorder_and_invalid():
    ei = generate_pareto_graph(300, 6.0, seed=8)
    topo = CSRTopo(edge_index=ei)
    mesh = _mesh()
    t = np.random.default_rng(8).normal(size=(topo.node_count, 8)).astype(np.float32)
    feat = ShardedFeature(mesh, device_cache_size=20 * 32, csr_topo=topo).from_cpu_tensor(t)
    ids = np.array([5, -1, 17, 200])
    out = np.asarray(feat[jnp.asarray(ids)])
    assert np.allclose(out[0], t[5]) and np.allclose(out[2], t[17]) and np.allclose(out[3], t[200])
    assert np.all(out[1] == 0)


def test_mesh_topo_cliques():
    topo = MeshTopo()
    assert sum(len(c) for c in topo.cliques) == len(jax.devices())
    # virtual CPU devices share slice 0 -> one clique
    assert len(topo.cliques) == 1
    assert can_device_access_peer(0, 7)
    assert "Clique 0" in topo.info


def test_sharded_tensor_routed_standalone_matches_psum_and_dense():
    """gather(routed=True) — ids sharded over every axis, owner-routed via
    all_to_all — must equal the psum gather and the dense oracle, across
    odd (padded) lengths."""
    import numpy as np
    import jax.numpy as jnp

    from quiver_tpu.feature.shard import ShardedTensor
    from quiver_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(data=2, feature=4)
    rng = np.random.default_rng(3)
    table = rng.normal(size=(777, 12)).astype(np.float32)
    st = ShardedTensor(mesh).from_cpu_tensor(table)
    for n in (8, 301, 777):
        ids = rng.integers(0, 777, n).astype(np.int32)
        a = np.asarray(st.gather(jnp.asarray(ids)))
        b = np.asarray(st.gather(jnp.asarray(ids), routed=True))
        assert np.array_equal(a, table[ids])
        assert np.array_equal(b, table[ids])


def test_sharded_feature_routed_matches_psum():
    """ShardedFeature.gather(routed=True) must equal the psum gather and
    the dense oracle, including through feature_order translation."""
    import numpy as np
    import jax.numpy as jnp

    from quiver_tpu import CSRTopo
    from quiver_tpu.feature.shard import ShardedFeature
    from quiver_tpu.parallel.mesh import make_mesh

    rng = np.random.default_rng(5)
    ei = np.stack([rng.integers(0, 400, 3000), rng.integers(0, 400, 3000)])
    topo = CSRTopo(edge_index=ei)
    n = topo.node_count
    feat = rng.normal(size=(n, 8)).astype(np.float32)
    mesh = make_mesh(data=2, feature=4)
    store = ShardedFeature(mesh, device_cache_size="1G",
                           csr_topo=topo).from_cpu_tensor(feat)
    ids = rng.integers(0, n, 96).astype(np.int32)
    a = np.asarray(store[jnp.asarray(ids)])
    b = np.asarray(store.gather(jnp.asarray(ids), routed=True))
    assert np.array_equal(a, feat[ids])
    assert np.array_equal(b, feat[ids])


def test_sharded_feature_int8_routed_dequant():
    """int8 quantized rows through the routed gather must dequantize the
    same as through the psum gather (scale indexing uses original ids)."""
    import numpy as np
    import jax.numpy as jnp

    from quiver_tpu import CSRTopo
    from quiver_tpu.feature.shard import ShardedFeature
    from quiver_tpu.parallel.mesh import make_mesh

    rng = np.random.default_rng(8)
    ei = np.stack([rng.integers(0, 300, 2000), rng.integers(0, 300, 2000)])
    topo = CSRTopo(edge_index=ei)
    n = topo.node_count
    feat = rng.normal(size=(n, 16)).astype(np.float32)
    mesh = make_mesh(data=2, feature=4)
    store = ShardedFeature(mesh, device_cache_size="1G", csr_topo=topo,
                           dtype="int8").from_cpu_tensor(feat)
    ids = rng.integers(0, n, 64).astype(np.int32)
    a = np.asarray(store[jnp.asarray(ids)])
    b = np.asarray(store.gather(jnp.asarray(ids), routed=True))
    assert np.array_equal(a, b)
    # dequant error bounded by absmax/254 per row
    err = np.abs(a - feat[ids]).max(axis=1)
    bound = np.abs(feat[ids]).max(axis=1) / 254 + 1e-7
    assert np.all(err <= bound)
