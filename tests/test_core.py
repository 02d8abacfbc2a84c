"""Core container tests: byte-size parser, CSRTopo round-trip, reorder invariant.

Mirrors the reference's test strategy (SURVEY §4): CSR construction
round-trip property tests and the reorder invariant of
test_graph_reindex.py:35-70.
"""

import numpy as np
import pytest

from quiver_tpu import CSRTopo, parse_size_bytes, reorder_by_degree
from quiver_tpu.core.config import CachePolicy, SampleMode
from quiver_tpu.utils.graphgen import generate_pareto_graph


def test_parse_size_bytes():
    assert parse_size_bytes("1K") == 1024
    assert parse_size_bytes("0.9M") == int(0.9 * 2**20)
    assert parse_size_bytes("3GB") == 3 * 2**30
    assert parse_size_bytes("2g") == 2 * 2**30
    assert parse_size_bytes(4096) == 4096
    assert parse_size_bytes("512") == 512
    with pytest.raises(ValueError):
        parse_size_bytes("12X")
    with pytest.raises(ValueError):
        parse_size_bytes("abc")


def test_policy_and_mode_parsing():
    assert CachePolicy.parse("p2p_clique_replicate") is CachePolicy.MESH_SHARD
    assert CachePolicy.parse("device_replicate") is CachePolicy.DEVICE_REPLICATE
    assert SampleMode.parse("UVA") is SampleMode.HOST
    assert SampleMode.parse("GPU") is SampleMode.HBM
    with pytest.raises(ValueError):
        SampleMode.parse("nope")


def test_csr_from_coo_roundtrip():
    # property test: build CSR from COO, export edge set back, compare
    # (reference tests/cpp/test_quiver.cu:122-165)
    rng = np.random.default_rng(0)
    n, e = 50, 400
    row = rng.integers(0, n, e)
    col = rng.integers(0, n, e)
    topo = CSRTopo(edge_index=np.stack([row, col]))
    assert topo.node_count >= max(row.max(), col.max()) + 1
    assert topo.edge_count == e
    # reconstruct COO from CSR
    re_row = np.repeat(np.arange(topo.node_count), topo.degree)
    re_edges = set(zip(re_row.tolist(), topo.indices.tolist()))
    orig_edges = set(zip(row.tolist(), col.tolist()))
    assert re_edges == orig_edges
    # eid maps CSR slots back to original COO positions
    assert np.all(row[topo.eid] == re_row)
    assert np.all(col[topo.eid] == topo.indices)


def test_csr_from_indptr_indices():
    indptr = np.array([0, 2, 2, 5])
    indices = np.array([1, 2, 0, 1, 2])
    topo = CSRTopo(indptr=indptr, indices=indices)
    assert topo.node_count == 3
    assert topo.edge_count == 5
    assert list(topo.degree) == [2, 0, 3]
    assert topo.max_degree == 3


def test_csr_degree_matches_bincount():
    ei = generate_pareto_graph(1000, 8.0, seed=1)
    topo = CSRTopo(edge_index=ei)
    expect = np.bincount(ei[0], minlength=topo.node_count)
    assert np.array_equal(topo.degree, expect)


def test_feature_order_slot():
    topo = CSRTopo(indptr=np.array([0, 1, 2]), indices=np.array([1, 0]))
    order = np.array([1, 0])
    topo.feature_order = order
    assert np.array_equal(topo.feature_order, order)
    with pytest.raises(ValueError):
        topo.feature_order = np.array([0, 1, 2])


def test_reorder_invariant():
    # original_feature[ids] == new_feature[new_order[ids]]
    rng = np.random.default_rng(0)
    n, f = 300, 16
    feat = rng.normal(size=(n, f)).astype(np.float32)
    deg = rng.integers(0, 100, n)
    new_feat, new_order = reorder_by_degree(feat, deg, hot_ratio=0.3, seed=7)
    ids = rng.integers(0, n, 64)
    assert np.allclose(feat[ids], new_feat[new_order[ids]])
    # hot prefix owns the highest-degree nodes
    hot = int(n * 0.3)
    hot_nodes = np.where(new_order < hot)[0]
    cold_nodes = np.where(new_order >= hot)[0]
    assert deg[hot_nodes].min() >= deg[cold_nodes].max() - 0  # sorted split


def test_csr_save_load_roundtrip(tmp_path):
    """save/load preserves CSR arrays, eid, CSR-ordered weights (and their
    prefix sums), and feature_order."""
    rng = np.random.default_rng(5)
    ei = rng.integers(0, 50, (2, 400))
    topo = CSRTopo(edge_index=ei)
    topo.set_edge_weight(rng.random(400).astype(np.float32), coo_order=True)
    topo.feature_order = np.asarray(rng.permutation(topo.node_count))

    p = str(tmp_path / "topo.npz")
    topo.save(p)
    back = CSRTopo.load(p)

    np.testing.assert_array_equal(topo.indptr, back.indptr)
    np.testing.assert_array_equal(topo.indices, back.indices)
    np.testing.assert_array_equal(topo.eid, back.eid)
    np.testing.assert_array_equal(topo.feature_order, back.feature_order)
    np.testing.assert_allclose(topo.edge_weight, back.edge_weight)
    np.testing.assert_allclose(topo.cum_weights, back.cum_weights)


def test_csr_save_load_minimal(tmp_path):
    """A weightless, orderless topology round-trips too (optional arrays
    absent from the npz, not stored as empties)."""
    ei = np.array([[0, 1, 2], [1, 2, 0]])
    topo = CSRTopo(edge_index=ei)
    p = str(tmp_path / "t.npz")
    topo.save(p)
    back = CSRTopo.load(p)
    np.testing.assert_array_equal(topo.indptr, back.indptr)
    np.testing.assert_array_equal(topo.indices, back.indices)
    assert back.edge_weight is None and back.feature_order is None


def test_resolve_platform_strategy_edge_cases(monkeypatch):
    """The shared env-override resolver behind every strategy knob
    (QUIVER_COUNTS, QUIVER_INFER_AGG): graftlint's
    env-at-trace rule points users at this helper, so its contract is
    pinned here — empty/whitespace fall through to the platform default,
    values are case/whitespace-normalized, and a typo'd FORCE raises with
    an actionable message instead of silently measuring the default."""
    import pytest

    from quiver_tpu.core.config import resolve_platform_strategy

    choices = ("scan", "scatter")

    def resolve():
        return resolve_platform_strategy(
            "QUIVER_TEST_STRAT", choices, tpu_default="scan",
            other_default="scatter",
        )

    # unset / empty / whitespace-only -> platform default (cpu here)
    monkeypatch.delenv("QUIVER_TEST_STRAT", raising=False)
    assert resolve() == "scatter"
    monkeypatch.setenv("QUIVER_TEST_STRAT", "")
    assert resolve() == "scatter"
    monkeypatch.setenv("QUIVER_TEST_STRAT", "   ")
    assert resolve() == "scatter"

    # case and surrounding whitespace are normalized, not rejected
    monkeypatch.setenv("QUIVER_TEST_STRAT", "  SCAN  ")
    assert resolve() == "scan"
    monkeypatch.setenv("QUIVER_TEST_STRAT", "Scatter")
    assert resolve() == "scatter"

    # a typo'd force must raise, naming the var, the value, and the menu
    monkeypatch.setenv("QUIVER_TEST_STRAT", "scann")
    with pytest.raises(ValueError) as ei:
        resolve()
    msg = str(ei.value)
    assert "QUIVER_TEST_STRAT" in msg and "scann" in msg
    assert "scan" in msg and "scatter" in msg


def test_to_pinned_host_is_loud_off_cpu(monkeypatch):
    """On a TPU the cold tier must land in pinned_host: a placement that
    comes back in device memory raises instead of quietly filling HBM. The
    CPU backend still degrades when it has no such memory space."""
    import jax

    from quiver_tpu.core import memory

    x = np.arange(12, dtype=np.float32).reshape(3, 4)
    real_put, real_dev = jax.device_put, jax.devices()[0]

    class FakeTpu:
        platform = "tpu"

        def addressable_memories(self):
            return []

    def put_in_hbm(arr, sharding):  # the placement silently ignored
        return real_put(arr, real_dev)

    with monkeypatch.context() as m:
        m.setattr(memory.jax, "devices", lambda: [FakeTpu()])
        m.setattr(memory.jax, "device_put", put_in_hbm)
        m.setattr(memory, "SingleDeviceSharding", lambda d, **kw: None)
        with pytest.raises(RuntimeError, match="pinned_host"):
            memory.to_pinned_host(x)

    class CpuNoPinned:
        platform = "cpu"

        def addressable_memories(self):
            return []

    with monkeypatch.context() as m:
        m.setattr(memory.jax, "devices", lambda: [CpuNoPinned()])
        arr, is_host = memory.to_pinned_host(x)
    assert not is_host
    np.testing.assert_array_equal(np.asarray(arr), x)

    arr, is_host = memory.to_pinned_host(x)  # the real backend
    assert is_host == (arr.sharding.memory_kind == "pinned_host")
    np.testing.assert_array_equal(np.asarray(arr), x)
