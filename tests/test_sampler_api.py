"""End-to-end GraphSageSampler contract tests (PyG-compat output)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from quiver_tpu import CSRTopo, GraphSageSampler
from quiver_tpu.sampling.saint import SAINTEdgeSampler, SAINTNodeSampler
from quiver_tpu.utils.graphgen import generate_pareto_graph


def _sampler(n=400, avg_deg=8.0, sizes=(5, 3), **kw):
    ei = generate_pareto_graph(n, avg_deg, seed=0)
    topo = CSRTopo(edge_index=ei)
    return topo, GraphSageSampler(topo, sizes, **kw)


def test_sample_output_shapes_and_seed_prefix():
    topo, sampler = _sampler()
    seeds = np.arange(10, 74)
    out = sampler.sample(seeds)
    assert out.batch_size == 64
    n_id = np.asarray(out.n_id)
    # n_id[:batch_size] == seeds (PyG label contract)
    assert np.array_equal(n_id[:64], seeds)
    assert len(out.adjs) == 2
    # deepest layer first: adjs[0] target count == layer-1 frontier cap
    assert out.adjs[0].size[1] == out.adjs[1].size[0]
    assert int(out.overflow) == 0


def test_sampled_edges_exist_in_graph():
    ei = generate_pareto_graph(300, 5.0, seed=2)
    topo = CSRTopo(edge_index=ei)
    sampler = GraphSageSampler(topo, [4, 3])
    edge_set = set(zip(ei[0].tolist(), ei[1].tolist()))

    seeds = np.random.default_rng(0).choice(300, 32, replace=False)
    out = sampler.sample(seeds)
    n_id = np.asarray(out.n_id)

    # walk adjs from deepest to shallowest, reconstructing global edges
    # adjs[-1] is the layer sampled directly from the seeds
    for li, adj in enumerate(reversed(out.adjs)):
        edge_index = np.asarray(adj.edge_index)
        src, dst = edge_index
        valid = src >= 0
        assert np.array_equal(valid, dst >= 0)
        gsrc = n_id[src[valid]]
        gdst = n_id[dst[valid]]
        for s, d in zip(gdst.tolist(), gsrc.tolist()):
            # target (seed-side) -> source (neighbor) must be a real edge
            assert (s, d) in edge_set


def test_full_neighborhood_fanout():
    ei = np.stack([np.array([0, 0, 0, 1, 2]), np.array([1, 2, 3, 2, 3])])
    topo = CSRTopo(edge_index=ei)
    sampler = GraphSageSampler(topo, [-1])
    out = sampler.sample(np.array([0, 1, 2, 3]))
    adj = out.adjs[0]
    src = np.asarray(adj.edge_index[0])
    dst = np.asarray(adj.edge_index[1])
    n_id = np.asarray(out.n_id)
    # node 0 (seed-local id 0) has 3 neighbors; all must be present
    got = sorted(n_id[src[(src >= 0) & (dst == 0)]].tolist())
    assert got == [1, 2, 3]


def test_determinism_under_seed():
    topo, s1 = _sampler(seed=42)
    _, s2 = _sampler(seed=42)
    seeds = np.arange(32)
    a = s1.sample(seeds)
    b = s2.sample(seeds)
    assert np.array_equal(np.asarray(a.n_id), np.asarray(b.n_id))
    for x, y in zip(a.adjs, b.adjs):
        assert np.array_equal(np.asarray(x.edge_index), np.asarray(y.edge_index))
    # and successive calls differ (fresh key per call)
    c = s1.sample(seeds)
    assert not np.array_equal(np.asarray(a.adjs[0].edge_index), np.asarray(c.adjs[0].edge_index))


def test_multilayer_frontier_growth_and_reuse():
    topo, sampler = _sampler(sizes=(6, 4, 2))
    out = sampler.sample(np.arange(16))
    assert len(out.adjs) == 3
    n_id = np.asarray(out.n_id)
    n_count = int(out.n_count)
    # all ids valid in prefix, -1 after
    assert np.all(n_id[:n_count] >= 0)
    assert np.all(n_id[n_count:] == -1)
    # no duplicate node ids in frontier
    vals = n_id[:n_count]
    assert len(np.unique(vals)) == len(vals)


@pytest.mark.parametrize("mode,extras", [
    ("HBM", {}), ("HBM", {"with_eid": True}), ("HBM", {"with_weights": True}),
    ("HBM", {"with_times": True}), ("HOST", {}), ("HOST", {"with_eid": True}),
])
def test_edge_count_is_the_csrs_on_a_padded_placement(mode, extras):
    """A placement may hold `indices` padded to whole 128-word blocks;
    `edge_count` stays the CSR's, through a pytree round trip and as a
    static value inside jit."""
    ei = generate_pareto_graph(300, 9.0, seed=3)
    topo = CSRTopo(edge_index=ei)
    assert topo.edge_count % 128
    if extras.get("with_weights"):
        topo.set_edge_weight(np.ones(topo.edge_count))
    if extras.get("with_times"):
        topo.set_edge_time(np.arange(topo.edge_count, dtype=np.float64))
    dev = topo.to_device(mode, **extras)
    assert dev.edge_count == topo.edge_count
    assert dev.indices.shape[0] >= topo.edge_count
    if mode == "HBM":
        assert dev.indices.shape[0] == -(-topo.edge_count // 128) * 128
    for name in ("eid", "cum_weights", "edge_time"):
        arr = getattr(dev, name)
        assert arr is None or arr.shape[0] == topo.edge_count  # not padded
    leaves, treedef = jax.tree_util.tree_flatten(dev)
    back = treedef.unflatten(leaves)
    assert back.edge_count == topo.edge_count
    assert back.max_degree == dev.max_degree
    assert jax.jit(lambda d: d.edge_count + 0 * d.indptr[0])(dev) \
        == topo.edge_count


def test_saint_edge_draw_never_names_a_padded_word():
    """The uniform edge draw ranges over the CSR's edges, not over the
    padded array: node 0 has no edge at either end here, and the padding
    words are zeros, so a draw from the padding would name it."""
    from quiver_tpu.sampling.saint import _uniform_edge_endpoints

    rng = np.random.default_rng(0)
    ei = rng.integers(1, 40, (2, 130))
    topo = CSRTopo(edge_index=ei)
    dev = topo.to_device()
    assert dev.indices.shape[0] == 256 and dev.edge_count == 130
    nodes, num = _uniform_edge_endpoints(dev, jax.random.PRNGKey(0), 4096)
    got = np.asarray(nodes)[: int(num)]
    assert got.size and 0 not in got


def test_share_ipc_roundtrip():
    topo, sampler = _sampler()
    rebuilt = GraphSageSampler.lazy_from_ipc_handle(sampler.share_ipc())
    assert rebuilt.sizes == sampler.sizes


def test_duplicate_seeds_keep_positions():
    # PyG contract: n_id[:batch_size] == seeds verbatim, duplicates included
    topo, sampler = _sampler()
    seeds = np.array([7, 7, 3, 9, 3])
    out = sampler.sample(seeds)
    assert np.array_equal(np.asarray(out.n_id)[:5], seeds)
    # later frontier ids still unique apart from the forced dups
    n_id = np.asarray(out.n_id)[: int(out.n_count)]
    rest = n_id[5:]
    assert len(np.unique(rest)) == len(rest)


def test_out_of_range_seeds_rejected():
    topo, sampler = _sampler(n=100)
    with pytest.raises(ValueError, match="seed ids"):
        sampler.sample(np.array([5, 100]))
    with pytest.raises(ValueError, match="seed ids"):
        sampler.sample(np.array([-2, 5]))


def test_eid_threading_maps_edges_to_coo_positions():
    """VERDICT r1 item 4: with_eid=True must populate Adj.e_id end-to-end.

    Oracle (reference sage_sampler.py:100-109 parity): for every valid
    sampled edge, the COO edge at position e_id is exactly
    (seed_global, neighbor_global). Frontiers are nested (seeds are forced
    first), so both locals of every layer index into the final n_id.
    """
    n = 400
    ei = generate_pareto_graph(n, 8.0, seed=1)
    topo = CSRTopo(edge_index=ei)
    sampler = GraphSageSampler(topo, [5, 3], with_eid=True, seed=3)
    out = sampler.sample(np.arange(40, 104))
    assert int(out.overflow) == 0
    n_id = np.asarray(out.n_id)
    checked = 0
    for adj in out.adjs:
        assert adj.e_id is not None
        e_id = np.asarray(adj.e_id)
        col, row = np.asarray(adj.edge_index)
        valid = col >= 0
        # e_id valid exactly where the edge is valid
        assert np.array_equal(e_id >= 0, valid)
        src_global = n_id[row[valid]]
        nbr_global = n_id[col[valid]]
        assert np.array_equal(ei[0, e_id[valid]], src_global)
        assert np.array_equal(ei[1, e_id[valid]], nbr_global)
        checked += int(valid.sum())
    assert checked > 100


def test_eid_none_without_flag():
    _, sampler = _sampler()
    out = sampler.sample(np.arange(16))
    assert all(adj.e_id is None for adj in out.adjs)


def test_eid_with_pallas_kernel():
    # with_eid + pallas rides the fused engine now (PR 16): the eid lane
    # comes back aligned with edge_index (bitwise differentials vs the
    # XLA oracle live in test_fused_sampler.py)
    ei = generate_pareto_graph(300, 6.0, seed=2)
    topo = CSRTopo(edge_index=ei)
    s = GraphSageSampler(topo, [4], kernel="pallas", with_eid=True,
                         seed_capacity=16)
    out = s.sample(np.arange(16))
    for adj in out.adjs:
        assert adj.e_id is not None
        src = np.asarray(adj.edge_index)[0]
        eids = np.asarray(adj.e_id)
        assert np.array_equal(eids >= 0, src >= 0)


# -- every caller of masked_unique, on the one reindex ----------------------
#
# What the reindex owes each sampler, read off the sampler's own output at
# its own lane count T: the forced prefix verbatim (``n_id[:batch] ==
# seeds``, duplicates included), every other kept id distinct, and every
# edge's local ids naming a CSR neighbour. The oracle is the host CSR.

def _dup_seeds(n_nodes, batch, seed):
    """``batch`` seeds with repeats among them."""
    rng = np.random.default_rng(seed)
    seeds = rng.integers(0, n_nodes, batch)
    seeds[batch // 2:] = seeds[: batch - batch // 2]
    return rng.permutation(seeds)


def _assert_frontier(n_id, seeds):
    n_id = np.asarray(n_id)
    batch = len(seeds)
    assert np.array_equal(n_id[:batch], seeds)
    rest = n_id[batch:]
    rest = rest[rest >= 0]
    assert len(np.unique(rest)) == len(rest)
    assert not np.isin(rest, seeds).any()


def _assert_edges(edge_index, src_ids, dst_ids, indptr, indices):
    """Each valid (src, dst) lane: ``src_ids[src]`` is in the CSR row of
    ``dst_ids[dst]``. Returns the number of lanes checked."""
    src, dst = np.asarray(edge_index)
    keep = src >= 0
    assert np.array_equal(keep, dst >= 0)
    u, v = np.asarray(src_ids)[src[keep]], np.asarray(dst_ids)[dst[keep]]
    assert (u >= 0).all() and (v >= 0).all()
    for a, b in zip(u.tolist(), v.tolist()):
        assert a in indices[indptr[b]:indptr[b + 1]]
    return int(keep.sum())


def _homogeneous(sizes, batch=64, n=600, avg_deg=12.0, mesh=None, **kw):
    ei = generate_pareto_graph(n, avg_deg, seed=4)
    topo = CSRTopo(edge_index=ei)
    if kw.get("weighted"):
        topo.set_edge_weight(
            np.random.default_rng(5).random(topo.edge_count) + 0.1)
    if "time_window" in kw:
        topo.set_edge_time(np.random.default_rng(6).random(topo.edge_count))
    seeds = _dup_seeds(n, batch, seed=7)
    if mesh is not None:
        sampler = GraphSageSampler(topo, sizes, seed=3, topo_sharding="mesh",
                                   mesh=mesh, **kw)
        outs = sampler.sample_per_worker(seeds, key=jax.random.PRNGKey(1))
        blocks = np.array_split(seeds, sampler.workers)
    else:
        sampler = GraphSageSampler(topo, sizes, seed=3, **kw)
        outs, blocks = [sampler.sample(seeds)], [seeds]
    checked = 0
    for out, blk in zip(outs, blocks):
        _assert_frontier(out.n_id, blk)
        for adj in out.adjs:
            checked += _assert_edges(adj.edge_index, out.n_id, out.n_id,
                                     topo.indptr, topo.indices)
    return checked


def _hetero(mesh=None):
    from quiver_tpu import HeteroCSRTopo, HeteroGraphSampler
    from quiver_tpu.sampling.dist_hetero import DistHeteroSampler

    rng = np.random.default_rng(8)
    nodes = {"paper": 120, "author": 60}
    topo = HeteroCSRTopo(nodes, {
        ("paper", "cites", "paper"): rng.integers(0, 120, (2, 500)),
        ("author", "writes", "paper"): np.stack(
            [rng.integers(0, 60, 400), rng.integers(0, 120, 400)]),
        ("paper", "written_by", "author"): np.stack(
            [rng.integers(0, 120, 400), rng.integers(0, 60, 400)]),
    })
    seeds = _dup_seeds(120, 32, seed=9)
    if mesh is not None:
        sampler = DistHeteroSampler(topo, [3, 2], input_type="paper", seed=2,
                                    mesh=mesh)
        outs = sampler.sample_per_worker(seeds, key=jax.random.PRNGKey(1))
        blocks = np.array_split(seeds, len(outs))
    else:
        sampler = HeteroGraphSampler(topo, [3, 2], input_type="paper", seed=2)
        outs, blocks = [sampler.sample(seeds)], [seeds]
    checked = 0
    for out, blk in zip(outs, blocks):
        _assert_frontier(out.n_id["paper"], blk)
        _assert_frontier(out.n_id["author"], blk[:0])
        for layer in out.adjs:
            for (s_t, _, d_t), adj in layer.adjs.items():
                rel = topo.relations[(s_t, _, d_t)]
                checked += _assert_edges(
                    adj.edge_index, out.n_id[s_t], out.n_id[d_t],
                    rel.indptr, rel.indices)
    return checked


def _saint(cls):
    ei = generate_pareto_graph(500, 8.0, seed=10)
    topo = CSRTopo(edge_index=ei)
    sub = cls(topo, 96, deg_cap=topo.max_degree, seed=1).sample()
    _assert_frontier(sub.node_id, np.zeros(0, np.int64))
    # an induced edge (u, v): v is in u's row
    src, dst = np.asarray(sub.edge_index)
    return _assert_edges(np.stack([dst, src]), sub.node_id, sub.node_id,
                         topo.indptr, topo.indices)


def _serve_ladder():
    from quiver_tpu.models.sage import GraphSAGE
    from quiver_tpu.serving.ladder import ServeLadder

    ei = generate_pareto_graph(400, 10.0, seed=11)
    topo = CSRTopo(edge_index=ei)
    sampler = GraphSageSampler(topo, [5, 5], seed=0)
    ladder = ServeLadder(
        sampler, GraphSAGE(hidden=8, num_classes=4, num_layers=2), 8)
    checked = 0
    for seed in (3, 77, 250):
        n_id, edge_indices, overflow = ladder._lane_sample(
            sampler.topo, jnp.int32(seed), jnp.int32(1), jnp.int32(seed),
            jax.random.PRNGKey(0))
        assert int(overflow) == 0
        _assert_frontier(n_id, np.array([seed]))
        for ei_l in edge_indices:
            checked += _assert_edges(ei_l, n_id, n_id, topo.indptr,
                                     topo.indices)
    return checked


def _mesh2():
    from quiver_tpu.parallel.mesh import make_mesh

    return make_mesh(n_devices=2, data=1, feature=2)


# the first two are the benchmark's hop shapes, the batch cut to 64: T is
# 64 * 16, then (cap + cap * 10), (cap + cap * 5) of the planned frontiers
_REINDEX_CALLERS = {
    "products_hops": lambda: _homogeneous([15, 10, 5], frontier_caps="auto"),
    "reddit_hops": lambda: _homogeneous(
        [25, 10], avg_deg=40.0, frontier_caps="auto"),
    "weighted": lambda: _homogeneous([5, 3], weighted=True),
    "with_eid": lambda: _homogeneous([5, 3], with_eid=True),
    "temporal": lambda: _homogeneous([5, 3], time_window=(0.2, 0.9)),
    "hetero": _hetero,
    "dist_mesh2": lambda: _homogeneous([4, 3], mesh=_mesh2()),
    "dist_hetero_mesh2": lambda: _hetero(mesh=_mesh2()),
    "saint_node": lambda: _saint(SAINTNodeSampler),
    "saint_edge": lambda: _saint(SAINTEdgeSampler),
    "serve_ladder": _serve_ladder,
}


@pytest.mark.parametrize("caller", list(_REINDEX_CALLERS))
def test_every_sampler_reindexes_by_the_contract(caller):
    assert _REINDEX_CALLERS[caller]() > 20


# -- the two keywords that take one value ------------------------------------

@pytest.mark.parametrize("dedup", ["sort", "map"])
def test_dedup_takes_auto_and_scan_only(dedup):
    topo, _ = _sampler(n=50)
    with pytest.raises(ValueError, match="removed"):
        GraphSageSampler(topo, [3], dedup=dedup)
    for ok in ("auto", "scan"):
        assert not hasattr(GraphSageSampler(topo, [3], dedup=ok), "dedup")


def test_kernel_auto_is_xla_whatever_the_backend(monkeypatch, tmp_path):
    """On a TPU the default constructors settle their kernel from the
    argument alone: nothing compiled, nothing timed, nothing written."""
    import time

    import jax.monitoring

    from quiver_tpu.feature.feature import Feature
    from quiver_tpu.feature.shard import ShardedFeature
    from quiver_tpu.utils import backend

    ei = generate_pareto_graph(200, 6.0, seed=0)
    topo = CSRTopo(edge_index=ei)
    mesh = _mesh2()
    monkeypatch.setattr(backend, "CHECKOUT", str(tmp_path))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiles = []

    def on_compile(name, *_a, **_k):
        if "compil" in name:
            compiles.append(name)

    def no_clock(*_a, **_k):
        raise AssertionError("a constructor read the clock")

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        with monkeypatch.context() as m:
            for fn in ("time", "perf_counter", "monotonic"):
                m.setattr(time, fn, no_clock)
            sampler = GraphSageSampler(topo, [3])
            Feature()
            ShardedFeature(mesh)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
    assert sampler.kernel == "xla"
    assert GraphSageSampler(topo, [3], kernel="auto").kernel == "xla"
    assert GraphSageSampler(topo, [3], kernel="pallas").kernel == "pallas"
    assert compiles == []
    assert not list(tmp_path.rglob("*"))
