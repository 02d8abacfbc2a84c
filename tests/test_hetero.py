"""Heterogeneous topology, sampler, and R-GCN tests (BASELINE config 5:
hetero R-GCN — the reference has no hetero support; this is capability
the TPU framework adds on top of parity).
"""

import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest

from quiver_tpu import HeteroCSRTopo, HeteroFeature, HeteroGraphSampler
from quiver_tpu.models.rgcn import RGCN


def _toy_schema(seed=0, n_paper=120, n_author=60, n_inst=20):
    """paper<-cites-paper, paper<-writes-author... stored incoming.

    Edge convention: (src, rel, dst) with edge_index=[src_ids, dst_ids];
    sampling from dst draws src messages.
    """
    rng = np.random.default_rng(seed)
    cites = np.stack([
        rng.integers(0, n_paper, 400), rng.integers(0, n_paper, 400)
    ])
    writes = np.stack([
        rng.integers(0, n_author, 300), rng.integers(0, n_paper, 300)
    ])
    affil = np.stack([
        rng.integers(0, n_inst, 100), rng.integers(0, n_author, 100)
    ])
    num_nodes = {"paper": n_paper, "author": n_author, "inst": n_inst}
    edges = {
        ("paper", "cites", "paper"): cites,
        ("author", "writes", "paper"): writes,
        ("inst", "employs", "author"): affil,
    }
    return HeteroCSRTopo(num_nodes, edges), edges, num_nodes


def test_topo_construction_and_validation():
    topo, edges, num_nodes = _toy_schema()
    assert set(topo.node_types) == {"paper", "author", "inst"}
    assert len(topo.edge_types) == 3
    rel = topo.relations[("author", "writes", "paper")]
    assert rel.node_count == num_nodes["paper"]  # rows = dst
    assert rel.src_node_count == num_nodes["author"]
    assert rel.edge_count == 300
    # incoming CSR: row d holds all authors a with (a -> d) in writes
    src, dst = edges[("author", "writes", "paper")]
    for d in range(0, 120, 17):
        expect = sorted(src[dst == d])
        got = sorted(rel.indices[rel.indptr[d]:rel.indptr[d + 1]])
        assert got == expect


def test_topo_rejects_bad_ids():
    with pytest.raises(ValueError, match="src node"):
        HeteroCSRTopo(
            {"a": 5, "b": 5},
            {("a", "r", "b"): np.array([[7], [0]])},
        )
    with pytest.raises(ValueError, match="dst id"):
        HeteroCSRTopo(
            {"a": 5, "b": 5},
            {("a", "r", "b"): np.array([[0], [9]])},
        )
    with pytest.raises(ValueError, match="unknown node type"):
        HeteroCSRTopo({"a": 5}, {("a", "r", "zzz"): np.zeros((2, 0))})


def test_hetero_sampler_contract():
    topo, edges, _ = _toy_schema()
    sampler = HeteroGraphSampler(topo, [3, 2], input_type="paper", seed=0)
    seeds = np.arange(32)
    out = sampler.sample(seeds)

    # seeds-first contract on the input type
    assert np.asarray(out.n_id["paper"])[:32].tolist() == seeds.tolist()
    assert out.batch_size == 32
    assert int(out.overflow) == 0
    # two hops -> two layers, deepest first
    assert len(out.adjs) == 2
    # hop 1 (deepest in list position 0) has all three relations active
    # (paper and author both have frontiers after hop 1)
    assert len(out.adjs[0].adjs) == 3
    # hop 0 (position 1): only relations into 'paper' are active
    assert set(out.adjs[1].adjs) == {
        ("paper", "cites", "paper"), ("author", "writes", "paper")
    }


def test_hetero_sampled_edges_are_real():
    topo, edges, _ = _toy_schema(seed=3)
    sampler = HeteroGraphSampler(topo, [4, 3], input_type="paper", seed=1)
    out = sampler.sample(np.arange(24))

    adj_sets = {
        et: {(int(s), int(d)) for s, d in zip(*edges[et])} for et in edges
    }
    # walk layers from seeds outward: position 1 is hop 0 (targets = seeds
    # frontier), position 0 is hop 1
    checked = 0
    for layer in reversed(out.adjs):
        for et, adj in layer.adjs.items():
            s_t, _, d_t = et
            src, dst = np.asarray(adj.edge_index)
            # n_id holds the DEEPEST frontier; for intermediate hops the
            # forced-first property means target ids are a prefix of it
            for sl, dl in zip(src, dst):
                if sl < 0:
                    continue
                u = int(np.asarray(out.n_id[s_t])[sl])
                v = int(np.asarray(out.n_id[d_t])[dl])
                assert (u, v) in adj_sets[et], f"{et}: ({u},{v}) not an edge"
                checked += 1
    assert checked > 50


def test_fanout_dict_disables_relation():
    topo, _, _ = _toy_schema()
    sampler = HeteroGraphSampler(
        topo,
        [{("paper", "cites", "paper"): 3}],
        input_type="paper",
    )
    out = sampler.sample(np.arange(16))
    assert set(out.adjs[0].adjs) == {("paper", "cites", "paper")}
    assert "author" not in out.n_id


def test_rgcn_trains():
    topo, edges, num_nodes = _toy_schema(seed=5)
    sampler = HeteroGraphSampler(topo, [4, 3], input_type="paper",
                                 seed_capacity=32, seed=2)
    rng = np.random.default_rng(0)
    feats = {
        t: rng.normal(size=(n, 16)).astype(np.float32)
        for t, n in num_nodes.items()
    }
    feature = HeteroFeature.from_cpu_tensors(feats, device_cache_size="64M")
    labels_all = rng.integers(0, 4, num_nodes["paper"]).astype(np.int32)

    model = RGCN(hidden=32, num_classes=4, target_type="paper", num_layers=2)
    out = sampler.sample(np.arange(32))
    x_dict = feature[out.n_id]
    params = model.init({"params": jax.random.PRNGKey(0)}, x_dict, out.adjs)[
        "params"
    ]
    tx = optax.adam(5e-3)
    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state, x_dict, layers, labels, rng):
        def loss_fn(p):
            logp = model.apply({"params": p}, x_dict, layers, train=True,
                               rngs={"dropout": rng})
            ll = jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
            return -ll.mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    losses = []
    for i in range(30):
        seeds = np.random.default_rng(i).integers(0, num_nodes["paper"], 32)
        out = sampler.sample(seeds)
        x_dict = feature[out.n_id]
        y = jnp.asarray(labels_all[seeds])
        params, opt_state, loss = step(
            params, opt_state, x_dict, out.adjs, y, jax.random.PRNGKey(i)
        )
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.9, f"no convergence: {losses[:3]} -> {losses[-3:]}"


def test_full_fanout_minus_one():
    topo, edges, _ = _toy_schema()
    sampler = HeteroGraphSampler(topo, [-1], input_type="paper",
                                 seed_capacity=16)
    out = sampler.sample(np.arange(8))
    # -1 = full neighborhood: every incoming edge of every seed appears
    adj = out.adjs[0].adjs[("author", "writes", "paper")]
    src_ids, dst_ids = edges[("author", "writes", "paper")]
    n_edges_expected = sum(int((dst_ids == s).sum()) for s in range(8))
    src = np.asarray(adj.edge_index[0])
    dst = np.asarray(adj.edge_index[1])
    got = int(((src >= 0) & (dst < 8) & (dst >= 0)).sum())
    assert got == n_edges_expected


def test_duplicate_seeds_keep_capacity():
    # more (duplicate) seeds than the input type has nodes: the frontier
    # must still hold every forced seed lane
    topo, _, _ = _toy_schema(n_paper=10, n_author=8, n_inst=4)
    sampler = HeteroGraphSampler(topo, [2], input_type="paper",
                                 seed_capacity=64)
    seeds = np.zeros(50, dtype=np.int64)  # 50 copies of node 0
    out = sampler.sample(seeds)
    nid = np.asarray(out.n_id["paper"])
    assert nid.shape[0] >= 50
    assert (nid[:50] == 0).all()
    assert int(out.overflow) == 0


def test_bad_fanout_rejected():
    topo, _, _ = _toy_schema()
    with pytest.raises(ValueError, match="fanout"):
        HeteroGraphSampler(topo, [-3], input_type="paper")


def test_rgcn_mixed_feature_dims_with_bases():
    topo, _, num_nodes = _toy_schema()
    sampler = HeteroGraphSampler(topo, [3, 2], input_type="paper",
                                 seed_capacity=16)
    rng = np.random.default_rng(2)
    dims = {"paper": 24, "author": 8, "inst": 4}
    feats = {
        t: rng.normal(size=(n, dims[t])).astype(np.float32)
        for t, n in num_nodes.items()
    }
    feature = HeteroFeature.from_cpu_tensors(feats, device_cache_size="64M")
    model = RGCN(hidden=16, num_classes=3, target_type="paper",
                 num_layers=2, num_bases=2)
    out = sampler.sample(np.arange(16))
    x_dict = feature[out.n_id]
    params = model.init({"params": jax.random.PRNGKey(0)}, x_dict, out.adjs)[
        "params"
    ]
    logp = model.apply({"params": params}, x_dict, out.adjs)
    assert np.isfinite(np.asarray(logp)[:16]).all()


def test_rgcn_basis_decomposition():
    topo, _, num_nodes = _toy_schema()
    sampler = HeteroGraphSampler(topo, [3, 2], input_type="paper",
                                 seed_capacity=16)
    rng = np.random.default_rng(1)
    feats = {
        t: rng.normal(size=(n, 8)).astype(np.float32)
        for t, n in num_nodes.items()
    }
    feature = HeteroFeature.from_cpu_tensors(feats, device_cache_size="64M")
    model = RGCN(hidden=16, num_classes=3, target_type="paper",
                 num_layers=2, num_bases=2)
    out = sampler.sample(np.arange(16))
    x_dict = feature[out.n_id]
    params = model.init({"params": jax.random.PRNGKey(0)}, x_dict, out.adjs)[
        "params"
    ]
    logp = model.apply({"params": params}, x_dict, out.adjs)
    assert logp.shape[-1] == 3
    assert np.isfinite(np.asarray(logp)[:16]).all()
    # basis params exist, per-relation dense kernels don't
    flat = jax.tree_util.tree_leaves_with_path(params)
    names = ["/".join(str(k) for k in path) for path, _ in flat]
    assert any("bases" in n for n in names)
    assert not any("rel_" in n and "kernel" in n for n in names)


def _powerlaw_schema(seed=0, n_paper=3000, n_author=1200):
    """Power-law hetero graph: worst-case caps overshoot badly here."""
    from quiver_tpu.utils.graphgen import generate_pareto_graph

    rng = np.random.default_rng(seed)
    cites = generate_pareto_graph(n_paper, 8.0, seed=seed)
    m = 4 * n_paper
    writes = np.stack([
        rng.integers(0, n_author, m), rng.integers(0, n_paper, m)
    ])
    return HeteroCSRTopo(
        {"paper": n_paper, "author": n_author},
        {
            ("paper", "cites", "paper"): cites,
            ("author", "writes", "paper"): writes,
        },
    )


@pytest.mark.slow  # 19s auto-caps sweep; overflow guards stay fast
def test_hetero_auto_caps_right_size(  ):
    """VERDICT r1 item 7: auto caps within 1.5x of observed uniques on a
    power-law hetero graph, no overflow, and strictly tighter than the
    worst-case plan."""
    topo = _powerlaw_schema()
    batch = 128
    auto = HeteroGraphSampler(
        topo, [10, 5], input_type="paper", seed_capacity=batch,
        frontier_caps="auto", seed=7,
    )
    worst = HeteroGraphSampler(
        topo, [10, 5], input_type="paper", seed_capacity=batch, seed=7,
    )
    seeds = np.random.default_rng(1).integers(0, 3000, batch)
    auto.sample(seeds)  # first call plans from worst case, then tightens
    out = auto.sample(seeds)
    out_w = worst.sample(seeds)
    assert int(out.overflow) == 0

    # per-layer, per-type: planned cap <= 1.5x observed uniques (+ padding
    # slack for tiny frontiers) and <= the worst-case cap
    for layer_i, (layer, layer_w) in enumerate(zip(out.adjs, out_w.adjs)):
        obs = {t: int(v) for t, v in out.frontier_counts[::-1][layer_i].items()}
        for t, cap in layer.src_caps.items():
            w_cap = layer_w.src_caps[t]
            assert cap <= w_cap
            if t in obs and obs[t] >= 512:  # rounding slack irrelevant
                assert cap <= 1.5 * obs[t] + 128, (
                    f"layer {layer_i} type {t}: cap {cap} vs observed {obs[t]}"
                )
    # the deepest frontier must be meaningfully tighter than worst case
    deep_auto = sum(out.adjs[0].src_caps.values())
    deep_worst = sum(out_w.adjs[0].src_caps.values())
    assert deep_auto < 0.8 * deep_worst, (deep_auto, deep_worst)

    # later batches reuse the plan without replanning (no overflow)
    out2 = auto.sample(np.random.default_rng(2).integers(0, 3000, batch))
    assert int(out2.overflow) == 0


def test_hetero_auto_caps_results_valid():
    """Auto-capped samples still satisfy the validity oracle: every sampled
    edge exists in the relation's adjacency."""
    topo = _powerlaw_schema(seed=3, n_paper=500, n_author=200)
    s = HeteroGraphSampler(
        topo, [6, 4], input_type="paper", seed_capacity=64,
        frontier_caps="auto", seed=11,
    )
    out = s.sample(np.arange(40))
    assert int(out.overflow) == 0
    n_id = {t: np.asarray(v) for t, v in out.n_id.items()}
    for layer in out.adjs:
        for et, adj in layer.adjs.items():
            s_t, _, d_t = et
            rel = topo.relations[et]
            col, row = np.asarray(adj.edge_index)
            valid = col >= 0
            src = n_id[s_t][col[valid]]
            # row indexes the PREVIOUS dst frontier == prefix of final n_id
            dst = n_id[d_t][row[valid]]
            indptr, indices = rel.indptr, rel.indices
            for sg, dg in zip(src[:200], dst[:200]):
                assert sg in indices[indptr[dg]:indptr[dg + 1]]


def test_hetero_eid_maps_edges_to_coo_positions():
    """VERDICT r2 item 8: hetero analogue of the homogeneous e_id oracle
    (tests/test_sampler_api.py::test_eid_threading_maps_edges_to_coo_positions)
    — with_eid=True must thread relation-local COO edge positions through
    every Adj: the COO edge at position e_id is exactly (src_global,
    dst_global)."""
    topo, edges, _ = _toy_schema(seed=5)
    sampler = HeteroGraphSampler(
        topo, [4, 3], input_type="paper", seed=2, with_eid=True
    )
    out = sampler.sample(np.arange(24))
    assert int(out.overflow) == 0
    n_id = {t: np.asarray(v) for t, v in out.n_id.items()}
    checked = 0
    for layer in out.adjs:
        for et, adj in layer.adjs.items():
            s_t, _, d_t = et
            assert adj.e_id is not None
            e_id = np.asarray(adj.e_id)
            col, row = np.asarray(adj.edge_index)
            valid = col >= 0
            assert np.array_equal(e_id >= 0, valid)
            ei = edges[et]
            src_global = n_id[s_t][col[valid]]
            dst_global = n_id[d_t][row[valid]]
            assert np.array_equal(ei[0, e_id[valid]], src_global)
            assert np.array_equal(ei[1, e_id[valid]], dst_global)
            checked += int(valid.sum())
    assert checked > 50


def test_hetero_weighted_relation_biases_draws():
    """VERDICT r2 item 8: weighted relations must thread through the typed
    sampler. Construction: one dst paper with many cite-sources where a
    single source holds ~all the weight — weighted draws must concentrate on
    it; an unweighted control must not."""
    n_paper, n_author = 40, 8
    hub_dst, hot_src = 0, 7
    src = np.arange(1, 31)  # papers 1..30 all cite paper 0
    cites = np.stack([src, np.zeros_like(src)])
    writes = np.stack([
        np.random.default_rng(0).integers(0, n_author, 60),
        np.random.default_rng(1).integers(0, n_paper, 60),
    ])
    topo = HeteroCSRTopo(
        {"paper": n_paper, "author": n_author},
        {("paper", "cites", "paper"): cites,
         ("author", "writes", "paper"): writes},
    )
    w = np.full(cites.shape[1], 1e-4, np.float32)
    w[src == hot_src] = 1.0
    topo.set_edge_weight(("paper", "cites", "paper"), w)
    assert topo.weighted_edge_types == [("paper", "cites", "paper")]

    def hot_rate(weighted):
        s = HeteroGraphSampler(
            topo, [1], input_type="paper", seed=3, weighted=weighted,
            seed_capacity=128,
        )
        hits = draws = 0
        for i in range(60):
            out = s.sample(np.asarray([hub_dst]))
            adj = out.adjs[0].adjs[("paper", "cites", "paper")]
            col, row = np.asarray(adj.edge_index)
            ids = np.asarray(out.n_id["paper"])[col[(col >= 0) & (row == 0)]]
            hits += int((ids == hot_src).sum())
            draws += int(((col >= 0) & (row == 0)).sum())
        return hits / max(draws, 1)

    assert hot_rate(True) > 0.9  # ~all weight on the hot edge
    assert hot_rate(False) < 0.3  # uniform control: 1/30 expected


def test_hetero_weighted_validation():
    topo, _, _ = _toy_schema()
    with pytest.raises(ValueError, match="edge weights"):
        HeteroGraphSampler(topo, [2], input_type="paper", weighted=True)
    with pytest.raises(ValueError, match="edge weights"):
        HeteroGraphSampler(
            topo, [2], input_type="paper",
            weighted=[("paper", "cites", "paper")],
        )
    with pytest.raises(ValueError, match="unknown relation"):
        topo.set_edge_weight(("x", "y", "z"), np.ones(3))
