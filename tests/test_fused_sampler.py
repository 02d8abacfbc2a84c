"""Fused Pallas megakernel differentials vs the XLA oracle (ISSUE 16).

Every cell of the old capability matrix that used to raise — pallas ×
{weighted, temporal, with_eid}, replicated AND sharded — is now a BITWISE
differential against the retained XLA path under the same PRNG key: the
fused kernel moves the windowed copy + select (+ weighted CDF walk + eid
lane) on-chip but consumes identical PRNG bits over identical shapes, so
any divergence is a real regression, not noise. Runs in interpret mode on
the CPU test mesh; the same programs compile unchanged on TPU.
"""

import logging

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from quiver_tpu import CSRTopo, DistHeteroSampler, GraphSageSampler, HeteroCSRTopo
from quiver_tpu.ops.pallas.fused import DEFAULT_WINDOW, fused_sample_layer
from quiver_tpu.ops.sample import sample_layer
from quiver_tpu.parallel.mesh import make_mesh
from quiver_tpu.utils.trace import reset_once


def _topo(n=400, e=6000, seed=3, weights=False, times=False):
    rng = np.random.default_rng(seed)
    # src >= 1 leaves node 0 isolated: deg-0 rows must stay bit-identical
    # (all -1 lanes) through the fused path's window arithmetic
    ei = np.stack([rng.integers(1, n, e), rng.integers(0, n, e)])
    ei[1, 0] = n - 1  # pin node_count
    t = CSRTopo(edge_index=ei.astype(np.int64))
    if weights:
        t.set_edge_weight(rng.random(e).astype(np.float32) + 0.1)
    if times:
        t.set_edge_time(rng.random(e))
    return t


def _assert_hop_bitwise(dev, *, k=5, weighted=False, time_window=None,
                        with_eid=False, num=50, cap=64, key_seed=7):
    n = int(dev.indptr.shape[0]) - 1
    rng = np.random.default_rng(11)
    seeds = np.full(cap, -1, np.int32)
    seeds[:num] = rng.integers(0, n, num)
    seeds[0] = 0  # the isolated (deg-0) row rides every variant
    seeds = jnp.asarray(seeds)
    key = jax.random.PRNGKey(key_seed)
    oracle = sample_layer(dev, seeds, jnp.int32(num), k, key,
                          with_eid=with_eid, weighted=weighted,
                          time_window=time_window)
    fused = fused_sample_layer(dev, seeds, jnp.int32(num), k, key,
                               weighted=weighted, time_window=time_window,
                               with_eid=with_eid)
    assert len(oracle) == len(fused)
    for i, (x, y) in enumerate(zip(oracle, fused)):
        assert np.array_equal(np.asarray(x), np.asarray(y)), (
            f"fused output {i} diverged from the XLA oracle"
        )


# -- hop-level bitwise differentials (the parity contract itself) -----------


@pytest.mark.parametrize("variant", [
    "uniform", "eid", "weighted", "weighted_eid", "temporal", "temporal_eid",
])
def test_hop_bitwise_differential(variant):
    weighted = variant.startswith("weighted")
    temporal = variant.startswith("temporal")
    with_eid = "eid" in variant
    t = _topo(weights=weighted, times=temporal)
    dev = t.to_device(with_eid=with_eid, with_weights=weighted,
                      with_times=temporal)
    assert t.edge_count >= DEFAULT_WINDOW  # the fused path must be live
    _assert_hop_bitwise(
        dev, weighted=weighted,
        time_window=(0.25, 0.8) if temporal else None, with_eid=with_eid,
    )


def test_hop_bitwise_full_batch_and_wide_fanout():
    """No padded tail (num == cap) and a fanout above most degrees (the
    take-all override path dominates): still bitwise."""
    t = _topo()
    dev = t.to_device(with_eid=True)
    _assert_hop_bitwise(dev, k=17, num=64, cap=64, with_eid=True)
    wt = _topo(weights=True, seed=9)
    wdev = wt.to_device(with_weights=True)
    _assert_hop_bitwise(wdev, k=17, num=64, cap=64, weighted=True)


# -- sampler-level parity ---------------------------------------------------


def test_sampler_parity_across_kernels():
    """Full GraphSageSampler outputs (n_id, every layer's edge_index and
    e_id) are bitwise identical between kernel='pallas' and 'xla' — the
    reindex stage downstream sees identical draws."""
    t = _topo()
    kw = dict(seed=5, seed_capacity=64, with_eid=True)
    sp = GraphSageSampler(t, [5, 3], kernel="pallas", **kw)
    sx = GraphSageSampler(t, [5, 3], kernel="xla", **kw)
    seeds = np.random.default_rng(2).integers(0, t.node_count, 60)
    a, b = sp.sample(seeds), sx.sample(seeds)
    assert np.array_equal(np.asarray(a.n_id), np.asarray(b.n_id))
    assert int(a.n_count) == int(b.n_count)
    assert int(a.overflow) == int(b.overflow)
    for la, lb in zip(a.adjs, b.adjs):
        assert np.array_equal(np.asarray(la.edge_index),
                              np.asarray(lb.edge_index))
        assert np.array_equal(np.asarray(la.e_id), np.asarray(lb.e_id))


# -- sharded (2-device mesh) parity, fast lane ------------------------------


def _dist_pair(topo, sizes, F=2, **kw):
    mesh = make_mesh(n_devices=F, data=1, feature=F)
    mk = dict(seed=7, seed_capacity=32,
              topo_sharding="mesh", mesh=mesh, **kw)
    return (GraphSageSampler(topo, sizes, kernel="pallas", **mk),
            GraphSageSampler(topo, sizes, kernel="xla", **mk))


def _assert_dist_parity(dp, dx, seeds, key, caplog):
    reset_once()
    with caplog.at_level(logging.INFO, logger="quiver_tpu"):
        per_p = dp.sample_per_worker(seeds, key=key)
    # the parity must come from the FUSED engine, not a silent degrade
    assert not [r for r in caplog.records
                if "falls back to the XLA path" in r.getMessage()]
    per_x = dx.sample_per_worker(seeds, key=key)
    for w, (a, b) in enumerate(zip(per_p, per_x)):
        assert np.array_equal(np.asarray(a.n_id), np.asarray(b.n_id)), (
            f"n_id diverged on worker {w}"
        )
        for la, lb in zip(a.adjs, b.adjs):
            assert np.array_equal(np.asarray(la.edge_index),
                                  np.asarray(lb.edge_index))


def _dist_graph(n=500, e=5000, seed=0, weights=False, times=False):
    rng = np.random.default_rng(seed)
    ei = np.stack([rng.integers(0, n, e), rng.integers(0, n, e)])
    t = CSRTopo(edge_index=ei.astype(np.int64))
    if weights:
        t.set_edge_weight(rng.random(e) + 0.1)
    if times:
        t.set_edge_time(rng.random(e))
    return t


def test_dist_parity_pallas_mesh2(caplog):
    t = _dist_graph()
    dp, dx = _dist_pair(t, [4, 3])
    seeds = np.random.default_rng(6).integers(0, t.node_count, 61)
    _assert_dist_parity(dp, dx, seeds, jax.random.PRNGKey(11), caplog)


def test_dist_parity_pallas_weighted_mesh2(caplog):
    t = _dist_graph(weights=True, seed=4)
    dp, dx = _dist_pair(t, [4, 3], weighted=True)
    seeds = np.random.default_rng(6).integers(0, t.node_count, 61)
    _assert_dist_parity(dp, dx, seeds, jax.random.PRNGKey(13), caplog)


def test_dist_parity_pallas_temporal_mesh2(caplog):
    t = _dist_graph(times=True, seed=8)
    dp, dx = _dist_pair(t, [4, 3], time_window=(0.2, 0.8))
    seeds = np.random.default_rng(9).integers(0, t.node_count, 61)
    _assert_dist_parity(dp, dx, seeds, jax.random.PRNGKey(17), caplog)


@pytest.mark.slow
@pytest.mark.parametrize("kind", ["uniform", "weighted"])
@pytest.mark.parametrize("F", [4, 8])
def test_dist_parity_pallas_widths(kind, F, caplog):
    """Wider meshes: each shard's slice must still host the DMA window
    (E/F >= window), and the fused owner-side path must stay bitwise."""
    t = _dist_graph(n=800, e=20000, seed=1, weights=kind == "weighted")
    kw = {"weighted": True} if kind == "weighted" else {}
    dp, dx = _dist_pair(t, [4, 3], F=F, **kw)
    seeds = np.random.default_rng(3).integers(0, t.node_count, 97)
    _assert_dist_parity(dp, dx, seeds, jax.random.PRNGKey(19), caplog)


def test_dist_pallas_degrades_on_small_shards(caplog):
    """Shards too small for the DMA window: kernel='pallas' degrades to
    the XLA path at compile time with ONE info log — and the outputs are
    still exactly the XLA sampler's."""
    reset_once()
    t = _dist_graph(n=200, e=1200, seed=2)  # 600/shard < DEFAULT_WINDOW
    dp, dx = _dist_pair(t, [3])
    seeds = np.arange(40)
    key = jax.random.PRNGKey(23)
    with caplog.at_level(logging.INFO, logger="quiver_tpu"):
        per_p = dp.sample_per_worker(seeds, key=key)
        dp.sample_per_worker(seeds, key=key)  # no repeat log
    hits = [r for r in caplog.records
            if "falls back to the XLA path" in r.getMessage()]
    assert len(hits) == 1 and "DMA window" in hits[0].getMessage()
    per_x = dx.sample_per_worker(seeds, key=key)
    for a, b in zip(per_p, per_x):
        assert np.array_equal(np.asarray(a.n_id), np.asarray(b.n_id))


def test_dist_sample_layer_explicit_pallas_raises():
    """Direct dist_sample_layer callers that break the window contract get
    a loud ValueError (only DistGraphSageSampler degrades silently — it
    owns the compile-time gate)."""
    from quiver_tpu.parallel.mesh import FEATURE_AXIS
    from quiver_tpu.sampling.dist import dist_sample_layer

    indptr = jnp.arange(101, dtype=jnp.int32) * 4
    indices = jnp.zeros(400, jnp.int32)  # E_local=400 < DEFAULT_WINDOW

    def body(seeds):
        return dist_sample_layer(
            indptr, indices, 100, seeds, jnp.int32(4), 3,
            jax.random.PRNGKey(0), axis=FEATURE_AXIS, num_shards=2,
            cap=None, kernel="pallas",
        )

    with pytest.raises(ValueError, match="use kernel='xla'"):
        jax.vmap(body, axis_name=FEATURE_AXIS)(
            jnp.zeros((2, 8), jnp.int32)
        )


# -- heterogeneous sharded parity -------------------------------------------


def _hetero_schema(seed=0, n_paper=300, n_author=80, e_cites=12000):
    rng = np.random.default_rng(seed)
    cites = np.stack([rng.integers(0, n_paper, e_cites),
                      rng.integers(0, n_paper, e_cites)])
    writes = np.stack([rng.integers(0, n_author, 600),
                       rng.integers(0, n_paper, 600)])
    return HeteroCSRTopo(
        {"paper": n_paper, "author": n_author},
        {("paper", "cites", "paper"): cites,
         ("author", "writes", "paper"): writes},
    )


def test_dist_hetero_parity_pallas_mesh2(caplog):
    """Mixed engines in ONE compiled program: the big relation's per-shard
    slice hosts the window (fused owner-side hop), the small one degrades
    per relation — outputs bitwise equal to the all-XLA sampler either
    way, and the degrade names only the small relation."""
    reset_once()
    topo = _hetero_schema()
    mesh = make_mesh(n_devices=2, data=1, feature=2)
    mk = dict(input_type="paper", mesh=mesh, seed=0)
    dp = DistHeteroSampler(topo, [3, 2], kernel="pallas", **mk)
    dx = DistHeteroSampler(topo, [3, 2], kernel="xla", **mk)
    seeds = np.arange(48)
    key = jax.random.PRNGKey(7)
    with caplog.at_level(logging.INFO, logger="quiver_tpu"):
        per_p = dp.sample_per_worker(seeds, key=key)
    hits = [r for r in caplog.records
            if "falls back to the XLA path" in r.getMessage()]
    assert len(hits) == 1
    assert "writes" in hits[0].getMessage()   # small rel degrades...
    assert "cites" not in hits[0].getMessage()  # ...the big one rides fused
    per_x = dx.sample_per_worker(seeds, key=key)
    for w, (a, b) in enumerate(zip(per_p, per_x)):
        assert set(a.n_id) == set(b.n_id)
        for t in a.n_id:
            assert np.array_equal(np.asarray(a.n_id[t]),
                                  np.asarray(b.n_id[t])), (
                f"n_id[{t}] diverged on worker {w}"
            )
        for la, lb in zip(a.adjs, b.adjs):
            assert set(la.adjs) == set(lb.adjs)
            for et in la.adjs:
                assert np.array_equal(
                    np.asarray(la.adjs[et].edge_index),
                    np.asarray(lb.adjs[et].edge_index),
                ), f"edge_index[{et}] diverged on worker {w}"
