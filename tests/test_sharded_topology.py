"""Sharded-topology sampling differentials (ISSUE 3 tentpole).

Parity bar: a ``topo_sharding="mesh"`` sampler — the CSR partitioned across
the mesh's feature axis, per-hop frontier routing over capped-bucket
all_to_all — must be BIT-IDENTICAL to the replicated ``GraphSageSampler``
per worker block for the same seeds/PRNG keys, at every mesh width, with
and without forced bucket overflow (fallback-served lanes included). The
partition plan must shrink per-chip topology bytes ~1/F. End-to-end, a
``DistributedTrainer`` driving the dist sampler must reproduce the
replicated trainer's loss trajectory bit-for-bit (slow lane).
"""

import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest

from quiver_tpu import CSRTopo, GraphSageSampler
from quiver_tpu.core.sharded_topology import ShardedTopology
from quiver_tpu.feature.shard import ShardedFeature
from quiver_tpu.models.sage import GraphSAGE
from quiver_tpu.parallel.mesh import make_mesh
from quiver_tpu.parallel.trainer import DistributedTrainer
from quiver_tpu.sampling.dist import DistGraphSageSampler, routed_sample_cap
from quiver_tpu.utils.graphgen import generate_pareto_graph


def _graph(n=400, deg=6.0, seed=0):
    return CSRTopo(edge_index=generate_pareto_graph(n, deg, seed=seed))


def _assert_worker_parity(dist, rep, seeds, key, seed_cap=32):
    """Each worker's dist SampleOutput must equal the replicated sampler's
    on that worker's seed block with key fold_in(key, worker)."""
    W = dist.workers
    outs = dist.sample_per_worker(seeds, key=key)
    run, _ = rep._compiled(seed_cap)
    for w, (o, blk) in enumerate(zip(outs, np.array_split(seeds, W))):
        padded = np.full(seed_cap, -1, np.int32)
        padded[: len(blk)] = blk
        n_id, _, adjs, _, _, _ = run(
            rep.topo, jnp.asarray(padded), jnp.int32(len(blk)),
            jax.random.fold_in(key, w),
        )
        assert np.array_equal(np.asarray(n_id), np.asarray(o.n_id)), (
            f"n_id diverged on worker {w}/{W}"
        )
        for l, (ra, da) in enumerate(zip(adjs, o.adjs)):
            assert np.array_equal(
                np.asarray(ra.edge_index), np.asarray(da.edge_index)
            ), f"edge_index diverged on worker {w} layer {l}"
            assert ra.size == da.size and ra.fanout == da.fanout


# -- partition plan ---------------------------------------------------------


def test_partition_plan_covers_csr_and_shrinks_bytes():
    """The row-range partition must cover the CSR exactly — every shard's
    rebased slice reconstructs the original — and per-chip bytes must
    shrink ~1/F vs the replicated placement (the acceptance criterion the
    dryrun asserts too)."""
    topo = _graph(n=500)
    mesh = make_mesh(data=1, feature=8)
    st = ShardedTopology(mesh, topo)
    plan = st.plan
    F, rps = plan["num_shards"], plan["rows_per_shard"]
    assert F == 8 and rps * F >= topo.node_count
    assert sum(plan["shard_edges"]) == topo.edge_count
    ip = np.asarray(st.indptr)
    ix = np.asarray(st.indices)
    gip = np.asarray(topo.indptr)
    gix = np.asarray(topo.indices)
    for d in range(F):
        lo, hi = min(d * rps, topo.node_count), min((d + 1) * rps,
                                                    topo.node_count)
        # rebased indptr reconstructs the global slice
        assert np.array_equal(
            ip[d, : hi - lo + 1] + gip[lo], gip[lo : hi + 1]
        )
        # padding rows stay degree-0
        assert np.all(ip[d, hi - lo:] == ip[d, hi - lo])
        e = plan["shard_edges"][d]
        assert np.array_equal(ix[d, :e], gix[gip[lo] : gip[lo] + e])
    assert plan["per_chip_bytes"] * F <= plan["replicated_bytes"] * 2, plan
    assert plan["shrink_factor"] >= F / 2


@pytest.mark.parametrize("F", [2, 8])
def test_edge_count_agrees_with_the_padded_replicated_placement(F):
    """The replicated placement pads its edge array to whole 128-word
    blocks; both placements still say the CSR's edge count, and the shards
    hold exactly the CSR's edges."""
    topo = _graph(n=500)
    assert topo.edge_count % 128
    mesh = make_mesh(n_devices=F, data=1, feature=F)
    st = ShardedTopology(mesh, topo)
    dev = topo.to_device()
    assert dev.indices.shape[0] > topo.edge_count
    assert st.edge_count == dev.edge_count == topo.edge_count
    assert sum(st.plan["shard_edges"]) == dev.edge_count


def test_routed_sample_cap_schedule():
    assert routed_sample_cap(128, 8, 2.0) == 32  # ceil(2*128/8)
    assert routed_sample_cap(128, 8, None) is None  # uncapped
    assert routed_sample_cap(128, 8, 100.0) is None  # cap >= L => uncapped
    assert routed_sample_cap(8, 8, 0.01) == 1  # floor at 1 lane
    with pytest.raises(ValueError):
        routed_sample_cap(128, 8, -1.0)


# -- bit-parity differentials ----------------------------------------------


def test_dist_parity_mesh8():
    """Full-width mesh (F=8): bit-identical to the replicated sampler for
    the same seeds/keys, telemetry surfaced."""
    topo = _graph(n=500)
    mesh = make_mesh(data=1, feature=8)
    dist = GraphSageSampler(topo, [4, 3], seed=7, seed_capacity=32,
                            topo_sharding="mesh", mesh=mesh)
    assert isinstance(dist, DistGraphSageSampler)
    rep = GraphSageSampler(topo, [4, 3], seed=7, seed_capacity=32)
    seeds = np.random.default_rng(1).integers(
        0, topo.node_count, 32 * dist.workers - 5
    )
    _assert_worker_parity(dist, rep, seeds, jax.random.PRNGKey(3))
    ov = np.asarray(dist.last_sample_overflow)
    assert ov.shape == (2,) and np.all(ov >= 0)


def test_dist_parity_weighted_mesh2():
    """Weighted draws over the sharded path: the owner's inverse-CDF
    search against its routed prefix-weight segment is bit-identical to
    the replicated weighted sampler."""
    topo = _graph(n=500)
    topo.set_edge_weight(
        np.random.default_rng(5).random(topo.edge_count) + 0.1
    )
    mesh = make_mesh(n_devices=2, data=1, feature=2)
    dist = GraphSageSampler(topo, [4, 3], seed=7, seed_capacity=32,
                            topo_sharding="mesh", mesh=mesh,
                            weighted=True)
    rep = GraphSageSampler(topo, [4, 3], seed=7, seed_capacity=32,
                           weighted=True)
    seeds = np.random.default_rng(6).integers(0, topo.node_count, 61)
    _assert_worker_parity(dist, rep, seeds, jax.random.PRNGKey(11))


def test_dist_parity_temporal_mesh2():
    """Temporal windowed draws over the sharded path: owner-answered
    (first, deg_t) in-window slot ranges, bit-identical to the replicated
    time_window sampler."""
    topo = _graph(n=500)
    topo.set_edge_time(np.random.default_rng(8).random(topo.edge_count))
    mesh = make_mesh(n_devices=2, data=1, feature=2)
    win = (0.2, 0.8)
    dist = GraphSageSampler(topo, [4, 3], seed=7, seed_capacity=32,
                            topo_sharding="mesh", mesh=mesh,
                            time_window=win)
    rep = GraphSageSampler(topo, [4, 3], seed=7, seed_capacity=32,
                           time_window=win)
    seeds = np.random.default_rng(9).integers(0, topo.node_count, 61)
    _assert_worker_parity(dist, rep, seeds, jax.random.PRNGKey(13))


@pytest.mark.slow
@pytest.mark.parametrize("kind", ["weighted", "temporal"])
@pytest.mark.parametrize("F", [1, 4, 8])
def test_dist_parity_attr_widths(kind, F):
    """Weighted/temporal differential at the wider mesh widths, capped
    tight enough to force routed overflow — the fallback must serve the
    attributed hops exactly too."""
    topo = _graph(n=500)
    kw = {}
    if kind == "weighted":
        topo.set_edge_weight(
            np.random.default_rng(5).random(topo.edge_count) + 0.1
        )
        kw["weighted"] = True
    else:
        topo.set_edge_time(np.random.default_rng(8).random(topo.edge_count))
        kw["time_window"] = (0.2, 0.8)
    mesh = make_mesh(n_devices=F, data=1, feature=F)
    dist = GraphSageSampler(topo, [4, 3], seed=7, seed_capacity=32,
                            topo_sharding="mesh", mesh=mesh,
                            routed_alpha=0.25, **kw)
    rep = GraphSageSampler(topo, [4, 3], seed=7, seed_capacity=32,
                           **kw)
    seeds = np.random.default_rng(F).integers(0, topo.node_count,
                                              32 * F - 3)
    _assert_worker_parity(dist, rep, seeds, jax.random.PRNGKey(F))
    assert int(np.asarray(dist.last_sample_overflow).sum()) > 0


@pytest.mark.slow
@pytest.mark.parametrize("F", [1, 2, 4])
def test_dist_parity_other_mesh_widths(F):
    """Same differential at the narrower mesh widths {1, 2, 4}."""
    topo = _graph(n=500)
    mesh = make_mesh(n_devices=F, data=1, feature=F)
    dist = GraphSageSampler(topo, [4, 3], seed=7, seed_capacity=32,
                            topo_sharding="mesh", mesh=mesh)
    rep = GraphSageSampler(topo, [4, 3], seed=7, seed_capacity=32)
    seeds = np.random.default_rng(F).integers(
        0, topo.node_count, 32 * F - 3
    )
    _assert_worker_parity(dist, rep, seeds, jax.random.PRNGKey(F))


def test_forced_overflow_exact():
    """Adversarial skew: every seed owned by shard 0 and a tiny routing
    budget — buckets overflow, the cond-gated psum fallback serves the
    overflowed lanes, results stay bit-identical, and the per-hop count
    surfaces as last_sample_overflow."""
    topo = _graph(n=500)
    mesh = make_mesh(n_devices=4, data=1, feature=4)
    dist = GraphSageSampler(topo, [4, 3], seed=7, seed_capacity=32,
                            topo_sharding="mesh", mesh=mesh,
                            routed_alpha=0.01)
    rep = GraphSageSampler(topo, [4, 3], seed=7, seed_capacity=32)
    # all seeds on shard 0's row range
    seeds = np.random.default_rng(2).integers(
        0, dist.topo.rows_per_shard, 32 * 4
    )
    _assert_worker_parity(dist, rep, seeds, jax.random.PRNGKey(9))
    ov = np.asarray(dist.last_sample_overflow)
    assert ov.shape == (2,) and int(ov.sum()) > 0, ov


# -- constructor guards -----------------------------------------------------


def test_mesh_sharding_constructor_guards():
    topo = _graph(n=200)
    mesh = make_mesh(data=1, feature=8)
    with pytest.raises(ValueError, match="requires mesh="):
        GraphSageSampler(topo, [4], topo_sharding="mesh")
    with pytest.raises(ValueError, match="topo_sharding"):
        GraphSageSampler(topo, [4], topo_sharding="nope")
    # weighted over mesh is SUPPORTED now — but only when the topology
    # actually carries weights (the shard partition needs cum_weights)
    with pytest.raises(ValueError, match="requires edge weights"):
        GraphSageSampler(topo, [4], topo_sharding="mesh", mesh=mesh,
                         weighted=True)
    w = np.ones(topo.edge_count, np.float32)
    t2 = _graph(n=200)
    t2.set_edge_weight(w)
    assert isinstance(
        GraphSageSampler(t2, [4], topo_sharding="mesh", mesh=mesh,
                         weighted=True),
        DistGraphSageSampler,
    )
    # temporal over mesh likewise needs timestamps on the topology
    with pytest.raises(ValueError, match="requires edge timestamps"):
        GraphSageSampler(topo, [4], topo_sharding="mesh", mesh=mesh,
                         time_window=(0.0, 1.0))
    with pytest.raises(ValueError, match="with_eid over a sharded"):
        GraphSageSampler(topo, [4], topo_sharding="mesh", mesh=mesh,
                         with_eid=True)
    # kernel='pallas' over mesh now rides the fused engine (PR 16); only
    # an unknown kernel name still raises
    with pytest.raises(ValueError, match="kernel"):
        GraphSageSampler(topo, [4], topo_sharding="mesh", mesh=mesh,
                         kernel="cuda")
    with pytest.raises(ValueError, match="HBM"):
        GraphSageSampler(topo, [4], topo_sharding="mesh", mesh=mesh,
                         mode="HOST")
    with pytest.raises(ValueError, match="routed_alpha"):
        GraphSageSampler(topo, [4], topo_sharding="mesh", mesh=mesh,
                         routed_alpha=-2.0)
    # the replicated path is untouched by the dispatch
    rep = GraphSageSampler(topo, [4])
    assert rep.topo_sharding == "replicated"
    assert not isinstance(rep, DistGraphSageSampler)


def test_trainer_requires_all_seed_sharding():
    topo = _graph(n=200)
    mesh = make_mesh(data=2, feature=4)
    dist = GraphSageSampler(topo, [4, 3], topo_sharding="mesh", mesh=mesh)
    feat = np.random.default_rng(0).normal(size=(topo.node_count, 8))
    feature = ShardedFeature(mesh, device_cache_size="1G").from_cpu_tensor(
        feat.astype(np.float32)
    )
    model = GraphSAGE(hidden=8, num_classes=3, num_layers=2)
    with pytest.raises(ValueError, match="seed_sharding"):
        DistributedTrainer(mesh, dist, feature, model, optax.adam(1e-3),
                           local_batch=8)  # default seed_sharding="data"
    other = make_mesh(data=1, feature=8)
    with pytest.raises(ValueError, match="mesh"):
        DistributedTrainer(other, dist, feature, model, optax.adam(1e-3),
                           local_batch=8, seed_sharding="all")


# -- end-to-end trainer parity (slow lane) ----------------------------------


@pytest.mark.slow
def test_trainer_loss_trajectory_parity():
    """DistributedTrainer over the dist sampler reproduces the replicated
    trainer's loss trajectory BIT-FOR-BIT on the 8-device mesh — capped
    tight (forced per-hop overflow) included — and surfaces the per-hop
    overflow vector per step of an epoch_scan."""
    ei = generate_pareto_graph(400, 6.0, seed=0)
    topo = CSRTopo(edge_index=ei)
    n = topo.node_count
    feat = np.random.default_rng(0).normal(size=(n, 8)).astype(np.float32)
    labels = jnp.asarray(
        np.random.default_rng(0).integers(0, 4, n).astype(np.int32)
    )
    mesh = make_mesh(data=2, feature=4)
    model = GraphSAGE(hidden=16, num_classes=4, num_layers=2)

    losses = {}
    for mode, alpha in (("replicated", 1.0), ("mesh", 1.0),
                        ("mesh-tight", 0.25)):
        if mode == "replicated":
            sampler = GraphSageSampler(topo, [4, 3], seed=3)
        else:
            sampler = GraphSageSampler(topo, [4, 3], seed=3,
                                       topo_sharding="mesh", mesh=mesh)
        feature = ShardedFeature(
            mesh, device_cache_size="1G", csr_topo=CSRTopo(edge_index=ei)
        ).from_cpu_tensor(feat)
        trainer = DistributedTrainer(
            mesh, sampler, feature, model, optax.adam(5e-3),
            local_batch=16, seed_sharding="all", routed_alpha=alpha,
        )
        params, opt = trainer.init(jax.random.PRNGKey(0))
        srng = np.random.default_rng(0)
        ls = []
        for step in range(3):
            seeds = srng.integers(0, n, trainer.global_batch)
            params, opt, loss = trainer.step(
                params, opt, seeds, labels, jax.random.PRNGKey(step)
            )
            ls.append(float(loss))
        losses[mode] = ls
        if mode == "mesh-tight":
            # the tight budget must actually exercise the fallback
            assert int(np.asarray(trainer.last_sample_overflow).sum()) > 0
    assert losses["replicated"] == losses["mesh"], losses
    assert losses["replicated"] == losses["mesh-tight"], losses

    # fused epoch: per-step (steps, num_layers) overflow vector
    sampler = GraphSageSampler(topo, [4, 3], seed=3, topo_sharding="mesh",
                               mesh=mesh)
    feature = ShardedFeature(
        mesh, device_cache_size="1G", csr_topo=CSRTopo(edge_index=ei)
    ).from_cpu_tensor(feat)
    trainer = DistributedTrainer(
        mesh, sampler, feature, model, optax.adam(5e-3), local_batch=16,
        seed_sharding="all", routed_alpha=0.25,
    )
    params, opt = trainer.init(jax.random.PRNGKey(0))
    seed_mat = trainer.pack_epoch(np.arange(3 * trainer.global_batch) % n,
                                  seed=0)
    params, opt, el = trainer.epoch_scan(params, opt, seed_mat, labels,
                                         jax.random.PRNGKey(1))
    assert np.all(np.isfinite(np.asarray(el)))
    sov = np.asarray(trainer.last_sample_overflow)
    assert sov.shape == (3, 2) and int(sov.sum()) > 0


@pytest.mark.slow
def test_trainer_shared_auto_alpha_tuner():
    """auto_alpha=True: one tuner reads BOTH overflow telemetries (feature
    gather + sampler hops) and doubles the shared routing budget after an
    overflowed eager batch."""
    ei = generate_pareto_graph(400, 6.0, seed=0)
    topo = CSRTopo(edge_index=ei)
    n = topo.node_count
    feat = np.random.default_rng(0).normal(size=(n, 8)).astype(np.float32)
    labels = jnp.asarray(
        np.random.default_rng(0).integers(0, 4, n).astype(np.int32)
    )
    mesh = make_mesh(data=2, feature=4)
    sampler = GraphSageSampler(topo, [4, 3], seed=3, topo_sharding="mesh",
                               mesh=mesh)
    feature = ShardedFeature(
        mesh, device_cache_size="1G", csr_topo=CSRTopo(edge_index=ei)
    ).from_cpu_tensor(feat)
    trainer = DistributedTrainer(
        mesh, sampler, feature, GraphSAGE(hidden=16, num_classes=4,
                                          num_layers=2),
        optax.adam(5e-3), local_batch=16, seed_sharding="all",
        routed_alpha=0.25, auto_alpha=True,
    )
    params, opt = trainer.init(jax.random.PRNGKey(0))
    srng = np.random.default_rng(0)
    alphas = []
    for step in range(3):
        seeds = srng.integers(0, n, trainer.global_batch)
        params, opt, _ = trainer.step(params, opt, seeds, labels,
                                      jax.random.PRNGKey(step))
        alphas.append(trainer.routed_alpha)
    assert alphas[-1] > 0.25, alphas  # grew after the overflowed batch
