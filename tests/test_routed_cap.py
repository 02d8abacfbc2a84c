"""Capped-bucket routed gather: differential tests on the 8-device mesh.

The comm-volume fix (VERDICT r5 weak #3): destination buckets capped at
ceil(alpha*L/F) lanes so each all_to_all hop moves ~alpha*L lanes instead
of F*L. Parity bar (ISSUE 1): bit-identical to the uncapped path on
non-overflow workloads, still-correct (fallback-served) under adversarial
skew, overflow observable as batch metadata. Oracle: the dense table.
"""

import logging

import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest

from quiver_tpu import CSRTopo, GraphSageSampler
from quiver_tpu.feature.shard import ShardedFeature, ShardedTensor
from quiver_tpu.models.sage import GraphSAGE
from quiver_tpu.parallel.mesh import make_mesh
from quiver_tpu.parallel.trainer import DistributedTrainer


def _table(n=800, f=12, seed=0):
    return np.random.default_rng(seed).normal(size=(n, f)).astype(np.float32)


def test_capped_bit_identical_to_uncapped_no_overflow():
    """Spread ids (every shard hit roughly evenly) with the default alpha:
    zero overflow, and capped output must equal uncapped BIT-FOR-BIT."""
    mesh = make_mesh(data=2, feature=4)
    t = _table()
    st = ShardedTensor(mesh).from_cpu_tensor(t)
    rng = np.random.default_rng(1)
    for n in (64, 301, 777):
        ids = rng.integers(0, t.shape[0], n).astype(np.int32)
        uncapped = np.asarray(st.gather(jnp.asarray(ids), routed=True,
                                        routed_cap=None))
        capped = np.asarray(st.gather(jnp.asarray(ids), routed=True))
        assert np.array_equal(uncapped, t[ids])
        assert np.array_equal(capped, uncapped)  # bit-identical


def test_capped_explicit_cap_and_invalid_lanes():
    """Explicit per-bucket capacity + -1 sentinel lanes: invalid lanes
    return zero rows and never eat bucket capacity."""
    mesh = make_mesh(data=2, feature=4)
    t = _table()
    st = ShardedTensor(mesh).from_cpu_tensor(t)
    ids = np.concatenate([
        np.random.default_rng(2).integers(0, t.shape[0], 90),
        [-1] * 6,
    ]).astype(np.int32)
    out = np.asarray(st.gather(jnp.asarray(ids), routed=True, routed_cap=8))
    assert np.array_equal(out[:90], t[ids[:90]])
    assert np.all(out[90:] == 0)


def test_forced_overflow_served_by_fallback():
    """Adversarial skew — every id owned by shard 0 and a tiny cap: the
    buckets overflow massively, the fallback serves the overflowed lanes
    exactly, and the count is observable as batch metadata."""
    mesh = make_mesh(data=2, feature=4)
    t = _table()
    st = ShardedTensor(mesh).from_cpu_tensor(t)
    rng = np.random.default_rng(3)
    # rows_per_shard = 200: ids < 200 all live on shard 0
    ids = rng.integers(0, st.rows_per_shard, 256).astype(np.int32)
    out = np.asarray(st.gather(jnp.asarray(ids), routed=True, routed_cap=4))
    assert np.array_equal(out, t[ids])  # fallback-served, still exact
    ov = int(st.last_routed_overflow)
    # per device: 32 lanes, bucket 0 keeps 4 => 28 overflow x 8 devices
    assert ov == 8 * (32 - 4)


def test_no_overflow_on_clean_batch_metadata_zero():
    mesh = make_mesh(data=2, feature=4)
    t = _table()
    st = ShardedTensor(mesh).from_cpu_tensor(t)
    # round-robin over the 4 owning shards: every device's 32-lane slice
    # sends 8 requests per bucket, well under cap=ceil(2*32/4)=16
    lanes = np.arange(256)
    ids = ((lanes % 4) * st.rows_per_shard
           + (lanes // 4) % st.rows_per_shard).astype(np.int32)
    out = np.asarray(st.gather(jnp.asarray(ids), routed=True))
    assert np.array_equal(out, t[ids])
    assert int(st.last_routed_overflow) == 0


def test_auto_tuner_grows_alpha_until_overflow_stops():
    """gather(routed_cap="auto") doubles routed_alpha on the call AFTER an
    overflowed batch, saturating at alpha=F (the uncapped program)."""
    mesh = make_mesh(data=2, feature=4)
    t = _table()
    st = ShardedTensor(mesh).from_cpu_tensor(t)
    st.routed_alpha = 1.0
    ids = np.random.default_rng(4).integers(
        0, st.rows_per_shard, 256).astype(np.int32)  # all on shard 0
    out = np.asarray(st.gather(jnp.asarray(ids), routed=True))
    assert np.array_equal(out, t[ids])
    assert int(st.last_routed_overflow) > 0
    out = np.asarray(st.gather(jnp.asarray(ids), routed=True))
    assert np.array_equal(out, t[ids])
    assert st.routed_alpha == 2.0  # grew after the overflowed batch
    out = np.asarray(st.gather(jnp.asarray(ids), routed=True))
    assert np.array_equal(out, t[ids])
    assert st.routed_alpha == 4.0  # == F: cap == L, uncapped program
    assert int(st.last_routed_overflow) == 0


def test_routed_cap_planning():
    mesh = make_mesh(data=2, feature=4)
    st = ShardedTensor(mesh)
    assert st.routed_cap(128) == 64  # ceil(2*128/4)
    assert st.routed_cap(128, alpha=1.0) == 32
    assert st.routed_cap(128, alpha=100.0) == 128  # clamped to L
    assert st.routed_cap(2, alpha=0.001) == 1  # never below 1
    with pytest.raises(ValueError):
        st.routed_cap(128, alpha=0)


def test_sharded_feature_capped_with_reorder_and_skew():
    """ShardedFeature: feature_order translation (degree reorder
    concentrates hot ids on shard 0 — the REAL skew source) through the
    capped routed gather, exact vs the dense oracle."""
    rng = np.random.default_rng(5)
    ei = np.stack([rng.integers(0, 400, 3000), rng.integers(0, 400, 3000)])
    topo = CSRTopo(edge_index=ei)
    n = topo.node_count
    feat = rng.normal(size=(n, 8)).astype(np.float32)
    mesh = make_mesh(data=2, feature=4)
    store = ShardedFeature(mesh, device_cache_size="1G", csr_topo=topo,
                           routed_alpha=1.0).from_cpu_tensor(feat)
    # degree-skewed draw: the sampler's access law, hits shard 0 hardest
    deg = topo.degree.astype(np.float64)
    ids = rng.choice(n, size=96, p=deg / deg.sum()).astype(np.int32)
    a = np.asarray(store[jnp.asarray(ids)])
    b = np.asarray(store.gather(jnp.asarray(ids), routed=True))
    assert np.array_equal(a, feat[ids])
    assert np.array_equal(b, a)
    assert int(store.last_routed_overflow) >= 0  # observable either way


def test_sharded_feature_int8_capped_routed_dequant():
    """int8 rows through capped routing + forced overflow must dequantize
    identically to the psum gather (fallback carries int8 codes too)."""
    rng = np.random.default_rng(8)
    ei = np.stack([rng.integers(0, 300, 2000), rng.integers(0, 300, 2000)])
    topo = CSRTopo(edge_index=ei)
    n = topo.node_count
    feat = rng.normal(size=(n, 16)).astype(np.float32)
    mesh = make_mesh(data=2, feature=4)
    store = ShardedFeature(mesh, device_cache_size="1G", csr_topo=topo,
                           dtype="int8").from_cpu_tensor(feat)
    hot_rows = store.hot.rows_per_shard  # force everything onto shard 0
    ids = rng.integers(0, min(hot_rows, n), 64).astype(np.int32)
    a = np.asarray(store[jnp.asarray(ids)])
    b = np.asarray(store.gather(jnp.asarray(ids), routed=True, routed_cap=2))
    assert np.array_equal(a, b)


@pytest.mark.slow  # IR-proven fast: graftaudit collective-parity +
# comm-budget walk the capped gather's lowered fallback cond and lane
# shapes every tier-1 run (tests/test_audit.py); this execution
# differential stays as the slow-lane end-to-end witness
def test_trainer_capped_loss_bit_identical_and_overflow_observable():
    """DistributedTrainer(seed_sharding="all"): the capped-bucket gather
    must not change the training math at all — losses bit-identical to the
    uncapped trainer on the same seeds/keys — and the per-step overflow
    count must surface via last_routed_overflow."""
    rng = np.random.default_rng(0)
    labels = rng.integers(0, 4, 400)
    feat = np.eye(4, dtype=np.float32)[labels] * 2.0
    feat += rng.normal(scale=0.8, size=(400, 4)).astype(np.float32)
    ei = np.stack([rng.integers(0, 400, 4000), rng.integers(0, 400, 4000)])
    topo = CSRTopo(edge_index=ei)
    n = topo.node_count
    mesh = make_mesh(data=2, feature=4)
    labels_dev = jnp.asarray(labels[:n].astype(np.int32))
    model = GraphSAGE(hidden=16, num_classes=4, num_layers=2)

    losses = {}
    for alpha in (None, 1.0):  # alpha=1: tightest cap, likeliest overflow
        sampler = GraphSageSampler(topo, [5, 5], seed=3)
        feature = ShardedFeature(
            mesh, device_cache_size="1G", csr_topo=topo
        ).from_cpu_tensor(feat[:n])
        trainer = DistributedTrainer(
            mesh, sampler, feature, model, optax.adam(5e-3), local_batch=32,
            seed_sharding="all", routed_alpha=alpha,
        )
        params, opt = trainer.init(jax.random.PRNGKey(0))
        srng = np.random.default_rng(0)
        ls = []
        for step in range(3):
            seeds = srng.integers(0, n, trainer.global_batch)
            params, opt, loss = trainer.step(
                params, opt, seeds, labels_dev, jax.random.PRNGKey(step)
            )
            ov = int(trainer.last_routed_overflow)
            assert ov == 0 if alpha is None else ov >= 0
            ls.append(float(loss))
        losses[alpha] = ls
    assert losses[None] == losses[1.0], losses  # bit-identical trajectories


def test_trainer_epoch_scan_overflow_vector():
    """epoch_scan surfaces a per-step overflow vector (batch metadata for
    the tuner/scoreboard)."""
    rng = np.random.default_rng(0)
    labels = rng.integers(0, 4, 300)
    feat = rng.normal(size=(300, 6)).astype(np.float32)
    ei = np.stack([rng.integers(0, 300, 2500), rng.integers(0, 300, 2500)])
    topo = CSRTopo(edge_index=ei)
    n = topo.node_count
    mesh = make_mesh(data=2, feature=4)
    sampler = GraphSageSampler(topo, [4, 3], seed=1)
    feature = ShardedFeature(
        mesh, device_cache_size="1G", csr_topo=topo
    ).from_cpu_tensor(feat[:n])
    model = GraphSAGE(hidden=8, num_classes=4, num_layers=2)
    trainer = DistributedTrainer(
        mesh, sampler, feature, model, optax.adam(5e-3), local_batch=16,
        seed_sharding="all", routed_alpha=1.0,
    )
    params, opt = trainer.init(jax.random.PRNGKey(0))
    seed_mat = trainer.pack_epoch(
        np.arange(3 * trainer.global_batch) % n, seed=0)
    params, opt, losses = trainer.epoch_scan(
        params, opt, seed_mat, jnp.asarray(labels[:n].astype(np.int32)),
        jax.random.PRNGKey(1),
    )
    ovs = np.asarray(trainer.last_routed_overflow)
    assert ovs.shape == (3,) and np.all(ovs >= 0)
    assert np.all(np.isfinite(np.asarray(losses)))


def test_bench_comm_model_reduction():
    """The benchmark's lanes-per-hop model: >= (F/alpha)x reduction at
    F=4 (acceptance criterion), exact bucket arithmetic."""
    import argparse

    from benchmarks.bench_feature import _routed_comm_model

    class _Store:
        pass

    class _Hot:
        num_shards = 4

        @staticmethod
        def routed_cap(length, alpha):
            st = ShardedTensor(make_mesh(data=2, feature=4))
            return st.routed_cap(length, alpha)

    store = _Store()
    store.hot = _Hot()
    args = argparse.Namespace(routed=True, routed_alpha=1.0,
                              gather_batch=4096)
    cap, model = _routed_comm_model(args, store)
    F, alpha = 4, 1.0
    assert model["lanes_per_hop_uncapped"] / model["lanes_per_hop"] >= F / alpha
    assert model["comm_reduction"] >= F / alpha
    assert cap == model["routed_cap"]
    # uncapped run still records the model (reduction 1.0)
    args = argparse.Namespace(routed=True, routed_alpha=0.0,
                              gather_batch=4096)
    cap, model = _routed_comm_model(args, store)
    assert cap is None and model["comm_reduction"] == 1.0
