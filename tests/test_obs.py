"""graftscope (quiver_tpu/obs) subsystem tests.

Covers: the MetricsRegistry/MetricsTape discipline (registration, tape
feeding through shard_map with per-metric psum placement, the
enabled/disabled program-level switch), the StepTimeline's streaming P²
percentiles and stage timing, Timer's registry hookup, both exporters'
round trips (JSONL and Prometheus exposition, including epoch_scan-shaped
``(steps, k)`` metrics), and the acceptance
differential: metrics collection disabled vs enabled yields a bit-identical
loss trajectory over an ``epoch_scan`` epoch.
"""

import io

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from quiver_tpu.obs import (
    MetricSnapshot,
    MetricsRegistry,
    P2Quantile,
    StepTimeline,
    from_prometheus,
    read_jsonl,
    to_prometheus,
    write_jsonl,
)
from quiver_tpu.parallel.mesh import DATA_AXIS, FEATURE_AXIS, make_mesh, shard_map
from quiver_tpu.utils import trace


# -- registry -----------------------------------------------------------------


def test_registry_register_and_record():
    reg = MetricsRegistry()
    reg.counter("a.count", doc="a counter")
    reg.gauge("b.vec", shape=(3,), doc="a gauge")
    reg.record({"a.count": jnp.int32(4), "b.vec": jnp.arange(3, dtype=jnp.int32)})
    assert int(reg.value("a.count")) == 4
    snap = reg.snapshot("b.vec")
    assert snap.kind == "gauge" and snap.steps is None
    assert snap.numpy.tolist() == [0, 1, 2]
    # epoch_scan-stacked values are detected by shape against the spec
    reg.record({"b.vec": jnp.ones((5, 3), jnp.int32)})
    assert reg.snapshot("b.vec").steps == 5
    reg.set("a.count", None)  # clear
    assert reg.value("a.count") is None
    assert [s.name for s in reg.snapshots()] == ["b.vec"]


def test_registry_spec_conflicts_and_unknown():
    reg = MetricsRegistry()
    reg.counter("x")
    reg.counter("x")  # idempotent re-register is fine
    with pytest.raises(ValueError, match="different spec"):
        reg.gauge("x")
    with pytest.raises(KeyError, match="not registered"):
        reg.spec("nope")
    tape = reg.tape()
    with pytest.raises(ValueError, match="is a counter"):
        tape.set("x", jnp.int32(1))


def test_tape_through_shard_map_psum():
    """The tape's metrics pytree rides shard_map out and psums once at the
    declared axes — the generalized last_routed_overflow discipline."""
    mesh = make_mesh(data=2, feature=4)
    reg = MetricsRegistry()
    reg.counter("ov", doc="per-device overflow, mesh-summed")
    reg.gauge("hits", shape=(2,))

    def body(x):
        tape = reg.tape()
        tape.add("ov", jnp.sum(x).astype(jnp.int32),
                 psum=(DATA_AXIS, FEATURE_AXIS))
        tape.set("hits", jnp.stack([jnp.sum(x), jnp.sum(x)]).astype(jnp.int32),
                 psum=DATA_AXIS)
        return tape.finalize()

    f = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P((DATA_AXIS, FEATURE_AXIS)),),
        out_specs={"ov": P(), "hits": P()}, check_vma=False,
    ))
    out = f(jnp.ones(16, jnp.int32))
    reg.record(out)
    assert int(reg.value("ov")) == 16  # all 8 devices' lanes, mesh total
    # hits psum'd over data only: 2 data groups x 2 lanes each... each
    # device holds 2 lanes -> per-device sum 2, data-psum = 4
    assert reg.value("hits").tolist() == [4, 4]


def test_tape_disabled_is_program_level_noop():
    reg = MetricsRegistry(enabled=False)
    reg.counter("ov")
    tape = reg.tape()
    tape.add("ov", jnp.int32(3))
    assert tape.finalize() == {}
    reg.record({})
    assert reg.value("ov") is None


def test_bucket_route_feeds_tape():
    """BucketRoute(tape=...) lands its overflow count on the tape — the
    shared comm core reports through the same registry discipline."""
    from quiver_tpu.parallel.routing import BucketRoute

    mesh = make_mesh(data=1, feature=8)
    reg = MetricsRegistry()
    reg.counter("route.ov")
    L, F = 16, 8

    def body(ids):
        tape = reg.tape()
        route = BucketRoute(
            ids, ids >= 0, ids, axis=FEATURE_AXIS, num_shards=F, cap=1,
            tape=tape, metric="route.ov",
        )
        rows = route.exchange(
            lambda req: jnp.where(req >= 0, req, 0).astype(jnp.int32)
        )
        return rows, tape.finalize()

    f = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P(FEATURE_AXIS),),
        out_specs=(P(FEATURE_AXIS), {"route.ov": P()}), check_vma=False,
    ))
    # every lane owned by shard 0 -> cap=1 buckets overflow heavily
    ids = jnp.zeros(F * L, jnp.int32)
    _, mtree = f(ids)
    reg.record(mtree)
    assert int(reg.value("route.ov")) == F * (L - 1)


# -- timeline -----------------------------------------------------------------


def test_p2_quantile_tracks_percentiles():
    rng = np.random.default_rng(0)
    xs = rng.uniform(0.0, 1.0, 5000)
    est = P2Quantile(0.95)
    for x in xs:
        est.update(float(x))
    assert est.count == 5000
    assert abs(est.value - np.percentile(xs, 95)) < 0.02


def test_p2_quantile_small_samples_exact():
    est = P2Quantile(0.5)
    for x in (3.0, 1.0, 2.0):
        est.update(x)
    assert est.value == 2.0


def test_p2_quantile_tiny_n_exact_nearest_rank():
    """Below the 5-marker warmup the estimator must return the exact
    nearest-rank order statistic (ceil(q*n), 1-based) — not an
    interpolated pick that undersells tail quantiles."""
    # n=1: every quantile IS the single sample
    for q in (0.01, 0.5, 0.99):
        est = P2Quantile(q)
        est.update(7.0)
        assert est.value == 7.0
    # n=2: p99 must be the max, p50 the lower sample (ceil(.5*2)=1)
    hi = P2Quantile(0.99)
    lo = P2Quantile(0.5)
    for x in (1.0, 2.0):
        hi.update(x)
        lo.update(x)
    assert hi.value == 2.0
    assert lo.value == 1.0
    # n=4: p50 -> 2nd order stat, p95 -> 4th
    med, tail = P2Quantile(0.5), P2Quantile(0.95)
    for x in (40.0, 10.0, 30.0, 20.0):
        med.update(x)
        tail.update(x)
    assert med.value == 20.0
    assert tail.value == 40.0


def test_p2_quantile_large_n_accuracy():
    rng = np.random.default_rng(7)
    xs = rng.normal(5.0, 2.0, 1000)
    for q in (0.5, 0.95, 0.99):
        est = P2Quantile(q)
        for x in xs:
            est.update(float(x))
        exact = float(np.percentile(xs, 100 * q))
        assert abs(est.value - exact) < 0.25, (q, est.value, exact)


def test_timeline_stage_and_report():
    tl = StepTimeline()
    for i in range(20):
        tl.observe("sample", 0.001 * (i + 1))
    with tl.stage("gather", sync=jnp.ones(8)):
        pass
    st = tl.stats("sample")
    assert st.count == 20
    assert st.max == pytest.approx(0.020)
    assert tl.stats("gather").count == 1
    rep = tl.report()
    assert "sample" in rep and "gather" in rep and "p95" in rep
    d = st.as_dict()
    assert d["count"] == 20 and d["p50_ms"] is not None


def test_timer_feeds_timeline():
    tl = StepTimeline()
    with trace.Timer("sample", quiet=True, registry=tl):
        pass
    with trace.Timer("sample", quiet=True, registry=tl, metric="renamed"):
        pass
    assert tl.stats("sample").count == 1
    assert tl.stats("renamed").count == 1
    assert tl.stats("sample").total >= 0.0


# -- exporters ----------------------------------------------------------------


def _sample_snapshots():
    return [
        MetricSnapshot("feature.routed_overflow", "counter",
                       np.int32(7), None, "lanes", "fallback lanes"),
        MetricSnapshot("feature.tier_hits", "gauge",
                       np.arange(12, dtype=np.int32).reshape(4, 3), 4,
                       "hits", "per-tier hits"),
        MetricSnapshot("loss.gauge", "gauge",
                       np.asarray([0.5, 0.25], np.float32), 2),
    ]


def _assert_same(a: MetricSnapshot, b: MetricSnapshot):
    assert a.name == b.name and a.kind == b.kind and a.steps == b.steps
    assert a.numpy.shape == b.numpy.shape
    assert a.numpy.dtype == b.numpy.dtype
    np.testing.assert_array_equal(a.numpy, b.numpy)


def test_jsonl_round_trip():
    snaps = _sample_snapshots()
    buf = io.StringIO()
    assert write_jsonl(snaps, buf, extra={"job": "t"}) == 3
    back = read_jsonl(buf.getvalue())
    assert len(back) == 3
    for a, b in zip(snaps, back):
        _assert_same(a, b)


def test_jsonl_file_round_trip(tmp_path):
    path = tmp_path / "metrics.jsonl"
    write_jsonl(_sample_snapshots(), str(path))
    write_jsonl(_sample_snapshots()[:1], str(path))  # append mode
    back = read_jsonl(str(path))
    assert len(back) == 4
    _assert_same(_sample_snapshots()[1], back[1])


def test_prometheus_round_trip():
    snaps = _sample_snapshots()
    text = to_prometheus(snaps)
    # scrapable exposition shape: HELP/TYPE lines + labeled samples
    assert "# TYPE quiver_feature_tier_hits gauge" in text
    assert "# HELP quiver_feature_tier_hits" in text
    assert ('quiver_feature_tier_hits'
            '{name="feature.tier_hits",idx="3,2"} 11') in text
    assert "# TYPE quiver_feature_routed_overflow counter" in text
    back = from_prometheus(text)
    assert len(back) == 3
    for a, b in zip(snaps, back):
        _assert_same(a, b)


def test_prometheus_hostile_names_round_trip():
    """Label-injection hygiene: names containing backslash, quote and
    newline survive the exposition round trip; distinct dotted names that
    sanitize to the same exposition name get numeric suffixes instead of
    silently merging; a hostile name cannot spoof the idx label."""
    snaps = [
        MetricSnapshot('evil\\name."quoted"\nline', "counter",
                       np.int32(3), None, "", 'doc with "quotes"\nand line'),
        # idx-spoof attempt: name label ends with what looks like idx=
        MetricSnapshot('spoof",idx="9,9', "gauge",
                       np.asarray([1.0, 2.0], np.float32), None),
        # collision pair: both sanitize to quiver_a_b
        MetricSnapshot("a.b", "counter", np.int32(1), None),
        MetricSnapshot("a_b", "counter", np.int32(2), None),
    ]
    text = to_prometheus(snaps)
    # every sample line stays one line (no raw newline broke out)
    for line in text.splitlines():
        assert line.startswith("#") or " " in line
    assert "quiver_a_b_2" in text  # collision got a suffix, not a merge
    back = from_prometheus(text)
    assert len(back) == 4
    for a, b in zip(snaps, back):
        _assert_same(a, b)
    # the spoofed gauge kept its true shape — idx wasn't hijacked
    assert back[1].numpy.shape == (2,)
    np.testing.assert_array_equal(back[1].numpy, [1.0, 2.0])


def test_exporters_agree_on_registry_output():
    """JSONL and Prometheus round trips reproduce the SAME values for a
    registry recording of an epoch_scan-shaped (steps, k) metric."""
    reg = MetricsRegistry()
    reg.counter("sample.hop_overflow", shape=(2,))
    reg.record({"sample.hop_overflow": jnp.asarray(
        [[1, 2], [3, 4], [5, 6]], jnp.int32)})
    snaps = reg.snapshots()
    assert snaps[0].steps == 3
    via_jsonl = read_jsonl(
        (lambda b: (write_jsonl(snaps, b), b.getvalue())[1])(io.StringIO())
    )
    via_prom = from_prometheus(to_prometheus(snaps))
    _assert_same(via_jsonl[0], via_prom[0])
    np.testing.assert_array_equal(
        via_jsonl[0].numpy, np.asarray([[1, 2], [3, 4], [5, 6]])
    )


def test_ledger_metrics_artifact(tmp_path, monkeypatch):
    """benchmarks.ledger append_metrics/read_metrics honor the env-pointed
    artifact path and round-trip snapshots."""
    from benchmarks import ledger

    path = tmp_path / "metrics.jsonl"
    monkeypatch.setenv("QUIVER_METRICS_JSONL", str(path))
    n = ledger.append_metrics(_sample_snapshots(), extra={"lane": "t"})
    assert n == 3 and path.exists()
    back = ledger.read_metrics()
    assert len(back) == 3
    monkeypatch.setenv("QUIVER_METRICS_JSONL", "")
    assert ledger.append_metrics(_sample_snapshots()) == 0  # disabled


# -- acceptance differential --------------------------------------------------


def _tiny_trainer(collect_metrics: bool):
    import optax

    from quiver_tpu import (
        CSRTopo,
        DistributedTrainer,
        GraphSageSampler,
        ShardedFeature,
    )
    from quiver_tpu.models.sage import GraphSAGE

    rng = np.random.default_rng(0)
    n = 96
    ei = rng.integers(0, n, size=(2, 800)).astype(np.int64)
    topo = CSRTopo(edge_index=ei)
    feat = rng.normal(size=(n, 8)).astype(np.float32)
    mesh = make_mesh(data=2, feature=4)
    store = ShardedFeature(
        mesh, device_cache_size=n * 8, csr_topo=topo
    ).from_cpu_tensor(feat)
    sampler = GraphSageSampler(topo, [3, 2], seed=0, seed_capacity=8)
    model = GraphSAGE(hidden=8, num_classes=4, num_layers=2)
    trainer = DistributedTrainer(
        mesh, sampler, store, model, optax.sgd(1e-2), local_batch=8,
        seed_sharding="all", collect_metrics=collect_metrics,
    )
    params, opt = trainer.init(jax.random.PRNGKey(0))
    labels = jnp.asarray(rng.integers(0, 4, n).astype(np.int32))
    return trainer, params, opt, labels


@pytest.mark.slow  # IR-proven fast: graftaudit's metrics-strip rule
# diffs the lowered on/off step programs every tier-1 run — identical
# data-movement collectives, exactly the declared metric psums stripped
# (tests/test_audit.py); this execution differential is the slow-lane
# end-to-end witness
def test_metrics_on_off_loss_bitwise_identical():
    """Acceptance: metrics collection disabled vs enabled yields a
    bit-identical loss trajectory over an epoch_scan epoch (the metric
    psums ride alongside the training math, never inside it)."""
    losses = {}
    for collect in (True, False):
        trainer, params, opt, labels = _tiny_trainer(collect)
        seed_mat = trainer.pack_epoch(np.arange(96), seed=0)
        _, _, ls = trainer.epoch_scan(
            params, opt, seed_mat, labels, jax.random.PRNGKey(7)
        )
        losses[collect] = np.asarray(ls)
        if collect:
            # telemetry present: per-step vectors in the registry views
            assert trainer.last_routed_overflow is not None
            assert np.asarray(trainer.last_tier_hits).shape == (
                seed_mat.shape[0], 3)
            rep = trainer.metrics_report()
            assert "feature.tier_hits" in rep and "timeline:" in rep
        else:
            assert trainer.last_routed_overflow is None
            assert trainer.last_tier_hits is None
            assert "collect_metrics=False" in trainer.metrics_report()
    assert losses[True].dtype == losses[False].dtype
    np.testing.assert_array_equal(
        losses[True].view(np.uint32), losses[False].view(np.uint32)
    )


def test_step_metrics_match_legacy_views():
    """One eager step: the registry snapshots ARE the legacy attributes
    (thin views), and the store receives the batch's tier hits."""
    from quiver_tpu.obs.registry import ROUTED_OVERFLOW, TIER_HITS

    trainer, params, opt, labels = _tiny_trainer(True)
    rng = np.random.default_rng(3)
    trainer.step(params, opt, rng.integers(0, 96, 32), labels,
                 jax.random.PRNGKey(1))
    assert int(np.asarray(trainer.last_routed_overflow)) == int(
        np.asarray(trainer.metrics.value(ROUTED_OVERFLOW)))
    np.testing.assert_array_equal(
        np.asarray(trainer.last_tier_hits),
        np.asarray(trainer.metrics.value(TIER_HITS)))
    # the store's own registry saw the fused batch totals
    np.testing.assert_array_equal(
        np.asarray(trainer.feature.last_tier_hits),
        np.asarray(trainer.last_tier_hits))
    assert trainer.timeline.stats("step").count == 1
