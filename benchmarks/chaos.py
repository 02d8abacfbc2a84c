"""Chaos lane: FaultPlan drills over a tiny epoch — the resilience layer's
evidence job (log-only).

Deterministic drills, each asserting the property the resilience
layer guarantees (quiver_tpu/resilience/):

* **guard**: a NaN-poisoned batch inside the fused step leaves params
  bit-unchanged and the skip counter reads exactly 1;
* **retry**: seeded transient sampler faults are absorbed by the
  Prefetcher's bounded backoff and the delivered stream is bit-identical
  to a fault-free run;
* **preempt/resume**: a simulated kill mid-epoch, then resume() — the
  remaining loss trajectory is bit-identical to the uninterrupted run;
* **resize**: the elastic drill — kill an F-shard run mid-epoch, resume
  onto HALF the devices (``resume(mesh=)``: topology + three-tier feature
  store re-planned, blocks-per-device doubled) and the remaining loss
  trajectory + final params stay bit-identical to the uninterrupted
  full-mesh run;
* **corrupt**: flip manifest-covered bytes in the NEWEST checkpoint (and
  plant an uncommitted partial directory) — resume() quarantines both and
  falls back to the previous valid checkpoint, no manual intervention;
* **cold-outage**: a cold-tier outage (consecutive feature-lookup
  failures) trips the circuit breaker into degraded serving — the epoch
  completes with ``resilience.degraded_lookups > 0`` instead of crashing,
  and a half-open probe closes the breaker once the outage ends;
* **pipeline**: the software-pipelined epoch's crash seam — preempt a
  ``pipeline_depth=1`` run mid-epoch, resume() (the pipelined chunk
  re-issues its carried batch from the seed matrix), and the remaining
  loss trajectory + final params are bit-identical to an UNINTERRUPTED
  SERIAL (depth=0) run — the pipeline survives kill/replay without ever
  serializing in-flight batch state;
* **mutate**: the streaming-mutation drill (quiver_tpu/streaming) — a
  malformed delta batch is quarantined whole at admission (counted,
  never staged), a mid-commit crash (injected at every pre-publish
  stage) leaves the old version readable with SAMPLING BIT-IDENTICAL to
  the pre-commit oracle and the failed commit quarantined not
  half-applied, and a successful commit bumps the version exactly once —
  stale samplers raise until refreshed, then serve the mutated graph;
* **scale-out**: the serving-fleet drill (quiver_tpu/serving/fleet.py) —
  a replica joins MID-TRAFFIC, warms every ladder program from the
  shared persisted AOT-executable cache with ZERO compiles, and serves
  responses bitwise-identical to the already-running replica for the
  same (node, seq) stream (and to the direct single-query oracle);
* **ooc**: the disk-tier drill (quiver_tpu/ooc/) — mid-epoch transient
  disk-read failures are absorbed by the AsyncStager's bounded backoff
  (epoch completes, loss trajectory bit-identical to the fault-free
  disk run), and a TORN raw directory (COMMIT marker missing) raises
  ``CorruptRawDir`` at load, is quarantined aside, and the loader falls
  back to the legacy ``.npz`` of the same topology with sampling
  bit-identical to the original;
* **postmortem**: the flight-recorder drill (quiver_tpu/obs/recorder.py)
  — every fault class above that wires a recorder (nonfinite-guard trip,
  circuit-breaker opening, aborted streaming commit) dumps an
  integrity-verified (CRC-manifested, COMMIT-marker-last) postmortem
  bundle naming the faulting stage (``train``/``gather``/``commit``),
  and a TORN bundle directory is quarantined aside — never trusted —
  while the earlier bundles keep verifying.

Any drill failure raises (the session marks the job failed); success
prints one ``CHAOS <drill> OK`` line per drill. ``--drills`` selects a
subset (the CI smoke runs ``--drills corrupt mutate`` on a 2-device CPU
mesh).

    python -m benchmarks.chaos --smoke
"""

import argparse
import tempfile

import numpy as np

from benchmarks import common

DRILLS = ("guard", "retry", "preempt", "resize", "corrupt", "cold-outage",
          "pipeline", "mutate", "scale-out", "ooc", "postmortem")


def _build_graph(nodes: int, feature_dim: int, seed: int):
    from quiver_tpu import CSRTopo

    rng = np.random.default_rng(seed)
    topo = CSRTopo(
        edge_index=rng.integers(0, nodes, size=(2, 10 * nodes)).astype(
            np.int64
        )
    )
    feat = rng.normal(size=(nodes, feature_dim)).astype(np.float32)
    labels = rng.integers(0, 4, nodes).astype(np.int32)
    return topo, feat, labels


def _build_trainer(topo, feat, local_batch, plan=None, guard=False,
                   checkpoint_dir=None, checkpoint_every=0,
                   pipeline_depth=0, tracer=None, recorder=None):
    import optax

    from quiver_tpu import Feature, GraphSageSampler
    from quiver_tpu.models.sage import GraphSAGE
    from quiver_tpu.parallel.mesh import make_mesh
    from quiver_tpu.parallel.trainer import DistributedTrainer

    mesh = make_mesh()  # data = all devices, feature = 1
    sampler = GraphSageSampler(
        topo, [5, 5], seed=3, seed_capacity=local_batch
    )
    feature = Feature(device_cache_size="1G").from_cpu_tensor(feat)
    model = GraphSAGE(hidden=16, num_classes=4, num_layers=2)
    kw = {}
    if checkpoint_dir is not None:
        kw = dict(checkpoint_dir=checkpoint_dir,
                  checkpoint_every=checkpoint_every)
    return DistributedTrainer(
        mesh, sampler, feature, model, optax.sgd(1e-2),
        local_batch=local_batch, nonfinite_guard=guard, fault_plan=plan,
        pipeline_depth=pipeline_depth, tracer=tracer, recorder=recorder,
        **kw
    )


def _tree_equal(a, b) -> bool:
    import jax

    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb)
    )


def drill_guard(topo, feat, labels, local_batch, seed):
    """NaN batch -> cond-skipped update, params preserved, counter = 1."""
    import jax
    import jax.numpy as jnp

    from quiver_tpu import FaultPlan
    from quiver_tpu.obs.registry import GUARD_SKIPPED

    plan = FaultPlan(nan_feature_steps=(1,), nan_rows=8)
    trainer = _build_trainer(topo, feat, local_batch, plan=plan, guard=True)
    params, opt = trainer.init(jax.random.PRNGKey(0))
    lab = jnp.asarray(labels)
    rng = np.random.default_rng(seed)
    for step in range(3):
        p_before = params
        params, opt, loss = trainer.step(
            params, opt, rng.integers(0, topo.node_count,
                                      trainer.global_batch),
            lab, jax.random.PRNGKey(step),
        )
        if step == 1:
            assert not np.isfinite(float(loss)), "poisoned loss was finite"
            assert _tree_equal(params, p_before), \
                "poisoned step mutated params"
            skipped = int(np.asarray(trainer.metrics.value(GUARD_SKIPPED)))
            assert skipped == 1, f"skip counter {skipped} != 1"
        else:
            assert np.isfinite(float(loss)), f"clean step {step} loss NaN"
    common.write_metrics(trainer, drill="chaos-guard")
    common.log("CHAOS guard OK (poisoned step skipped, params preserved)")


def drill_retry(topo, steps, local_batch, seed):
    """Seeded transient sampler faults -> retried, stream bit-identical."""
    from quiver_tpu import FaultPlan, GraphSageSampler
    from quiver_tpu.obs import StepTimeline
    from quiver_tpu.parallel.pipeline import Prefetcher

    plan = FaultPlan.chaos(
        seed=seed, steps=steps, transient_p=0.4, max_transient=2
    )
    if not plan.sampler_faults:
        # a sparse draw must not turn the drill into a no-op
        import dataclasses

        plan = dataclasses.replace(plan, sampler_faults={1: 2})
    seeds = [
        np.random.default_rng(seed + i).integers(
            0, topo.node_count, local_batch
        )
        for i in range(steps)
    ]
    oracle = GraphSageSampler(topo, [5, 5], seed=3,
                              seed_capacity=local_batch)
    clean = [oracle.sample(s) for s in seeds]
    faulty = plan.wrap_sampler(
        GraphSageSampler(topo, [5, 5], seed=3, seed_capacity=local_batch)
    )
    timeline = StepTimeline()
    pf = Prefetcher(faulty, None, depth=2, retries=3, backoff=1e-3,
                    timeline=timeline)
    batches = list(pf.run(seeds))
    assert len(batches) == steps, f"{len(batches)}/{steps} delivered"
    planned = sum(plan.sampler_faults.values())
    assert pf.retries_total == planned, \
        f"retries {pf.retries_total} != planned {planned}"
    for c, b in zip(clean, batches):
        assert np.array_equal(np.asarray(c.n_id), np.asarray(b.out.n_id)), \
            "recovered stream diverged from the fault-free oracle"
    common.log(
        f"CHAOS retry OK ({planned} transient faults absorbed, stream "
        "bit-identical)"
    )


def drill_preempt_resume(topo, feat, labels, local_batch, seed):
    """Kill at a planned step, resume, compare the trajectory bitwise."""
    import jax
    import jax.numpy as jnp

    from quiver_tpu import FaultPlan, Preemption

    lab = jnp.asarray(labels)
    idx = np.random.default_rng(seed).integers(
        0, topo.node_count, 6 * local_batch * jax.device_count()
    )
    with tempfile.TemporaryDirectory() as tmp:
        trainer_a = _build_trainer(
            topo, feat, local_batch, checkpoint_dir=f"{tmp}/a",
            checkpoint_every=2,
        )
        seed_mat = trainer_a.pack_epoch(idx, seed=0)
        key = jax.random.PRNGKey(7)
        pa, oa = trainer_a.init(jax.random.PRNGKey(0))
        pa, oa, losses_a = trainer_a.epoch_scan(pa, oa, seed_mat, lab, key)
        losses_a = np.asarray(losses_a)

        trainer_b = _build_trainer(
            topo, feat, local_batch, checkpoint_dir=f"{tmp}/b",
            checkpoint_every=2, plan=FaultPlan(preempt_at_step=3),
        )
        p0, o0 = trainer_b.init(jax.random.PRNGKey(0))
        preempted = False
        try:
            trainer_b.epoch_scan(p0, o0, seed_mat, lab, key)
        except Preemption:
            preempted = True
        assert preempted, "FaultPlan preemption never fired"
        pr, orr, key_r, step, epoch = trainer_b.resume(p0, o0)
        assert step == 2, f"resumed at step {step}, expected 2"
        pr, orr, losses_r = trainer_b.epoch_scan(
            pr, orr, seed_mat, lab, key_r, epoch=epoch, start_step=step
        )
        losses_r = np.asarray(losses_r)
        assert np.array_equal(
            losses_r.view(np.uint32), losses_a[step:].view(np.uint32)
        ), "resumed loss trajectory diverged"
        assert _tree_equal(pa, pr), "resumed final params diverged"
        trainer_a.checkpointer.close()
        trainer_b.checkpointer.close()
    common.log(
        f"CHAOS preempt/resume OK (killed at step 3, resumed at {step}, "
        f"{losses_r.shape[0]} remaining steps bit-identical)"
    )


def _build_elastic_trainer(topo, feat, mesh, local_batch, workers,
                           checkpoint_dir=None, checkpoint_every=2,
                           plan=None):
    """Elastic config: mesh-sharded topology + three-tier sharded feature
    + logical_workers (the resize drill's trainer shape)."""
    import optax

    from quiver_tpu import GraphSageSampler
    from quiver_tpu.feature.shard import ShardedFeature
    from quiver_tpu.models.sage import GraphSAGE
    from quiver_tpu.parallel.mesh import FEATURE_AXIS
    from quiver_tpu.parallel.trainer import DistributedTrainer

    n, d = feat.shape
    F = mesh.shape[FEATURE_AXIS]
    store = ShardedFeature(
        mesh,
        device_cache_size=max(n // (2 * F), 1) * d * feat.dtype.itemsize,
        replicate_budget=8 * d * feat.dtype.itemsize,
        csr_topo=topo,
    ).from_cpu_tensor(feat)
    sampler = GraphSageSampler(
        topo, [5, 5], seed=3, seed_capacity=local_batch,
        topo_sharding="mesh", mesh=mesh,
    )
    model = GraphSAGE(hidden=16, num_classes=4, num_layers=2)
    kw = {}
    if checkpoint_dir is not None:
        kw = dict(checkpoint_dir=checkpoint_dir,
                  checkpoint_every=checkpoint_every)
    return DistributedTrainer(
        mesh, sampler, store, model, optax.sgd(1e-2),
        local_batch=local_batch, seed_sharding="all",
        logical_workers=workers, fault_plan=plan, **kw
    )


def drill_resize(topo, feat, labels, local_batch, seed):
    """Kill at F, resume(mesh=F/2): trajectory + params bit-identical."""
    import jax
    import jax.numpy as jnp

    from quiver_tpu import FaultPlan, Preemption
    from quiver_tpu.parallel.mesh import make_mesh

    F = jax.device_count()
    if F % 2 or F < 2:
        common.log(
            f"CHAOS resize SKIPPED ({F} devices; needs an even count >= 2)"
        )
        return
    lab = jnp.asarray(labels)
    mesh_hi = make_mesh(n_devices=F, data=1, feature=F)
    idx = np.random.default_rng(seed).integers(
        0, topo.node_count, 6 * local_batch * F
    )
    with tempfile.TemporaryDirectory() as tmp:
        trainer_a = _build_elastic_trainer(
            topo, feat, mesh_hi, local_batch, F, checkpoint_dir=f"{tmp}/a",
        )
        seed_mat = trainer_a.pack_epoch(idx, seed=0)
        key = jax.random.PRNGKey(7)
        pa, oa = trainer_a.init(jax.random.PRNGKey(0))
        pa, oa, losses_a = trainer_a.epoch_scan(pa, oa, seed_mat, lab, key)
        losses_a = np.asarray(losses_a)

        trainer_b = _build_elastic_trainer(
            topo, feat, mesh_hi, local_batch, F, checkpoint_dir=f"{tmp}/b",
            plan=FaultPlan(preempt_at_step=3),
        )
        p0, o0 = trainer_b.init(jax.random.PRNGKey(0))
        try:
            trainer_b.epoch_scan(p0, o0, seed_mat, lab, key)
            raise AssertionError("FaultPlan preemption never fired")
        except Preemption:
            pass
        mesh_lo = make_mesh(n_devices=F // 2, data=1, feature=F // 2)
        pr, orr, key_r, step, epoch = trainer_b.resume(p0, o0, mesh=mesh_lo)
        assert trainer_b.blocks_per_device == 2, \
            f"blocks/device {trainer_b.blocks_per_device} != 2"
        pr, orr, losses_r = trainer_b.epoch_scan(
            pr, orr, seed_mat, lab, key_r, epoch=epoch, start_step=step
        )
        losses_r = np.asarray(losses_r)
        assert np.array_equal(
            losses_r.view(np.uint32), losses_a[step:].view(np.uint32)
        ), "resized loss trajectory diverged from the full-mesh run"
        assert _tree_equal(pa, pr), "resized final params diverged"
        trainer_a.checkpointer.close()
        trainer_b.checkpointer.close()
    common.log(
        f"CHAOS resize OK (killed at step 3 on F={F}, resumed at step "
        f"{step} on F={F // 2}, {losses_r.shape[0]} remaining steps "
        "bit-identical)"
    )


def drill_pipeline(topo, feat, labels, local_batch, seed):
    """Preempt a pipeline_depth=1 epoch mid-flight, resume, and compare
    the remaining trajectory + final params bitwise against an
    UNINTERRUPTED SERIAL (depth=0) run — the crash/replay seam composes
    with the one-step skew because pipelined chunks re-issue their
    carried batch from the seed matrix instead of serializing it."""
    import jax
    import jax.numpy as jnp

    from quiver_tpu import FaultPlan, Preemption
    from quiver_tpu.obs.registry import PIPELINE_REISSUES

    lab = jnp.asarray(labels)
    idx = np.random.default_rng(seed).integers(
        0, topo.node_count, 6 * local_batch * jax.device_count()
    )
    with tempfile.TemporaryDirectory() as tmp:
        trainer_a = _build_trainer(topo, feat, local_batch)
        seed_mat = trainer_a.pack_epoch(idx, seed=0)
        key = jax.random.PRNGKey(7)
        pa, oa = trainer_a.init(jax.random.PRNGKey(0))
        pa, oa, losses_a = trainer_a.epoch_scan(pa, oa, seed_mat, lab, key)
        losses_a = np.asarray(losses_a)

        trainer_b = _build_trainer(
            topo, feat, local_batch, checkpoint_dir=f"{tmp}/b",
            checkpoint_every=2, plan=FaultPlan(preempt_at_step=3),
            pipeline_depth=1,
        )
        p0, o0 = trainer_b.init(jax.random.PRNGKey(0))
        preempted = False
        try:
            trainer_b.epoch_scan(p0, o0, seed_mat, lab, key)
        except Preemption:
            preempted = True
        assert preempted, "FaultPlan preemption never fired"
        pr, orr, key_r, step, epoch = trainer_b.resume(p0, o0)
        assert step == 2, f"resumed at step {step}, expected 2"
        pr, orr, losses_r = trainer_b.epoch_scan(
            pr, orr, seed_mat, lab, key_r, epoch=epoch, start_step=step
        )
        losses_r = np.asarray(losses_r)
        assert np.array_equal(
            losses_r.view(np.uint32), losses_a[step:].view(np.uint32)
        ), "resumed pipelined trajectory diverged from the serial oracle"
        assert _tree_equal(pa, pr), "resumed pipelined params diverged"
        reissues = int(np.asarray(
            trainer_b.metrics.value(PIPELINE_REISSUES)
        ))
        assert reissues > 0, "chunked pipelined run never re-issued"
        trainer_b.checkpointer.close()
    common.log(
        f"CHAOS pipeline OK (depth=1 killed at step 3, resumed at {step}, "
        f"{losses_r.shape[0]} remaining steps bit-identical to the serial "
        f"run, {reissues} chunk re-issues)"
    )


def drill_corrupt_checkpoint(topo, feat, labels, local_batch, seed):
    """Flip manifest-covered bytes in the newest checkpoint: resume()
    quarantines it (and a planted uncommitted dir) and falls back."""
    import glob
    import os

    import jax
    import jax.numpy as jnp

    lab = jnp.asarray(labels)
    idx = np.random.default_rng(seed).integers(
        0, topo.node_count, 6 * local_batch * jax.device_count()
    )
    with tempfile.TemporaryDirectory() as tmp:
        ckdir = f"{tmp}/ck"
        trainer = _build_trainer(
            topo, feat, local_batch, checkpoint_dir=ckdir, checkpoint_every=2
        )
        seed_mat = trainer.pack_epoch(idx, seed=0)
        key = jax.random.PRNGKey(7)
        p0, o0 = trainer.init(jax.random.PRNGKey(0))
        trainer.epoch_scan(p0, o0, seed_mat, lab, key)
        trainer.checkpointer.wait_until_finished()
        newest = trainer.checkpointer.latest_step()
        prev_valid = trainer.checkpointer.all_steps()[-2]
        # flip a manifest-covered byte in the newest payload
        apath = os.path.join(ckdir, f"step-{newest}", "arrays.bin")
        with open(apath, "r+b") as fh:
            fh.seek(os.path.getsize(apath) // 2)
            b = fh.read(1)
            fh.seek(-1, os.SEEK_CUR)
            fh.write(bytes([b[0] ^ 0xFF]))
        # plant an uncommitted partial directory "newer" than everything
        partial = os.path.join(ckdir, f"step-{newest + 50}")
        os.makedirs(partial)
        with open(os.path.join(partial, "arrays.bin"), "wb") as fh:
            fh.write(b"\x00" * 16)  # no manifest, no COMMIT: a crashed save
        assert trainer.checkpointer.latest_step() == newest, \
            "uncommitted directory leaked into the step scan"
        pr, orr, key_r, step, epoch = trainer.resume(p0, o0)
        meta = trainer.checkpointer.metadata(prev_valid)
        assert step == meta["step"], \
            f"fell back to step {step}, expected {meta['step']}"
        quarantined = glob.glob(os.path.join(ckdir, "quarantine-*"))
        assert quarantined, "corrupt checkpoint was not quarantined"
        # the run continues from the fallback without manual intervention
        pr, orr, losses_r = trainer.epoch_scan(
            pr, orr, seed_mat, lab, key_r, epoch=epoch, start_step=step
        )
        assert np.isfinite(np.asarray(losses_r)).all()
        trainer.checkpointer.close()
    common.log(
        f"CHAOS corrupt-checkpoint OK (newest checkpoint poisoned + "
        f"partial dir planted; auto-fell-back to step {step}, "
        f"{np.asarray(losses_r).shape[0]} steps completed after)"
    )


def drill_cold_outage(topo, feat, labels, local_batch, seed):
    """Cold-tier outage: the circuit breaker serves fallback rows, the
    epoch completes, degraded_lookups > 0, breaker closes after."""
    import jax
    import optax

    from quiver_tpu import (
        DegradedFeature,
        FaultPlan,
        Feature,
        GraphSageSampler,
    )
    from quiver_tpu.models.sage import GraphSAGE
    from quiver_tpu.obs.registry import DEGRADED_LOOKUPS
    from quiver_tpu.parallel.mesh import make_mesh
    from quiver_tpu.parallel.trainer import DataParallelTrainer

    mesh = make_mesh()  # data = all devices, feature = 1
    store = Feature(device_cache_size="1G").from_cpu_tensor(feat)
    # outage: 6 consecutive lookup failures starting at lookup 3 (the
    # init lookup is 0); breaker opens after 3, probes every 2 calls
    plan = FaultPlan(feature_faults={3: 6})
    degraded = DegradedFeature(
        plan.wrap_feature(store), failures=3, probe_every=2,
        fallback="zeros",
    )
    sampler = GraphSageSampler(
        topo, [5, 5], seed=3, seed_capacity=local_batch
    )
    trainer = DataParallelTrainer(
        mesh, sampler, degraded,
        GraphSAGE(hidden=16, num_classes=4, num_layers=2),
        optax.sgd(1e-2), local_batch=local_batch, prefetch_retries=3,
        prefetch_backoff=1e-3,
    )
    params, opt = trainer.init(jax.random.PRNGKey(0))
    idx = np.random.default_rng(seed).integers(
        0, topo.node_count, 10 * trainer.global_batch
    )
    params, opt, mean_loss, steps = trainer.train_epoch(
        params, opt, idx, np.asarray(labels), jax.random.PRNGKey(1)
    )
    assert steps == 10, f"epoch delivered {steps}/10 steps"
    assert np.isfinite(mean_loss), "degraded epoch produced NaN mean loss"
    served = int(np.asarray(degraded.metrics.value(DEGRADED_LOOKUPS)))
    assert served > 0 and degraded.degraded_total == served, \
        f"degraded_lookups {served} (expected > 0)"
    assert degraded.breaker.state == "closed", \
        f"breaker ended {degraded.breaker.state!r} (outage was finite)"
    common.write_metrics(degraded, trainer, drill="chaos-cold-outage")
    common.log(
        f"CHAOS cold-outage OK ({served} lookups served degraded, epoch "
        f"completed {steps}/10 steps, breaker closed after the outage)"
    )


def drill_postmortem(topo, feat, labels, local_batch, seed):
    """Every chaos fault class dumps an integrity-verified postmortem
    bundle naming the faulting stage — guard trip (train), breaker open
    (gather), aborted streaming commit (commit) — and a torn bundle
    directory is quarantined, never trusted, while the earlier bundles
    keep verifying."""
    import os

    import jax
    import jax.numpy as jnp

    from quiver_tpu import (
        CommitAborted,
        CSRTopo,
        DegradedFeature,
        DeltaBatch,
        FaultPlan,
        Feature,
        FlightRecorder,
        StreamingGraph,
        Tracer,
        TransientFault,
    )
    from quiver_tpu.obs.recorder import TornBundle, list_bundles, \
        verify_bundle

    rng = np.random.default_rng(seed)
    n = topo.node_count
    with tempfile.TemporaryDirectory() as tmp:
        tracer = Tracer()
        rec = FlightRecorder(tmp, capacity=64, keep=8, tracer=tracer)

        # fault class 1 — nonfinite-guard trip names stage "train"
        plan = FaultPlan(nan_feature_steps=(1,), nan_rows=8)
        trainer = _build_trainer(topo, feat, local_batch, plan=plan,
                                 guard=True, tracer=tracer, recorder=rec)
        params, opt = trainer.init(jax.random.PRNGKey(0))
        lab = jnp.asarray(labels)
        for step in range(2):
            params, opt, _loss = trainer.step(
                params, opt, rng.integers(0, n, trainer.global_batch),
                lab, jax.random.PRNGKey(step),
            )

        # fault class 2 — the breaker opening names stage "gather"
        store = Feature(device_cache_size="1G").from_cpu_tensor(feat)
        degraded = DegradedFeature(
            FaultPlan(feature_faults={0: 5}).wrap_feature(store),
            failures=3, probe_every=2, fallback="zeros", recorder=rec,
        )
        ids = rng.integers(0, n, 4)
        for _ in range(2):  # closed breaker propagates the outage
            try:
                degraded[ids]
                raise AssertionError("closed breaker swallowed the fault")
            except TransientFault:
                pass
        degraded[ids]  # third consecutive failure opens it -> bundle
        assert degraded.breaker.state == "open", degraded.breaker.state

        # fault class 3 — an aborted streaming commit names stage "commit"
        sg = StreamingGraph(
            CSRTopo(indptr=topo.indptr, indices=topo.indices),
            recorder=rec,
        )
        assert sg.ingest(DeltaBatch(
            edge_inserts=rng.integers(0, n, size=(2, 8))
        )), "good delta batch rejected"
        try:
            sg.commit(inject_failure="merge")
            raise AssertionError("injected commit failure did not abort")
        except CommitAborted:
            pass

        stages = {m["reason"]: m["stage"] for _p, m in rec.bundles()}
        want = {"nonfinite_guard": "train", "breaker_open": "gather",
                "commit_abort": "commit"}
        for reason, stage in want.items():
            assert stages.get(reason) == stage, \
                f"{reason}: stage {stages.get(reason)!r} != {stage!r}"
        for path, _m in rec.bundles():
            verify_bundle(path)  # raises TornBundle on any corruption

        # fault class 4 — a torn dump is quarantined, never trusted
        torn = rec.trigger("torn_drill", stage="train",
                           inject_failure="torn")
        try:
            verify_bundle(torn)
            raise AssertionError("torn bundle passed verification")
        except TornBundle:
            pass
        survivors = list_bundles(tmp, quarantine=True)
        assert len(survivors) == len(want), \
            f"{len(survivors)} bundles survived, expected {len(want)}"
        assert any(name.startswith("quarantine-")
                   for name in os.listdir(tmp)), "torn dir not quarantined"
        for path, _m in survivors:
            verify_bundle(path)  # quarantine left the good bundles intact
        common.log(
            f"CHAOS postmortem OK ({len(want)} fault classes bundled + "
            "verified, torn dir quarantined)"
        )


def drill_scale_out(topo, feat, seed):
    """Serving-fleet scale-out: a replica joining mid-traffic warms from
    the shared AOT cache (zero compiles) and answers the same
    (node, seq) stream bitwise-identically to the running replica."""
    import jax

    from quiver_tpu import Feature, GraphSageSampler
    from quiver_tpu.models.sage import GraphSAGE
    from quiver_tpu.parallel.train import empty_adjs, init_model
    from quiver_tpu.serving import ServingFleet

    rng = np.random.default_rng(seed)
    n = topo.node_count
    d = feat.shape[1]
    store = Feature(device_cache_size="1G").from_cpu_tensor(feat)
    sampler = GraphSageSampler(topo, [4, 3], seed=3)
    model = GraphSAGE(hidden=16, num_classes=4, num_layers=2)
    adjs = empty_adjs([4, 3], batch=4, node_count=n)
    params = init_model(
        model, jax.random.PRNGKey(seed),
        np.zeros((adjs[0].size[0], d), np.float32), adjs,
    )

    with tempfile.TemporaryDirectory() as tmp:
        fleet = ServingFleet(
            sampler, model, params, store, replicas=1,
            aot_cache=f"{tmp}/aot", seed=5, max_batch=2,
        )
        first = fleet.cold_starts[0]
        assert first["compiled"] > 0 and first["loaded"] == 0, first
        nodes = rng.integers(0, n, 12)
        out0 = fleet.servers[0].serve(nodes)  # traffic before the join

        joiner = fleet.add_replica()  # joins mid-traffic
        join = fleet.cold_starts[-1]
        assert join["compiled"] == 0, f"join compiled programs: {join}"
        assert join["loaded"] == first["compiled"], (join, first)
        assert joiner.recompiles == 0, joiner.recompiles

        # replay the same node stream on the joiner: its batcher starts
        # at seq 0 exactly like replica 0 did, so the (node, seq) pairs
        # match and (shared base seed) responses must be bitwise equal
        out1 = joiner.serve(nodes)
        for a, b in zip(out0, out1):
            assert (a.node, a.seq) == (b.node, b.seq), (a, b)
            assert np.array_equal(a.result, b.result), \
                f"replica divergence at (node={a.node}, seq={a.seq})"
            assert np.array_equal(b.result, fleet.oracle(b.node, b.seq)), \
                f"oracle divergence at (node={b.node}, seq={b.seq})"

        # the grown fleet keeps serving mixed-class traffic compile-free
        fleet.serve(rng.integers(0, n, 8), priority="bronze")
        assert fleet.recompiles == first["compiled"], \
            (fleet.recompiles, first)
    common.log(
        f"CHAOS scale-out OK (mid-traffic join warmed {join['loaded']} "
        f"programs from the shared AOT cache with 0 compiles; "
        f"{len(nodes)} (node, seq) responses bitwise-identical across "
        f"replicas and vs the oracle)"
    )


def drill_mutate(topo_seed_graph, feat, local_batch, seed):
    """Malformed-delta quarantine; mid-commit crash at every pre-publish
    stage leaves the old version readable and sampling bit-identical;
    a published commit invalidates stale samplers exactly once."""
    import jax

    from quiver_tpu import (
        CommitAborted,
        CSRTopo,
        DeltaBatch,
        GraphSageSampler,
        StreamingGraph,
        VersionMismatchError,
    )
    from quiver_tpu.feature.shard import ShardedFeature
    from quiver_tpu.obs.registry import DELTAS_QUARANTINED
    from quiver_tpu.parallel.mesh import FEATURE_AXIS, make_mesh

    F = jax.device_count()
    mesh = make_mesh(n_devices=F, data=1, feature=F)
    # a fresh topology: the drill mutates it, the shared one must survive
    rng = np.random.default_rng(seed)
    n = topo_seed_graph.node_count
    topo = CSRTopo(indptr=topo_seed_graph.indptr,
                   indices=topo_seed_graph.indices)
    d = feat.shape[1]
    store = ShardedFeature(
        mesh, device_cache_size=max(n // (2 * F), 1) * d * feat.dtype.itemsize,
        replicate_budget=8 * d * feat.dtype.itemsize, csr_topo=topo,
    ).from_cpu_tensor(feat)
    sampler = GraphSageSampler(topo, [5, 5], seed=3,
                               seed_capacity=local_batch,
                               topo_sharding="mesh", mesh=mesh)
    sg = StreamingGraph(topo, feature=store)
    seeds = rng.integers(0, n, local_batch * F)
    key = jax.random.PRNGKey(11)
    oracle = sampler.sample(seeds, key=key)

    # 1. malformed batches: quarantined whole, never staged
    rejects = (
        DeltaBatch(edge_inserts=np.array([[0], [n + 7]]), tag="oob"),
        DeltaBatch(update_ids=np.array([1]),
                   update_rows=np.full((1, d), np.nan, np.float32),
                   tag="nan-row"),
        DeltaBatch(edge_inserts=np.array([[2, 2], [3, 3]]), tag="dup"),
    )
    for bad in rejects:
        assert not sg.ingest(bad), f"malformed batch {bad.tag} was staged"
    q = int(np.asarray(sg.metrics.value(DELTAS_QUARANTINED)))
    assert q == len(rejects), f"quarantine counter {q} != {len(rejects)}"
    assert not sg.staged

    # 2. mid-commit crash at every pre-publish stage: old version stays
    # readable and sampling is bit-identical to the pre-commit oracle
    live_src = int(np.repeat(
        np.arange(n), topo.degree)[0])  # a row with at least one edge
    live_dst = int(np.asarray(topo.indices)[
        np.asarray(topo.indptr, dtype=np.int64)[live_src]])
    good = DeltaBatch(
        edge_inserts=rng.integers(0, n, size=(2, 8)),
        edge_deletes=np.array([[live_src], [live_dst]]),
        update_ids=np.array([0, n // 2]),
        update_rows=rng.normal(size=(2, d)).astype(np.float32),
    )
    for stage in ("merge", "verify", "features"):
        assert sg.ingest(good), f"good batch rejected before {stage}"
        try:
            sg.commit(inject_failure=stage)
            raise AssertionError(f"injected {stage} failure did not abort")
        except CommitAborted:
            pass
        assert topo.version == 0 and store.version == 0, \
            f"crash at {stage} leaked a version bump"
        assert not sg.staged, f"crash at {stage} left batches staged"
        replay = sampler.sample(seeds, key=key)
        assert np.array_equal(np.asarray(oracle.n_id),
                              np.asarray(replay.n_id)), \
            f"sampling diverged after aborted commit at {stage}"

    # 3. a real commit publishes once; stale sampler raises, refreshed
    # sampler serves the mutated graph
    assert sg.ingest(good)
    res = sg.commit()
    assert res.version == 1 and topo.version == 1 and store.version == 1
    try:
        sampler.sample(seeds, key=key)
        raise AssertionError("stale sampler did not raise after commit")
    except VersionMismatchError:
        pass
    sampler.refresh_topology()
    out = sampler.sample(seeds, key=key)
    assert out.n_id.shape == oracle.n_id.shape
    updated = np.asarray(store.gather(good.update_ids))
    assert np.array_equal(updated, good.update_rows), \
        "committed row updates not served"
    common.log(
        f"CHAOS mutate OK ({len(rejects)} malformed batches quarantined; "
        f"3 mid-commit crashes rolled back bit-identically; commit v1 "
        f"published +{res.edges_inserted}/-{res.edges_deleted} edges, "
        f"{res.rows_updated} row updates, stale sampler raised then "
        f"refreshed)"
    )


def drill_ooc(topo_shared, feat, labels, local_batch, seed):
    """Disk-tier chaos: transient read faults mid-epoch are retried by
    the AsyncStager's backoff (trajectory bit-identical to the
    fault-free disk run); a torn raw dir is quarantined and the loader
    falls back to the legacy .npz with sampling bit-identical."""
    import os

    import jax
    import jax.numpy as jnp
    import optax

    from quiver_tpu import CSRTopo, GraphSageSampler
    from quiver_tpu.models.sage import GraphSAGE
    from quiver_tpu.ooc import (
        CorruptRawDir,
        MmapFeatureStore,
        quarantine_raw_dir,
    )
    from quiver_tpu.parallel.mesh import make_mesh
    from quiver_tpu.parallel.trainer import DataParallelTrainer

    # private topology: the store's degree reorder writes feature_order,
    # which must not leak into the other drills' shared graph
    topo = CSRTopo(indptr=topo_shared.indptr, indices=topo_shared.indices)
    n, d = feat.shape
    lab = jnp.asarray(labels)
    idx = np.random.default_rng(seed).integers(
        0, n, 6 * local_batch * jax.device_count()
    )

    with tempfile.TemporaryDirectory() as tmp:
        rows = os.path.join(tmp, "rows")
        MmapFeatureStore.write(
            rows, feat, device_cache_size=max(n // 5, 1) * d * 4,
            csr_topo=topo,
        )

        def run_epoch(inject_faults):
            store = MmapFeatureStore(rows, window_rows=16, cache_windows=8,
                                     retries=3, backoff=1e-3)
            injected = set()
            if inject_faults:
                real = store.stager._read_window

                def flaky(window):
                    # first read of the first 3 distinct windows fails
                    # once; the stager's backoff re-read succeeds
                    if len(injected) < 3 and window not in injected:
                        injected.add(window)
                        raise OSError(
                            f"injected disk fault on window {window}"
                        )
                    return real(window)

                store.stager._read_window = flaky
            sampler = GraphSageSampler(topo, [5, 5], seed=3,
                                       seed_capacity=local_batch)
            trainer = DataParallelTrainer(
                make_mesh(), sampler, store,
                GraphSAGE(hidden=16, num_classes=4, num_layers=2),
                optax.sgd(1e-2), local_batch=local_batch,
            )
            params, opt = trainer.init(jax.random.PRNGKey(0))
            params, opt, loss, steps = trainer.train_epoch(
                params, opt, idx, lab, jax.random.PRNGKey(1),
                rng=np.random.default_rng(seed),
            )
            retries = store.stager.read_retries_total
            store.close()
            return float(loss), int(steps), retries, len(injected)

        clean_loss, clean_steps, _, _ = run_epoch(False)
        loss, steps, retries, injected = run_epoch(True)
        assert injected == 3, f"only {injected}/3 faults injected"
        assert retries == injected, \
            f"stager retries {retries} != {injected} injected faults"
        assert steps == clean_steps, f"epoch delivered {steps}/{clean_steps}"
        assert loss == clean_loss, \
            "recovered epoch diverged from the fault-free disk run"

        # torn publish: COMMIT marker missing -> quarantine + npz fallback
        raw = os.path.join(tmp, "topo.raw")
        npz = os.path.join(tmp, "topo.npz")
        topo.save(raw, format="raw")
        topo.save(npz)
        os.remove(os.path.join(raw, "COMMIT"))
        torn = False
        try:
            CSRTopo.load(raw, mmap=True)
        except CorruptRawDir:
            torn = True
            quarantine_raw_dir(raw)
            recovered = CSRTopo.load(npz)
        assert torn, "torn raw dir loaded without complaint"
        assert not os.path.exists(raw), "torn raw dir not quarantined"
        # fresh same-seed samplers: first draws are deterministic, so the
        # fallback topology must reproduce the original stream bitwise
        seeds = np.random.default_rng(seed).integers(0, n, local_batch)
        a = GraphSageSampler(topo, [5, 5], seed=3,
                             seed_capacity=local_batch).sample(seeds)
        b = GraphSageSampler(recovered, [5, 5], seed=3,
                             seed_capacity=local_batch).sample(seeds)
        assert np.array_equal(np.asarray(a.n_id), np.asarray(b.n_id)), \
            "sampling off the npz fallback diverged from the original"
    common.log(
        f"CHAOS ooc OK ({retries} mid-epoch disk faults retried, epoch "
        f"{steps}/{clean_steps} steps bit-identical to fault-free; torn "
        "raw dir quarantined, npz fallback sampling bit-identical)"
    )


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--nodes", type=int, default=2000)
    p.add_argument("--feature-dim", type=int, default=16)
    p.add_argument("--local-batch", type=int, default=16)
    p.add_argument("--retry-steps", type=int, default=8)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--drills", nargs="*", default=None, choices=DRILLS,
                   help="subset of drills to run (default: all)")
    p.add_argument("--smoke", action="store_true",
                   help="shrink the drills further (rehearsal mode)")
    args = p.parse_args()
    if args.smoke:
        args.nodes = min(args.nodes, 800)
        args.retry_steps = min(args.retry_steps, 4)

    common.init_backend(smoke=args.smoke)
    topo, feat, labels = _build_graph(
        args.nodes, args.feature_dim, args.seed
    )
    selected = tuple(args.drills) if args.drills else DRILLS

    def body():
        if "guard" in selected:
            drill_guard(topo, feat, labels, args.local_batch, args.seed)
        if "retry" in selected:
            drill_retry(topo, args.retry_steps, args.local_batch, args.seed)
        if "preempt" in selected:
            drill_preempt_resume(
                topo, feat, labels, args.local_batch, args.seed
            )
        if "resize" in selected:
            drill_resize(topo, feat, labels, args.local_batch, args.seed)
        if "corrupt" in selected:
            drill_corrupt_checkpoint(
                topo, feat, labels, args.local_batch, args.seed
            )
        if "cold-outage" in selected:
            drill_cold_outage(
                topo, feat, labels, args.local_batch, args.seed
            )
        if "pipeline" in selected:
            drill_pipeline(topo, feat, labels, args.local_batch, args.seed)
        if "mutate" in selected:
            drill_mutate(topo, feat, args.local_batch, args.seed)
        if "scale-out" in selected:
            drill_scale_out(topo, feat, args.seed)
        if "ooc" in selected:
            drill_ooc(topo, feat, labels, args.local_batch, args.seed)
        if "postmortem" in selected:
            drill_postmortem(
                topo, feat, labels, args.local_batch, args.seed
            )
        common.log(f"CHAOS all drills passed ({', '.join(selected)})")
        return 0

    return common.run_guarded(body, args)


if __name__ == "__main__":
    raise SystemExit(main())
