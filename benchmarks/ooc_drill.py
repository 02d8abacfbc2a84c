"""Out-of-core drill: train under a HARD address-space budget with the
graph on disk (quiver_tpu/ooc/) — the papers100M-shaped evidence job.

The claim under test is the ooc tier's whole reason to exist: a training
epoch completes when the graph does NOT fit in memory. Enforced, not
asserted — the measured child process runs under ``RLIMIT_AS`` set to its
warmed-up ``VmSize`` plus a budget that is at most 1/4 of the on-disk
graph bytes, so eagerly materializing the feature table (or leaking
per-step allocations) kills the epoch with ``MemoryError`` instead of
quietly passing on a big machine.

Shape of the run (child process, 2-virtual-device CPU mesh):

1. build a synthetic graph + feature table, publish both through the raw
   on-disk format (``CSRTopo.save(format="raw")``,
   :meth:`MmapFeatureStore.write`), and drop the in-RAM copies;
2. reopen the topology memory-mapped and the rows in ``pread`` mode (an
   mmap of the rows file would count its full size against RLIMIT_AS —
   the pread path keeps address space O(window cache), which is the
   point);
3. warm up one DataParallelTrainer epoch (compiles the step), trace the
   SAME cached step on a probe batch group and gate graftmem's static
   peak estimate against the address budget about to be enforced (via
   ``CostModel.calibrate_hbm``/``predict_hbm`` — the drill fails by
   prediction before it can fail by rlimit kill), read ``VmSize`` from
   /proc/self/status, then ``setrlimit(RLIMIT_AS, VmSize + budget)``;
4. run the measured epochs under the limit and require: the epoch
   completes, ``ooc.readahead_hits > 0`` (the stager's window
   amortization did real work), and ``len(trainer._step_cache)`` is
   unchanged from warmup (zero steady-state recompiles).

The parent emits the scoreboard record (``feature-ooc`` row); RLIMIT_AS
is process-wide and irreversible-downward, which is why the measured
body lives in a subprocess.

    python -m benchmarks.ooc_drill --smoke
"""

import argparse
import json
import os
import subprocess
import sys
import time

from benchmarks import common

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the child's mesh: 2 virtual CPU devices (same shape as the CI smoke)
_CHILD_XLA = "--xla_force_host_platform_device_count=2"


def _parser():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--budget-mb", type=float, default=64.0,
                   help="address-space headroom granted ABOVE the "
                        "warmed-up VmSize; the on-disk graph is sized to "
                        ">= 4x this")
    p.add_argument("--feature-dim", type=int, default=128)
    p.add_argument("--avg-degree", type=int, default=10)
    p.add_argument("--hot-frac", type=float, default=0.1,
                   help="fraction of rows resident in the store's hot tier")
    p.add_argument("--local-batch", type=int, default=128)
    p.add_argument("--steps", type=int, default=8,
                   help="train steps per epoch")
    p.add_argument("--epochs", type=int, default=2,
                   help="measured epochs run UNDER the rlimit")
    p.add_argument("--window-rows", type=int, default=1024)
    p.add_argument("--cache-windows", type=int, default=8)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--timeout", type=float, default=900.0,
                   help="parent-side hard timeout on the child")
    p.add_argument("--smoke", action="store_true",
                   help="small budget/graph: a CI runner finishes in ~1 min")
    p.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    return p


def _apply_smoke(args):
    if args.smoke:
        args.budget_mb = min(args.budget_mb, 24.0)
        args.feature_dim = min(args.feature_dim, 96)
        args.steps = min(args.steps, 4)
        args.local_batch = min(args.local_batch, 64)


def _derived(args):
    """Graph sizing: rows alone must be >= 4x the budget (with ~5% slack
    so filesystem rounding can't drop the ratio below the gate)."""
    budget = int(args.budget_mb * 1024 * 1024)
    row_bytes = args.feature_dim * 4  # float32 rows
    nodes = -(-int(4.2 * budget) // row_bytes)
    return budget, nodes


def _vm_size_bytes() -> int:
    with open("/proc/self/status") as fh:
        for line in fh:
            if line.startswith("VmSize:"):
                return int(line.split()[1]) * 1024
    raise RuntimeError("VmSize not found in /proc/self/status")


def _child(args) -> int:
    """The measured body. Runs with JAX_PLATFORMS=cpu and 2 virtual
    devices (parent-set env); everything after warmup runs under
    RLIMIT_AS."""
    import gc
    import resource
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from quiver_tpu import CSRTopo, GraphSageSampler, MmapFeatureStore
    from quiver_tpu.models.sage import GraphSAGE
    from quiver_tpu.obs import MetricsRegistry, StepTimeline
    from quiver_tpu.parallel.mesh import make_mesh
    from quiver_tpu.parallel.trainer import DataParallelTrainer

    budget, nodes = _derived(args)
    f = args.feature_dim
    rng = np.random.default_rng(args.seed)

    common.log(f"[child] graph: {nodes} nodes x {f} f32 features "
               f"({nodes * f * 4 / 1e6:.0f} MB rows), budget "
               f"{budget / 1e6:.0f} MB")
    topo = CSRTopo(edge_index=rng.integers(
        0, nodes, size=(2, args.avg_degree * nodes)).astype(np.int64))
    feat = rng.normal(size=(nodes, f)).astype(np.float32)
    labels = rng.integers(0, 4, nodes).astype(np.int32)
    hot_budget = int(args.hot_frac * nodes) * f * 4

    tmp = tempfile.mkdtemp(prefix="quiver-ooc-drill-")
    topo_dir = os.path.join(tmp, "topo")
    rows_dir = os.path.join(tmp, "rows")
    topo.save(topo_dir, format="raw")
    MmapFeatureStore.write(rows_dir, feat, device_cache_size=hot_budget,
                           csr_topo=topo)
    graph_bytes = nodes * f * 4 + topo.indices.nbytes + topo.indptr.nbytes
    assert graph_bytes >= 4 * budget, (graph_bytes, budget)
    del feat, topo
    gc.collect()

    # reopen everything disk-backed: mmap'd CSR, pread feature rows
    topo = CSRTopo.load(topo_dir, mmap=True)
    timeline = StepTimeline()
    metrics = MetricsRegistry()
    store = MmapFeatureStore(
        rows_dir, access="pread", window_rows=args.window_rows,
        cache_windows=args.cache_windows, metrics=metrics,
        timeline=timeline,
    )
    mesh = make_mesh(data=2, feature=1, devices=jax.devices()[:2])
    sampler = GraphSageSampler(topo, [5, 5], seed=3,
                               seed_capacity=args.local_batch)
    trainer = DataParallelTrainer(
        mesh, sampler, store, GraphSAGE(hidden=16, num_classes=4,
                                        num_layers=2),
        optax.sgd(1e-2), local_batch=args.local_batch,
    )
    params, opt = trainer.init(jax.random.PRNGKey(0))
    lab = jnp.asarray(labels)
    idx = rng.integers(0, nodes, args.steps * trainer.global_batch)

    t0 = time.time()
    params, opt, _, _ = trainer.train_epoch(
        params, opt, idx, lab, jax.random.PRNGKey(1),
        rng=np.random.default_rng(1),
    )
    warm_s = time.time() - t0
    cache_warm = len(trainer._step_cache)

    # graftmem gate: statically predict the step program's peak bytes
    # from the SAME cached jit the measured epochs will run (trace-only
    # — nothing executes) and require it to fit the address budget about
    # to be enforced, through the controller-facing CostModel surface.
    # A step that cannot fit fails here, by prediction, instead of an
    # opaque MemoryError mid-epoch under the rlimit.
    from types import SimpleNamespace

    from quiver_tpu.control.cost import CostModel
    from quiver_tpu.tools.audit import mem as graftmem

    probe = [
        SimpleNamespace(out=out_, x=store[out_.n_id])
        for out_ in (sampler.sample(np.asarray(blk))
                     for blk in trainer.seed_blocks(
                         idx[:trainer.global_batch]))
    ]
    caps, fanouts, xs, n_id, eis, bsz = trainer._stack(probe)
    step = trainer._compiled_step(caps, fanouts, xs.shape[-1])
    traced = step.trace(params, opt, xs, eis, n_id, bsz, lab,
                        jax.random.PRNGKey(9))
    est = graftmem.estimate_peak(traced.jaxpr)
    # est is per-device; every virtual device lives in THIS process, so
    # the address-space gate sees the whole mesh's residency
    predicted = est.peak_bytes * int(mesh.devices.size)
    del probe, xs, n_id, eis, bsz, traced

    vm = _vm_size_bytes()
    model = CostModel(local_len=args.local_batch, num_shards=1)
    model.calibrate_hbm({"ooc_step": predicted})
    fit = model.predict_hbm("ooc_step", budget_bytes=vm + budget)
    common.log(f"[child] graftmem: step peak {est.peak_bytes / 1e6:.1f} "
               f"MB/device ({predicted / 1e6:.1f} MB mesh-wide) vs "
               f"{(vm + budget) / 1e6:.0f} MB address budget")
    assert fit["fits"], (
        f"static step peak {predicted} B cannot fit the enforced "
        f"RLIMIT_AS {vm + budget} B (headroom {fit['headroom_bytes']})"
    )

    common.log(f"[child] warmup epoch {warm_s:.1f}s, VmSize "
               f"{vm / 1e6:.0f} MB; clamping RLIMIT_AS to +"
               f"{budget / 1e6:.0f} MB")
    _, hard = resource.getrlimit(resource.RLIMIT_AS)
    resource.setrlimit(resource.RLIMIT_AS, (vm + budget, hard))

    epoch_times = []
    for epoch in range(2, 2 + args.epochs):
        t0 = time.time()
        params, opt, loss, steps = trainer.train_epoch(
            params, opt, idx, lab, jax.random.PRNGKey(epoch),
            rng=np.random.default_rng(epoch),
        )
        epoch_times.append(time.time() - t0)
        assert steps == args.steps, f"epoch delivered {steps}/{args.steps}"
        assert np.isfinite(float(loss)), "rlimit'd epoch produced NaN loss"
    cache_after = len(trainer._step_cache)
    assert cache_after == cache_warm, \
        f"steady-state recompiles: {cache_warm} -> {cache_after}"
    hits = int(store.stager.readahead_hits_total)
    reads = int(store.stager.page_reads_total)
    assert hits > 0, "stager window amortization never fired"
    wait = timeline.summary().get("ooc.stage_wait")
    store.close()

    print(json.dumps({
        "ooc_drill": 1,
        "epoch_s": round(min(epoch_times), 3),
        "epochs": args.epochs,
        "steps": args.steps,
        "nodes": nodes,
        "feature_dim": f,
        "graph_bytes": int(graph_bytes),
        "budget_bytes": int(budget),
        "graph_over_budget": round(graph_bytes / budget, 2),
        "vm_warm_bytes": int(vm),
        "readahead_hits": hits,
        "page_reads": reads,
        "stage_wait_s": round(float(wait.total), 4) if wait else 0.0,
        "recompiles_steady": 0,
        "hot_rows": int(store.hot_rows),
        "predicted_peak_bytes": int(predicted),
    }), flush=True)
    return 0


def main():
    args = _parser().parse_args()
    _apply_smoke(args)
    if args.child:
        return _child(args)

    # parent: never touches jax itself — the measured body needs a fresh
    # process so RLIMIT_AS (irreversible-downward) dies with the child
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (flags + " " + _CHILD_XLA).strip()
    env["PYTHONPATH"] = (
        REPO + os.pathsep + env["PYTHONPATH"]
        if env.get("PYTHONPATH") else REPO
    )
    argv = [sys.executable, "-m", "benchmarks.ooc_drill", "--child"]
    for flag, val in (
        ("--budget-mb", args.budget_mb),
        ("--feature-dim", args.feature_dim),
        ("--avg-degree", args.avg_degree),
        ("--hot-frac", args.hot_frac),
        ("--local-batch", args.local_batch),
        ("--steps", args.steps),
        ("--epochs", args.epochs),
        ("--window-rows", args.window_rows),
        ("--cache-windows", args.cache_windows),
        ("--seed", args.seed),
    ):
        argv += [flag, str(val)]
    common.log(f"spawning rlimit'd child: {' '.join(argv[1:])}")
    r = subprocess.run(argv, capture_output=True, text=True,
                       timeout=args.timeout, env=env, cwd=REPO)
    sys.stderr.write(r.stderr or "")
    rec = None
    for line in (r.stdout or "").splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                cand = json.loads(line)
            except ValueError:
                continue
            if isinstance(cand, dict) and cand.get("ooc_drill"):
                rec = cand
    if r.returncode != 0 or rec is None:
        tail = (r.stderr or r.stdout or "").strip()[-400:]
        raise RuntimeError(
            f"ooc drill child failed (rc={r.returncode}): {tail}"
        )
    common.set_record_context(
        nodes=rec["nodes"], smoke=True if args.smoke else None
    )
    common.emit(
        "ooc-epoch-time", rec["epoch_s"], "s", None,
        store="pread",
        graph_bytes=rec["graph_bytes"],
        budget_bytes=rec["budget_bytes"],
        graph_over_budget=rec["graph_over_budget"],
        readahead_hits=rec["readahead_hits"],
        page_reads=rec["page_reads"],
        ooc_stage_wait_s=rec["stage_wait_s"],
        recompiles_steady=rec["recompiles_steady"],
        hot_rows=rec["hot_rows"],
        steps=rec["steps"],
        predicted_peak_bytes=rec.get("predicted_peak_bytes"),
    )
    common.log(
        f"OOC drill OK: {rec['graph_over_budget']}x graph-over-budget, "
        f"{rec['readahead_hits']} readahead hits, "
        f"{rec['page_reads']} page reads, 0 steady recompiles"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
