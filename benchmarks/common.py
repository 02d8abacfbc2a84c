"""Shared plumbing for the benchmark harnesses.

Mirrors the reference's benchmark conventions (SURVEY §6): dataset-free
synthetic power-law graphs (benchmarks/generated_graph/gen_graph.py),
synchronized timing, and the canonical metrics — SEPS for sampling
(benchmarks/sample/bench_sampler.py:33-43), GB/s for feature collection
(benchmarks/feature/bench_feature.py:35-46), trimmed-mean iteration time for
end-to-end epochs (benchmarks/ogbn-papers100M/dist_sampling_ogb_paper100M_quiver.py:159-165).

Every script prints ONE JSON line:
``{"metric", "value", "unit", "vs_baseline", ...extras}`` — the same schema
as the repo-root ``bench.py`` headline benchmark.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


# ogbn-products scale: 2.45M nodes, 123.7M edges (docs/Introduction_en.md)
PRODUCTS_NODES = 2_450_000
PRODUCTS_AVG_DEG = 50.5
PRODUCTS_TRAIN_NODES = 196_615

# reference 1-GPU UVA SEPS on ogbn-products [15,10,5] (Introduction_en.md:41)
BASELINE_UVA_SEPS = 34.29e6


def stream_seps(sampler, node_count: int, batch: int, stream: int, rng,
                reps: int = 3):
    """Shared fused-stream SEPS measurement: ONE compiled program scans
    ``stream`` seed batches (in-program valid-edge tallies, one scalar
    readback). Used by bench_sampler's --stream headline.

    Returns (median SEPS, last overflow, stream actually used), or None
    when even a single batch's worst-case edge count would wrap the int32
    in-carry tally (no stream config is sound then — the caller's per-call
    number stands).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    run, caps = sampler._compiled(batch)
    ins = (batch,) + tuple(caps[:-1])
    max_epb = sum(i * k for i, k in zip(ins, sampler.sizes))
    if max_epb > 2**31 - 1:
        log(f"stream skipped: worst-case {max_epb} edges/batch exceeds the "
            "int32 tally range")
        return None
    max_stream = max(1, (2**31 - 1) // max(max_epb, 1))
    if stream > max_stream:
        log(f"stream clamped {stream} -> {max_stream} "
            f"(int32 edge-tally bound at <= {max_epb} edges/batch)")
        stream = max_stream
    n_vec = jnp.full((stream,), jnp.int32(batch))

    @jax.jit
    def streamf(topo_dev, seed_mat, nums, key0):
        def step(carry, xs):
            key, total, oflo = carry
            seeds, n = xs
            key, sub = jax.random.split(key)
            _, _, _, overflow, ec, _ = run(topo_dev, seeds, n, sub)
            return (key, total + jnp.sum(jnp.stack(ec)), oflo + overflow), None
        init = (key0, jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))
        (_, total, oflo), _ = lax.scan(step, init, (seed_mat, nums))
        return total, oflo

    import numpy as np

    def one_rep():
        seed_np = rng.integers(0, node_count, (stream, batch)).astype(np.int32)
        key = jax.random.PRNGKey(int(rng.integers(1 << 30)))
        t0 = time.time()
        total, oflo = streamf(sampler.topo, jnp.asarray(seed_np), n_vec, key)
        total, oflo = int(total), int(oflo)
        return total / (time.time() - t0), oflo

    t0 = time.time()
    one_rep()  # compile
    log(f"stream compile: {time.time()-t0:.1f}s ({stream} batches/scan)")
    results = [one_rep() for _ in range(reps)]
    seps = float(np.median([r[0] for r in results]))
    return seps, results[-1][1], stream


def hbm_bandwidth_gbps() -> float | None:
    """Nominal HBM bandwidth of the current device for roofline estimates.

    Env-overridable (QUIVER_HBM_GBPS). Defaults: TPU v5e ("v5 lite")
    819 GB/s; unknown platforms return None and callers skip
    the roofline line rather than report one against a made-up ceiling.
    """
    import os

    env = os.environ.get("QUIVER_HBM_GBPS")
    if env:
        try:
            return float(env)
        except ValueError:
            pass
    try:
        import jax

        d = jax.devices()[0]
        if d.platform == "tpu":
            # normalize "TPU v5 lite" / "tpu-v5e" spellings before matching
            kind = str(getattr(d, "device_kind", "")).lower()
            kind = kind.replace(" ", "").replace("-", "").replace("_", "")
            for tag, bw in (("v5lite", 819.0), ("v5e", 819.0),
                            ("v5p", 2765.0), ("v6", 1640.0), ("v4", 1228.0)):
                if tag in kind:
                    return bw
            # unrecognized TPU: no ceiling is better than a made-up one
    except Exception:  # noqa: BLE001
        pass
    return None


def sampler_roofline(sampler, batch: int):
    """Coarse HBM-traffic lower bound for ONE seed batch through the fused
    sampler — the denominator for "how far from the chip's ceiling is this
    SEPS number" (VERDICT r3 item 2), not a precise model.

    Traffic counted per layer (worst-case frontiers = the static caps):
    sample: 2 indptr gathers (base/deg) + the random CSR indices gather +
    the neighbor write; reindex: four payload sorts, each ~log2(T) passes
    over (key, payload) pairs, and the compacted write. Every RANDOM
    4-byte access is charged a full 32-byte HBM granule — a pure-byte count would put the ceiling ~8x too
    high for gather-dominated programs. Returns (bytes_per_batch,
    ceiling_seps) or None when bandwidth is unknown.
    """
    import math

    bw = hbm_bandwidth_gbps()
    if bw is None:
        return None
    GRANULE = 32  # bytes served per random access
    _, caps = sampler._compiled(batch)
    ins = (batch,) + tuple(caps[:-1])
    ptr_b = max(sampler.topo.indptr.dtype.itemsize, GRANULE)
    total = 0
    worst_edges = 0
    for l, (S, k) in enumerate(zip(ins, sampler.sizes)):
        # base+deg are adjacent indptr slots: one granule per row; the k
        # CSR slots per row are contiguous strata picks — charge a granule
        # each (pessimistic for low-degree rows, right for high-degree)
        total += S * ptr_b + S * k * GRANULE + S * k * 4  # reads + write
        worst_edges += S * k
        T = S * k + S
        # sort passes stream sequentially: pure bytes
        total += 4 * int(math.log2(max(T, 2))) * T * 8 + caps[l] * 4
    ceiling = worst_edges / (total / (bw * 1e9))
    return total, ceiling


def base_parser(desc: str) -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=desc)
    p.add_argument("--nodes", type=int, default=PRODUCTS_NODES)
    p.add_argument("--avg-degree", type=float, default=PRODUCTS_AVG_DEG)
    p.add_argument("--batch", type=int, default=2048)
    p.add_argument("--iters", type=int, default=30)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument(
        "--smoke",
        action="store_true",
        help="tiny graph + few iters; the only mode allowed off the TPU",
    )
    return p


def _select_prng(platform: str) -> str | None:
    """Pick the PRNG implementation for benchmark runs.

    Threefry (jax's default) burns vector cycles generating bits; XLA's
    ``rbg`` RngBitGenerator is the fast TPU path and the sampler draws
    ~1M randints per products batch, so on TPU benchmarks default to rbg
    (override with QUIVER_PRNG=threefry|rbg|default). Correctness is
    PRNG-agnostic — the validity oracle and dedup semantics never depend
    on WHICH uniform bits arrive (tests/test_sampler_api.py) — only
    draw-for-draw reproducibility across impls changes, which no recorded
    artifact relies on. Returns the impl applied, or None for default.
    """
    import os

    import jax

    forced = os.environ.get("QUIVER_PRNG", "").strip().lower()
    known = ("threefry", "threefry2x32", "rbg", "unsafe_rbg", "default")
    if forced and forced not in known:
        # the env var FORCES an impl; a typo silently measuring the
        # default would be recorded as the forced impl — same rule as
        # resolve_platform_strategy
        raise ValueError(f"QUIVER_PRNG={forced!r} is not one of {known}")
    impl = forced or ("rbg" if platform == "tpu" else "")
    if impl in ("", "default", "threefry", "threefry2x32"):
        return None
    try:
        jax.config.update("jax_default_prng_impl", impl)
        return impl
    except Exception as e:  # noqa: BLE001 — an UNFORCED perf default must
        # not kill a run (e.g. a backend without the rbg impl)
        if forced:
            raise
        log(f"prng impl {impl!r} not applied: {e}")
        return None


def init_backend(smoke: bool = False):
    """Touch the JAX backend in this process, before any set-up work.

    A benchmark measures the chip: off the TPU it exits non-zero unless
    ``--smoke`` was asked for, and a smoke run's records carry their
    platform. No probe child, no retry, no fallback — one process per chip.
    """
    import jax

    dev = jax.devices()[0]
    log(f"backend: {dev.platform} ({dev.device_kind})")
    if dev.platform != "tpu" and not smoke:
        log("FATAL: no TPU backend; only --smoke runs off the chip")
        sys.exit(2)
    impl = _select_prng(dev.platform)
    if impl:
        log(f"prng: {impl}")
        set_record_context(prng=impl)
    return dev


# workload-identity fields (nodes, smoke) stamped into every emit() record
_RECORD_CONTEXT: dict = {}


def set_record_context(**fields) -> None:
    """Merge workload-identity fields into all subsequent emit() records.

    ``None`` values are dropped (so ``smoke=None`` leaves clean records
    unannotated). Called by build_graph; harnesses with custom setup call it
    directly."""
    _RECORD_CONTEXT.update({k: v for k, v in fields.items() if v is not None})


def run_guarded(body, args):
    """Run a benchmark's post-argparse work with the compile cache on.

    Failures propagate: the process exits non-zero with the traceback.
    """
    from quiver_tpu.utils.backend import enable_compile_cache

    del args
    enable_compile_cache()
    return body()


def apply_smoke(args) -> None:
    """Shrink the workload to a dry-run size under ``--smoke``."""
    if getattr(args, "smoke", False):
        args.nodes = min(args.nodes, 200_000)
        args.iters = min(args.iters, 5)
        args.warmup = min(args.warmup, 2)
        if getattr(args, "stream", 0):
            args.stream = min(args.stream, 4)
        if hasattr(args, "train_nodes"):
            args.train_nodes = min(args.train_nodes, 20_000)
        log(f"smoke mode: nodes={args.nodes} iters={args.iters}")


def _graphgen_tag() -> str:
    """Short content hash of the generator source.

    The cache key must change whenever generate_pareto_graph's output
    could: a (nodes, degree, seed)-only key silently serves stale graphs
    across generator edits — the same staleness class the explicit eid
    guard below already caught once.
    """
    import hashlib
    import os

    src = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "quiver_tpu", "utils", "graphgen.py",
    )
    try:
        with open(src, "rb") as fh:
            return hashlib.md5(fh.read()).hexdigest()[:8]
    except OSError:
        return "nosrc"


def _graph_cache_path(nodes: int, avg_degree: float, seed: int) -> str:
    import os

    d = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".graph_cache",
    )
    return os.path.join(
        d, f"pareto_n{nodes}_d{avg_degree:g}_s{seed}_g{_graphgen_tag()}.npz"
    )


def build_graph(args):
    """Synthetic products-scale power-law CSRTopo (+ build-time report).

    Touches the backend BEFORE the (potentially multi-minute) graph build so
    backend failures surface in seconds. The built CSR is cached on disk
    keyed by (nodes, avg_degree, seed), so the processes of one run build
    the same synthetic graph once.
    """
    import os

    init_backend(smoke=getattr(args, "smoke", False))
    apply_smoke(args)

    from quiver_tpu import CSRTopo

    t0 = time.time()
    cache = _graph_cache_path(args.nodes, args.avg_degree, args.seed)
    topo = None
    if os.path.exists(cache):
        try:
            import numpy as np

            z = np.load(cache)
            if "eid" not in z.files:
                # pre-eid-fix cache: both CSR builders always produce eid,
                # so its absence means a stale file — regenerate, don't
                # silently load an inequivalent topology
                raise ValueError("stale cache (no eid)")
            topo = CSRTopo(indptr=z["indptr"], indices=z["indices"],
                           eid=z["eid"])
            log(f"graph: loaded CSR cache {os.path.basename(cache)}")
        except Exception as e:  # noqa: BLE001 — cache must never break a run
            log(f"graph cache load failed ({e}); regenerating")
            topo = None
    if topo is None:
        from quiver_tpu.utils.graphgen import generate_pareto_graph

        ei = generate_pareto_graph(args.nodes, args.avg_degree, seed=args.seed)
        topo = CSRTopo(edge_index=ei)
        del ei
        try:
            import numpy as np

            os.makedirs(os.path.dirname(cache), exist_ok=True)
            tmp = cache + ".tmp"
            arrays = {"indptr": topo.indptr, "indices": topo.indices}
            if topo.eid is not None:
                # equivalence: a cache hit must carry the same eid the
                # COO build produced (with_eid consumers, HBM footprint)
                arrays["eid"] = topo.eid
            with open(tmp, "wb") as fh:
                np.savez(fh, **arrays)
                fh.flush()
                os.fsync(fh.fileno())
            os.replace(tmp, cache)
        except Exception as e:  # noqa: BLE001
            log(f"graph cache save failed ({e}); continuing uncached")
    log(
        f"graph: {topo.node_count} nodes, {topo.edge_count} edges "
        f"({time.time() - t0:.1f}s build)"
    )
    set_record_context(
        nodes=int(topo.node_count),
        smoke=True if getattr(args, "smoke", False) else None,
    )
    return topo


def model_from_name(name: str, hidden: int, classes: int,
                    num_layers: int, heads: int = 4, dtype=None):
    """Shared --model dispatch for the homogeneous families.

    Returns (model, layerwise_inference_fn, edge_sweeps_per_layer) — the
    sweep count feeds honest edge-throughput extras (GAT walks the edge
    array twice per layer: segment-max then the fused num/denom pass).
    """
    from quiver_tpu.models import (
        gat_layerwise_inference,
        gcn_layerwise_inference,
        gin_layerwise_inference,
        sage_layerwise_inference,
    )

    kw = dict(hidden=hidden, num_classes=classes, num_layers=num_layers,
              dtype=dtype)
    if name == "gat":
        from quiver_tpu.models.gat import GAT

        return GAT(**kw, heads=heads), gat_layerwise_inference, 2
    if name == "gcn":
        from quiver_tpu.models.gcn import GCN

        return GCN(**kw), gcn_layerwise_inference, 1
    if name == "gin":
        from quiver_tpu.models.gin import GIN

        return GIN(**kw), gin_layerwise_inference, 1
    if name == "sage":
        from quiver_tpu.models.sage import GraphSAGE

        return GraphSAGE(**kw), sage_layerwise_inference, 1
    raise ValueError(f"unknown model family {name!r}")


def trimmed_mean(times) -> float:
    """10%-trimmed mean of iteration times (the reference drops the first
    epoch and averages the rest; per-iteration trimming is the same idea at
    iter scale)."""
    import numpy as np

    times = np.sort(np.asarray(times, dtype=float))
    k = max(1, len(times) // 10)
    if len(times) > 2 * k:
        times = times[k:-k]
    return float(np.mean(times))


def log(msg: str) -> None:
    print(msg, file=sys.stderr)


def write_metrics(*sources, **extra) -> int:
    """Persist graftscope registry snapshots to the run's metrics.jsonl
    artifact (``QUIVER_METRICS_JSONL``; unset, the call is a no-op).

    ``sources``: objects carrying a ``.metrics`` registry (stores,
    samplers, trainers), bare registries, or ``None`` (skipped). Record-
    context fields (nodes, smoke, prng) and ``extra`` ride on every row so
    the artifact lines are attributable to their workload. Best-effort —
    telemetry persistence must never break a measurement run.
    """
    snaps = []
    for src in sources:
        if src is None:
            continue
        reg = getattr(src, "metrics", src)
        get = getattr(reg, "snapshots", None)
        if callable(get):
            snaps.extend(get())
    if not snaps:
        return 0
    fields = {k: v for k, v in _RECORD_CONTEXT.items()}
    fields.update({k: v for k, v in extra.items() if v is not None})
    try:
        from benchmarks import ledger

        n = ledger.append_metrics(snaps, extra=fields)
        if n:
            log(f"metrics: {n} snapshot rows -> {ledger.metrics_jsonl_path()}")
        return n
    except Exception as e:  # noqa: BLE001 — artifact write must not cost a run
        log(f"metrics artifact write failed: {type(e).__name__}: {e}")
        return 0


def emit(
    metric: str,
    value: float,
    unit: str,
    baseline: float | None,
    invert: bool = False,
    **extras,
):
    """Print the one-line JSON result. ``vs_baseline`` > 1 always means
    better than the reference: value/baseline for throughput metrics,
    baseline/value when ``invert=True`` (time/latency metrics where lower is
    better)."""
    if baseline is None:
        vs = None
    elif invert:
        vs = round(baseline / value, 3) if value else None
    else:
        vs = round(value / baseline, 3)
    rec = {
        "metric": metric,
        "value": round(value, 3),
        "unit": unit,
        "vs_baseline": vs,
    }
    import jax

    rec["platform"] = jax.devices()[0].platform
    rec.update(_RECORD_CONTEXT)
    rec.update(extras)
    print(json.dumps(rec), flush=True)
    return rec
