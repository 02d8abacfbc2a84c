"""Feature-collection throughput (GB/s) benchmark.

Methodology: GB/s = Σ gathered bytes / synchronized wall time, the
reference's benchmarks/feature/bench_feature.py:35-46. Ids are drawn
degree-skewed (high-degree nodes proportionally more often), matching what a
neighbor sampler actually requests — this is exactly the access pattern the
degree-ordered hot tier exploits (docs/Introduction_en.md:73-119).

Baseline: 14.82 GB/s = reference 1-GPU, ogbn-products, 20% cache, remainder
served over UVA from host memory (docs/Introduction_en.md:95).

Policies: ``replicate`` = hot tier replicated per device + pinned-host cold
tier (reference device_replicate); ``shard`` = hot tier sharded over the
mesh's feature axis with ICI-collective gathers (reference
p2p_clique_replicate; needs >1 device to mean anything).
"""

import time

import numpy as np

from benchmarks.common import (
    base_parser,
    build_graph,
    emit,
    log,
    run_guarded,
    write_metrics,
)

BASELINE_GBPS = 14.82


def main():
    p = base_parser(__doc__)
    p.add_argument("--feature-dim", type=int, default=100)  # products: 100 floats
    p.add_argument("--cache-ratio", type=float, default=0.2)
    p.add_argument("--policy", default="replicate", choices=["replicate", "shard"])
    p.add_argument("--gather-batch", type=int, default=65536)
    p.add_argument(
        "--routed", action="store_true",
        help="shard policy: owner-routed all_to_all hot-tier gather (ids "
        "sharded over every mesh axis) instead of the psum flavor — the "
        "seed_sharding='all' trainer's gather",
    )
    p.add_argument(
        "--routed-alpha", type=float, default=0.0, metavar="A",
        help="capped-bucket factor for --routed: per-destination bucket "
        "capacity ceil(A*L/F), so each all_to_all hop moves ~A*L lanes "
        "instead of the exact-safe F*L; overflow is fallback-served and "
        "counted. 0 = uncapped full-length buckets",
    )
    p.add_argument(
        "--dtype", default="f32", choices=["f32", "bf16", "int8"],
        help="feature storage dtype: bf16 halves row bytes; int8 "
        "(per-row absmax quantization, dequant on gather) quarters them",
    )
    p.add_argument(
        "--store", default="ram", choices=["ram", "mmap", "pread"],
        help="feature residency: ram = the in-RAM tiered Feature; mmap/"
        "pread = the disk-backed MmapFeatureStore (quiver-ooc) with the "
        "cold tier window-read off a raw-format dir through the async "
        "stager — mmap maps the row file, pread uses positioned reads "
        "(bounded address space, the rlimit-drill mode). Gathers are "
        "bitwise-identical across all three; replicate policy only",
    )
    p.add_argument(
        "--ooc-window", type=int, default=4096, metavar="ROWS",
        help="--store mmap/pread: rows per disk read window (readahead "
        "granularity)",
    )
    p.add_argument(
        "--ooc-cache-windows", type=int, default=64, metavar="N",
        help="--store mmap/pread: stager LRU capacity in windows (bounds "
        "resident staging bytes at N * window * row bytes)",
    )
    p.add_argument(
        "--replicate-budget", default="0", metavar="BYTES",
        help="per-chip byte budget for the L0 replicated super-hot tier "
        "(same parser as device_cache_size, e.g. '16M'): the top-degree "
        "rows are replicated in every chip's HBM and served with ZERO "
        "interconnect lanes; the sharded tier only carries the remaining "
        "(1-h0) of the traffic, and the routed cap is tightened by the "
        "measured L0 hit rate. 0 = the two-tier (PR 1) path",
    )
    p.add_argument(
        "--controller", action="store_true",
        help="quiver-ctl lane (needs --policy shard and a nonzero "
        "--replicate-budget): replay a recorded skewed trace whose heat "
        "does NOT follow degree through the frequency sketch, re-tier L0 "
        "to the measured-hottest rows (ShardedFeature.repin), and emit "
        "the measured L0 hit-rate delta vs the static degree-prefix "
        "placement at the SAME budget, plus the audited JSONL "
        "decision-log path",
    )
    p.add_argument(
        "--stream", type=int, default=0, metavar="N",
        help="headline via a fused id stream: lax.scan over N pre-staged "
        "device id batches in ONE compiled program (ids come from the "
        "sampler on-device in real use — per-call H2D of each id batch "
        "measures the host link, not the gather). The per-call loop is "
        "still emitted as a dispatch=percall record",
    )
    p.set_defaults(iters=50, warmup=5)
    args = p.parse_args()
    if args.controller and args.policy != "shard":
        p.error("--controller requires --policy shard (repin is the "
                "sharded store's actuator)")
    if args.store != "ram":
        if args.policy != "replicate":
            p.error("--store mmap/pread requires --policy replicate (the "
                    "disk tier backs the replicated store's cold rows)")
        if args.stream:
            p.error("--store mmap/pread is eager (host-staged disk "
                    "reads); the fused --stream lane needs --store ram")
        if args.dtype == "bf16":
            p.error("--store mmap/pread supports f32 and int8 (the raw "
                    "writer mirrors Feature's quantize path)")
    run_guarded(lambda: _body(args), args)


def _body(args):
    import jax
    import jax.numpy as jnp

    from quiver_tpu import Feature, ShardedFeature
    from quiver_tpu.parallel.mesh import make_mesh

    topo = build_graph(args)
    n, f = topo.node_count, args.feature_dim
    feat = np.random.default_rng(args.seed).normal(size=(n, f)).astype(np.float32)
    budget = int(args.cache_ratio * n) * f * 4

    dtype = {"f32": None, "bf16": "bfloat16", "int8": "int8"}[args.dtype]
    if args.store != "ram":
        import os
        import tempfile

        from quiver_tpu.ooc import MmapFeatureStore

        raw_dir = os.path.join(
            tempfile.mkdtemp(prefix="quiver-ooc-bench-"), "rows"
        )
        t0 = time.time()
        MmapFeatureStore.write(raw_dir, feat, device_cache_size=budget,
                               csr_topo=topo, dtype=dtype)
        log(f"raw feature dir written in {time.time()-t0:.1f}s: {raw_dir}")
        store = MmapFeatureStore(
            raw_dir, access=args.store,
            window_rows=args.ooc_window,
            cache_windows=args.ooc_cache_windows,
        )
    elif args.policy == "replicate":
        store = Feature(
            device_cache_size=budget, csr_topo=topo, dtype=dtype,
            replicate_budget=args.replicate_budget,
        ).from_cpu_tensor(feat)
    else:
        mesh = make_mesh(feature=len(jax.devices()))
        store = ShardedFeature(
            mesh,
            device_cache_size=budget // len(jax.devices()),
            csr_topo=topo,
            dtype=dtype,
            routed_alpha=args.routed_alpha or 2.0,
            replicate_budget=args.replicate_budget,
        ).from_cpu_tensor(feat)
    del feat

    # degree-skewed id stream: P(node) ∝ degree — the sampler's access law
    rng = np.random.default_rng(args.seed + 1)
    deg = topo.degree.astype(np.float64)
    prob = deg / deg.sum()
    batches = [
        rng.choice(n, size=args.gather_batch, p=prob).astype(np.int32)
        for _ in range(min(args.iters, 8))  # reuse id sets; drawing is slow
    ]

    # capped-bucket routing: --routed-alpha > 0 pins cap = ceil(A*L/F) as
    # an EXPLICIT capacity (not "auto") so mid-run overflow is
    # fallback-served and reported rather than silently re-planned — the
    # emitted comm model must match what actually ran
    routed_cap, routed_model = _routed_comm_model(args, store)

    def fetch(ids):
        if args.routed:
            if args.policy != "shard":
                raise ValueError("--routed requires --policy shard")
            return store.gather(ids, routed=True, routed_cap=routed_cap)
        return store[ids]

    t0 = time.time()
    for i in range(args.warmup):
        res = fetch(jnp.asarray(batches[i % len(batches)]))
    jax.block_until_ready(res)
    log(f"warmup+compile: {time.time()-t0:.1f}s; hot ratio {store.cache_ratio:.2f}")

    # three-tier: the warmup measured the L0 hit rate; L0 lanes enter the
    # routed gather as invalid and occupy no bucket capacity, so the cap
    # can be tightened by (1-h0) — the sharded tier physically moves
    # ~alpha*L*(1-h0) lanes per hop instead of alpha*L. Re-plan, then pay
    # the one retrace outside the clock.
    h0 = _tier_hit_rates(store).get("hit_rep", 0.0)
    if h0 > 0 and routed_cap is not None:
        routed_cap, routed_model = _routed_comm_model(args, store, h0=h0)
        log(f"L0 hit rate {h0:.3f}: routed cap tightened to {routed_cap} "
            f"({routed_model['lanes_per_hop']} lanes/hop)")
        res = fetch(jnp.asarray(batches[0]))
        jax.block_until_ready(res)

    # count bytes PHYSICALLY moved by the gather: the stored dtype's row
    # bytes (+ the 4-byte dequant scale per row for int8) — int8's output
    # is dequantized f32, and counting that would inflate GB/s 4x
    stored_itemsize = np.dtype(store.dtype).itemsize
    row_overhead = 4 if args.dtype == "int8" else 0
    total_bytes = 0
    t0 = time.time()
    for i in range(args.iters):
        if args.store != "ram":
            # the training pipeline's overlap seam: batch i+1's cold
            # windows dispatch while batch i's gather runs
            store.prefetch(batches[(i + 1) % len(batches)])
        res = fetch(jnp.asarray(batches[i % len(batches)]))
        total_bytes += res.shape[0] * (
            res.shape[1] * stored_itemsize + row_overhead
        )
    jax.block_until_ready(res)
    dt = time.time() - t0

    percall_gbps = total_bytes / dt / 1e9

    if args.stream:
        # guarded: a stream failure must not discard the measured per-call
        # number (run_guarded would retry the whole body and degrade)
        try:
            _stream_gbps(args, store, batches, stored_itemsize, row_overhead,
                         routed_cap=routed_cap, routed_model=routed_model)
        except Exception as e:  # noqa: BLE001
            log(f"stream measure failed (per-call record stands): "
                f"{type(e).__name__}: {str(e)[:200]}")

    emit(
        "feature-collection-GBps/chip",
        percall_gbps,
        "GB/s",
        BASELINE_GBPS,
        policy=args.policy,
        dtype=args.dtype,
        cache_ratio=round(store.cache_ratio, 3),
        gather_batch=args.gather_batch,
        dispatch="percall",
        routed=getattr(args, "routed", False),
        store=args.store,
        **_tier_hit_rates(store),
        **_routed_extras(store, routed_model),
        **_ooc_extras(args, store),
    )
    # metrics.jsonl artifact: the store's registry snapshots (tier hits)
    # plus the hot tier's (routed overflow), attributed to this lane
    write_metrics(store, getattr(store, "hot", None),
                  lane="feature", policy=args.policy)

    if args.controller:
        _controller_lane(args, store, topo)


def _controller_lane(args, store, topo):
    """quiver-ctl replay: measured-frequency placement vs degree-static.

    The initial placement can only pin a degree-order PREFIX into L0;
    the controller re-tiers to the rows a trace actually hammers. The
    recorded trace is built so heat does NOT follow degree (80% of the
    mass on the LOWEST-degree rows — the pattern a degree prefix cannot
    see), replayed through the sketch, and ``maybe_repin`` re-tiers the
    live store. The record carries the trace-measured L0 hit rate
    before/after at the SAME replicate budget, the in-program tier hits
    of a post-repin device gather, and the audited decision-log path.
    """
    import os

    import jax
    import jax.numpy as jnp

    from benchmarks import ledger
    from quiver_tpu import CacheController
    from quiver_tpu.control.freq import FreqSketch

    n = store.shape[0]
    rep = store.rep_rows
    if rep <= 0:
        log("controller lane skipped: no L0 tier "
            "(--replicate-budget is 0 or degraded to cold-only)")
        return

    # recorded skewed trace, heat != degree: hot set = lowest-degree rows
    rng = np.random.default_rng(args.seed + 2)
    hot_k = min(rep, 1024)  # the sketch's exact heavy-hitter capacity
    hot = np.argsort(topo.degree.astype(np.int64), kind="stable")[:hot_k]
    trace = [
        np.where(
            rng.random(args.gather_batch) < 0.8,
            rng.choice(hot, size=args.gather_batch),
            rng.integers(0, n, args.gather_batch),
        ).astype(np.int32)
        for _ in range(4)
    ]

    def trace_l0_hit_rate():
        order = np.asarray(store.feature_order)
        hits = sum(int((order[b] < store.rep_rows).sum()) for b in trace)
        return hits / float(sum(b.size for b in trace))

    static_rate = trace_l0_hit_rate()
    mpath = ledger.metrics_jsonl_path()
    dlog = os.path.join(os.path.dirname(mpath) if mpath else ".",
                        "controller_decisions.jsonl")
    ctl = CacheController(sketch=FreqSketch(n, top_k=max(hot_k, 1024)),
                          decision_log=dlog)
    t0 = time.time()
    for batch in trace:
        ctl.observe_ids(batch)
    repinned = ctl.maybe_repin(store)
    measured_rate = trace_l0_hit_rate()
    log(f"controller lane: L0 hit rate {static_rate:.3f} -> "
        f"{measured_rate:.3f} (repin={repinned}, "
        f"{time.time() - t0:.1f}s observe+repin)")
    # one post-repin device gather: exercises the re-tiered tiers end to
    # end and lands the in-program tier hits in the record
    res = store[jnp.asarray(trace[0])]
    jax.block_until_ready(res)
    emit(
        "feature-controller-L0-hit-rate",
        measured_rate,
        "fraction",
        None,
        policy=args.policy,
        dtype=args.dtype,
        rep_rows=int(store.rep_rows),
        static_hit_rate=round(static_rate, 4),
        hit_rate_delta=round(measured_rate - static_rate, 4),
        repinned=repinned,
        pinned_hot_rows=int(hot_k),
        decisions=ctl.stats()["decisions"],
        decision_log=dlog,
        **_tier_hit_rates(store),
    )
    write_metrics(store, ctl, lane="feature-controller", policy=args.policy)


def _ooc_extras(args, store):
    """Ledger extras for a disk-backed (--store mmap/pread) run: the
    stager's lifetime read/readahead counters and the exposed blocking
    share of disk cost."""
    if args.store == "ram" or getattr(store, "stager", None) is None:
        return {}
    st = store.stager
    return {
        "ooc_window_rows": st.window_rows,
        "ooc_page_reads": st.page_reads_total,
        "ooc_readahead_hits": st.readahead_hits_total,
        "ooc_stage_wait_s": round(st.stage_wait_total, 4),
    }


def _routed_comm_model(args, store, h0: float = 0.0):
    """Per-device comm-volume model of the routed hot-tier gather.

    Lanes (feature-row slots) each all_to_all hop carries per device:
    ``F * L`` for the exact-safe full-length buckets, ``F * cap`` for
    capped buckets (``cap = ceil(alpha * L / F)`` => ``~alpha * L``), where
    L is the per-device request length after padding. The model is exact —
    bucket shapes are static — and the measured overflow count (fallback-
    served lanes) rides alongside it in the record.

    ``h0`` is the measured L0 (replicated-tier) hit rate: L0 lanes enter
    the routed gather as invalid and occupy no bucket capacity, so the cap
    shrinks to ``ceil(alpha * (1-h0) * L / F)`` and the effective per-hop
    volume to ``~alpha * L * (1-h0)`` — strictly below the two-tier capped
    path whenever the super-hot tier is catching traffic.

    Returns (explicit_cap_or_None, model_extras_dict_or_None).
    """
    if not getattr(args, "routed", False) or store.hot is None:
        return None, None
    import jax

    n_dev = len(jax.devices())
    batch = args.gather_batch
    local_len = (batch + (-batch) % n_dev) // n_dev
    F = store.hot.num_shards
    uncapped_lanes = F * local_len
    if not args.routed_alpha:
        return None, {
            "lanes_per_hop": uncapped_lanes,
            "lanes_per_hop_uncapped": uncapped_lanes,
            "comm_reduction": 1.0,
        }
    h0 = min(max(float(h0), 0.0), 1.0)
    alpha_eff = max(args.routed_alpha * (1.0 - h0), 1e-6)
    cap = store.hot.routed_cap(local_len, alpha_eff)
    extras = {
        "routed_alpha": args.routed_alpha,
        "routed_cap": cap,
        "lanes_per_hop": F * cap,
        "lanes_per_hop_uncapped": uncapped_lanes,
        "comm_reduction": round(uncapped_lanes / (F * cap), 2),
    }
    if h0 > 0:
        extras["l0_hit_rate"] = round(h0, 4)
        extras["effective_lanes_per_hop"] = round(
            args.routed_alpha * local_len * (1.0 - h0), 1
        )
    return cap, extras


def _tier_hit_rates(store):
    """Measured per-tier hit rates of the store's last eager gather, read
    from its graftscope registry (``feature.tier_hits``; {} for stores
    without a registry or before any eager batch)."""
    from quiver_tpu.obs.registry import TIER_HITS

    reg = getattr(store, "metrics", None)
    hits = reg.value(TIER_HITS) if hasattr(reg, "value") else None
    if hits is None:
        # duck-typed stores without a registry still surface the legacy
        # attribute (kept as a thin view on real stores)
        hits = getattr(store, "last_tier_hits", None)
    if hits is None:
        return {}
    h = np.asarray(hits).astype(np.float64)
    tot = h.sum()
    if tot <= 0:
        return {}
    return {
        "hit_rep": round(h[0] / tot, 4),
        "hit_sharded": round(h[1] / tot, 4),
        "hit_cold": round(h[2] / tot, 4),
    }


def _routed_extras(store, routed_model):
    """Ledger extras for a routed run: the comm model + the measured
    fallback-served overflow count of the last gather (from the hot
    tier's graftscope registry, ``feature.routed_overflow``)."""
    from quiver_tpu.obs.registry import ROUTED_OVERFLOW

    if routed_model is None:
        return {}
    extras = dict(routed_model)
    hot = getattr(store, "hot", None)
    snap = None if hot is None else hot.metrics.snapshot(ROUTED_OVERFLOW)
    extras["routed_overflow"] = 0 if snap is None else int(snap.numpy)
    return extras


def _stream_gbps(args, store, batches, stored_itemsize, row_overhead,
                 reps: int = 3, routed_cap=None, routed_model=None):
    """GB/s over a fused id stream: ONE compiled program scans pre-staged
    device id batches; a full-row checksum in the carry keeps every gathered
    column live (summing a slice would let XLA narrow the gather). Timed
    region = the scan + one scalar readback; ids are staged outside the
    clock because in real training they are sampler output already in HBM.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    id_mat = jnp.asarray(
        np.stack([batches[i % len(batches)] for i in range(args.stream)])
    )

    # the store is CLOSED OVER, not passed: Feature is a pytree but
    # ShardedFeature is not (its gather wraps a shard_map program); captured
    # device buffers are hoisted to program parameters either way, so one
    # code path serves both policies
    routed = getattr(args, "routed", False)

    @jax.jit
    def stream(ids_all):
        def step(carry, ids):
            rows = (
                store.gather(ids, routed=True, routed_cap=routed_cap)
                if routed else store[ids]
            )
            return carry + jnp.sum(rows.astype(jnp.float32)), None
        total, _ = lax.scan(step, jnp.float32(0), ids_all)
        return total

    def one_rep():
        t0 = time.time()
        float(stream(id_mat))
        dt = time.time() - t0
        nbytes = args.stream * args.gather_batch * (
            store.shape[1] * stored_itemsize + row_overhead
        )
        return nbytes / dt / 1e9

    t0 = time.time()
    one_rep()  # compile
    log(f"stream compile: {time.time()-t0:.1f}s ({args.stream} batches/scan)")
    gbps = float(np.median([one_rep() for _ in range(reps)]))
    extras = {}
    ceiling = _gather_ceiling_gbps(args, store, stored_itemsize, row_overhead)
    if ceiling is not None:
        extras = {"roofline_frac": round(gbps / ceiling, 3),
                  "ceiling_gbps": round(ceiling, 1)}
    emit(
        "feature-collection-GBps/chip",
        gbps,
        "GB/s",
        BASELINE_GBPS,
        policy=args.policy,
        dtype=args.dtype,
        cache_ratio=round(store.cache_ratio, 3),
        gather_batch=args.gather_batch,
        dispatch="stream",
        stream_batches=args.stream,
        routed=getattr(args, "routed", False),
        **extras,
        **_tier_hit_rates(store),
        **_routed_extras(store, routed_model),
    )


def _gather_ceiling_gbps(args, store, stored_itemsize, row_overhead):
    """HBM-traffic ceiling for the row gather, in COUNTED GB/s (counted
    bytes = stored row bytes, the number the headline reports).

    Per gathered row the chip must move: one 32-byte granule for the random
    row-start access, the stored row (contiguous read), the OUTPUT row
    write (f32-dequantized for int8 — 4 bytes/element regardless of the
    stored tier), and for int8 a granule for the per-row scale gather.
    Only meaningful when every row lives in this chip's HBM: with a cold
    tier the bound is the host link, and with a sharded table it is the
    ICI collective path — a made-up ceiling would flatter those numbers,
    so both cases emit none.
    """
    from benchmarks.common import hbm_bandwidth_gbps

    if store.cache_ratio < 1.0 or args.policy != "replicate":
        return None
    bw = hbm_bandwidth_gbps()
    if bw is None:
        return None
    dim = store.shape[1]
    stored_row = dim * stored_itemsize + row_overhead
    out_itemsize = 4 if args.dtype == "int8" else stored_itemsize
    traffic = 32 + stored_row + dim * out_itemsize
    if args.dtype == "int8":
        traffic += 32  # random access to the f32 dequant scale row
    return bw * stored_row / traffic


if __name__ == "__main__":
    main()
