"""Sampling throughput (SEPS) benchmark — both topology placements.

Methodology: SEPS = Σ valid sampled edges / synchronized wall time, the
reference's benchmarks/sample/bench_sampler.py:33-43. Padded lanes are NOT
counted (BASELINE.md honesty rule, SURVEY §7.4.6). Modes:

* ``HBM`` — topology in device HBM (reference "GPU" mode).
* ``HOST`` — topology in pinned host memory with staged windows (reference
  "UVA" mode, sage_sampler.py:25-27); the beyond-HBM placement.

Baseline: 34.29M SEPS = reference 1-GPU UVA on ogbn-products [15,10,5]
(docs/Introduction_en.md:41).
"""

import time

import numpy as np

from benchmarks.common import (
    BASELINE_UVA_SEPS,
    base_parser,
    build_graph,
    emit,
    hbm_bandwidth_gbps,
    log,
    run_guarded,
    sampler_roofline,
    stream_seps,
    write_metrics,
)


def main():
    p = base_parser(__doc__)
    p.add_argument("--fanout", type=int, nargs="+", default=[15, 10, 5])
    p.add_argument("--mode", default="HBM", choices=["HBM", "HOST", "GPU", "UVA"])
    p.add_argument(
        "--kernel",
        default="xla",
        choices=["xla", "pallas", "fused", "auto"],
        help="sampling kernel: exact XLA stratified sampler ('auto' means "
        "the same) or the fused Pallas megakernel ('pallas' and 'fused' "
        "are the same engine — one windowed-DMA kernel behind every "
        "variant, weighted and sharded included; 'fused' names the "
        "scoreboard lane)",
    )
    p.add_argument(
        "--weighted", action="store_true",
        help="weight-proportional neighbor draws (inverse-CDF over per-row "
        "prefix weights) on exp(N(0,1)) synthetic edge weights — the path "
        "the reference plumbed but never shipped reachable "
        "(quiver.cu.hpp:240-272 commented out)",
    )
    p.add_argument(
        "--caps",
        default="auto",
        choices=["auto", "worst"],
        help="frontier capacities: auto right-sizes every layer from the "
        "first batch's observed uniques (results stay exact — overflow "
        "triggers a regrow+resample); worst pads to the theoretical bound, "
        "which on a power-law graph means sorting node_count-sized arrays "
        "in every reindex (SURVEY §7.4.2)",
    )
    p.add_argument(
        "--stages",
        action="store_true",
        help="also emit a per-layer sample/reindex stage profile (one JSON "
        "line per stage) — the attribution the headline number needs when "
        "it falls short of baseline",
    )
    p.add_argument(
        "--topo-sharding",
        default="replicated",
        choices=["replicated", "mesh"],
        dest="topo_sharding",
        help="topology placement: 'replicated' (every chip holds the full "
        "CSR — the reference's per-GPU device-resident registration) or "
        "'mesh' — the CSR partitioned across the mesh's feature axis "
        "(~1/F topology bytes per chip); each hop routes frontier "
        "vertices to their owning shard over capped-bucket all_to_all "
        "collectives (sampling/dist.py) and the record carries the exact "
        "lanes-per-hop comm model + the measured fallback overflow",
    )
    p.add_argument(
        "--routed-alpha",
        type=float,
        default=2.0,
        metavar="A",
        dest="routed_alpha",
        help="--topo-sharding mesh: capped-bucket factor — per-destination "
        "bucket capacity ceil(A*L/F) per hop, so each all_to_all moves "
        "~A*L lanes instead of F*L; 0 = uncapped full-length buckets. "
        "Overflow lanes are fallback-served (exact) and counted",
    )
    p.add_argument(
        "--stream",
        type=int,
        default=0,
        metavar="N",
        help="headline via a fused seed stream: lax.scan over N batches in "
        "ONE compiled program with in-program valid-edge tallies and a "
        "single scalar readback. The per-call loop (one dispatch + one "
        "host sync per batch) is still measured and emitted as a second "
        "record with dispatch=percall: it carries one dispatch and one "
        "host sync per batch on top of the sample compute. The stream is "
        "also how the fused train step actually consumes the sampler "
        "(sample_padded inside the step program).",
    )
    p.set_defaults(warmup=25, iters=50)
    args = p.parse_args()
    run_guarded(lambda: _body(args), args)


def _stage_profile(args, sampler, topo, reps: int = 30):
    """Time each layer's sample and reindex stages as separate compiled
    programs on realistic frontier inputs (the fused program hides the
    split; this attributes it)."""
    import jax
    import jax.numpy as jnp

    from quiver_tpu.ops.reindex import reindex_layer
    from quiver_tpu.ops.sample import sample_layer

    cap = args.batch
    caps = sampler._caps_for(cap)
    rng = np.random.default_rng(args.seed + 7)
    padded = np.full(cap, -1, dtype=np.int32)
    seeds = rng.integers(0, topo.node_count, args.batch)
    padded[: args.batch] = seeds
    cur = jnp.asarray(padded)
    cur_n = jnp.int32(args.batch)
    key = jax.random.PRNGKey(args.seed + 7)

    def timed(fn, *fn_args):
        out = fn(*fn_args)
        jax.block_until_ready(out)
        ts = []
        for _ in range(reps):
            t0 = time.time()
            out = fn(*fn_args)
            jax.block_until_ready(out)
            ts.append(time.time() - t0)
        ts = np.sort(ts)
        k = max(1, len(ts) // 10)
        return out, float(np.mean(ts[k:-k]) * 1e3)

    use_pallas = sampler.kernel == "pallas"
    if use_pallas:
        from quiver_tpu.ops.pallas.fused import (
            DEFAULT_WINDOW,
            fused_sample_layer,
        )

        # same trace-time fallback rules the fused program applies
        E = int(sampler.topo.edge_count)
        md = getattr(sampler.topo, "max_degree", None)
        use_pallas = (
            E >= DEFAULT_WINDOW
            and max(sampler.sizes) <= DEFAULT_WINDOW
            and not (sampler.weighted
                     and (md is None or md > DEFAULT_WINDOW))
        )

    weighted = sampler.weighted
    for l, k in enumerate(sampler.sizes):
        key, sub = jax.random.split(key)
        if use_pallas:
            f_sample = jax.jit(
                lambda t, c, n, kk, fan=k: fused_sample_layer(
                    t, c, n, fan, kk, weighted=weighted
                )
            )
        else:
            f_sample = jax.jit(
                lambda t, c, n, kk, fan=k: sample_layer(
                    t, c, n, fan, kk, weighted=weighted
                )
            )
        (nbr, counts), t_sample = timed(f_sample, sampler.topo, cur, cur_n, sub)
        f_reindex = jax.jit(
            lambda c, n, nb, fc=caps[l]: reindex_layer(c, n, nb, fc)
        )
        (frontier, n_frontier, _, _), t_reindex = timed(
            f_reindex, cur, cur_n, nbr
        )
        emit(
            "sampler-stage-ms",
            t_sample,
            "ms",
            None,
            layer=l,
            stage="sample",
            kernel="pallas" if use_pallas else "xla",
            fanout=k,
            frontier_in=int(cur.shape[0]),
        )
        emit(
            "sampler-stage-ms",
            t_reindex,
            "ms",
            None,
            layer=l,
            stage="reindex",
            frontier_cap=int(caps[l]),
        )
        cur, cur_n = frontier, n_frontier


def _stream_seps(args, sampler, topo, reps: int = 3):
    """Fused-stream headline (see benchmarks.common.stream_seps).

    Methodology note: per-batch outputs (Adj stacks) are produced and
    discarded inside the scan — the sample + reindex compute that defines
    SEPS is all live (the tallies depend on it); only the final
    reshape/stack assembly is dead code. Timed wall includes the seed
    matrix H2D and the scalar readback. Valid edges only (BASELINE.md
    honesty rule).
    """
    cap = sampler._seed_capacity  # _body always sets seed_capacity=batch
    rng = np.random.default_rng(args.seed + 13)
    res = stream_seps(sampler, topo.node_count, cap, args.stream, rng, reps)
    if res is None:
        return
    seps, oflo, stream = res
    # roofline sanity: how far from the chip's HBM ceiling this number
    # is, not just how far from a 2021 GPU's (VERDICT r3 item 2)
    extra = {}
    try:
        rl = sampler_roofline(sampler, args.batch)
        if rl is not None:
            extra = {
                "roofline_ceiling_seps": round(rl[1]),
                "roofline_frac": round(seps / rl[1], 3),
                "roofline_model": "hbm-traffic lower bound "
                f"({rl[0] / 1e6:.0f} MB/batch @ "
                f"{hbm_bandwidth_gbps():g} GB/s)",
            }
    except Exception as e:  # noqa: BLE001 — analytics must not cost a record
        log(f"roofline estimate failed: {type(e).__name__}: {str(e)[:120]}")
    emit(
        "sampled-edges/sec/chip",
        seps,
        "SEPS",
        BASELINE_UVA_SEPS,
        mode=args.mode,
        kernel=args.kernel,
        fanout=args.fanout,
        batch=args.batch,
        caps=args.caps,
        weighted=getattr(args, "weighted", False),
        dispatch="stream",
        stream_batches=stream,
        overflow=oflo,
        **extra,
    )


def _sharded_comm_model(sampler, seed_cap: int, caps) -> dict:
    """Exact per-device lanes-per-hop model of the mesh-sharded sampler.

    Hop ``l`` (seeds outward) routes a per-worker frontier of width
    ``S_l = (seed_cap, caps[0], ..., caps[-2])[l]`` through four
    ``all_to_all`` exchanges — ids out, degrees back, offsets out,
    ``(cap, k)`` neighbor blocks back — moving
    ``F * cap_l * (2 + 2 * k_l)`` lanes with capped buckets
    (``cap_l = ceil(alpha * S_l / F)``) vs ``F * S_l * (2 + 2 * k_l)``
    uncapped. A weighted sampler adds one f32 exchange per hop (row
    weight totals back: ``+F * cap_l`` lanes). Bucket shapes are static,
    so the model is exact; the measured fallback overflow rides
    alongside it in the record.
    """
    from quiver_tpu.sampling.dist import routed_sample_cap

    F = sampler.topo.num_shards
    alpha = sampler.routed_alpha
    extra = 1 if sampler.weighted else 0
    widths = (seed_cap,) + tuple(caps[:-1])
    lanes, lanes_unc, hop_caps = [], [], []
    for S_l, k in zip(widths, sampler.sizes):
        cap_l = routed_sample_cap(S_l, F, alpha) or S_l
        hop_caps.append(int(cap_l))
        lanes.append(F * cap_l * (2 + extra + 2 * k))
        lanes_unc.append(F * S_l * (2 + extra + 2 * k))
    model = {
        "topo_sharding": "mesh",
        "routed_alpha": alpha,
        "hop_caps": hop_caps,
        "lanes_per_hop": lanes,
        "lanes_per_hop_uncapped": lanes_unc,
        "comm_reduction": round(sum(lanes_unc) / max(sum(lanes), 1), 2),
    }
    plan = sampler.topo.plan
    model.update(
        topo_bytes_per_chip=plan["per_chip_bytes"],
        topo_bytes_replicated=plan["replicated_bytes"],
        topo_shrink=round(plan["shrink_factor"], 2),
    )
    return model


def _body_sharded(args):
    """--topo-sharding mesh lane: the distributed sampler over the CSR
    partitioned across the mesh's feature axis. SEPS methodology is
    unchanged (valid sampled edges / synchronized wall, per chip); the
    record adds the exact lanes-per-hop comm model and the measured
    per-hop fallback overflow (``last_sample_overflow``)."""
    import jax

    from quiver_tpu import GraphSageSampler
    from quiver_tpu.parallel.mesh import make_mesh

    if args.mode not in ("HBM", "GPU"):
        raise SystemExit("--topo-sharding mesh requires --mode HBM (each "
                         "shard's slice is device-resident — that is the "
                         "point)")
    if args.stream:
        log("WARNING: --stream is not supported with --topo-sharding mesh; "
            "measuring the per-call dispatch loop only")

    topo = build_graph(args)
    if args.weighted:
        # sharded weighted draws: each shard ships its row-local
        # prefix-weight segments; the owner answers the inverse-CDF search
        w = np.exp(
            np.random.default_rng(args.seed + 5).normal(size=topo.edge_count)
        ).astype(np.float32)
        topo.set_edge_weight(w)
    F = len(jax.devices())
    mesh = make_mesh(data=1, feature=F)
    alpha = args.routed_alpha or None
    sampler = GraphSageSampler(
        topo, args.fanout, mode="HBM", seed=args.seed,
        kernel="pallas" if args.kernel == "fused" else args.kernel,
        topo_sharding="mesh", mesh=mesh, routed_alpha=alpha,
        weighted=args.weighted,
        frontier_caps="auto" if args.caps == "auto" else None,
    )
    W = sampler.workers
    rng = np.random.default_rng(args.seed)

    t0 = time.time()
    for _ in range(args.warmup):
        out = sampler.sample(rng.integers(0, topo.node_count, args.batch))
        jax.block_until_ready(out.n_id)
    log(f"warmup+compile: {time.time()-t0:.1f}s")

    total_edges = 0
    t0 = time.time()
    for _ in range(args.iters):
        out = sampler.sample(rng.integers(0, topo.node_count, args.batch))
        total_edges += int(sum(out.edge_counts))
    jax.block_until_ready(out.n_id)
    dt = time.time() - t0
    seps_chip = total_edges / dt / W

    per_worker = -(-args.batch // W)
    seed_cap = sampler._seed_capacity or max(
        _bench_round_up(per_worker, 128), 128
    )
    caps = sampler._caps_for(seed_cap)
    model = _sharded_comm_model(sampler, seed_cap, caps)
    # per-hop fallback overflow from the sampler's graftscope registry
    # (``sample.hop_overflow``) instead of poking the legacy attribute
    from quiver_tpu.obs.registry import SAMPLE_OVERFLOW

    snap = sampler.metrics.snapshot(SAMPLE_OVERFLOW)
    sample_overflow = (
        [int(v) for v in snap.numpy] if snap is not None
        else [0] * len(sampler.sizes)
    )
    emit(
        "sampled-edges/sec/chip",
        seps_chip,
        "SEPS",
        BASELINE_UVA_SEPS,
        mode="HBM",
        kernel=args.kernel,
        fanout=args.fanout,
        batch=args.batch,
        caps=args.caps,
        dispatch="percall",
        weighted=args.weighted,
        mesh_devices=W,
        seps_mesh_total=round(total_edges / dt),
        sample_overflow=sample_overflow,
        **model,
    )
    write_metrics(sampler, lane="sampler-sharded")


def _bench_round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _body(args):
    import jax

    from quiver_tpu import GraphSageSampler

    if getattr(args, "topo_sharding", "replicated") == "mesh":
        return _body_sharded(args)

    topo = build_graph(args)
    if args.weighted:
        # the fused megakernel serves weighted draws too (ISSUE 16): no
        # kernel restriction — the inverse-CDF walk runs in-kernel
        w = np.exp(
            np.random.default_rng(args.seed + 5).normal(size=topo.edge_count)
        ).astype(np.float32)
        topo.set_edge_weight(w)
    sampler = GraphSageSampler(
        topo, args.fanout, mode=args.mode, seed_capacity=args.batch,
        seed=args.seed,
        kernel="pallas" if args.kernel == "fused" else args.kernel,
        weighted=args.weighted,
        frontier_caps="auto" if args.caps == "auto" else None,
    )
    rng = np.random.default_rng(args.seed)

    t0 = time.time()
    for _ in range(args.warmup):
        out = sampler.sample(rng.integers(0, topo.node_count, args.batch))
        jax.block_until_ready(out.n_id)
    log(f"warmup+compile: {time.time()-t0:.1f}s")

    n_compiled = len(sampler._compiled_cache)
    total_edges = 0
    t0 = time.time()
    for _ in range(args.iters):
        out = sampler.sample(rng.integers(0, topo.node_count, args.batch))
        # one device->host scalar read per iter (sum folds on device)
        total_edges += int(sum(out.edge_counts))
    jax.block_until_ready(out.n_id)
    dt = time.time() - t0
    percall_seps = total_edges / dt
    # steady state must never recompile: the warmup loop owns every
    # (seed_cap, caps) program this batch shape can demand
    recompiles_steady = len(sampler._compiled_cache) - n_compiled
    if recompiles_steady:
        log(f"WARNING: {recompiles_steady} steady-state recompile(s) — "
            "the sampler program must be compiled once per shape")

    if args.stream:
        # stream headline FIRST (the first SEPS record is the headline),
        # per-call after as the dispatch=percall record.
        # Guarded: a stream failure must not discard the per-call number
        # already in hand (same discipline as _stage_profile below)
        try:
            _stream_seps(args, sampler, topo)
        except Exception as e:  # noqa: BLE001
            log(f"stream measure failed (per-call record stands): "
                f"{type(e).__name__}: {str(e)[:200]}")

    emit(
        "sampled-edges/sec/chip",
        percall_seps,
        "SEPS",
        BASELINE_UVA_SEPS,
        mode=args.mode,
        kernel=args.kernel,
        fanout=args.fanout,
        batch=args.batch,
        caps=args.caps,
        weighted=args.weighted,
        dispatch="percall",
        recompiles_steady=recompiles_steady,
    )

    if getattr(args, "stages", False):
        # the headline is already emitted — a stage-profile failure must
        # not take the run down (each stage is a fresh compile, each a
        # fresh chance at a transient backend error)
        try:
            _stage_profile(args, sampler, topo)
        except Exception as e:  # noqa: BLE001
            log(f"stage profile failed (headline unaffected): "
                f"{type(e).__name__}: {str(e)[:200]}")


if __name__ == "__main__":
    main()
