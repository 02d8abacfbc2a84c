"""Sampler configuration sweep in ONE process: dedup strategies x batch
sizes, all fused-stream dispatch.

A chip call starts cold, so its time is dominated by per-config compiles
(~min each, amortized by the persistent cache); running the sweep in one
process pays start-up once, and every sampler shares ONE device-resident
topology (GraphSageSampler(device_topo=...)) so the ~500MB CSR is placed
once, not once per configuration. Emits one JSON line per config (same
schema as bench_sampler) — feed the winner back into bench.py's headline
arguments.

    python -m benchmarks.sweep_sampler                       # default grid
    python -m benchmarks.sweep_sampler --batches 2048 8192 --dedups map
"""

import numpy as np

from benchmarks.common import (
    BASELINE_UVA_SEPS,
    base_parser,
    build_graph,
    emit,
    log,
    run_guarded,
    stream_seps,
)


def main():
    p = base_parser(__doc__)
    p.add_argument("--fanout", type=int, nargs="+", default=[15, 10, 5])
    p.add_argument("--batches", type=int, nargs="+",
                   default=[2048, 4096, 8192])
    p.add_argument("--dedups", nargs="+", default=["sort", "map", "scan"],
                   choices=["sort", "map", "scan"])
    p.add_argument("--stream", type=int, default=64)
    p.add_argument("--reps", type=int, default=3)
    args = p.parse_args()
    run_guarded(lambda: _body(args), args)


def _body(args):
    from quiver_tpu import GraphSageSampler
    from quiver_tpu.core.config import SampleMode

    topo = build_graph(args)
    rng = np.random.default_rng(args.seed)
    dev_topo = topo.to_device(SampleMode.HBM)  # shared across every config

    # evidence-ordered: the strategy head-to-head at the headline batch
    # first (a run cut short must decide dedup before batch scaling)
    grid = sorted(
        ((d, b) for d in args.dedups for b in args.batches),
        key=lambda db: (db[1] != args.batches[0], args.batches.index(db[1]),
                        args.dedups.index(db[0])),
    )
    for dedup, batch in grid:
            log(f"config dedup={dedup} batch={batch}")
            sampler = GraphSageSampler(
                topo, args.fanout, mode="HBM", seed_capacity=batch,
                seed=args.seed, dedup=dedup, frontier_caps="auto",
                device_topo=dev_topo,
            )
            # plan auto caps from one eager batch
            sampler.sample(rng.integers(0, topo.node_count, batch))
            try:
                res = stream_seps(
                    sampler, topo.node_count, batch, args.stream, rng,
                    args.reps,
                )
            except Exception as e:  # noqa: BLE001 — one config must not kill the sweep
                log(f"  config failed: {type(e).__name__}: {str(e)[:200]}")
                continue
            if res is None:
                continue
            seps, oflo, stream = res
            emit(
                "sampled-edges/sec/chip",
                seps,
                "SEPS",
                BASELINE_UVA_SEPS,
                mode="HBM",
                kernel="xla",
                fanout=args.fanout,
                batch=batch,
                caps="auto",
                dedup=dedup,
                dispatch="stream",
                stream_batches=stream,
                overflow=oflo,
            )


if __name__ == "__main__":
    main()
