"""Headline benchmark: sampled edges per second on the chip.

Runs ``benchmarks.bench_sampler`` (the single source of truth for the SEPS
methodology — see benchmarks/README.md) in this process and exits with its
code. One process per chip: no probe child, no retry, no CPU fallback. Off
the TPU it exits non-zero without printing a metric.

Headline config: products-scale synthetic power-law graph, fanout [15,10,5],
batch 2048, HBM-resident topology. ``vs_baseline`` is against the
reference's 34.29M 1-GPU UVA SEPS (docs/Introduction_en.md:41).
"""

import sys

HEADLINE_ARGS = ["--stream", "128"]


def main() -> int:
    from benchmarks import bench_sampler

    sys.argv = [sys.argv[0]] + HEADLINE_ARGS + sys.argv[1:]
    return bench_sampler.main() or 0


if __name__ == "__main__":
    sys.exit(main())
