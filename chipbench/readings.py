"""The readings a limit's upper end is set from, several seeds in one
process, on one chip.

    python3 -m chipbench.readings --workload products-sage.clique2x2 --seeds 401,402,403

For each seed: the cell's inputs at the cell's own size, the blocks of the
first followed steps for as many workers as the cell's mesh has (drawn by
the program's sampler with the keys the step derives), then the reference
against the control (the reference in the next lower precision) and against
each planted fault. The program's own step is not run, so this needs one
chip whatever the cell needs; the program's readings (the lower end) come
from the cell's ordinary runs, which print each compared number. One JSON
line per seed; PERF.md section 2 records what the limits were set from.
"""

from __future__ import annotations

import argparse
import json
import sys

from . import check, inputs, spec


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from .adapter import Program, enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("chipbench.readings: needs a TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    cell = spec.cell(spec.load_benchmark(), args.workload)
    cfg = spec.load_config(cell["config"])
    traffic = spec.load_traffic(cell["traffic"])
    mesh = traffic["mesh"]
    workers = int(mesh["data"]) * (
        int(mesh["feature"]) if traffic["seed_sharding"] == "all" else 1)
    one_chip = dict(traffic, mesh={"data": 1, "feature": 1},
                    seed_sharding="data",
                    feature={"store": "plain", "cache_ratio": 1.0})
    for seed in (int(s) for s in args.seeds.split(",")):
        data = inputs.make_inputs(cfg, seed)
        weights0 = inputs.make_weights(cfg, seed)
        program = Program(cfg, one_chip, data, weights0, jax.devices()[:1])
        feed = inputs.Feed.of(data, workers * int(cfg["batch"]), seed)
        blocks = [program.blocks(feed.seeds(i), feed.key(i), workers)
                  for i in range(int(traffic["followed_steps"]))]
        program.close()
        out = check.readings(cfg, jnp.asarray(data.features),
                             jnp.asarray(data.labels), weights0, blocks)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "workers": workers, "readings": out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
