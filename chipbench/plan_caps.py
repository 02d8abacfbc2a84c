"""Plan a configuration's ``frontier_caps`` on the host, from counts alone.

    python3 -m chipbench.plan_caps --config products-sage --seeds 8 --batches 4

Draws the configuration's graph for several seeds, samples a few batches
with a plain numpy sampler (``min(deg, k)`` distinct neighbours per target,
as the program's neighbour sampler draws), and prints the largest number of
distinct nodes each hop reached, with the sampler's own margin (1.25)
rounded up to 128: the numbers a configuration file pins under
``frontier_caps``. It runs no program and measures no time; any sampler
that draws ``min(deg, k)`` neighbours uniformly reaches frontiers of the
same size.
"""

from __future__ import annotations

import argparse

import numpy as np

from . import inputs, spec

MARGIN = 1.25  # GraphSageSampler's auto_margin


def frontiers(indptr, indices, seeds, fanouts, rng) -> list[np.ndarray]:
    """The distinct nodes after each hop, seeds included."""
    frontier = np.unique(seeds)
    out = []
    for k in fanouts:
        base = indptr[frontier]
        deg = indptr[frontier + 1] - base
        j = np.arange(k)[None, :]
        u = rng.random((frontier.shape[0], k))
        # one draw in each of k strata of the row: distinct when deg > k
        off = np.where(deg[:, None] > k,
                       ((j + u) * deg[:, None] / k).astype(np.int64),
                       np.minimum(j, np.maximum(deg[:, None] - 1, 0)))
        keep = j < np.minimum(deg, k)[:, None]
        nbr = indices[(base[:, None] + off)[keep]]
        frontier = np.unique(np.concatenate([frontier, nbr]))
        out.append(frontier)
    return out


def frontier_sizes(indptr, indices, seeds, fanouts, rng) -> list[int]:
    return [int(f.shape[0]) for f in frontiers(indptr, indices, seeds,
                                               fanouts, rng)]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, default=8)
    ap.add_argument("--batches", type=int, default=4)
    args = ap.parse_args()
    cfg = spec.load_config(args.config)
    worst = [0] * len(cfg["fanout"])
    for seed in range(args.seeds):
        data = inputs.make_inputs(cfg, seed)
        feed = inputs.Feed.of(data, cfg["batch"], seed)
        rng = np.random.default_rng([seed, 4])
        for b in range(args.batches):
            got = frontier_sizes(data.indptr, data.indices, feed.seeds(b),
                                 cfg["fanout"], rng)
            worst = [max(w, g) for w, g in zip(worst, got)]
            print(f"seed {seed} batch {b}: {got}", flush=True)
    caps = [int(-(-MARGIN * w // 128) * 128) for w in worst]
    print(f"observed {worst} -> frontier_caps {caps}")


if __name__ == "__main__":
    main()
