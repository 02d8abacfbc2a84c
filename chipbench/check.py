"""The comparison that decides ``correct``.

The program's first steps, taken through the window's own call and feed,
are followed by the plain reference on the same rows: each step's loss, the
first gradient as the optimizer got it (worked out from Adam's first moment
after one step), and the change of the parameters after the last followed
step. Norms are compared leaf by leaf and the worst leaf counts: the gap
between the two norms, against the reference's norm of that leaf or of the
median leaf, whichever is larger. Every number has a limit of its own in
the configuration's file (``limits``), set from readings on the chip.
"""

from __future__ import annotations

import dataclasses
import json
import sys

import numpy as np

from . import spec
from .reference import graph

__all__ = ["Observed", "compare", "readings", "verdict", "report"]

# a leaf whose reference gradient is under this share of the median leaf's
# moves under Adam by round-off alone and is left out of `update_gap`
DEAD_LEAF = 1e-3


@dataclasses.dataclass
class Observed:
    """What the timed path produced in its first steps."""

    losses: list          # one float per followed step
    first_moment: list    # Adam's m after step 1, the reference's naming
    params: list          # parameters after the last followed step
    blocks: list          # per step, one graph.Block per worker
    seeds: list           # per step, per worker, the seed nodes as fed


def worst_leaf_gap(got: dict, want: dict, leaves=None) -> float:
    floor = float(np.median(list(want.values())))
    names = list(want) if leaves is None else leaves
    return max(abs(got[n] - want[n]) / max(want[n], floor) for n in names)


def delta(after, before):
    return [{k: np.asarray(a[k], np.float64) - np.asarray(b[k], np.float64)
             for k in a} for a, b in zip(after, before)]


def numbers(model, obs_losses, obs_grads, obs_params, ref_losses, ref_grads,
            ref_params, weights0) -> dict:
    """The compared numbers of one side against the reference; ``model``
    is the plain side of the configuration's model."""
    out = {}
    for i, (a, b) in enumerate(zip(obs_losses, ref_losses)):
        out[f"loss_gap_{i + 1}"] = abs(a - b) / abs(b)
    g_ref = model.leaf_norms(ref_grads)
    out["grad_gap"] = worst_leaf_gap(model.leaf_norms(obs_grads), g_ref)
    floor = float(np.median(list(g_ref.values())))
    alive = [n for n, v in g_ref.items() if v >= DEAD_LEAF * floor]
    out["update_gap"] = worst_leaf_gap(
        model.leaf_norms(delta(obs_params, weights0)),
        model.leaf_norms(delta(ref_params, weights0)), alive)
    return out


def compare(cfg: dict, data, weights0, obs: Observed, seed: int) -> dict:
    """Check ``obs.blocks`` against the graph (and, where the graph's file
    has a ``lane_faults``, what their lanes carry against what its edges
    do), run the reference over them and return the compared numbers."""
    import jax.numpy as jnp

    rng = np.random.default_rng([int(seed), 5])
    lane_faults = getattr(spec.load_graph(cfg["graph"]["generator"]),
                          "lane_faults", None)
    faults = {}
    for step_blocks, step_seeds in zip(obs.blocks, obs.seeds):
        for block, seeds in zip(step_blocks, step_seeds):
            found = graph.block_faults(
                data.indptr, data.indices, seeds, block, cfg["fanout"], rng)
            if lane_faults is not None:
                found.update(lane_faults(data, seeds, block))
            for k, v in found.items():
                faults[k] = faults.get(k, 0) + v
    features = jnp.asarray(data.features)
    labels = jnp.asarray(data.labels)
    opt = cfg["optimizer"]
    model = spec.load_model(cfg["model"], "reference")
    ref = model.train(weights0, features, labels, obs.blocks, opt)
    b1 = opt["b1"]
    obs_grads = [{k: np.asarray(v, np.float64) / (1 - b1)
                  for k, v in layer.items()} for layer in obs.first_moment]
    out = {"block_faults": float(sum(faults.values()))}
    out.update(numbers(model, obs.losses, obs_grads, obs.params, *ref,
                       weights0))
    return {"numbers": out, "block_detail": faults,
            "losses": {"program": obs.losses, "reference": ref[0]}}


def readings(cfg: dict, features, labels, weights0, blocks) -> dict:
    """What a limit's upper end is set from, each put in the program's
    place and compared with the reference as the program is: the control
    (the reference in the next lower precision; ``control_mixed`` keeps
    float32 parameters and optimizer, the recipe of the program's model
    built with ``dtype="bfloat16"``) and the faults a training cell can
    have."""
    import jax.numpy as jnp

    opt = cfg["optimizer"]
    model = spec.load_model(cfg["model"], "reference")
    ref = model.train(weights0, features, labels, blocks, opt)
    lower = jnp.dtype(cfg["precision"]["control"])
    sides = {
        "control": model.train(weights0, features, labels, blocks, opt,
                               param_dtype=lower, compute_dtype=lower),
        "control_mixed": model.train(weights0, features, labels, blocks, opt,
                                     compute_dtype=lower),
        "fault_half_batch": model.train(
            weights0, features, labels, blocks, opt,
            seed_mask=np.arange(blocks[0][0].num_seeds) % 2 == 0),
    }
    if len(blocks[0]) > 1:
        sides["fault_no_exchange"] = model.train(
            weights0, features, labels, blocks, opt, workers=[0])
    out = {name: numbers(model, *side, *ref, weights0)
           for name, side in sides.items()}
    # a step that returns its state unchanged reads 1 by this measure
    out["fault_state_unchanged"] = {"update_gap": 1.0}
    return out


def verdict(values: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and, for the result line, every number beside its limit.
    A number with no limit in the configuration's file fails the run: a
    comparison without a limit decides nothing."""
    table, ok = {}, True
    for name, value in values.items():
        # the followed steps' losses share one limit
        limit = limits.get(
            "loss_gap" if name.startswith("loss_gap_") else name)
        good = (limit is not None and np.isfinite(value)
                and value <= limit)
        ok = ok and bool(good)
        table[name] = {"value": float(value), "limit": limit}
    return ok, table


def report(table: dict, stream=None) -> None:
    """Each number compared beside its limit, one per line."""
    stream = stream or sys.stderr
    for name, row in table.items():
        print(f"compared {name} = {row['value']:.6g} (limit "
              f"{json.dumps(row['limit'])})", file=stream)
    stream.flush()
