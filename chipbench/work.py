"""Operations and bytes that one training step needs, from the
configuration's shapes and the step's valid counts.

These count the work of the algorithm, not of an implementation: they know
nothing of which kernel or dedup strategy ran, of padding to the frontier
caps, or of memory granules. A roofline share computed from them may read
low; it cannot pass 100 % unless the time leaves out part of the work.

``counts`` is what the harness observed of one step, per worker:

* ``hops``: seeds-outward, one dict per hop with ``targets`` (valid target
  nodes ``S``), ``fanout`` (``k``), ``edges`` (valid sampled edges) and
  ``unique`` (distinct nodes of the frontier after the hop, ``U``);
* ``gathered_rows``: valid feature rows gathered; ``feature_dim``,
  ``feature_itemsize``;
* ``model`` and ``layer_dims``: the configuration's model and the
  ``(in, out)`` of each of its layers, input layer first; the step's
  operations are the model's to count (``reference/<model>.py``).
"""

from __future__ import annotations

from . import spec

__all__ = ["sample_bytes", "reindex_bytes", "gather_bytes", "step_flops",
           "WORK"]


def sample_bytes(counts: dict) -> float:
    """Per hop and valid target: two ``indptr`` words read, ``k`` neighbour
    ids read and ``k`` written, 4 bytes each: ``S * (8 + 8k)``."""
    return float(sum(h["targets"] * (8 + 8 * h["fanout"])
                     for h in counts["hops"]))


def reindex_bytes(counts: dict) -> float:
    """Per hop: ``T = S * (k + 1)`` ids in (targets and their neighbour
    lanes), ``T`` local ids and ``U`` distinct nodes out: ``4 * (2T + U)``."""
    return float(sum(
        4 * (2 * h["targets"] * (h["fanout"] + 1) + h["unique"])
        for h in counts["hops"]))


def gather_bytes(counts: dict) -> float:
    """Per valid row: the row read and written, and its 4-byte id read."""
    row = counts["feature_dim"] * counts["feature_itemsize"]
    return float(counts["gathered_rows"] * (2 * row + 4))


def step_flops(counts: dict) -> float:
    """Forward and backward of the model over the valid rows, nothing
    recomputed, as the model's plain side counts them."""
    return spec.load_model(counts["model"], "reference").step_flops(counts)


WORK = {
    "sample_bytes": sample_bytes,
    "reindex_bytes": reindex_bytes,
    "gather_bytes": gather_bytes,
    "step_flops": step_flops,
}
