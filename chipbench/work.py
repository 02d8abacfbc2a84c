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
* ``layer_dims``: ``(in, out)`` of each SAGE layer, input layer first.
"""

from __future__ import annotations

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
    """Forward and backward of the SAGE layers over the valid rows, nothing
    recomputed. Layer ``i`` (input layer first) has the targets of hop
    ``L-1-i``: two matmuls forward (neighbour mean and self), their two
    weight gradients, and their two input gradients except at the input
    layer, whose inputs are data; the mean adds one flop per edge and
    feature each way."""
    hops = counts["hops"][::-1]  # input layer first
    total = 0.0
    for i, ((d_in, d_out), hop) in enumerate(zip(counts["layer_dims"], hops)):
        matmul = 2.0 * hop["targets"] * d_in * d_out
        total += 2 * matmul                    # forward
        total += 2 * matmul                    # weight gradients
        total += 2 * matmul if i else 0.0      # input gradients
        total += 2.0 * hop["edges"] * d_in     # mean, forward and backward
    return total


WORK = {
    "sample_bytes": sample_bytes,
    "reindex_bytes": reindex_bytes,
    "gather_bytes": gather_bytes,
    "step_flops": step_flops,
}
