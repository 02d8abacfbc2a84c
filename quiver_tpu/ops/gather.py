"""How many lanes a row gather is padded by, so that a v5e keeps 256 rows in
flight.

XLA's TPU row gather pads its index vector to whole 1,024-word tiles itself;
where that pad comes to under 128 words (none included) it keeps 128 rows in
flight, and 256 otherwise, whatever the rows' width or dtype (CPU compiles
for a described v5e; on the chip 20.9 against 13.2 ns a 1.5 KB float16 row:
PERF.md section 7 (3)). The decision is read off the lane count, a static
shape, at trace time. The model's fanout-major gathers
(``models/layers.py``) pad by whole targets, the feature store's hot tier by
lanes (:func:`padded_row_gather`); the sampler's block gather pads to 512
past a tile (``ops/sample.py::_gather_indices``).
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from ..utils.trace import info_once

__all__ = ["INDEX_TILE", "SHORT_PAD", "tile_pad", "padded_row_gather"]

INDEX_TILE, SHORT_PAD = 1024, 128


def tile_pad(count: int, width: int = 1) -> int:
    """Units of ``width`` lanes to add to a row gather of ``count * width``
    lanes so that it keeps 256 rows in flight: the fewest, a multiple of 8
    (a count in whole sublanes stays so), that leave the lanes 128 or more
    short of whole 1,024-word tiles. 0 where they already are, or where no
    multiple of 8 gets them there."""
    for pad in range(0, INDEX_TILE + 1, 8):
        if -(width * (count + pad)) % INDEX_TILE >= SHORT_PAD:
            return pad
    return 0


def padded_row_gather(table, ids):
    """``table[ids]``, with the index padded by :func:`tile_pad` lanes so that
    the gather keeps 256 rows in flight (PERF.md section 7 (3)).

    A pad lane names a row of its own (its number in the padded gather
    modulo the table's rows): lanes that all name one row are served one
    after the other (PERF.md section 6, the sampler's block gather). The
    pad rows are sliced off; the rows returned are the plain gather's, bit
    for bit."""
    lanes = ids.shape[0]
    pad = tile_pad(lanes)
    if not pad:
        return table[ids]
    info_once(f"row-gather-pad-{lanes}",
              "row gather of %d lanes: %d padded lanes, so that it keeps 256 "
              "rows in flight", lanes, pad)
    own = (lanes + lax.iota(ids.dtype, pad)) % table.shape[0]
    return table[jnp.concatenate([ids, own])][:lanes]
