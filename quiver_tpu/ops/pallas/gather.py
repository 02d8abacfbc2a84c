"""Pallas row-gather kernel: feature collection from an HBM-resident table.

TPU-native equivalent of the reference's ``quiver_tensor_gather`` CUDA kernel
(torch-quiver shard_tensor.cu.hpp:16-58 — warp per output row, UVA loads):
each grid step serves a tile of output rows. Row indices arrive via scalar
prefetch (pltpu.PrefetchScalarGridSpec) and drive the index maps of ``tile``
one-row input blocks over the same table, so the pipeline fetches the rows
of step ``i + 1`` from HBM while step ``i`` copies its rows into the output
block (the DMA engines play the role of the GPU's coalesced warp loads).

The table rides as an ``(N, 1, F)`` view: a block must cover whole memory
tiles, and only with the row on an untiled leading axis is one row a whole
tile for any feature width (a hand-written row DMA is refused unless
``F % 128 == 0``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import resolve_interpret

__all__ = ["gather_rows"]


def _gather_kernel(tile: int, ids_ref, *refs):
    del ids_ref  # consumed by the index maps
    rows, out_ref = refs[:tile], refs[tile]
    for j in range(tile):
        out_ref[pl.ds(j, 1), :] = rows[j][...]


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _gather_rows_impl(table, ids, tile: int, interpret: bool):
    n_ids = ids.shape[0]
    n, f = table.shape

    def row_spec(j):
        return pl.BlockSpec(
            (None, 1, f), lambda i, ids: (ids[i * tile + j], 0, 0)
        )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_ids // tile,),
        in_specs=[row_spec(j) for j in range(tile)],
        out_specs=pl.BlockSpec((tile, f), lambda i, ids: (i, 0)),
    )
    return pl.pallas_call(
        functools.partial(_gather_kernel, tile),
        out_shape=jax.ShapeDtypeStruct((n_ids, f), table.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )(ids, *([table.reshape(n, 1, f)] * tile))


def gather_rows(table, ids, tile: int = 16, interpret: bool | None = None):
    """Gather ``table[ids]`` with pipelined row DMAs.

    Args:
      table: (N, F) array in HBM.
      ids: (B,) int32 row indices; must be in-range (callers mask/clamp).
      tile: rows per grid step (= row DMAs in flight per step).
      interpret: run the kernel in the Pallas interpreter (None = the
        package default, which only the CPU test configuration turns on).

    Returns (B, F) gathered rows.
    """
    interpret = resolve_interpret(interpret)
    n = ids.shape[0]
    pad = (-n) % tile
    if pad:
        ids = jnp.concatenate([ids, jnp.zeros(pad, ids.dtype)])
    out = _gather_rows_impl(table, ids, tile, interpret)
    return out[:n] if pad else out
