"""Fused per-hop Pallas sampling megakernel (windowed row DMA + in-kernel
select), the one engine behind every sampler variant.

TPU-native counterpart of the reference's per-hop CUDA kernel pair —
``CSRRowWiseSampleKernel`` (torch-quiver cuda_random.cu.hpp:7-69) and the
weighted ``WarpSampler`` CDF walk (cuda_random.cu.hpp:143-186) — plus the
eid lane of ``quiver_sample.cu``'s reindex plumbing. The GPU kernels issue
k random cache-line loads per row; TPUs want contiguous DMA, so the design
flips to **window sampling**: per hop, one pass over the HBM-resident CSR
does the degree lookup (XLA indptr gather), the draw, the neighbor-block
copy, and the select:

 1. XLA computes per-row window starts and the PRNG-bit-dependent parts of
    the draw (stratified offsets + rotation for uniform/temporal, the raw
    ``(S, k)`` uniform block for weighted) — everything whose bits depend
    only on the key, keeping bit-parity with the XLA oracle provable.
 2. The kernel DMAs the lane-aligned window that covers
    ``indices[start : start+window]`` (and the matching ``cum_weights``
    and ``eid`` windows when the variant needs them) into VMEM — one bulk
    DMA per row per table, all rows of a tile in flight at once
    (``_fetch_windows`` has the memory-tile rules this obeys).
 3. Topology-dependent work happens on-chip against the VMEM window: the
    weighted inverse-CDF binary search walks the row's prefix-weight
    segment in VMEM (``_wselect_kernel`` — the WarpSampler walk without
    the log2(deg) random HBM probes), and selection is an exact integer
    one-hot masked-sum on the VPU (no float round-trip, node ids beyond
    2^24 stay exact).

Bit-parity contract (pinned by tests/test_fused_sampler.py): for rows
whose draw span fits the window (uniform/temporal with ``deg <= window``;
weighted always, enforced via ``max_degree <= window``), outputs are
BITWISE equal to ``ops.sample.sample_layer`` under the same key — the
uniform path consumes ``kj, kr = split(key)`` over the same shapes, the
weighted path consumes the key unsplit over the same ``(S, k)`` uniform
block and walks an affine-shifted copy of the same f32 prefix array, and
the temporal path shares ``temporal_window_counts`` outright. Window
placement for over-window rows draws from ``fold_in(key, 1)`` so parity
lanes never consume those bits.

Distribution for ``deg > window`` rows (uniform/temporal only): a
uniformly-placed contiguous window — interior slots boosted by ``deg/T``
over the exact ``k/deg`` (``T = deg-window+1`` placements), first/last
``window-1`` slots attenuated linearly. Policy (decided r5, pinned by
tests/test_pallas_hub_distribution.py): the hub-row attenuation is
ACCEPTED; the XLA path remains the exact reference. The weighted walk
refuses windowing instead (callers degrade to XLA below
``max_degree <= window`` — a truncated CDF would re-weight, not
attenuate).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..sample import rotate_offsets, stratified_offsets, temporal_window_counts
from . import resolve_interpret

__all__ = [
    "DEFAULT_WINDOW",
    "MIN_EDGES",
    "fused_sample_layer",
    "fused_select_hop",
    "fused_weighted_hop",
]

DEFAULT_WINDOW = 2048  # default neighbor-window length

_I32MAX = 2**31 - 1
_LANE = 128  # a DMA may only start on a lane-tile boundary

# fewest edges a table may have at the default window: the aligned DMA
# window is one lane tile longer than the draw window. Callers deciding
# between this kernel and the XLA path compare edge_count against it
# (quiver_tpu/sampling/sampler.py, sampling/dist.py)
MIN_EDGES = DEFAULT_WINDOW + _LANE


def _split(refs, *sizes):
    """``refs`` cut into consecutive groups of the given sizes."""
    groups, i = [], 0
    for n in sizes:
        groups.append(refs[i:i + n])
        i += n
    return groups


def _fetch_windows(tile: int, wbuf: int, start_ref, tabs, stages, packed,
                   sems):
    """DMA one ``wbuf``-slot window per row per table and pack the rows of
    the tile onto sublanes.

    Mosaic only lets a DMA address whole memory tiles. So the tables ride
    as ``(1, E)`` views (tiled ``(1, 128)``: a lane-aligned start is a
    tile boundary), each row's window lands in its own ``(1, wbuf)`` slab
    of a ``(tile, 1, wbuf)`` stage, and a VMEM copy moves slab ``j`` to
    sublane ``j`` of the ``(tile, wbuf)`` buffer the select reads —
    window slots on lanes, rows on sublanes, every vector op full-width.
    """
    i = pl.program_id(0)

    def dma(t, j):
        st = pl.multiple_of(start_ref[i * tile + j], _LANE)
        return pltpu.make_async_copy(
            tabs[t].at[:, pl.ds(st, wbuf)], stages[t].at[j], sems.at[t, j]
        )

    # fan out: every row-window DMA of this tile (all tables) in flight
    for t in range(len(tabs)):
        for j in range(tile):
            dma(t, j).start()
    for t in range(len(tabs)):
        for j in range(tile):
            dma(t, j).wait()
    for t in range(len(tabs)):
        for j in range(tile):
            packed[t][pl.ds(j, 1), :] = stages[t][j]


def _lane(x, lane_k, c: int):
    """Column ``c`` of a ``(tile, k)`` block as ``(tile, 1)``: a masked
    lane sum with one live term, so it is exact for ints and floats and
    needs no lane-to-sublane relayout."""
    return jnp.sum(jnp.where(lane_k == c, x, 0), axis=1, keepdims=True)


def _pick(col, slot, window_vals):
    """``window_vals[r, slot[r]]`` per row as ``(tile, 1)``: exact one-hot
    masked sum over the window lanes."""
    return jnp.sum(jnp.where(col == slot, window_vals, 0), axis=1,
                   keepdims=True)


def _select_kernel(tile: int, wbuf: int, k: int, n_tab: int,
                   start_ref, *refs):
    """Windowed gather-select over ``n_tab`` aligned int32 tables.

    ``out[t][j, c] = tables[t][start[j] + offs[j, c]]`` — the uniform /
    temporal / dist-owner select core; the eid lane is just a second table
    riding the same offsets.
    """
    tabs, (offs_ref,), outs, stages, packed, (sems,) = _split(
        refs, n_tab, 1, n_tab, n_tab, n_tab, 1
    )
    _fetch_windows(tile, wbuf, start_ref, tabs, stages, packed, sems)

    offs = offs_ref[...]
    lane_k = jax.lax.broadcasted_iota(jnp.int32, (tile, k), 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (tile, wbuf), 1)
    acc = [jnp.zeros((tile, k), jnp.int32) for _ in range(n_tab)]
    for c in range(k):
        slot = _lane(offs, lane_k, c)
        for t in range(n_tab):
            acc[t] = jnp.where(
                lane_k == c, _pick(col, slot, packed[t][...]), acc[t]
            )
    for t in range(n_tab):
        outs[t][...] = acc[t]


def _wselect_kernel(tile: int, wbuf: int, k: int, iters: int,
                    with_eid: bool, scale_u: bool, start_ref, *refs):
    """Weighted select: in-kernel inverse-CDF walk over the VMEM window.

    The WarpSampler CDF walk (cuda_random.cu.hpp:143-186) against the DMA'd
    prefix-weight window instead of log2(deg) random HBM probes. Row-local
    bisection over window positions ``[off0, off0+wlen)`` is the affine
    shift of ``ops.sample._cdf_search`` by ``start`` — same probed f32
    values, same compares, same bits out. Emits the selected row-local
    offsets too (the eids-without-a-table lane is ``base + off`` in XLA).
    """
    # tables indices, cum_weights[, eid]; outputs nbr, row_off[, eid]
    n_tab = n_out = 3 if with_eid else 2
    tabs, (meta_ref, u_ref), outs, stages, packed, (sems,) = _split(
        refs, n_tab, 2, n_out, n_tab, n_tab, 1
    )
    _fetch_windows(tile, wbuf, start_ref, tabs, stages, packed, sems)

    meta = meta_ref[...]
    lane_2 = jax.lax.broadcasted_iota(jnp.int32, (tile, 2), 1)
    off0 = _lane(meta, lane_2, 0)  # window offset of the row start
    wl = _lane(meta, lane_2, 1)    # row length (== deg; fits the window)
    w = packed[1][...]
    col = jax.lax.broadcasted_iota(jnp.int32, (tile, wbuf), 1)
    # row weight total: the window copy of the row's LAST inclusive-prefix
    # entry — bitwise the oracle's staged_gather(cum_weights, base+deg-1)
    tot = _pick(col, jnp.maximum(off0 + wl - 1, 0), w)
    tot = jnp.where(wl > 0, tot, 1.0)
    u = u_ref[...]
    lane_k = jax.lax.broadcasted_iota(jnp.int32, (tile, k), 1)
    nonempty = (wl > 0).astype(jnp.int32)
    acc = [jnp.zeros((tile, k), jnp.int32) for _ in range(n_out)]
    for c in range(k):
        uc = _lane(u, lane_k, c)
        if scale_u:
            uc = uc * tot
        # row-local inverse-CDF bisection (ops.sample._cdf_search shifted
        # by start: (2*off0 + lo + hi) // 2 = off0 + (lo + hi) // 2, so
        # every probe touches the same array element the global search
        # would)
        lo = off0
        hi = off0 + (wl - 1) * nonempty
        for _ in range(iters):
            mid = (lo + hi) // 2
            # the min() is a safety clamp only: valid rows satisfy
            # off0 + wlen <= wbuf, so mid <= wbuf-1 already
            pm = _pick(col, jnp.minimum(mid * nonempty, wbuf - 1), w)
            go = pm < uc
            lo = jnp.where(go, mid + 1, lo)
            hi = jnp.where(go, hi, mid)
        # take-all override (weighted_offsets / dist serve_wnbr): deg <= k
        # rows keep CSR order — in-kernel so emitted offsets match XLA's
        row_off = jnp.where(
            wl <= k, jnp.minimum(c, jnp.maximum(wl - 1, 0)), lo - off0
        )
        slot = off0 + row_off
        picked = [_pick(col, slot, packed[0][...]), row_off]
        if with_eid:
            picked.append(_pick(col, slot, packed[2][...]))
        for o in range(n_out):
            acc[o] = jnp.where(lane_k == c, picked[o], acc[o])
    for o in range(n_out):
        outs[o][...] = acc[o]


def _window_scratch(tile: int, wbuf: int, dtypes):
    """Stage slabs, packed buffers and DMA semaphores for ``dtypes``
    tables (the order ``_select_kernel``/``_wselect_kernel`` unpack)."""
    return (
        [pltpu.VMEM((tile, 1, wbuf), dt) for dt in dtypes]
        + [pltpu.VMEM((tile, wbuf), dt) for dt in dtypes]
        + [pltpu.SemaphoreType.DMA((len(dtypes), tile))]
    )


def _aligned_windows(E: int, start, window: int):
    """Lane-aligned DMA starts covering ``[start, start + window)``.

    Returns ``(wbuf, st, delta)``: every row DMAs ``[st, st + wbuf)`` with
    ``st`` a multiple of 128, and a slot ``o`` of the caller's window is
    slot ``o + delta`` of the fetched one. ``st + wbuf`` may pass ``E`` by
    up to 127 slots; they lie inside the array's last memory tile (see
    ``_as_rows``) and no offset ever selects them.
    """
    wbuf = window + _LANE
    e_pad = -(-E // _LANE) * _LANE
    st = jnp.clip((start // _LANE) * _LANE, 0, e_pad - wbuf)
    return wbuf, st, start - st


def _as_rows(table, interpret: bool):
    """``(E,)`` table as the ``(1, E)`` view the window DMAs slice.

    On the chip the view is free and its last tile is padded to 128 lanes
    in HBM, which is what lets an aligned window run past ``E``. The
    interpreter has no tiles, so there the padding is made explicit.
    """
    if interpret:
        table = jnp.pad(table, (0, (-table.shape[0]) % _LANE))
    return table.reshape(1, -1)


@functools.partial(
    jax.jit, static_argnames=("tile", "window", "k", "interpret")
)
def _run_select(tables, start, offs, tile, window, k, interpret):
    Sp = start.shape[0]
    n_tab = len(tables)
    wbuf, st, delta = _aligned_windows(tables[0].shape[0], start, window)
    blk = pl.BlockSpec((tile, k), lambda i, *_: (i, 0),
                       memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # start addresses
        grid=(Sp // tile,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * n_tab + [blk],
        out_specs=[blk] * n_tab,
        scratch_shapes=_window_scratch(tile, wbuf, [jnp.int32] * n_tab),
    )
    outs = pl.pallas_call(
        functools.partial(_select_kernel, tile, wbuf, k, n_tab),
        out_shape=[jax.ShapeDtypeStruct((Sp, k), jnp.int32)] * n_tab,
        grid_spec=grid_spec,
        interpret=interpret,
    )(st, *[_as_rows(t, interpret) for t in tables], offs + delta[:, None])
    return tuple(outs)


@functools.partial(
    jax.jit,
    static_argnames=("tile", "window", "k", "iters", "scale_u", "interpret"),
)
def _run_wselect(indices, cum_weights, eid, start, meta, u, tile, window, k,
                 iters, scale_u, interpret):
    Sp = start.shape[0]
    with_eid = eid is not None
    wbuf, st, delta = _aligned_windows(indices.shape[0], start, window)
    meta = meta.at[:, 0].add(delta)  # row start within the fetched window
    blk = pl.BlockSpec((tile, k), lambda i, *_: (i, 0),
                       memory_space=pltpu.VMEM)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    tables = [indices, cum_weights] + ([eid] if with_eid else [])
    in_specs = [any_spec] * len(tables) + [
        pl.BlockSpec((tile, 2), lambda i, *_: (i, 0),
                     memory_space=pltpu.VMEM),
        blk,
    ]
    n_out = len(tables)  # nbr, row_off[, eid]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Sp // tile,),
        in_specs=in_specs,
        out_specs=[blk] * n_out,
        scratch_shapes=_window_scratch(
            tile, wbuf, [t.dtype for t in tables]),
    )
    outs = pl.pallas_call(
        functools.partial(
            _wselect_kernel, tile, wbuf, k, iters, with_eid, scale_u
        ),
        out_shape=[jax.ShapeDtypeStruct((Sp, k), jnp.int32)] * n_out,
        grid_spec=grid_spec,
        interpret=interpret,
    )(st, *[_as_rows(t, interpret) for t in tables], meta, u)
    return tuple(outs)


def fused_select_hop(indices, start, offs, *, eid=None,
                     window: int = DEFAULT_WINDOW, tile: int = 8,
                     interpret: bool | None = None):
    """Raw windowed gather-select: ``out[r, c] = indices[start[r] +
    offs[r, c]]`` (plus an aligned ``eid`` lane when given).

    The dist owner-side select core. Contract: ``start`` int32 ``(S,)``
    with ``start + window <= indices.shape[0]`` everywhere,
    ``indices.shape[0] >= window + 128``, ``offs`` int32 ``(S, k)`` in
    ``[0, window)``. Returns a tuple of ``(S, k)`` int32
    arrays, one per table.
    """
    interpret = resolve_interpret(interpret)
    S, k = offs.shape
    pad = (-S) % tile
    if pad:
        start = jnp.concatenate([start, jnp.zeros(pad, start.dtype)])
        offs = jnp.concatenate([offs, jnp.zeros((pad, k), offs.dtype)])
    tables = (indices,) if eid is None else (indices, eid)
    outs = _run_select(tables, start, offs, tile, window, k, interpret)
    return tuple(o[:S] for o in outs)


def fused_weighted_hop(indices, cum_weights, start, off0, wlen, u,
                       iters: int, *, eid=None, scale_u: bool = True,
                       window: int = DEFAULT_WINDOW, tile: int = 8,
                       interpret: bool | None = None):
    """Raw windowed weighted select: in-kernel inverse-CDF walk over the
    row window ``[start, start+window)`` with the row at window offset
    ``off0`` and length ``wlen`` (== deg; must fit the window).

    ``u`` is the ``(S, k)`` f32 draw block — raw uniforms scaled by the
    in-kernel row totals when ``scale_u`` (the replicated path), or
    pre-scaled by the owner-exchange totals when not (the dist path).
    Returns ``(nbr, row_off[, eids])``, each ``(S, k)`` int32; ``row_off``
    is the selected row-local offset after the take-all override —
    bitwise ``ops.sample.weighted_offsets``.
    """
    interpret = resolve_interpret(interpret)
    S, k = u.shape
    meta = jnp.stack(
        [off0.astype(jnp.int32), wlen.astype(jnp.int32)], axis=1
    )
    pad = (-S) % tile
    if pad:
        start = jnp.concatenate([start, jnp.zeros(pad, start.dtype)])
        meta = jnp.concatenate([meta, jnp.zeros((pad, 2), meta.dtype)])
        u = jnp.concatenate([u, jnp.zeros((pad, k), u.dtype)])
    outs = _run_wselect(indices, cum_weights, eid, start, meta, u, tile,
                        window, k, iters, scale_u, interpret)
    return tuple(o[:S] for o in outs)


def fused_sample_layer(topo, seeds, num_seeds, k: int, key, *,
                       weighted: bool = False, time_window=None,
                       with_eid: bool = False,
                       window: int = DEFAULT_WINDOW, tile: int = 8,
                       interpret: bool | None = None):
    """Fused Pallas per-hop sample; same contract as
    ``ops.sample.sample_layer`` (and bitwise equal wherever the draw span
    fits the window — see the module docstring's parity contract).

    Requires an HBM-resident topology with ``edge_count >= window + 128``
    (callers fall back to the XLA path otherwise); the weighted walk
    additionally requires ``topo.max_degree <= window`` so every row's
    prefix segment is fully VMEM-resident.
    """
    if k < 1:
        raise ValueError(f"fanout k must be >= 1, got {k}")
    if k > 46340:
        raise ValueError(f"fanout k must be <= 46340, got {k}")
    interpret = resolve_interpret(interpret)
    E = topo.edge_count
    if E < window + _LANE:
        raise ValueError(
            f"edge_count {E} < window {window} + {_LANE}; use the XLA path"
        )
    if E - window > _I32MAX:
        # window starts ride scalar-prefetch SMEM as int32; past 2^31 edges
        # they would wrap (the XLA path keeps indptr dtype and stays exact)
        raise ValueError(
            f"edge_count {E} exceeds the int32 windowed-DMA range; "
            "use the XLA path"
        )
    if k > window:
        raise ValueError(f"fanout k={k} must be <= window={window}")
    if weighted and time_window is not None:
        raise ValueError(
            "time_window cannot be combined with weighted=True; pick one "
            "biased draw per sampler"
        )
    if weighted:
        if topo.cum_weights is None:
            raise ValueError(
                "weighted sampling needs topo.cum_weights; build the "
                "DeviceTopology with to_device(with_weights=True)"
            )
        md = getattr(topo, "max_degree", None)
        if md is None or md > window:
            raise ValueError(
                f"the fused weighted walk needs max_degree <= window "
                f"(got {md} vs {window}); use the XLA path"
            )
    if time_window is not None and topo.edge_time is None:
        raise ValueError(
            "temporal sampling needs topo.edge_time; build the "
            "DeviceTopology with to_device(with_times=True)"
        )
    if with_eid and topo.eid is not None and E > _I32MAX:
        raise ValueError(
            f"edge_count {E} exceeds the int32 eid-lane range; use the "
            "XLA path"
        )

    S = seeds.shape[0]
    valid = (jnp.arange(S) < num_seeds) & (seeds >= 0)
    s = jnp.where(valid, seeds, 0)
    # jnp views of the topology arrays: a host-numpy array indexed by a
    # traced value raises TracerArrayConversionError, so the kernel path
    # would silently lose its jit/lowering story (the PR 15 regression
    # class, kept covered by graftaudit's fused target)
    indptr = jnp.asarray(topo.indptr)
    base = indptr[s]  # keep indptr dtype: values can exceed int32 ranges
    deg = (indptr[s + 1] - base).astype(jnp.int32)
    deg = jnp.where(valid, deg, 0)

    first = None
    if time_window is not None:
        lo_t, hi_t = time_window
        first, deg = temporal_window_counts(
            jnp.asarray(topo.edge_time), base, deg, lo_t, hi_t,
            topo.search_iters,
        )
        deg = jnp.where(valid, deg, 0)
    # global start of the row's draw span (temporal draws begin at the
    # first in-window slot — the oracle rebases offsets by `first`)
    row0 = base if first is None else base + first.astype(base.dtype)

    indices = jnp.asarray(topo.indices).astype(jnp.int32)
    eid_tab = None
    if with_eid and topo.eid is not None:
        eid_tab = jnp.asarray(topo.eid).astype(jnp.int32)

    if weighted:
        cw = jnp.asarray(topo.cum_weights)
        # key UNSPLIT over the same (S, k) block as weighted_offsets; the
        # u * tot scaling happens in-kernel against the same f32 total
        u01 = jax.random.uniform(key, (S, k), dtype=cw.dtype)
        start_wide = jnp.clip(row0, 0, E - window)
        off0 = (row0 - start_wide).astype(jnp.int32)
        res = fused_weighted_hop(
            indices, cw, start_wide.astype(jnp.int32), off0, deg, u01,
            topo.search_iters, eid=eid_tab, scale_u=True, window=window,
            tile=tile, interpret=interpret,
        )
        nbr, row_off = res[0], res[1]
        eid_sel = res[2] if eid_tab is not None else None
        i = jnp.arange(k, dtype=jnp.int32)[None, :]
        mask_sel = i < jnp.minimum(deg[:, None], k)
    else:
        # identical draw scheme/key discipline as ops.sample.sample_layer:
        # kj jitters the strata, kr rotates — deg <= window rows consume
        # exactly the oracle's bits
        kj, kr = jax.random.split(key)
        wlen = jnp.minimum(deg, window)
        offs, mask_sel = stratified_offsets(kj, wlen, k)
        offs = rotate_offsets(kr, offs, wlen, k)
        # window placement for deg > window rows only, from a fold_in key
        # so the parity lanes above never consume these bits
        max_start = jnp.maximum(deg - window, 0)
        r = jax.random.randint(
            jax.random.fold_in(key, 1), (S,), 0, max_start + 1,
            dtype=jnp.int32,
        )
        pos = row0 + r.astype(base.dtype)
        # window never leaves the array (computed in indptr dtype, cast
        # only after the clip bounds it under 2^31 — checked above); the
        # clip can shift a tail-of-array row's window left of pos, and the
        # offsets still land inside the row because offs < wlen <= deg
        start_wide = jnp.clip(pos, 0, E - window)
        off0 = (pos - start_wide).astype(jnp.int32)
        row_off = r[:, None] + offs
        res = fused_select_hop(
            indices, start_wide.astype(jnp.int32), offs + off0[:, None],
            eid=eid_tab, window=window, tile=tile, interpret=interpret,
        )
        nbr = res[0]
        eid_sel = res[1] if eid_tab is not None else None

    mask = valid[:, None] & mask_sel
    nbr = jnp.where(mask, nbr, -1).astype(jnp.int32)
    counts = jnp.where(valid, jnp.minimum(deg, k), 0)
    if not with_eid:
        return nbr, counts
    if eid_tab is None:
        epos = row0[:, None] + row_off.astype(base.dtype)
        eids = jnp.where(mask, epos, -1)
    else:
        eids = jnp.where(mask, eid_sel.astype(topo.eid.dtype), -1)
    return nbr, counts, eids
