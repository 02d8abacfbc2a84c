"""Fixed-fanout neighbor sampling, XLA-native with static shapes.

TPU-native replacement for the reference's warp-per-row reservoir kernel
(torch-quiver cuda_random.cu.hpp:7-69 ``CSRRowWiseSampleKernel``) and its
driver (quiver_sample.cu:100-187). Design divergence from the reference
(SURVEY §7.1): outputs are padded ``(S, K)`` blocks with -1 sentinels instead
of ragged flat-list + counts, so everything jits.

Sampling scheme for ``deg > k`` (the reference uses per-warp curand
reservoir sampling): **stratified offsets + uniform random rotation**.
Split ``[0, deg)`` into k contiguous integer strata, pick one jittered point
per stratum, then rotate the whole set by ``r ~ U[0, deg)`` mod deg.
Properties:
  * the k offsets are distinct (strata are disjoint; rotation is a bijection),
  * every neighbor's inclusion probability is exactly ``k/deg`` (rotation
    symmetry), matching the reservoir's first-order marginals,
  * fully vectorized — no per-row loops, no atomics, no rejection.
Higher-order joint inclusion differs from true reservoir sampling (offsets
are negatively correlated within a row, which if anything *reduces* estimator
variance for mean aggregation).

For ``deg <= k`` all neighbors are taken, like the reference's copy-all branch
(cuda_random.cu.hpp:30-39).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.topology import EDGE_BLOCK

__all__ = [
    "sample_layer",
    "stratified_offsets",
    "temporal_window_counts",
    "weighted_offsets",
    "staged_gather",
]


def stratified_offsets(key, deg, k: int):
    """k distinct offsets per row: one jittered pick per integer stratum.

    Returns (offsets (S, k) int32 in [0, max(deg,1)), sel_mask (S, k) with
    lane i valid iff i < min(deg, k)). For deg <= k the offsets are simply
    0..deg-1 (take-all, CSR order); for deg > k, stratum i covers
    [floor(deg*i/k), floor(deg*(i+1)/k)) and one uniform point is drawn per
    stratum — distinct by construction. Stratum boundaries are computed
    overflow-free in int32 via i*(deg//k) + floor(i*(deg%k)/k) (every
    intermediate <= deg), valid for k <= 46340.
    """
    S = deg.shape[0]
    i = jnp.arange(k, dtype=jnp.int32)[None, :]
    degc = deg[:, None]
    q, r_ = degc // k, degc % k
    lo = i * q + (i * r_) // k
    hi = (i + 1) * q + ((i + 1) * r_) // k
    span = jnp.maximum(hi - lo, 1)
    jitter = jax.random.randint(key, (S, k), 0, span, dtype=jnp.int32)
    off = jnp.where(degc <= k, jnp.minimum(i, jnp.maximum(degc - 1, 0)), lo + jitter)
    sel_mask = i < jnp.minimum(degc, k)
    return off, sel_mask


def rotate_offsets(key, offs, length, k: int):
    """Rotate per-row offsets by a uniform amount modulo ``length``.

    Makes the stratified picks' marginals exactly k/length (strata alone
    are non-uniform when length % k != 0). Take-all rows (length <= k)
    keep CSR order. Overflow-free: offs < length and rot < length, so one
    conditional subtract replaces the mod.
    """
    S = offs.shape[0]
    lenc = length[:, None]
    rot = jax.random.randint(key, (S, 1), 0, jnp.maximum(lenc, 1), dtype=jnp.int32)
    shifted = offs + rot
    rotated = jnp.where(shifted >= lenc, shifted - lenc, shifted)
    return jnp.where(lenc <= k, offs, rotated)


def _cdf_search(cum_weights, u, base, deg, iters: int):
    """Vectorized per-row inverse-CDF binary search.

    For each lane (s, j): smallest CSR slot m in row [base_s, base_s+deg_s)
    with cum_weights[m] >= u[s, j]. ``iters`` >= ceil(log2(max_degree+1))
    guarantees convergence. Returns row-local offsets (S, k) int32.
    """
    S, k = u.shape
    degc = deg[:, None].astype(base.dtype)
    basec = base[:, None]
    # arithmetic masking instead of jnp.where-with-literals: under
    # compute_on("device_host") every select_n operand must share the host
    # memory space, and broadcast scalar literals land in device space
    nonempty = degc > 0
    lo = jnp.broadcast_to(basec, (S, k))
    hi = lo + (degc - 1) * nonempty
    for _ in range(iters):
        mid = (lo + hi) // 2
        pm = cum_weights[mid * nonempty]
        go_right = pm < u
        lo = jnp.where(go_right, mid + 1, lo)
        hi = jnp.where(go_right, hi, mid)
    return (lo - basec).astype(jnp.int32)


def _cdf_search_host(cum_weights, u, base, deg, iters: int):
    """_cdf_search staged as host compute (HOST mode keeps the prefix array
    in pinned host memory; only the small u/base/deg blocks transit — the
    same memory-space dance as _staged_gather)."""
    from jax.experimental.compute_on import compute_on
    from jax.memory import Space

    u_h = jax.device_put(u, Space.Host)
    base_h = jax.device_put(base, Space.Host)
    deg_h = jax.device_put(deg, Space.Host)

    @compute_on("device_host")
    def search(cw, uu, bb, dd):
        return _cdf_search(cw, uu, bb, dd, iters)

    return jax.device_put(search(cum_weights, u_h, base_h, deg_h), Space.Device)




def weighted_offsets(key, cum_weights, base, deg, k: int, iters: int,
                     host: bool = False):
    """k weight-proportional draws per row via inverse-CDF binary search.

    The TPU rebuild of the reference's ``weight_sample``
    (cuda_random.cu.hpp:143-186): each of the k slots draws independently
    (with replacement, matching the reference's semantics) from the row's
    categorical distribution over the row-local inclusive prefix
    ``cum_weights``. Rows with ``deg <= k`` take all neighbors in CSR order
    instead — the reference's ``safe_sample`` copy-all branch
    (cuda_random.cu.hpp:196-205). With ``host=True`` the search runs as host
    compute against the host-resident prefix array.

    Returns (offsets (S, k) int32 row-local, sel_mask (S, k)).
    """
    S = deg.shape[0]
    degc = deg[:, None]
    end = jnp.maximum(base + deg.astype(base.dtype) - 1, 0)
    tot = staged_gather(cum_weights, end, host)
    tot = jnp.where(deg > 0, tot, 1.0)
    u = jax.random.uniform(key, (S, k), dtype=cum_weights.dtype) * tot[:, None]
    if host:
        off = _cdf_search_host_call(cum_weights, u, base, deg, iters)
    else:
        off = _cdf_search(cum_weights, u, base, deg, iters)
    i = jnp.arange(k, dtype=jnp.int32)[None, :]
    off = jnp.where(degc <= k, jnp.minimum(i, jnp.maximum(degc - 1, 0)), off)
    sel_mask = i < jnp.minimum(degc, k)
    return off, sel_mask


def temporal_window_counts(edge_time, base, deg, lo_t, hi_t, iters: int):
    """Per-row slot range of edges whose timestamp falls in ``[lo_t, hi_t]``.

    Requires rows time-sorted (``CSRTopo.set_edge_time``). Two vectorized
    binary searches over each row's ``deg + 1`` candidate split points:
    ``first`` counts edges with ``t < lo_t``; the window's masked degree
    ``deg_t`` counts edges with ``lo_t <= t <= hi_t``, so the in-window
    edges occupy row-local slots ``[first, first + deg_t)``. ``iters`` >=
    ceil(log2(max_degree + 1)) guarantees convergence (converged lanes are
    frozen arithmetically, so extra iterations are no-ops). Returns
    ``(first, deg_t)``, both (S,) int32.
    """
    degc = deg.astype(base.dtype)
    zero = jnp.zeros_like(degc)
    probe_cap = jnp.maximum(degc - 1, 0)

    def count(cmp):
        lo = zero
        hi = degc
        for _ in range(iters):
            active = lo < hi
            mid = (lo + hi) // 2
            # clamp the probe into the row; inactive/empty lanes read a
            # garbage-but-in-range slot and are masked out of the update
            tv = edge_time[base + jnp.minimum(mid, probe_cap)]
            go = cmp(tv) & active
            lo = jnp.where(go, mid + 1, lo)
            hi = jnp.where(go | ~active, hi, mid)
        return lo

    first = count(lambda t: t < lo_t)
    below_hi = count(lambda t: t <= hi_t)
    return first.astype(jnp.int32), (below_hi - first).astype(jnp.int32)


def sample_layer(topo, seeds, num_seeds, k: int, key, with_eid: bool = False,
                 weighted: bool = False, time_window=None):
    """Sample up to ``k`` neighbors for each valid seed.

    The neighbour ids at the drawn positions are read as 512-byte blocks
    of the edge array (``_gather_indices``); the draw itself, and so every
    returned value, does not depend on how they are read.

    Args:
      topo: DeviceTopology (indptr (N+1,), indices (E,), or (E',) padded
        to whole 128-word blocks by ``place_csr_arrays``).
      seeds: (S,) node ids, -1 padded; valid entries occupy a prefix.
      num_seeds: scalar count of valid seeds.
      k: static fanout. Must be >= 1 (use max_degree for full neighborhood,
         the reference's fanout -1, sage_sampler.py:67).
      key: PRNG key.
      with_eid: also return global CSR edge positions per sample.
      time_window: optional ``(lo, hi)`` scalar timestamps; only edges with
        ``lo <= t <= hi`` are drawn from (masked degrees — expired edges
        never appear). Requires a time-sorted topology placed with
        ``to_device(with_times=True)``; mutually exclusive with weighted.

    Returns:
      neighbors: (S, K) sampled node ids, -1 where invalid.
      counts: (S,) number of valid samples per row (min(deg, k), 0 for
        invalid seeds) — the padded analogue of the reference's counts output.
      eids: (S, K) CSR edge slots or -1, only if ``with_eid``.
      relations: (S, K) int8 edge relations or -1, last, only over a
        topology placed with ``to_device(with_relations=True)``: its edge
        words carry the relation above ``topo.relation_shift``, read from
        the word each lane fetches, no second gather.
    """
    if k < 1:
        raise ValueError(f"fanout k must be >= 1, got {k}")
    if k > 46340:
        # the int32 stratum arithmetic below needs i*r_ <= k^2 < 2^31
        raise ValueError(f"fanout k must be <= 46340, got {k}")
    S = seeds.shape[0]
    valid = (jnp.arange(S) < num_seeds) & (seeds >= 0)
    s = jnp.where(valid, seeds, 0)

    base = topo.indptr[s]
    deg = (topo.indptr[s + 1] - base).astype(jnp.int32)
    deg = jnp.where(valid, deg, 0)

    first = None
    if time_window is not None:
        if weighted:
            raise ValueError(
                "time_window cannot be combined with weighted=True; pick "
                "one biased draw per sampler"
            )
        if topo.edge_time is None:
            raise ValueError(
                "temporal sampling needs topo.edge_time; build the "
                "DeviceTopology with to_device(with_times=True)"
            )
        lo_t, hi_t = time_window
        first, deg = temporal_window_counts(
            topo.edge_time, base, deg, lo_t, hi_t, topo.search_iters
        )
        deg = jnp.where(valid, deg, 0)

    if weighted:
        if topo.cum_weights is None:
            raise ValueError(
                "weighted sampling needs topo.cum_weights; build the "
                "DeviceTopology with to_device(with_weights=True)"
            )
        off, mask_sel = weighted_offsets(
            key, topo.cum_weights, base, deg, k, topo.search_iters,
            host=topo.host_indices,
        )
    else:
        kj, kr = jax.random.split(key)
        off_nr, mask_sel = stratified_offsets(kj, deg, k)
        off = rotate_offsets(kr, off_nr, deg, k)
    if first is not None:
        # window offsets are row-local within [first, first + deg_t);
        # rebase them onto the full row before the CSR gather
        off = first[:, None] + off
    mask = valid[:, None] & mask_sel

    epos = base[:, None] + off.astype(base.dtype)
    nbr = _gather_indices(topo, epos, mask)
    relations = None
    if getattr(topo, "num_relations", 0):
        shift = topo.relation_shift
        relations = jnp.where(mask, nbr >> shift, -1).astype(jnp.int8)
        nbr = nbr & ((1 << shift) - 1)
    nbr = jnp.where(mask, nbr, -1).astype(jnp.int32)
    counts = jnp.where(valid, jnp.minimum(deg, k), 0)

    out = (nbr, counts)
    if with_eid:
        eids = jnp.where(mask, epos, -1)
        if topo.eid is not None:
            safe_epos = jnp.where(mask, epos, 0)
            eids = jnp.where(
                mask, staged_gather(topo.eid, safe_epos, topo.host_indices), -1
            )
        out += (eids,)
    if relations is not None:
        out += (relations,)
    return out


_BLOCK_SHIFT = EDGE_BLOCK.bit_length() - 1  # a block is 128 words, 512 bytes


def _gather_indices(topo, epos, mask):
    """``topo.indices[epos]`` where ``mask``; masked lanes return anything.

    An edge array held as whole 128-word blocks (``place_csr_arrays`` pads
    it so in HBM mode; decided from the shape, at trace time) is read
    through its ``(E'/128, 128)`` view, a bitcast: one row gather fetches
    each lane's block, then the word inside it is selected by comparing the
    minor axis with the lane's column and summing (one term is non-zero, so
    the sum is exact). A v5e moves a 512-byte tile row in a third of the
    time it takes to fetch one word of a 1-D array, and a second 4-byte
    gather (``take_along_axis``) would give that back. Three things about
    the row gather are the chip's, each measured (PERF.md section 6,
    PR 38):

    * lanes are gathered flat and fanout-major, so that the blocks are the
      gather's own 2-D output: viewed ``(S, k, 128)`` they would be copied
      into tiles with ``k`` padded to 8;
    * a masked lane reads a block of its own (its lane number modulo the
      block count), not block 0: rows that all name one block are served
      one after the other, 13 ns each against 4;
    * the lanes are padded to 512 past a multiple of 1,024: XLA's TPU row
      gather keeps 256 rows in flight when it pads its index vector to
      whole 1,024-word tiles itself and 128 when the vector already fills
      them, 4 ns a row against 10 (``tests/test_dense_aggregation.py``
      holds the compiled gather to that).

    A host-resident array (HOST mode) keeps ``staged_gather`` and a ragged
    hand-built one the plain gather, a word a lane.
    """
    indices = topo.indices
    if getattr(topo, "host_indices", False):
        return staged_gather(indices, jnp.where(mask, epos, 0), True)
    if indices.shape[0] == 0 or indices.shape[0] % EDGE_BLOCK:
        return indices[jnp.where(mask, epos, 0)]
    blocks = indices.reshape(-1, EDGE_BLOCK)
    S, k = epos.shape
    lanes = S * k
    pad = (512 - lanes) % 1024
    pos = jnp.pad(epos.T.reshape(-1), (0, pad))
    live = jnp.pad(mask.T.reshape(-1), (0, pad))
    own = jnp.arange(lanes + pad, dtype=pos.dtype) % blocks.shape[0]
    # (lanes + pad, 128)
    rows = blocks[jnp.where(live, pos >> _BLOCK_SHIFT, own)]
    col = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 1)
    hit = col == (pos & (EDGE_BLOCK - 1)).astype(jnp.int32)[:, None]
    word = jnp.sum(jnp.where(hit, rows, 0), axis=-1)
    return word[:lanes].reshape(k, S).T


def staged_host_call(fn, static_argnums=()):
    """Wrap a host-compute ``fn`` with the traced-vs-eager dispatch.

    Traced calls run ``fn`` inline (its compute_on block composes into the
    enclosing jit). Eager calls go through a cached jit wrapper, because
    eager compute_on leaves a host memory space in the result aval that
    later eager ops reject — the jit boundary re-anchors the result in
    device space.
    """
    static = set(static_argnums)
    jitted = jax.jit(fn, static_argnums=tuple(static_argnums))

    def call(*args):
        dyn = [a for i, a in enumerate(args) if i not in static]
        if any(
            isinstance(x, jax.core.Tracer)
            for x in jax.tree_util.tree_leaves(dyn)
        ):
            return fn(*args)
        return jitted(*args)

    return call


def staged_gather(table, idx, host: bool):
    """Gather rows of ``table``, staging through host memory when ``host``.

    The reference's UVA mode lets the sampling kernel dereference pinned host
    memory directly over PCIe (quiver_sample.cu:400-408). TPUs cannot do
    that, so the HOST-mode equivalent is a *staged* gather: the (small) index
    block hops to host memory, the gather runs as host compute against the
    host-resident table, and only the result returns to HBM — the large
    table itself never transits. Transfers are memory-SPACE moves
    (``jax.memory.Space``), sharding-preserving, so the same code composes
    at the jit level, under vmap/scan, and inside ``shard_map`` bodies (the
    fused beyond-HBM trainer) — a concrete-sharding ``device_put`` would be
    ill-formed in per-device SPMD code.
    """
    if not host:
        return table[idx]
    return _staged_gather_call(table, idx)


def _staged_gather(table, idx):
    from jax.experimental.compute_on import compute_on
    from jax.memory import Space

    idx_h = jax.device_put(idx, Space.Host)

    @compute_on("device_host")
    def host_gather(t, i):
        return t[i]

    out_h = host_gather(table, idx_h)
    return jax.device_put(out_h, Space.Device)


# module-level wrappers so repeated eager calls hit the jit dispatch fastpath
# (iters is hashable, so it rides as a static arg)
_staged_gather_call = staged_host_call(_staged_gather)
_cdf_search_host_call = staged_host_call(_cdf_search_host, static_argnums=(4,))
