"""Sample-validity oracle tests for the XLA neighbor sampler.

The oracle (reference test_quiver_cpu.cpp:9-75 pattern): every sampled
neighbor must be a member of the seed's adjacency list, counts must equal
min(deg, k), and rows with deg > k must have no duplicates. Plus a
distributional check on inclusion frequency (the stratified+rotation scheme
guarantees first-order inclusion probability k/deg).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from quiver_tpu import CSRTopo, SampleMode
from quiver_tpu.ops.sample import sample_layer
from quiver_tpu.utils.graphgen import generate_pareto_graph


def _simple_graph(n, deg):
    """Node i's neighbors are exactly {(j+1)*n + i | j in range(deg)} % V.

    Deterministic membership check (reference test_quiver_cpu.cpp simple_graph).
    """
    row = np.repeat(np.arange(n), deg)
    col = (np.arange(deg)[None, :] + 1) * n + np.arange(n)[:, None]
    v = n * (deg + 1)
    return np.stack([row, col.reshape(-1) % v]), v


@pytest.mark.parametrize("n,deg,k", [(32, 3, 5), (32, 8, 8), (64, 12, 4)])
def test_sample_validity(n, deg, k):
    ei, v = _simple_graph(n, deg)
    # pad indptr out to v+1 nodes so every id is a valid seed
    topo = CSRTopo(edge_index=ei)
    indptr = np.concatenate([topo.indptr, np.full(v - topo.node_count, topo.edge_count)])
    topo = CSRTopo(indptr=indptr, indices=topo.indices)
    dev = topo.to_device()

    S = 48
    seeds = np.full(S, -1, np.int32)
    num = 40
    seeds[:num] = np.random.default_rng(0).integers(0, n, num)
    nbr, counts = sample_layer(dev, jnp.asarray(seeds), jnp.int32(num), k, jax.random.PRNGKey(0))
    nbr, counts = np.asarray(nbr), np.asarray(counts)

    adj = {i: set(((np.arange(deg) + 1) * n + i) % v) for i in range(n)}
    for r in range(S):
        if r >= num:
            assert counts[r] == 0 and np.all(nbr[r] == -1)
            continue
        s = seeds[r]
        expect = min(deg, k)
        assert counts[r] == expect
        got = nbr[r][nbr[r] >= 0]
        assert len(got) == expect
        assert set(got.tolist()) <= adj[s]
        if deg > k:
            assert len(set(got.tolist())) == k  # distinct when subsampling


def test_sample_take_all_exact():
    # deg <= k rows must return the full neighborhood (intra-row order is
    # unspecified — the native CSR scatter is unordered across threads)
    ei, v = _simple_graph(16, 4)
    topo = CSRTopo(edge_index=ei).to_device()
    seeds = jnp.arange(10, dtype=jnp.int32)
    nbr, counts = sample_layer(topo, seeds, jnp.int32(10), 6, jax.random.PRNGKey(1))
    nbr = np.asarray(nbr)
    for r in range(10):
        expect = sorted((((np.arange(4) + 1) * 16 + r) % v).tolist())
        assert sorted(nbr[r, :4].tolist()) == expect
        assert np.all(nbr[r, 4:] == -1)


def test_sample_zero_degree_and_padding():
    indptr = np.array([0, 0, 2, 2])
    indices = np.array([0, 2])
    topo = CSRTopo(indptr=indptr, indices=indices).to_device()
    seeds = jnp.array([0, 1, 2, -1], dtype=jnp.int32)
    nbr, counts = sample_layer(topo, seeds, jnp.int32(3), 3, jax.random.PRNGKey(2))
    assert list(np.asarray(counts)) == [0, 2, 0, 0]
    assert np.all(np.asarray(nbr)[0] == -1)
    assert np.all(np.asarray(nbr)[3] == -1)


def test_inclusion_probability_uniform():
    # one node with degree 20, fanout 5: each neighbor should appear with
    # frequency ~ k/deg = 0.25 over many trials
    deg, k, trials = 20, 5, 400
    # node 0 has `deg` neighbors (ids 100..119); nodes 1..119 are isolated
    indptr = np.concatenate([[0], np.full(120, deg)])
    indices = np.arange(100, 100 + deg)
    topo = CSRTopo(indptr=indptr, indices=indices).to_device()
    seeds = jnp.zeros(1, jnp.int32)
    counts = np.zeros(deg)
    for t in range(trials):
        nbr, _ = sample_layer(topo, seeds, jnp.int32(1), k, jax.random.PRNGKey(t))
        got = np.asarray(nbr)[0]
        got = got[got >= 0] - 100
        assert len(set(got.tolist())) == k
        counts[got] += 1
    freq = counts / trials
    # expected 0.25; binomial std ≈ sqrt(.25*.75/400) ≈ 0.0217 → 5 sigma
    assert np.all(np.abs(freq - k / deg) < 0.11), freq


def test_sample_with_eid():
    ei, v = _simple_graph(8, 3)
    topo = CSRTopo(edge_index=ei)
    dev = topo.to_device(with_eid=True)
    seeds = jnp.arange(5, dtype=jnp.int32)
    nbr, counts, eids = sample_layer(dev, seeds, jnp.int32(5), 2, jax.random.PRNGKey(0), with_eid=True)
    nbr, eids = np.asarray(nbr), np.asarray(eids)
    # each returned eid must point at the COO edge (seed -> neighbor)
    for r in range(5):
        for c in range(2):
            if eids[r, c] >= 0:
                assert ei[0, eids[r, c]] == r
                assert ei[1, eids[r, c]] == nbr[r, c]


def test_host_mode_matches_hbm_mode():
    ei = generate_pareto_graph(500, 6.0, seed=3)
    topo = CSRTopo(edge_index=ei)
    hbm = topo.to_device(SampleMode.HBM)
    host = topo.to_device(SampleMode.HOST)
    seeds = jnp.asarray(np.random.default_rng(0).integers(0, 500, 64), dtype=jnp.int32)
    key = jax.random.PRNGKey(9)
    a, ca = sample_layer(hbm, seeds, jnp.int32(64), 4, key)
    b, cb = sample_layer(host, seeds, jnp.int32(64), 4, key)
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(ca), np.asarray(cb))


def test_duplicate_seeds_exceeding_node_count_keep_capacity():
    # regression: caps were clamped to node_count, dropping forced duplicate
    # seed lanes when batch > number of nodes
    from quiver_tpu import GraphSageSampler

    ei = np.stack([np.arange(10), (np.arange(10) + 1) % 10])
    topo = CSRTopo(edge_index=ei)
    sampler = GraphSageSampler(topo, [2], seed_capacity=64)
    seeds = np.zeros(50, dtype=np.int64)
    out = sampler.sample(seeds)
    nid = np.asarray(out.n_id)
    assert nid.shape[0] >= 50
    assert (nid[:50] == 0).all()
    assert int(out.overflow) == 0


# -- the edge array read as 128-word blocks (PR 38) --------------------------
# A placement pads `indices` to whole blocks and `sample_layer` reads a lane's
# id as a row of the (E'/128, 128) view plus a select inside the row. The draw
# is untouched, so against a hand-built topology over the ragged array (which
# keeps the plain `indices[p]`) every output is equal bit for bit.

_HUB = 128 * 25 + 77  # more blocks than any fanout here has lanes


def _blocky_graph():
    """Rows of degree 0 and 1, one that straddles a block boundary, a hub
    of `_HUB` edges, a tail of small rows; E is not a multiple of 128 and
    the last row's edges lie in the padded last block."""
    rng = np.random.default_rng(38)
    deg = np.concatenate([[0, 1, 120, 130, _HUB, 0],
                          rng.integers(0, 40, 200), [3]]).astype(np.int64)
    indptr = np.zeros(deg.shape[0] + 1, np.int64)
    np.cumsum(deg, out=indptr[1:])
    E = int(indptr[-1])
    assert E % 128 not in (0, 1, 2), E
    assert indptr[3] // 128 != (indptr[4] - 1) // 128  # row 3 straddles
    indices = rng.integers(0, deg.shape[0], E).astype(np.int32)
    return CSRTopo(indptr=indptr, indices=indices)


def _ragged(topo, with_eid=False):
    """Today's path: a hand-built topology over the unpadded array."""
    from quiver_tpu.core.topology import DeviceTopology

    return DeviceTopology(
        jnp.asarray(topo.indptr), jnp.asarray(topo.indices),
        eid=jnp.asarray(topo.eid) if with_eid and topo.eid is not None
        else None)


def _seed_block(topo, S=64, num=50):
    seeds = np.full(S, -1, np.int32)
    # the special rows first, then random ones, a padded -1 inside the
    # valid prefix, and `num < S`
    head = [0, 1, 2, 3, 4, 5, topo.node_count - 1, 4]
    seeds[:num] = np.random.default_rng(1).integers(0, topo.node_count, num)
    seeds[: len(head)] = head
    seeds[20] = -1
    return jnp.asarray(seeds), jnp.int32(num)


def _run(mode, fn, *args):
    if mode == "eager":
        return fn(*args)
    if mode == "jit":
        return jax.jit(fn)(*args)
    from jax.sharding import PartitionSpec as P

    from quiver_tpu.parallel.mesh import make_mesh, shard_map

    mesh = make_mesh(n_devices=2, data=2, feature=1)
    return jax.jit(shard_map(
        fn, mesh=mesh, in_specs=tuple(P() for _ in args), out_specs=P(),
        check_vma=False))(*args)


def test_placement_pads_the_edge_array_to_whole_blocks():
    topo = _blocky_graph()
    dev = topo.to_device()
    assert dev.indices.ndim == 1 and dev.indices.shape[0] % 128 == 0
    assert 0 < dev.indices.shape[0] - topo.edge_count < 128
    assert dev.edge_count == topo.edge_count
    got = np.asarray(dev.indices)
    np.testing.assert_array_equal(got[: topo.edge_count], topo.indices)
    assert not got[topo.edge_count:].any()


@pytest.mark.parametrize("words,dtype", [
    (5003, np.int32), (5003, np.int64), (1, np.int32), (127, np.int64),
    (1280, np.int32)])
def test_the_padded_host_copy_is_whole_blocks_in_the_device_dtype(
        words, dtype):
    """The host-side padding behind a placement: zeros after the edges,
    the dtype the device will hold (no second conversion copy), and the
    array itself, untouched, where it needs no padding."""
    from quiver_tpu.core.topology import _whole_blocks

    indices = np.random.default_rng(words).integers(
        0, 1 << 30, words).astype(dtype)
    got = _whole_blocks(indices)
    if words % 128 == 0:
        assert got is indices
        return
    assert got.shape[0] == -(-words // 128) * 128
    assert got.dtype == jnp.asarray(indices[:1]).dtype
    np.testing.assert_array_equal(got[:words], indices)
    assert not got[words:].any()
    np.testing.assert_array_equal(np.asarray(jnp.asarray(got)), got)


@pytest.mark.parametrize("mode", ["eager", "jit", "shard_map"])
@pytest.mark.parametrize("k", [5, 10, 15, 25])
def test_block_read_equals_plain_gather_bitwise(k, mode):
    topo = _blocky_graph()
    seeds, num = _seed_block(topo)
    key = jax.random.PRNGKey(k)

    def draw(dev, seeds, num, key):
        return sample_layer(dev, seeds, num, k, key)

    nbr, cnt = _run(mode, draw, topo.to_device(), seeds, num, key)
    want_nbr, want_cnt = draw(_ragged(topo), seeds, num, key)
    np.testing.assert_array_equal(np.asarray(nbr), np.asarray(want_nbr))
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(want_cnt))
    nbr = np.asarray(nbr)
    # the hub takes k distinct positions over more than k blocks; rows of
    # degree 0 and 1, the padded seed and the seeds past `num` as ever
    assert (nbr[4] >= 0).all() and (nbr[0] == -1).all()
    assert nbr[1, 0] == topo.indices[0] and (nbr[1, 1:] == -1).all()
    assert (nbr[20] == -1).all() and (nbr[int(num):] == -1).all()
    # the last row's three edges sit in the padded last block
    np.testing.assert_array_equal(nbr[6, :3], topo.indices[-3:])


@pytest.mark.parametrize("mode", ["eager", "jit"])
def test_block_read_with_eid_equals_plain_gather_bitwise(mode):
    rng = np.random.default_rng(2)
    ei = generate_pareto_graph(300, 9.0, seed=4)
    ei = ei[:, rng.permutation(ei.shape[1])]  # eid is a real permutation
    topo = CSRTopo(edge_index=ei)
    assert topo.edge_count % 128
    seeds, num = _seed_block(topo)
    key = jax.random.PRNGKey(9)

    def draw(dev, seeds, num, key):
        return sample_layer(dev, seeds, num, 10, key, with_eid=True)

    got = _run(mode, draw, topo.to_device(with_eid=True), seeds, num, key)
    want = draw(_ragged(topo, with_eid=True), seeds, num, key)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    nbr, _, eids = (np.asarray(x) for x in got)
    ok = nbr >= 0
    np.testing.assert_array_equal(ei[1][eids[ok]], nbr[ok])


@pytest.mark.parametrize("edges", [127, 128, 129, 256, 1000])
def test_gather_indices_reads_every_position(edges):
    """`_gather_indices` against numpy's `indices[p]` at every position of
    the array, masked lanes anywhere (out of range too): whole blocks or a
    ragged hand-built array alike."""
    from quiver_tpu.core.topology import DeviceTopology, place_csr_arrays
    from quiver_tpu.ops.sample import _gather_indices

    rng = np.random.default_rng(edges)
    indices = rng.integers(0, 1 << 30, edges).astype(np.int32)
    indptr = np.asarray([0, edges], np.int64)
    placed = place_csr_arrays(indptr, indices, None, None, edges, "HBM")
    ragged = DeviceTopology(jnp.asarray(indptr), jnp.asarray(indices))
    assert placed.indices.shape[0] == -(-edges // 128) * 128
    assert ragged.indices.shape[0] == edges and ragged.edge_count == edges
    pos = np.concatenate([np.arange(edges), rng.integers(0, edges, 24)])
    pos = pos[: pos.shape[0] // 8 * 8].reshape(-1, 8)
    mask = rng.random(pos.shape) < 0.8
    wild = np.where(mask, pos, rng.integers(-5, edges + 500, pos.shape))
    for dev in (placed, ragged):
        got = jax.jit(_gather_indices)(dev, jnp.asarray(wild, jnp.int32),
                                       jnp.asarray(mask))
        np.testing.assert_array_equal(np.asarray(got)[mask],
                                      indices[pos][mask])


def test_block_read_takes_rows_not_words():
    """The mechanism, in the jaxpr: on a padded placement the only gather
    from the edge array takes (1, 128) slices of its 2-D view; on a ragged
    array it is the one-word gather it was."""
    topo = _blocky_graph()
    seeds, num = _seed_block(topo)

    def gathers(dev):
        jaxpr = jax.make_jaxpr(
            lambda d: sample_layer(d, seeds, num, 5, jax.random.PRNGKey(0))
        )(dev)
        return [tuple(e.params["slice_sizes"]) for e in jaxpr.jaxpr.eqns
                if e.primitive.name == "gather"
                and e.invars[0].aval.size >= topo.edge_count]

    assert gathers(topo.to_device()) == [(1, 128)]
    assert gathers(_ragged(topo)) == [(1,)]
