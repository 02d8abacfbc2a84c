"""Differential + property tests for order-preserving masked unique / reindex.

Oracles, neither of them JAX: the plain-Python first-occurrence contract
below and the hash-map reference in ops/cpu_ref.py (parity with the
reference's reindex_group, quiver.cpp:39-84).
"""

import pytest
import numpy as np
import jax.numpy as jnp

from quiver_tpu.ops.reindex import (
    _spread_bits, masked_unique, reindex_layer)
from quiver_tpu.ops.cpu_ref import reindex_layer_ref


def test_masked_unique_basic():
    ids = jnp.array([5, 3, 5, 7, 3, 9])
    valid = jnp.ones(6, bool)
    uniq, n, local = masked_unique(ids, valid, size=8)
    assert n == 4
    assert list(uniq[:4]) == [5, 3, 7, 9]
    assert list(uniq[4:]) == [-1, -1, -1, -1]
    assert list(local) == [0, 1, 0, 2, 1, 3]


def test_masked_unique_with_invalid():
    ids = jnp.array([5, -1, 5, 7, -1, 5])
    valid = ids >= 0
    uniq, n, local = masked_unique(ids, valid, size=4)
    assert n == 2
    assert list(uniq[:2]) == [5, 7]
    assert list(local) == [0, -1, 0, 1, -1, 0]


def test_masked_unique_all_invalid():
    ids = jnp.full(5, -1)
    uniq, n, local = masked_unique(ids, ids >= 0, size=3)
    assert n == 0
    assert list(uniq) == [-1, -1, -1]
    assert list(local) == [-1] * 5


def test_masked_unique_overflow():
    ids = jnp.array([1, 2, 3, 4, 5])
    valid = jnp.ones(5, bool)
    uniq, n, local = masked_unique(ids, valid, size=3)
    assert n == 5  # total uniques reported even beyond capacity
    assert list(uniq) == [1, 2, 3]
    assert list(local) == [0, 1, 2, -1, -1]  # overflowed get -1


def test_masked_unique_all_invalid_and_oversize():
    """Every lane invalid, and size > T."""
    ids = jnp.asarray([5, 5, 2])
    none = jnp.zeros(3, bool)
    uniq, n, local = masked_unique(ids, none, size=6)
    assert int(n) == 0
    assert np.all(np.asarray(uniq) == -1) and np.all(np.asarray(local) == -1)
    uniq, n, local = masked_unique(ids, jnp.ones(3, bool), size=6)
    assert list(np.asarray(uniq)) == [5, 2, -1, -1, -1, -1]
    assert int(n) == 2 and list(np.asarray(local)) == [0, 0, 1]


def _masked_unique_oracle(ids, valid, size, forced):
    """Plain-Python contract of masked_unique: forced lanes take a slot
    each, every lane is labelled by its value's FIRST slot."""
    uniq, slot, local = [], {}, []
    for p, (v, ok) in enumerate(zip(ids, valid)):
        if not ok:
            local.append(-1)
            continue
        if v not in slot:
            slot[v] = len(uniq)
            uniq.append(v)
        elif p < forced:
            uniq.append(v)
        local.append(slot[v] if slot[v] < size else -1)
    return (uniq + [-1] * size)[:size], len(uniq), local


def _contract_case(t, bound, seed, p_valid=0.8, forced=None, size=None):
    """Random lanes over ``bound`` ids; the forced prefix is drawn like the
    rest, so it holds duplicates and invalid lanes as a batch may."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, bound, t)
    valid = rng.random(t) < p_valid
    forced = int(rng.integers(0, min(t, 10) + 1)) if forced is None else forced
    size = int(rng.integers(1, t + 5)) if size is None else size
    return ids, valid, size, forced


# few duplicates: four ids a lane; many: a lane in eight is a new id
_CONTRACT_CASES = {
    **{f"T{t}_few_duplicates": (t, 4 * t, 100 + t)
       for t in (1, 2, 63, 64, 65, 300, 1024)},
    **{f"T{t}_many_duplicates": (t, max(2, t // 8), 200 + t)
       for t in (1, 2, 63, 64, 65, 300, 1024)},
    "forced_duplicates": (200, 6, 1, 1.0, 32, 200),
    "overflow": (400, 300, 2, 0.9, 8, 17),
    "mostly_invalid": (512, 40, 3, 0.05),
}


@pytest.mark.parametrize("case", list(_CONTRACT_CASES))
def test_masked_unique_matches_the_plain_contract(case):
    """The one path against the plain-Python contract, ``num_forced`` and
    ``size`` included: what the differentials between three JAX strategies
    used to hold, against an oracle that is not JAX."""
    ids, valid, size, forced = _contract_case(*_CONTRACT_CASES[case])
    want = _masked_unique_oracle(ids.tolist(), valid.tolist(), size, forced)
    got = masked_unique(jnp.asarray(ids, jnp.int32), jnp.asarray(valid),
                        size=size, num_forced=forced)
    for g, w, name in zip(got, want, ("uniq", "num_unique", "local")):
        assert np.array_equal(np.asarray(g), np.asarray(w)), (case, name)
    # and the contract's own reading: a valid lane's label finds its id
    uniq, local = np.asarray(got[0]), np.asarray(got[2])
    kept = valid & (local >= 0)
    assert np.array_equal(uniq[local[kept]], ids[kept])


def _random_case(t, forced, size, bound, seed):
    """Random lanes over ``bound`` ids, a fifth invalid past the forced
    prefix (whose lanes are distinct: ops/cpu_ref.py then speaks too)."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, bound, t)
    ids[:forced] = rng.choice(bound, forced, replace=False)
    valid = rng.random(t) < 0.8
    valid[:forced] = True
    return ids, valid, size, forced


_TOP = np.iinfo(np.int32).max - 1

# (ids, valid, size, forced), or a function that makes them; the number of
# broadcast passes follows from (T, size) alone, so the shapes below force
# 1, 2 and 3 of them
_COMPACT_CASES = {
    "all_invalid": ([5, 5, 2, 9], [0, 0, 0, 0], 3, 2),
    "size_over_T": ([5, 3, 5, 7], [1, 1, 1, 1], 9, 1),
    "overflow": ([4, 1, 4, 2, 8, 1, 6, 3], [1, 1, 1, 1, 1, 1, 1, 1], 3, 2),
    "forced_duplicates": ([7, 7, 3, 3, 7, 5, -1, 3],
                          [1, 1, 1, 1, 1, 1, 0, 1], 8, 3),
    "single_lane": ([6], [1], 2, 1),
    "all_duplicates_of_lane_0": ([4, 4, 4, 4, 4, 4], [1, 1, 1, 1, 1, 1], 4, 1),
    "every_lane_one_value_some_invalid": ([9] * 7, [1, 0, 1, 1, 0, 1, 1], 7, 2),
    "ids_at_the_top": ([_TOP, 5, _TOP, _TOP - 1, 5, 0, _TOP - 1, _TOP],
                       [1, 1, 1, 1, 1, 1, 0, 1], 8, 1),
    "overflow_into_the_forced": ([3, 1, 2, 1, 3, 4], [1, 1, 1, 1, 1, 1], 2, 3),
    "T_one_below_a_power_of_two": lambda: _random_case(63, 5, 40, 30, 63),
    "T_a_power_of_two": lambda: _random_case(64, 5, 64, 30, 64),
    "T_one_above_a_power_of_two": lambda: _random_case(65, 5, 70, 30, 65),
    "one_pass": lambda: _random_case(5_000, 16, 3_000, 4_000, 1),
    "two_passes": lambda: _random_case(70_000, 64, 40_000, 90_000, 2),
    "three_passes": lambda: _random_case(
        1_600_000, 512, 1_200_000, 6_000_000, 3),
}
_PASSES = {"one_pass": 1, "two_passes": 2, "three_passes": 3}


@pytest.mark.parametrize("fn", ["masked_unique", "reindex_layer"])
@pytest.mark.parametrize("case", list(_COMPACT_CASES))
def test_scan_compaction_edge_cases(case, fn):
    """The payload sorts against the plain-Python contract and
    ops/cpu_ref.py."""
    made = _COMPACT_CASES[case]
    ids, valid, size, forced = made() if callable(made) else made
    ids = np.asarray(ids, np.int32)
    valid = np.asarray(valid, bool)
    want_uniq, want_n, want_local = _masked_unique_oracle(
        ids.tolist(), valid.tolist(), size, forced)
    if case in _PASSES:  # that many passes, and the last carries something
        bits, passes = _spread_bits(len(ids), size)
        assert passes == _PASSES[case]
        assert max(want_local) >> (bits * (passes - 1)) > 0
    lanes = np.where(valid, ids, -1)
    # the hash-map reference dedups its seeds, so it speaks for the cases
    # whose forced lanes hold no duplicate
    seed_lanes = lanes[:forced][lanes[:forced] >= 0]
    if len(set(seed_lanes.tolist())) == len(seed_lanes):
        ref_frontier, ref_col = reindex_layer_ref(
            lanes[:forced], lanes[forced:][None, :])
        assert ref_frontier.tolist()[:size] == want_uniq[:want_n][:size]
        ref_col = np.where(ref_col < size, ref_col, -1)
        assert ref_col[0].tolist() == want_local[forced:]
    if fn == "masked_unique":
        got = masked_unique(jnp.asarray(ids), jnp.asarray(valid), size,
                            num_forced=forced)
        want = (want_uniq, want_n, want_local)
    else:
        # seeds = the forced lanes (a valid prefix), one neighbour row each
        k = -(-(len(ids) - forced) // forced)
        nbr = np.full(forced * k, -1, np.int32)
        nbr[:len(ids) - forced] = lanes[forced:]
        args = (jnp.asarray(lanes[:forced]), jnp.int32(valid[:forced].sum()),
                jnp.asarray(nbr.reshape(forced, k)), size)
        got = reindex_layer(*args)
        col = np.full(forced * k, -1)
        col[:len(ids) - forced] = want_local[forced:]
        want = (want_uniq, min(want_n, size), col.reshape(forced, k),
                max(want_n - size, 0))
    for out, expect in zip(got, want):
        assert np.array_equal(np.asarray(out), np.asarray(expect)), case


@pytest.mark.parametrize("T", [1, 2, 3, 63, 64, 65, 16_384, 178_816, 852_480,
                               26_624, 332_288, 1 << 20, (1 << 20) + 1,
                               1 << 29, 1 << 30])
def test_the_packed_word_never_passes_31_bits(T):
    """(bits, passes) of the run broadcast: the largest lane index shifted
    over a full chunk stays a non-negative int32, and the chunks together
    hold every local id up to ``size`` (and no more than T - 1)."""
    for size in (0, 1, T // 2, T - 1, T, T + 1, 4 * T):
        bits, passes = _spread_bits(T, size)
        assert bits >= 1 and passes >= 1
        assert ((T - 1) << bits) | ((1 << bits) - 1) < 1 << 31
        assert min(size, T - 1) < 1 << (bits * passes)
        assert passes == 1 or min(size, T - 1) >= 1 << (bits * (passes - 1))
    with pytest.raises(ValueError, match="lanes"):
        _spread_bits((1 << 30) + 1, 8)


def test_the_cells_hops_take_one_or_two_passes():
    """The pinned shapes of the benchmark's configurations (T, cap)."""
    hops = {(16_384, 16_256): 1, (178_816, 142_080): 2, (852_480, 672_384): 2,
            (26_624, 30_208): 1, (332_288, 195_328): 2}
    assert {k: _spread_bits(*k)[1] for k in hops} == hops


def test_sampler_device_topo_reuse():
    """Samplers sharing one prebuilt DeviceTopology must behave exactly like
    samplers that upload their own copy, and incompatible reuse is rejected."""
    import pytest

    from quiver_tpu import CSRTopo, GraphSageSampler
    from quiver_tpu.core.config import SampleMode

    rng = np.random.default_rng(11)
    ei = np.stack([rng.integers(0, 300, 2500), rng.integers(0, 300, 2500)])
    topo = CSRTopo(edge_index=ei)
    dev = topo.to_device(SampleMode.HBM)
    seeds = rng.integers(0, topo.node_count, 48)

    own = GraphSageSampler(topo, [4, 3], seed=5)
    shared = GraphSageSampler(topo, [4, 3], seed=5, device_topo=dev)
    a, b = own.sample(seeds), shared.sample(seeds)
    assert np.array_equal(np.asarray(a.n_id), np.asarray(b.n_id))
    for adj_a, adj_b in zip(a.adjs, b.adjs):
        assert np.array_equal(
            np.asarray(adj_a.edge_index), np.asarray(adj_b.edge_index)
        )

    with pytest.raises(ValueError, match="eid"):
        GraphSageSampler(topo, [4], seed=0, with_eid=True, device_topo=dev)


def test_reindex_layer_matches_reference():
    rng = np.random.default_rng(1)
    S, K = 16, 5
    num_seeds = 11
    seeds = np.full(S, -1, np.int64)
    seeds[:num_seeds] = rng.choice(100, num_seeds, replace=False)
    neighbors = rng.integers(0, 100, (S, K))
    neighbors[num_seeds:] = -1
    mask = rng.random((S, K)) < 0.7
    neighbors = np.where(mask, neighbors, -1)
    neighbors[num_seeds:] = -1

    frontier, n_frontier, col, overflow = reindex_layer(
        jnp.asarray(seeds), jnp.int32(num_seeds), jnp.asarray(neighbors), 128
    )
    ref_frontier, ref_col = reindex_layer_ref(seeds[:num_seeds], neighbors)
    assert int(overflow) == 0
    assert int(n_frontier) == len(ref_frontier)
    assert np.array_equal(np.asarray(frontier[: len(ref_frontier)]), ref_frontier)
    assert np.array_equal(np.asarray(col), ref_col)
    # seeds-first contract: frontier[:num_seeds] == seeds
    assert np.array_equal(np.asarray(frontier[:num_seeds]), seeds[:num_seeds])


def test_inverse_permutation_property():
    """Reference test_reindex.cu:187-247 analogue: q[p[i]] == i across sizes."""
    from quiver_tpu.ops.reindex import inverse_permutation

    for n in (1, 5, 100, 10000):
        p = np.random.default_rng(n).permutation(n).astype(np.int32)
        q = np.asarray(inverse_permutation(jnp.asarray(p)))
        assert np.array_equal(q[p], np.arange(n))
        # inverse of inverse is the original
        assert np.array_equal(
            np.asarray(inverse_permutation(jnp.asarray(q))), p
        )


def test_complete_permutation_property():
    """Partial prefix preserved verbatim; missing values appended ascending;
    result is a permutation (reference complete_permutation semantics,
    reindex.cu.hpp:277-300)."""
    from quiver_tpu.ops.reindex import complete_permutation

    rng = np.random.default_rng(0)
    for n, m in ((5, 3), (100, 40), (10000, 1234), (64, 0), (64, 64)):
        p = rng.permutation(n)[:m].astype(np.int32)
        full = np.asarray(complete_permutation(jnp.asarray(p), n))
        assert np.array_equal(np.sort(full), np.arange(n))  # is a permutation
        assert np.array_equal(full[:m], p)  # prefix preserved
        missing = np.setdiff1d(np.arange(n), p)
        assert np.array_equal(full[m:], missing)  # ascending completion


def test_complete_permutation_rejects_overlong():
    import pytest
    from quiver_tpu.ops.reindex import complete_permutation

    with pytest.raises(ValueError, match="longer"):
        complete_permutation(jnp.arange(10, dtype=jnp.int32), 5)


def test_sampler_dedup_auto_samples():
    from quiver_tpu import CSRTopo, GraphSageSampler

    rng = np.random.default_rng(0)
    topo = CSRTopo(edge_index=rng.integers(0, 50, (2, 400)).astype(np.int64))
    s = GraphSageSampler(topo, [3], seed_capacity=16)
    out = s.sample(np.arange(16))
    assert int(out.n_count) >= 16
    with pytest.raises(ValueError, match="dedup"):
        GraphSageSampler(topo, [3], dedup="hash")
