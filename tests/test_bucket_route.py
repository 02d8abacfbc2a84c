"""``BucketRoute`` against a plain numpy routing, bit for bit.

The plan (owner buckets from one payload sort and running counts, answers
un-bucketed by one gather in lane order) has three consumers: the sharded
feature gather, the sharded-topology sampler and its hetero sibling. Only
the first runs in a benchmark cell, so the exactness of the others rests
here: every lane gets the row its owner holds for its id (plus what the
payload asks for), zero where the lane is invalid, whatever the bucket
capacity, and the overflow count is the number of lanes past their bucket.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from quiver_tpu.parallel.mesh import shard_map
from quiver_tpu.parallel.routing import BucketRoute

AXIS = "feature"
L = 24  # lanes per device
ROWS = 10  # table rows per shard
DIM = 5
K = 3  # width of the 2-d payload

PAYLOADS = (None, "int", "float2d")
CAPS = ("none", "L", "overflows", "one")
SCENARIOS = ("one_owner", "all_invalid", "other_tiers")


def _cap(kind):
    return {"none": None, "L": L, "overflows": 3, "one": 1}[kind]


def _requests(F, scenario, seed):
    """(F, L) ids, valid and owner as each device would hand them over."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, F * ROWS, (F, L)).astype(np.int32)
    if scenario == "one_owner":
        ids = (ids % ROWS + (F - 1) * ROWS).astype(np.int32)
        valid = np.ones((F, L), bool)
    elif scenario == "all_invalid":
        valid = np.zeros((F, L), bool)
    elif scenario == "other_tiers":
        # ``routed_gather``'s own law: another tier's lanes arrive as -1
        ids = np.where(rng.random((F, L)) < 0.4, -1, ids).astype(np.int32)
        valid = ids >= 0
    else:
        valid = rng.random((F, L)) < 0.8
    owner = np.where(valid, ids // ROWS, 0).astype(np.int32)
    if scenario in ("mixed", "all_invalid"):
        # invalid lanes may hold anything, owner included
        junk = rng.integers(-7, F + 7, (F, L)).astype(np.int32)
        owner = np.where(valid, owner, junk)
    return ids, valid, owner


def _payload(kind, F, seed):
    rng = np.random.default_rng(seed + 100)
    if kind == "int":
        return rng.integers(-50, 50, (F, L)).astype(np.int32)
    if kind == "float2d":
        return rng.normal(size=(F, L, K)).astype(np.float32)
    return None


def _answer(table, ids, payload):
    """What the owner of ``ids`` answers: its rows, shifted by the payload."""
    rows = table[ids]
    if payload is None:
        return rows
    if payload.ndim == 1:
        return rows + payload[:, None].astype(np.float32)
    return rows[:, :K] + payload


def _plain(table, ids, valid, payload):
    """For every lane the answer of the owner's table, zero where invalid."""
    out = _answer(table, np.where(valid, ids, 0), payload)
    return np.where(valid[:, None], out, 0).astype(np.float32)


def _routed(F, cap, kinds):
    """The jitted shard_map program: one plan, one exchange per kind."""
    mesh = Mesh(np.array(jax.devices()[:F]), (AXIS,))

    def body(local_table, ids, valid, owner, *payloads):
        my = jax.lax.axis_index(AXIS)

        def serve(req, pay=None):
            mine = (req >= 0) & (req // ROWS == my)
            rows = local_table[jnp.where(mine, req - my * ROWS, 0)]
            if pay is not None and pay.ndim == 1:
                rows = rows + pay[:, None].astype(jnp.float32)
            elif pay is not None:
                rows = rows[:, :K] + pay
            return jnp.where(mine[:, None], rows, 0)

        route = BucketRoute(ids, valid, owner, axis=AXIS, num_shards=F,
                            cap=cap)
        assert route.cap == (L if cap is None else min(cap, L))
        assert route.ov_budget == L - route.cap
        pays = iter(payloads)
        outs = tuple(
            route.exchange(serve) if kind is None
            else route.exchange(serve, payload=next(pays))
            for kind in kinds)
        return outs, route.overflow

    n_pay = sum(kind is not None for kind in kinds)
    return jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(P(AXIS),) * (4 + n_pay),
        out_specs=((P(AXIS),) * len(kinds), P()),
        check_vma=False,
    ))


def _check(F, cap_kind, kinds, scenario, seed):
    cap = _cap(cap_kind)
    table = np.random.default_rng(seed + 7).normal(
        size=(F * ROWS, DIM)).astype(np.float32)
    ids, valid, owner = _requests(F, scenario, seed)
    payloads = [_payload(kind, F, seed + i) for i, kind in enumerate(kinds)]
    sent = [p.reshape((F * L,) + p.shape[2:]) for p in payloads
            if p is not None]
    outs, overflow = _routed(F, cap, kinds)(
        table, ids.reshape(-1), valid.reshape(-1), owner.reshape(-1), *sent)
    for out, payload in zip(outs, payloads):
        out = np.asarray(out)
        for d in range(F):
            want = _plain(table, ids[d], valid[d],
                          None if payload is None else payload[d])
            got = out[d * L:(d + 1) * L]
            assert got.dtype == want.dtype
            assert np.array_equal(got, want), (d, np.argwhere(got != want)[:4])
    # lanes past their bucket's capacity, over the whole axis group
    past = 0
    if cap is not None and cap < L:
        for d in range(F):
            per_owner = np.bincount(ids[d][valid[d]] // ROWS, minlength=F)
            past += int(np.maximum(per_owner - cap, 0).sum())
    assert int(overflow) == past
    return past


@pytest.mark.parametrize("payload", PAYLOADS, ids=lambda p: f"payload-{p}")
@pytest.mark.parametrize("cap", CAPS, ids=lambda c: f"cap-{c}")
@pytest.mark.parametrize("F", (1, 2, 4), ids=lambda f: f"F{f}")
def test_exchange_is_the_plain_routing(F, cap, payload):
    """Mixed traffic, and a second exchange of another kind on the plan."""
    second = PAYLOADS[(PAYLOADS.index(payload) + 1) % len(PAYLOADS)]
    past = _check(F, cap, (payload, second), "mixed", seed=F)
    if cap in ("overflows", "one"):
        assert past > 0  # the case does run the fallback


@pytest.mark.parametrize("scenario", SCENARIOS)
@pytest.mark.parametrize("cap", CAPS, ids=lambda c: f"cap-{c}")
@pytest.mark.parametrize("F", (1, 2, 4), ids=lambda f: f"F{f}")
def test_exchange_at_the_edges_of_the_traffic(F, cap, scenario):
    """Every lane on one owner, every lane invalid, other tiers' lanes as
    -1: with no payload, then with one, on the same plan."""
    past = _check(F, cap, (None, "float2d"), scenario, seed=10 + F)
    if scenario == "all_invalid":
        assert past == 0  # invalid lanes eat no capacity, fake no overflow
    if scenario == "one_owner" and cap in ("overflows", "one"):
        assert past == F * (L - _cap(cap))


@pytest.mark.parametrize("cap", (None, 3))
def test_the_ids_are_sent_once_per_plan(cap):
    """Three exchanges on one plan: one ``all_to_all`` for the ids, one per
    payload, one per answer."""
    F = 2
    ids, valid, owner = _requests(F, "mixed", 0)
    pay = _payload("int", F, 0).reshape(-1)
    table = np.zeros((F * ROWS, DIM), np.float32)
    text = str(jax.make_jaxpr(_routed(F, cap, (None, "int", None)))(
        table, ids.reshape(-1), valid.reshape(-1), owner.reshape(-1), pay))
    assert text.count("all_to_all[") == 1 + 1 + 3
