"""Serving fleet scale-out over the persisted AOT-executable cache
(ISSUE 17): program fingerprinting, the warm-replica zero-compile +
bitwise-parity contract, corrupt-entry fail-safe, SLO-class admission
control (bronze sheds before gold) with per-class counters in the obs
registry, least-depth fleet routing, and the refresh-after-commit cache
re-check (one replica pays the mutation epoch's compiles, the next
replica deserializes them)."""

import logging

import numpy as np
import pytest

from quiver_tpu import (
    DeltaBatch,
    InferenceServer,
    ServeQueueFull,
    ServingFleet,
    StreamingGraph,
    VersionMismatchError,
)
from quiver_tpu.obs.registry import (
    SERVE_AOT_LOADS,
    SERVE_CLASS_MISSES,
    SERVE_SHED,
)
from quiver_tpu.serving import DeadlineBatcher
from quiver_tpu.serving.aot import program_fingerprint
from test_serving import FakeClock, _graph, _stack


@pytest.fixture(scope="module", autouse=True)
def _fresh_compiles_only():
    """Every executable this module publishes is compiled here, by this
    module: JAX's persistent compilation cache is off while it runs.

    XLA:CPU cannot re-serialize an executable that the persistent cache
    served (``serialize`` drops its compiled functions; the entry loads,
    then fails at the first run with ``NOT_FOUND: ... Function
    <name>_fusion not found``). So if an earlier test of the same worker
    left that cache on, the republish in ``test_corrupt_aot_entry_recovers``
    published such an entry and the tests after it failed: whether this
    module passed depended on what the process had done before.
    """
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def warm_stack(tmp_path_factory):
    """One shared graph/model stack + one disk AOT cache populated by a
    first replica (4 programs: sample+forward at buckets 1 and 2)."""
    cache_dir = str(tmp_path_factory.mktemp("aot") / "executables")
    topo = _graph(n=160, e=900, seed=2)
    _x, feat, sampler, model, params = _stack(
        topo, feature_dim=8, hidden=8, classes=3, sizes=(3, 2), seed=2)
    server = InferenceServer(sampler, model, params, feat, max_batch=2,
                             clock=FakeClock(), seed=7, aot_cache=cache_dir)
    first = server.warm_from_cache()

    def replica(**kw):
        kw.setdefault("max_batch", 2)
        kw.setdefault("clock", FakeClock())
        kw.setdefault("seed", 7)
        kw.setdefault("aot_cache", cache_dir)
        return InferenceServer(sampler, model, params, feat, **kw)

    return {"server": server, "first": first, "cache_dir": cache_dir,
            "replica": replica, "stack": (sampler, model, params, feat)}


# -- program fingerprint -----------------------------------------------------


def test_fingerprint_keying(warm_stack):
    """Same program -> same fingerprint; any keyed component moving
    (bucket, target, committed CSR version) -> a different one. The hash
    is over canonical JSON, so dict insertion order is irrelevant."""
    lad = warm_stack["server"]._ladder
    assert lad.fingerprint("sample", 2) == lad.fingerprint("sample", 2)
    assert lad.fingerprint("sample", 1) != lad.fingerprint("sample", 2)
    assert lad.fingerprint("forward", 2) != lad.fingerprint("sample", 2)
    comp = lad.fingerprint_components("sample", 2)
    bumped = dict(comp, csr_version=comp["csr_version"] + 1)
    assert program_fingerprint(bumped) != program_fingerprint(comp)
    shuffled = dict(reversed(list(comp.items())))
    assert program_fingerprint(shuffled) == program_fingerprint(comp)


# -- compile-free cold start -------------------------------------------------


def test_warm_replica_zero_compiles_bitwise(warm_stack):
    """The acceptance contract: a second replica warming from the cache
    performs ZERO compiles and answers every (node, seq) bitwise
    identically to the replica that compiled."""
    a = warm_stack["server"]
    assert warm_stack["first"]["compiled"] > 0  # cache-cold first replica
    b = warm_stack["replica"]()
    ws = b.warm_from_cache()
    assert ws == {"loaded": warm_stack["first"]["compiled"], "compiled": 0}
    assert b.recompiles == 0
    assert b.aot_loads == ws["loaded"]
    assert int(b.metrics.value(SERVE_AOT_LOADS)) == ws["loaded"]

    nodes = [3, 11, 19]  # batches of 2 + a forced tail of 1
    out_a = a.serve(nodes)
    out_b = b.serve(nodes)
    assert b.recompiles == 0  # steady state stays compile-free
    for ra, rb in zip(out_a, out_b):
        assert (ra.node, ra.seq) == (rb.node, rb.seq)
        np.testing.assert_array_equal(ra.result, rb.result)
        np.testing.assert_array_equal(rb.result, b.oracle(rb.node, rb.seq))


def test_corrupt_aot_entry_recovers(warm_stack, caplog):
    """A truncated cache entry degrades to compile-and-republish with a
    single WARNING (``aot.tolerant_cache_read``); the republish
    heals the entry so the NEXT replica is compile-free again."""
    import pathlib

    cache_path = pathlib.Path(warm_stack["server"].aot_cache.path)
    entries = sorted(cache_path.glob("*.aotx"))
    assert len(entries) == warm_stack["first"]["compiled"]
    victim = entries[0]
    victim.write_bytes(victim.read_bytes()[:20])

    c = warm_stack["replica"]()
    with caplog.at_level(logging.WARNING, logger="quiver_tpu"):
        ws = c.warm_from_cache()
    assert ws == {"loaded": len(entries) - 1, "compiled": 1}
    assert c.recompiles == 1
    warns = [r for r in caplog.records if "unreadable" in r.getMessage()]
    assert len(warns) == 1, [r.getMessage() for r in caplog.records]

    # the fallback compile republished over the corrupt entry — the next
    # replica is compile-free again, and the atomic publish left no residue
    d = warm_stack["replica"]()
    assert d.warm_from_cache() == {"loaded": len(entries), "compiled": 0}
    residue = [p.name for p in cache_path.iterdir() if ".tmp." in p.name]
    assert not residue, residue


def test_batcher_priority_shedding():
    """Under a full queue bronze sheds before any gold request — newest
    bronze first (least sunk wait) — and only with nothing lower-class
    pending does admission raise; shed counts land per class."""
    clock = FakeClock()
    b = DeadlineBatcher(buckets=(1, 2), default_deadline_s=1.0,
                        max_queue=2, clock=clock,
                        class_deadlines={"bronze": 4.0})
    r0 = b.submit(0, priority="bronze")
    r1 = b.submit(1, priority="bronze")
    assert (r0.deadline_s, r1.deadline_s) == (4.0, 4.0)  # per-class default
    g2 = b.submit(2)  # gold; queue full -> newest bronze shed
    assert g2.deadline_s == 1.0
    assert r1.shed and r1.done and r1.result is None
    assert not r0.shed
    assert b.shed_by_class == {"gold": 0, "bronze": 1}
    b.submit(3)  # gold; sheds the remaining bronze
    assert r0.shed
    assert b.shed_by_class["bronze"] == 2
    with pytest.raises(ServeQueueFull):
        b.submit(4)  # all-gold queue: nothing below gold to shed
    assert b.shed_by_class["gold"] == 1
    with pytest.raises(ServeQueueFull):
        b.submit(5, priority="bronze")  # bronze never evicts gold
    assert b.shed_by_class["bronze"] == 3
    reqs, bucket = b.pop(force=True)
    assert bucket == 2 and [r.node for r in reqs] == [2, 3]

    # mixed-class pop packs gold first (FIFO within a class)
    b2 = DeadlineBatcher(buckets=(1, 2), max_queue=4, clock=clock)
    b2.submit(10, priority="bronze")
    b2.submit(11, priority="gold")
    reqs, bucket = b2.pop(force=True)
    assert bucket == 2 and [r.node for r in reqs] == [11, 10]

    with pytest.raises(ValueError, match="priority"):
        b2.submit(12, priority="silver")
    with pytest.raises(ValueError, match="class_deadlines"):
        DeadlineBatcher(class_deadlines={"silver": 1.0})


def test_server_shed_and_class_miss_metrics(warm_stack):
    """Shed and deadline-miss counts are attributed per class on the
    server's obs registry (vectors in PRIORITIES order: gold, bronze)."""
    clock = FakeClock()
    e = warm_stack["replica"](clock=clock, max_queue=2,
                              class_deadlines={"gold": 1.0, "bronze": 0.5})
    assert e.warm_from_cache()["compiled"] == 0
    e.submit(1, priority="bronze")
    e.submit(2, priority="bronze")
    e.submit(3, priority="gold")  # sheds bronze node 2
    np.testing.assert_array_equal(
        np.asarray(e.metrics.value(SERVE_SHED)), [0, 1])
    clock.advance(5.0)  # both survivors blow their class deadline
    out = e.pump(force=True)
    assert sorted(r.node for r in out) == [1, 3]
    np.testing.assert_array_equal(
        np.asarray(e.metrics.value(SERVE_CLASS_MISSES)), [1, 1])
    st = e.stats()
    assert st["shed"] == {"gold": 0, "bronze": 1}
    assert st["class_deadline_misses"] == {"gold": 1, "bronze": 1}
    assert st["deadline_misses"] == 2


# -- fleet -------------------------------------------------------------------


def test_fleet_two_replicas_share_cache(warm_stack):
    """A 2-replica fleet over the populated cache joins compile-free,
    routes by least queue depth, and every response matches the shared
    deterministic oracle bitwise."""
    sampler, model, params, feat = warm_stack["stack"]
    fleet = ServingFleet(sampler, model, params, feat, replicas=2,
                         aot_cache=warm_stack["cache_dir"], seed=7,
                         max_batch=2, clock=FakeClock())
    assert [c["compiled"] for c in fleet.cold_starts] == [0, 0]
    assert fleet.recompiles == 0
    assert len(fleet.aot_cache) == warm_stack["first"]["compiled"]
    out = fleet.serve(range(6))
    assert all(r.done and not r.shed for r in out)
    for r in out:
        np.testing.assert_array_equal(r.result, fleet.oracle(r.node, r.seq))
    st = fleet.stats()
    assert st["requests"] == 6 and st["recompiles"] == 0
    assert st["replicas"] == 2


def test_refresh_after_commit_rechecks_cache(tmp_path):
    """A streaming commit invalidates every fingerprint (csr_version is
    keyed); the FIRST replica to refresh pays the epoch's compiles and
    publishes — the second replica's refresh deserializes them, staying
    at zero lifetime compiles with bitwise parity."""
    topo = _graph(n=60, e=400, seed=4)
    _x, feat, sampler, model, params = _stack(
        topo, feature_dim=6, hidden=8, classes=3, sizes=(3, 2), seed=4)
    cd = str(tmp_path / "aot")
    f = InferenceServer(sampler, model, params, feat, max_batch=1,
                        clock=FakeClock(), seed=5, aot_cache=cd)
    first = f.warm_from_cache()
    assert first["compiled"] > 0
    g = InferenceServer(sampler, model, params, feat, max_batch=1,
                        clock=FakeClock(), seed=5, aot_cache=cd)
    assert g.warm_from_cache() == {"loaded": first["compiled"],
                                   "compiled": 0}

    sg = StreamingGraph(topo)
    src = np.repeat(np.arange(topo.node_count), topo.degree)
    dst = np.asarray(topo.indices)[: src.size]
    live = set((src * topo.node_count + dst).tolist())
    k = next(k for k in range(topo.node_count ** 2) if k not in live)
    assert sg.ingest(DeltaBatch(edge_inserts=np.array(
        [[k // topo.node_count], [k % topo.node_count]])))
    sg.commit()

    with pytest.raises(VersionMismatchError):
        g.pump(force=True)
    f.refresh()  # pays the epoch's compiles, publishes the new programs
    assert f.recompiles == 2 * first["compiled"]
    loads_before = g.aot_loads
    g.refresh()  # re-checks the cache: hands over f's programs
    assert g.recompiles == 0
    assert g.aot_loads == loads_before + first["compiled"]
    rf = f.serve([7])[0]
    rg = g.serve([7])[0]
    assert (rf.node, rf.seq) == (rg.node, rg.seq)
    np.testing.assert_array_equal(rf.result, rg.result)
    np.testing.assert_array_equal(rg.result, g.oracle(rg.node, rg.seq))
