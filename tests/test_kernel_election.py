"""kernel=auto election: by measured throughput, not compile success
(VERDICT r3 item 4) — now one shared ``ops.election.KernelElection``
machinery behind both the gather (feature) and sample (fused megakernel)
elections, with one nested disk-cache file (ISSUE 16 satellite 2)."""

import json

import pytest

import quiver_tpu.ops.election as EL
from quiver_tpu.feature import feature as F
from quiver_tpu.sampling import sampler as S


@pytest.fixture(autouse=True)
def fresh_election(tmp_path, monkeypatch):
    # the election AND its env knobs are resolved once per process
    # (env-before-first-use); tests reset all the caches to re-resolve
    monkeypatch.setattr(EL, "_ELECTION_CACHE_PATH", None)
    monkeypatch.setenv("QUIVER_ELECTION_CACHE",
                       str(tmp_path / "election.json"))
    monkeypatch.delenv("QUIVER_GATHER_KERNEL", raising=False)
    monkeypatch.delenv("QUIVER_SAMPLE_KERNEL", raising=False)
    F.GATHER_ELECTION.reset()
    S.SAMPLE_ELECTION.reset()
    yield tmp_path / "election.json"
    # leave the module-level singletons as a fresh process would find them
    F.GATHER_ELECTION.reset()
    S.SAMPLE_ELECTION.reset()


def test_measure_gather_gbps_runs():
    gbps = F._measure_gather_gbps("xla", rows=512, dim=8, batch=64, reps=4)
    assert gbps > 0


def test_measure_sample_eps_runs():
    eps = S._measure_sample_eps("xla", nodes=64, edges=512, batch=16,
                                k=4, reps=2)
    assert eps > 0


@pytest.mark.parametrize("which", ["gather", "sample"])
def test_election_picks_measured_winner(which, fresh_election, monkeypatch):
    mod, elec = ((F, F.GATHER_ELECTION) if which == "gather"
                 else (S, S.SAMPLE_ELECTION))
    smoke = ("_pallas_gather_usable" if which == "gather"
             else "_pallas_sample_usable")
    meas = ("_measure_gather_gbps" if which == "gather"
            else "_measure_sample_eps")
    monkeypatch.setattr(mod, smoke, lambda: True)
    monkeypatch.setattr(mod, meas,
                        lambda k, **kw: {"xla": 10.0, "pallas": 4.0}[k])
    assert elec.elect() == "xla"
    assert elec.result["how"] == "measured"
    # and the loser would have won with the numbers flipped
    elec.reset()
    monkeypatch.setattr(EL, "_ELECTION_CACHE_PATH", None)
    monkeypatch.setenv("QUIVER_ELECTION_CACHE",
                       str(fresh_election.parent / "election2.json"))
    monkeypatch.setattr(mod, meas,
                        lambda k, **kw: {"xla": 4.0, "pallas": 10.0}[k])
    assert elec.elect() == "pallas"


def test_election_disk_cache_roundtrip(fresh_election, monkeypatch):
    monkeypatch.setattr(F, "_pallas_gather_usable", lambda: True)
    monkeypatch.setattr(
        F, "_measure_gather_gbps",
        lambda k, **kw: {"xla": 1.0, "pallas": 9.0}[k])
    assert F.GATHER_ELECTION.elect() == "pallas"
    blob = json.loads(fresh_election.read_text())
    cached = blob["gather"]  # nested by election name (one shared file)
    assert cached["kernel"] == "pallas" and cached["score"]["pallas"] == 9.0

    # a fresh process (reset memo) must trust the cache, not re-measure
    F.GATHER_ELECTION.reset()

    def boom(k, **kw):
        raise AssertionError("re-measured despite disk cache")

    monkeypatch.setattr(F, "_measure_gather_gbps", boom)
    assert F.GATHER_ELECTION.elect() == "pallas"
    assert F.GATHER_ELECTION.result["how"] == "disk cache"

    # ...but a different cache key (device kind / jax version / kernel
    # revision) invalidates it
    cached["key"] = "rev0-jaxother-chip"
    fresh_election.write_text(json.dumps({"gather": cached}))
    F.GATHER_ELECTION.reset()
    monkeypatch.setattr(
        F, "_measure_gather_gbps",
        lambda k, **kw: {"xla": 9.0, "pallas": 1.0}[k])
    assert F.GATHER_ELECTION.elect() == "xla"


def test_shared_cache_holds_both_elections(fresh_election, monkeypatch):
    """One file, nested by election name — the gather and sample entries
    coexist, and a pre-generalization FLAT gather cache pointed at by
    QUIVER_ELECTION_CACHE is tolerated (ignored, then rewritten nested)."""
    # legacy flat format from before the ops/election.py refactor
    fresh_election.write_text(json.dumps(
        {"kernel": "pallas", "gbps": {"pallas": 9.0, "xla": 1.0},
         "key": "rev1-jaxold-chip"}))
    monkeypatch.setattr(F, "_pallas_gather_usable", lambda: True)
    monkeypatch.setattr(
        F, "_measure_gather_gbps",
        lambda k, **kw: {"xla": 2.0, "pallas": 8.0}[k])
    monkeypatch.setattr(S, "_pallas_sample_usable", lambda: True)
    monkeypatch.setattr(
        S, "_measure_sample_eps",
        lambda k, **kw: {"xla": 7.0, "pallas": 3.0}[k])
    assert F.GATHER_ELECTION.elect() == "pallas"  # flat file not trusted
    assert F.GATHER_ELECTION.result["how"] == "measured"
    assert S.SAMPLE_ELECTION.elect() == "xla"
    blob = json.loads(fresh_election.read_text())
    assert blob["gather"]["kernel"] == "pallas"
    assert blob["sample"]["kernel"] == "xla"
    assert "gbps" not in blob  # legacy keys dropped on rewrite


def test_corrupt_cache_fails_safe_with_one_warning(fresh_election,
                                                   monkeypatch, caplog):
    """A corrupt/truncated shared cache file degrades to re-election with
    a single WARNING — never a raise on the gather/sample path — and the
    re-election's atomic republish heals the file (ISSUE 17 satellite:
    the serving AOT cache shares this tolerant loader)."""
    import logging

    fresh_election.write_text('{"gather": {"kernel": "pal')  # truncated
    monkeypatch.setattr(F, "_pallas_gather_usable", lambda: True)
    monkeypatch.setattr(
        F, "_measure_gather_gbps",
        lambda k, **kw: {"xla": 2.0, "pallas": 8.0}[k])
    with caplog.at_level(logging.WARNING, logger="quiver_tpu"):
        assert F.GATHER_ELECTION.elect() == "pallas"
    assert F.GATHER_ELECTION.result["how"] == "measured"
    warns = [r for r in caplog.records if "unreadable" in r.getMessage()]
    assert len(warns) == 1, [r.getMessage() for r in caplog.records]

    # the same corrupt read (load before store) happens again inside
    # _store's read-merge — still only ONE warning per process...
    # and the republish over the bad file is valid, nested JSON again
    blob = json.loads(fresh_election.read_text())
    assert blob["gather"]["kernel"] == "pallas"

    # a fresh process (reset) now trusts the healed cache
    F.GATHER_ELECTION.reset()

    def boom(k, **kw):
        raise AssertionError("re-measured despite healed disk cache")

    monkeypatch.setattr(F, "_measure_gather_gbps", boom)
    assert F.GATHER_ELECTION.elect() == "pallas"
    assert F.GATHER_ELECTION.result["how"] == "disk cache"
    # no temp residue from the atomic publish
    residue = [p.name for p in fresh_election.parent.iterdir()
               if ".tmp." in p.name]
    assert not residue, residue


def test_env_knobs_pinned_at_first_use(fresh_election, monkeypatch):
    """QUIVER_GATHER_KERNEL / QUIVER_ELECTION_CACHE resolve ONCE per
    process: flipping them after the first use is inert without a cache
    reset — the env-before-first-use contract graftlint's env-at-trace
    rule enforces repo-wide (forcing must precede the first gather)."""
    monkeypatch.setenv("QUIVER_GATHER_KERNEL", "xla")
    assert F.GATHER_ELECTION.forced() == "xla"
    first_path = EL._election_cache_path()
    assert first_path == str(fresh_election)
    # post-first-use flips are inert...
    monkeypatch.setenv("QUIVER_GATHER_KERNEL", "pallas")
    monkeypatch.setenv("QUIVER_ELECTION_CACHE",
                       str(fresh_election.parent / "other.json"))
    assert F.GATHER_ELECTION.forced() == "xla"
    assert EL._election_cache_path() == first_path
    # ...including through the election itself
    assert F.GATHER_ELECTION.elect() == "xla"
    assert F.GATHER_ELECTION.result["how"] == "env override"
    # a cache reset (= a fresh process) re-reads the env
    F.GATHER_ELECTION.reset()
    assert F.GATHER_ELECTION.forced() == "pallas"


def test_election_env_override_and_loud_failures(fresh_election,
                                                 monkeypatch):
    monkeypatch.setenv("QUIVER_SAMPLE_KERNEL", "xla")
    assert S.SAMPLE_ELECTION.elect() == "xla"
    assert S.SAMPLE_ELECTION.result["how"] == "env override"

    # a diverging pallas smoke is an error, raised before any measuring
    S.SAMPLE_ELECTION.reset()
    monkeypatch.delenv("QUIVER_SAMPLE_KERNEL")
    monkeypatch.setattr(S, "_pallas_sample_usable", lambda: False)

    def never(k, **kw):
        raise AssertionError("measured despite failed smoke")

    monkeypatch.setattr(S, "_measure_sample_eps", never)
    with pytest.raises(RuntimeError, match="sample pallas smoke diverged"):
        S.SAMPLE_ELECTION.elect()
    assert S.SAMPLE_ELECTION.result is None

    # a measurement crash propagates too: it is a Pallas call failing
    monkeypatch.setattr(S, "_pallas_sample_usable", lambda: True)

    def boom(k, **kw):
        raise RuntimeError("kernel fault")

    monkeypatch.setattr(S, "_measure_sample_eps", boom)
    with pytest.raises(RuntimeError, match="kernel fault"):
        S.SAMPLE_ELECTION.elect()
    assert S.SAMPLE_ELECTION.result is None


def test_raising_smoke_propagates_from_auto_on_tpu(monkeypatch):
    """On a (faked) TPU backend a Pallas sample smoke that raises — a
    kernel the compiler refuses — propagates out of
    resolve_sample_kernel("auto"); nothing degrades to xla."""
    from quiver_tpu.ops.pallas import fused

    def refuse(*a, **k):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(fused, "fused_sample_layer", refuse)
    monkeypatch.setattr(S.jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        S.resolve_sample_kernel("auto")
    assert S.SAMPLE_ELECTION.result is None


def test_resolve_passthrough_and_off_tpu(monkeypatch):
    """Explicit kernels bypass the election entirely; auto off-TPU is xla
    without running smoke or measure (the CPU interpret path is correct
    but slow)."""
    def never():
        raise AssertionError("smoke ran for an explicit/off-TPU resolve")

    monkeypatch.setattr(S, "_pallas_sample_usable", never)
    monkeypatch.setattr(S, "_measure_sample_eps",
                        lambda k, **kw: never())
    assert S.resolve_sample_kernel("pallas") == "pallas"
    assert S.resolve_sample_kernel("xla") == "xla"
    assert S.resolve_sample_kernel("auto") == "xla"  # CPU test runner
    with pytest.raises(ValueError, match="kernel"):
        S.resolve_sample_kernel("nope")
