"""Benchmark harness: graph cache equivalence, PRNG selection, the
backend gate, and the scoreboard's merge discipline.

The contracts under test:

* a ``build_graph`` cache hit is EQUIVALENT to a fresh build (same indptr/
  indices/eid), and a stale pre-eid cache file is regenerated, not loaded;
* ``init_backend`` exits non-zero off the TPU unless ``--smoke``;
* ``scoreboard.write_outputs(merge=True)`` never lets a failed re-run
  clobber a prior good row, and labels kept/smoke rows in the table.
"""

import argparse
import json
import os
import sys

import numpy as np
import pytest

from benchmarks import common, scoreboard

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _args(nodes=5000, deg=8.0, seed=3):
    # smoke: the only mode a benchmark runs in off the TPU
    return argparse.Namespace(
        nodes=nodes, avg_degree=deg, seed=seed, smoke=True, iters=5,
        warmup=2,
    )


@pytest.fixture()
def cache_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(
        common, "_graph_cache_path",
        lambda nodes, avg_degree, seed: str(
            tmp_path / f"pareto_n{nodes}_d{avg_degree:g}_s{seed}.npz"),
    )
    return tmp_path


class TestGraphCache:
    def test_hit_is_equivalent_to_fresh_build(self, cache_dir):
        fresh = common.build_graph(_args())
        files = list(cache_dir.glob("*.npz"))
        assert len(files) == 1
        cached = common.build_graph(_args())
        np.testing.assert_array_equal(fresh.indptr, cached.indptr)
        np.testing.assert_array_equal(fresh.indices, cached.indices)
        assert fresh.eid is not None and cached.eid is not None
        np.testing.assert_array_equal(fresh.eid, cached.eid)

    def test_stale_no_eid_cache_regenerates(self, cache_dir):
        fresh = common.build_graph(_args())
        path = next(cache_dir.glob("*.npz"))
        with open(path, "wb") as fh:
            np.savez(fh, indptr=fresh.indptr, indices=fresh.indices)
        again = common.build_graph(_args())
        assert again.eid is not None
        np.testing.assert_array_equal(fresh.eid, again.eid)
        # and the stale file was replaced with a complete one
        assert "eid" in np.load(path).files

    def test_corrupt_cache_regenerates(self, cache_dir):
        common.build_graph(_args())
        path = next(cache_dir.glob("*.npz"))
        path.write_bytes(b"not an npz")
        topo = common.build_graph(_args())
        assert topo.node_count == 5000


class TestBackendGate:
    def test_exits_nonzero_off_the_tpu(self, capsys):
        with pytest.raises(SystemExit) as exc:
            common.init_backend(smoke=False)
        assert exc.value.code not in (0, None)
        assert capsys.readouterr().out == ""  # no metric printed

    def test_smoke_runs_off_the_tpu(self):
        assert common.init_backend(smoke=True).platform == "cpu"

    def test_bench_py_gates_before_any_metric(self, monkeypatch, capsys):
        import bench
        from quiver_tpu.utils import backend

        # run_guarded turns the persistent compile cache on for the whole
        # process before the gate: left on, every later compile of this
        # worker goes through <checkout>/.jax_cache (what broke
        # tests/test_serving_fleet.py under six workers until PR 30)
        monkeypatch.setattr(backend, "enable_compile_cache", lambda: "")
        monkeypatch.setattr(sys, "argv", ["bench.py", "--nodes", "2000"])
        with pytest.raises(SystemExit) as exc:
            bench.main()
        assert exc.value.code not in (0, None)
        assert capsys.readouterr().out == ""


class TestPrngSelection:
    def _restore(self):
        import jax

        jax.config.update("jax_default_prng_impl", "threefry2x32")

    def test_tpu_defaults_to_rbg(self, monkeypatch):
        monkeypatch.delenv("QUIVER_PRNG", raising=False)
        try:
            assert common._select_prng("tpu") == "rbg"
        finally:
            self._restore()

    def test_cpu_defaults_to_none(self, monkeypatch):
        monkeypatch.delenv("QUIVER_PRNG", raising=False)
        assert common._select_prng("cpu") is None

    def test_explicit_threefry_means_default(self, monkeypatch):
        monkeypatch.setenv("QUIVER_PRNG", "threefry")
        assert common._select_prng("tpu") is None

    def test_override_applies_on_cpu(self, monkeypatch):
        monkeypatch.setenv("QUIVER_PRNG", "rbg")
        try:
            assert common._select_prng("cpu") == "rbg"
        finally:
            self._restore()

    def test_typod_force_raises(self, monkeypatch):
        import pytest

        monkeypatch.setenv("QUIVER_PRNG", "rgb")  # the classic transposition
        with pytest.raises(ValueError, match="QUIVER_PRNG"):
            common._select_prng("tpu")


def _job(key, value=1.0, error=None, smoke=False, records=None):
    if records is None:
        records = [] if error else [
            {"metric": "m", "value": value, "unit": "u", "vs_baseline": None,
             "platform": "tpu", **({"smoke": True} if smoke else {})}
        ]
    return {"key": key, "note": "n", "records": records, "error": error,
            "seconds": 1.0, "smoke": smoke}


class TestScoreboardMerge:
    def test_failed_rerun_keeps_prior_good_row(self, tmp_path, capsys):
        scoreboard.write_outputs([_job("sampler-hbm", 5.0)], str(tmp_path),
                                 smoke=False)
        scoreboard.write_outputs([_job("sampler-hbm", error="timeout>1s")],
                                 str(tmp_path), smoke=False, merge=True)
        data = json.loads((tmp_path / "tpu_results.json").read_text())
        jobs = {j["key"]: j for j in data["jobs"]}
        assert jobs["sampler-hbm"]["records"][0]["value"] == 5.0
        assert jobs["sampler-hbm"]["retry_error"] == "timeout>1s"
        md = (tmp_path / "TPU_RESULTS.md").read_text()
        assert "kept: newer retry failed" in md

    def test_good_rerun_replaces_prior(self, tmp_path, capsys):
        scoreboard.write_outputs([_job("sampler-hbm", 5.0)], str(tmp_path),
                                 smoke=False)
        scoreboard.write_outputs([_job("sampler-hbm", 9.0)], str(tmp_path),
                                 smoke=False, merge=True)
        data = json.loads((tmp_path / "tpu_results.json").read_text())
        jobs = {j["key"]: j for j in data["jobs"]}
        assert jobs["sampler-hbm"]["records"][0]["value"] == 9.0
        assert "retry_error" not in jobs["sampler-hbm"]

    def test_smoke_records_labeled_in_table(self, tmp_path, capsys):
        scoreboard.write_outputs([_job("sampler-hbm", 5.0, smoke=True)],
                                 str(tmp_path), smoke=True)
        md = (tmp_path / "TPU_RESULTS.md").read_text()
        assert "(smoke)" in md


def _rec(metric, **kw):
    return json.dumps({"metric": metric, "value": 1.0, **kw})


class TestScoreboardChild:
    def test_harvest_skips_garbage_and_job_keys_unique(self):
        recs = scoreboard._harvest("\n".join([
            "garbage", _rec("m1"), "{bad", _rec("m2", x=1),
        ]))
        assert [r["metric"] for r in recs] == ["m1", "m2"]
        # job keys stay unique (the --only validation and merge rely on it)
        keys = [k for k, *_ in scoreboard.JOBS]
        assert len(keys) == len(set(keys))

    def test_timeout_keeps_partial_records(self, monkeypatch):
        """A job killed at its timeout keeps the records it had already
        flushed to stdout (emit flushes exactly so this works)."""
        import subprocess

        def timed_out(argv, **kw):
            raise subprocess.TimeoutExpired(
                argv, kw["timeout"], output=_rec("sampled-edges/sec/chip"))

        monkeypatch.setattr(scoreboard.subprocess, "run", timed_out)
        recs, err, _ = scoreboard.run_job("mod", [], smoke=False, timeout_s=5)
        assert [r["metric"] for r in recs] == ["sampled-edges/sec/chip"]
        assert err.startswith("timeout")

    def test_failed_child_is_one_attempt_with_its_error(self, monkeypatch):
        import subprocess

        calls = []

        def failing(argv, **kw):
            calls.append(argv)
            return subprocess.CompletedProcess(argv, 2, "", "FATAL: no TPU")

        monkeypatch.setattr(scoreboard.subprocess, "run", failing)
        recs, err, _ = scoreboard.run_job("mod", ["--x"], smoke=True,
                                          timeout_s=5)
        assert recs == [] and "no TPU" in err
        assert len(calls) == 1 and calls[0][-2:] == ["--x", "--smoke"]


def test_stream_seps_int32_guard():
    """The shared fused-stream helper must refuse configs whose single-batch
    worst-case edge count wraps int32, and clamp oversized stream lengths."""
    import jax.numpy as jnp

    class _StubSampler:
        """caps/sizes chosen so max_edges_per_batch ~= 4.2e9 > 2^31-1."""
        sizes = (1000, 1000, 1000)
        topo = jnp.zeros(4, jnp.int32)

        def _compiled(self, batch):
            def run(topo, seeds, n, key):
                raise AssertionError("run must not execute when guarded out")
            return run, (2**21, 2**21, 2**21)

    rng = np.random.default_rng(0)
    assert common.stream_seps(_StubSampler(), 100, 2048, 64, rng) is None

    class _SmallSampler:
        """max_edges_per_batch = 8*2 + 16*2 + 16*2 = 80 -> max_stream huge;
        a tiny real-ish run validates the tally path end to end."""
        sizes = (2, 2)
        topo = jnp.zeros(4, jnp.int32)

        def _compiled(self, batch):
            def run(topo, seeds, n, key):
                ec = (jnp.int32(3), jnp.int32(5))
                return (seeds, n, (), jnp.int32(0), ec, (n, n))
            return run, (16, 16)

    res = common.stream_seps(_SmallSampler(), 100, 8, 4, rng, reps=2)
    assert res is not None
    seps, oflo, stream = res
    assert stream == 4 and oflo == 0 and seps > 0


def _bench_records(*argv):
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    r = subprocess.run([sys.executable, "-m", *argv], capture_output=True,
                       text=True, timeout=600, env=env, cwd=REPO)
    recs = [json.loads(line) for line in r.stdout.splitlines()
            if line.strip().startswith("{")]
    return recs, r


@pytest.mark.slow
def test_stream_record_comes_before_percall():
    """--stream emits its one stream record first (the first SEPS record is
    the headline) and the per-call record last.

    slow: a full bench-harness subprocess, compiled end to end."""
    recs, r = _bench_records("benchmarks.bench_sampler", "--smoke",
                             "--stream", "2")
    seps = [x for x in recs if x["metric"] == "sampled-edges/sec/chip"]
    assert [x["dispatch"] for x in seps] == ["stream", "percall"], (
        r.stdout + r.stderr[-500:])
    assert all(x["value"] > 0 and "dedup" not in x for x in seps)
