"""Tracing/timing/logging + debug-introspection subsystem tests.

Covers the observability parity layer (SURVEY §5): trace_scope gating
(reference TRACE_SCOPE, trace.hpp:6-14), Timer (timer.hpp:7-28), the
structured logger replacing LOG>>> prints, and show_tensor_info
(tensor.cpp:74-95).
"""

import logging

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from quiver_tpu.utils import debug, trace


@pytest.fixture(autouse=True)
def _reset_trace_state():
    # the log tests below switch the package logger's propagation: put it
    # back, or every later log assertion in this process reads nothing
    logger = logging.getLogger("quiver_tpu")
    propagate = logger.propagate
    yield
    trace._enabled = None  # restore env-var-driven default
    logger.propagate = propagate


def test_trace_scope_disabled_is_noop(monkeypatch):
    monkeypatch.delenv("QUIVER_ENABLE_TRACE", raising=False)
    trace._enabled = None
    assert not trace.trace_enabled()
    with trace.trace_scope("x"):
        pass  # must not raise or require a profiler session


def test_trace_scope_env_enable(monkeypatch):
    monkeypatch.setenv("QUIVER_ENABLE_TRACE", "1")
    trace._enabled = None
    assert trace.trace_enabled()
    with trace.trace_scope("region"):
        y = jnp.arange(4) + 1
    assert int(y[0]) == 1


def test_enable_disable_override_env(monkeypatch):
    monkeypatch.setenv("QUIVER_ENABLE_TRACE", "1")
    trace.disable_trace()
    assert not trace.trace_enabled()
    trace.enable_trace()
    assert trace.trace_enabled()


def test_trace_scope_inside_jit():
    trace.enable_trace()

    @jax.jit
    def f(x):
        with trace.trace_scope("inner"):
            return x * 2

    assert int(f(jnp.int32(3))) == 6


def test_timer_measures_and_syncs():
    x = jnp.ones((64, 64))
    with trace.Timer("matmul", sync=x, quiet=True) as t:
        x = x @ x
    assert t.seconds > 0


def test_timer_logs(caplog):
    logger = trace.get_logger()
    with caplog.at_level(logging.INFO, logger="quiver_tpu"):
        logger.propagate = True
        try:
            with trace.Timer("scope"):
                pass
        finally:
            logger.propagate = False
    assert any("[scope]" in r.message for r in caplog.records)


def test_get_logger_invalid_level_falls_back(monkeypatch, capsys):
    """QUIVER_LOG_LEVEL=bogus must not crash the process at the first log
    call — the bootstrap falls back to the NullHandler path with a one-line
    stderr warning."""
    root = logging.getLogger("quiver_tpu")
    saved = root.handlers[:]
    saved_propagate, saved_level = root.propagate, root.level
    try:
        root.handlers = []
        monkeypatch.setenv("QUIVER_LOG_LEVEL", "bogus")
        logger = trace.get_logger()
        logger.info("still works")  # must not raise
        assert any(
            isinstance(h, logging.NullHandler) for h in root.handlers
        )
        err = capsys.readouterr().err
        assert "QUIVER_LOG_LEVEL" in err and "bogus" in err
    finally:
        root.handlers = saved
        root.propagate, root.level = saved_propagate, saved_level


def test_info_once_reset(caplog):
    logger = trace.get_logger()
    with caplog.at_level(logging.INFO, logger="quiver_tpu"):
        logger.propagate = True
        try:
            trace.info_once("k-reset-test", "once msg")
            trace.info_once("k-reset-test", "once msg")
            assert sum("once msg" in r.message for r in caplog.records) == 1
            trace.reset_once()  # the test-fixture hook (conftest autouse)
            trace.info_once("k-reset-test", "once msg")
            assert sum("once msg" in r.message for r in caplog.records) == 2
        finally:
            logger.propagate = False


def test_get_logger_singleton_handler():
    a, b = trace.get_logger(), trace.get_logger()
    root = logging.getLogger("quiver_tpu")
    assert a is b is root
    assert len(root.handlers) == 1
    assert trace.get_logger("feature").name == "quiver_tpu.feature"


def test_tensor_info_numpy_and_jax():
    s = debug.tensor_info(np.zeros((3, 4), np.float32))
    assert "numpy" in s and "(3, 4)" in s and "float32" in s
    arr = jnp.zeros((2, 5), jnp.int32)
    s = debug.tensor_info(arr)
    assert "jax.Array" in s and "(2, 5)" in s and "int32" in s


def test_show_tensor_info_prints(capsys):
    out = debug.show_tensor_info(jnp.ones(3))
    assert out in capsys.readouterr().out


def test_feature_placement_log(caplog):
    from quiver_tpu import Feature

    logger = trace.get_logger()
    feat = np.random.default_rng(0).normal(size=(100, 8)).astype(np.float32)
    with caplog.at_level(logging.INFO, logger="quiver_tpu"):
        logger.propagate = True
        try:
            Feature(device_cache_size=50 * 8 * 4).from_cpu_tensor(feat)
        finally:
            logger.propagate = False
    msgs = [r.message for r in caplog.records]
    assert any("cached in HBM" in m for m in msgs)


def test_sampler_works_with_tracing_enabled():
    from quiver_tpu import CSRTopo, GraphSageSampler

    trace.enable_trace()
    rng = np.random.default_rng(0)
    ei = rng.integers(0, 50, size=(2, 400)).astype(np.int64)
    topo = CSRTopo(edge_index=ei)
    sampler = GraphSageSampler(topo, [4, 3], seed=0)
    out = sampler.sample(np.arange(16))
    assert int(out.n_count) >= 16
