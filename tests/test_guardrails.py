"""Guardrail satellites (ISSUE 1): QUIVER_CHECK layout assertion, the list
of environment names the package reads, inert-parity-arg signals, and the
DataParallelTrainer auto-cap pinning that removes the mid-epoch _stack raise."""

import logging

import numpy as np
import jax.numpy as jnp
import optax
import pytest

from quiver_tpu import CSRTopo, GraphSageSampler
from quiver_tpu.feature.feature import Feature
from quiver_tpu.models.layers import gather_mean_aggregate
from quiver_tpu.parallel.mesh import make_mesh
from quiver_tpu.utils import trace as trace_mod


@pytest.fixture(autouse=True)
def _fresh_once_keys():
    """info_once is once-per-process; tests need a fresh slate."""
    saved = set(trace_mod._ONCE_KEYS)
    trace_mod._ONCE_KEYS.clear()
    yield
    trace_mod._ONCE_KEYS.clear()
    trace_mod._ONCE_KEYS.update(saved)


# -- QUIVER_CHECK dense-layout assertion (ADVICE layers.py:93) -------------

def _regular_adj(num_dst=4, fanout=3, dim=2):
    """Source rows, and a block whose lane ``i`` reads row ``i``."""
    x = np.arange(num_dst * fanout * dim, dtype=np.float32).reshape(
        num_dst * fanout, dim)
    src = np.arange(num_dst * fanout)
    dst = np.repeat(np.arange(num_dst), fanout)
    return jnp.asarray(x), jnp.asarray(src), jnp.asarray(dst)


def _reset_check_cache(monkeypatch):
    # QUIVER_CHECK is resolved once per process (env-before-first-use —
    # the gate runs inside traced aggregation code, graftlint env-at-trace)
    from quiver_tpu.models import layers

    monkeypatch.setattr(layers, "_check_cache", None)


def test_quiver_check_passes_on_regular_layout(monkeypatch):
    _reset_check_cache(monkeypatch)
    monkeypatch.setenv("QUIVER_CHECK", "1")
    x, src, dst = _regular_adj()
    out = gather_mean_aggregate(x, src, dst, 4, fanout=3)
    assert out.shape == (4, 2)


def test_quiver_check_catches_layout_violation(monkeypatch):
    """A shape-coincident but WRONG fanout claim must fail loudly under
    QUIVER_CHECK instead of silently mis-aggregating."""
    _reset_check_cache(monkeypatch)
    monkeypatch.setenv("QUIVER_CHECK", "1")
    x, src, dst = _regular_adj()
    bad_dst = jnp.asarray(np.roll(np.asarray(dst), 1))  # breaks regularity
    with pytest.raises(Exception, match="QUIVER_CHECK"):
        np.asarray(gather_mean_aggregate(x, src, bad_dst, 4, fanout=3))


def test_quiver_check_off_by_default(monkeypatch):
    _reset_check_cache(monkeypatch)
    monkeypatch.delenv("QUIVER_CHECK", raising=False)
    x, src, dst = _regular_adj()
    bad_dst = jnp.asarray(np.roll(np.asarray(dst), 1))
    # dense path trusts the claim (documented); no error without the flag
    out = gather_mean_aggregate(x, src, bad_dst, 4, fanout=3)
    assert out.shape == (4, 2)


def test_dense_gate_shape_fallback_logged(caplog):
    """fanout set but E != num_dst*fanout: the silent revert to the
    segment-scatter path now logs once."""
    x, src, dst = _regular_adj(num_dst=4, fanout=3)
    with caplog.at_level(logging.INFO, logger="quiver_tpu"):
        out = gather_mean_aggregate(x, src, dst, 4, fanout=5)  # wrong
    assert out.shape == (4, 2)
    assert any("segment-scatter" in r.message for r in caplog.records)


# -- the environment the package reads --------------------------------------

def test_the_environment_names_the_package_reads():
    """Every ``QUIVER_*`` name in ``quiver_tpu/`` is in the one list in
    ``docs/Introduction.md`` and the list names nothing else: a new knob
    (or a removed one) shows up in a diff of that list."""
    import pathlib
    import re

    root = pathlib.Path(__file__).resolve().parent.parent
    name = re.compile(r"QUIVER_[A-Z0-9_]+")
    read = set()
    for path in (root / "quiver_tpu").rglob("*.py"):
        read.update(name.findall(path.read_text()))
    doc = (root / "docs" / "Introduction.md").read_text()
    section = doc.split("## Environment variables the package reads", 1)[1]
    listed = set(name.findall(section.split("\n## ", 1)[0]))
    assert read == listed, (sorted(read - listed), sorted(listed - read))


# -- inert parity-arg signals (VERDICT r5 weak #7) -------------------------

def test_feature_inert_args_log_once(caplog):
    with caplog.at_level(logging.INFO, logger="quiver_tpu"):
        Feature(rank=1, device_list=[0, 1], device_cache_size="1M")
        Feature(rank=2, device_list=[2], device_cache_size="1M")
    inert = [r for r in caplog.records if "INERT" in r.message]
    assert len(inert) == 1  # one-shot


def test_feature_default_args_stay_silent(caplog):
    with caplog.at_level(logging.INFO, logger="quiver_tpu"):
        Feature(device_cache_size="1M")
    assert not any("INERT" in r.message for r in caplog.records)


def test_sampler_inert_device_logs_once(caplog):
    rng = np.random.default_rng(0)
    ei = np.stack([rng.integers(0, 50, 300), rng.integers(0, 50, 300)])
    topo = CSRTopo(edge_index=ei)
    with caplog.at_level(logging.INFO, logger="quiver_tpu"):
        GraphSageSampler(topo, [3], device=0)
        GraphSageSampler(topo, [3], device=1)
    inert = [r for r in caplog.records if "INERT" in r.message]
    assert len(inert) == 1


# -- DataParallelTrainer auto-cap pinning (VERDICT r5 weak #6) -------------

def _dp_setup(frontier_caps):
    from quiver_tpu.models.sage import GraphSAGE
    from quiver_tpu.parallel.trainer import DataParallelTrainer

    rng = np.random.default_rng(0)
    n = 300
    labels = rng.integers(0, 4, n)
    feat = rng.normal(size=(n, 6)).astype(np.float32)
    ei = np.stack([rng.integers(0, n, 2500), rng.integers(0, n, 2500)])
    topo = CSRTopo(edge_index=ei)
    sampler = GraphSageSampler(topo, [4, 3], seed_capacity=16, seed=2,
                               frontier_caps=frontier_caps)
    feature = Feature(device_cache_size="1G").from_cpu_tensor(feat)
    mesh = make_mesh(data=8, feature=1)
    model = GraphSAGE(hidden=8, num_classes=4, num_layers=2)
    trainer = DataParallelTrainer(mesh, sampler, feature, model,
                                  optax.adam(1e-3), local_batch=16)
    return trainer, topo, labels


def test_dp_trainer_pins_auto_caps_no_midepoch_raise():
    """auto caps + skewed blocks: construction pins the plan, so a whole
    epoch of diverse blocks stacks without the mid-epoch ValueError."""
    import jax

    trainer, topo, labels = _dp_setup("auto")
    assert trainer.sampler._auto_caps is False  # pinned at construction
    assert trainer.sampler._frontier_caps is not None
    params, opt = trainer.init(jax.random.PRNGKey(0))
    params, opt, loss, steps = trainer.train_epoch(
        params, opt, np.arange(topo.node_count), jnp.asarray(labels),
        jax.random.PRNGKey(1),
    )
    assert steps >= 1 and np.isfinite(loss)


def test_dp_trainer_fixed_caps_untouched():
    trainer, _, _ = _dp_setup(None)
    assert trainer.sampler._auto_caps is False


def test_dp_stack_carries_fanout_from_batches():
    """_stack reads per-layer fanout off the blocks' own Adjs (ADVICE
    trainer.py:446) — metadata agrees with the sampler's sizes."""
    import jax
    from quiver_tpu.parallel.pipeline import Batch

    trainer, topo, labels = _dp_setup(None)
    blocks = trainer.seed_blocks(np.arange(trainer.global_batch))
    batches = []
    for b in blocks:
        out = trainer.sampler.sample(b)
        batches.append(Batch(b, out, trainer.feature[out.n_id]))
    caps, fanouts, x, n_id, eis, bsz = trainer._stack(batches)
    # deepest-first, matching the step body's eis order
    assert fanouts == tuple(trainer.sampler.sizes)[::-1]
    assert len(caps) == 2
    # the carried metadata must keep the dense-path regression green: a
    # data=1 step through these batches must run (dense gate satisfied)
    assert all(f is not None for f in fanouts)
