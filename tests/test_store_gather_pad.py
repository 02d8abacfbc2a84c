"""The feature store's hot row gather, padded so that a v5e keeps 256 rows in
flight (``ops/gather.py``): the rows it returns are the plain gather's, bit
for bit, through ``Feature[...]`` and through the trainer's fused step.

Lane counts of 0 and 897 - 1023 modulo 1,024 are padded; XLA's own pad of
the index vector to whole 1,024-word tiles would come to under 128 words
there (the compiled gather is checked in ``tests/test_dense_aggregation.py``).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

import quiver_tpu as quiver
from quiver_tpu.feature.feature import Feature
from quiver_tpu.obs.registry import GATHER_PAD_LANES
from quiver_tpu.ops import gather
from quiver_tpu.parallel.mesh import make_mesh
from quiver_tpu.parallel.trainer import DistributedTrainer
from quiver_tpu.utils.graphgen import generate_pareto_graph

DTYPES = [np.float32, np.float16, "int8"]


def _unpadded(monkeypatch):
    monkeypatch.setattr(gather, "tile_pad", lambda count, width=1: 0)


@pytest.mark.parametrize("lanes,pad", [
    (0, 8), (1024, 8), (2048, 8), (897, 128), (960, 72), (1023, 8),
    (1024 + 1000, 32), (896, 0), (1000 - 512, 0)])
def test_tile_pad_leaves_the_lanes_128_or_more_short_of_a_tile(lanes, pad):
    assert gather.tile_pad(lanes) == pad
    assert pad % 8 == 0
    assert -(lanes + pad) % gather.INDEX_TILE >= gather.SHORT_PAD


def test_pad_lanes_name_rows_of_their_own(monkeypatch):
    """The pad lanes read rows ``(lanes + i) % rows``, not row 0."""
    seen = []
    table = jnp.arange(50 * 4, dtype=jnp.float32).reshape(50, 4)

    class Spy:
        shape = table.shape

        def __getitem__(self, ids):
            seen.append(np.asarray(ids))
            return table[ids]

    ids = jnp.zeros(1024, jnp.int32)
    rows = gather.padded_row_gather(Spy(), ids)
    assert rows.shape == (1024, 4)
    np.testing.assert_array_equal(
        seen[0][1024:], (1024 + np.arange(8)) % 50)


@pytest.mark.parametrize("dtype", DTYPES, ids=str)
@pytest.mark.parametrize("lanes", [1024, 2048, 897, 1023, 1024 + 960])
@pytest.mark.parametrize("cold", [False, True], ids=["hot", "hot+cold"])
def test_feature_rows_are_the_unpadded_rows_bit_for_bit(
        monkeypatch, dtype, lanes, cold):
    """``Feature[n_id]`` with the pad against the same store without it, at
    lane counts that take a pad, invalid lanes (-1) among them, with and
    without a cold tier behind the hot one."""
    assert gather.tile_pad(lanes) > 0
    rng = np.random.default_rng(lanes)
    n, f = 3000, 16
    rows = rng.normal(size=(n, f)).astype(np.float32)
    itemsize = 1 if dtype == "int8" else np.dtype(dtype).itemsize
    budget = (n * f * itemsize + 4 * n) // (2 if cold else 1)
    store = Feature(device_cache_size=budget, dtype=dtype).from_cpu_tensor(
        rows)
    assert (store.cold is not None) == cold
    n_id = rng.integers(0, n, lanes).astype(np.int32)
    n_id[rng.random(lanes) < 0.2] = -1
    lookup = jax.jit(lambda s, i: s[i])

    padded = np.asarray(lookup(store, n_id))
    _unpadded(monkeypatch)
    plain = np.asarray(jax.jit(lambda s, i: s[i])(store, n_id))
    assert padded.shape == (lanes, f)
    np.testing.assert_array_equal(padded.view(np.uint8), plain.view(np.uint8))
    assert not padded[n_id < 0].any()
    if dtype != "int8":
        np.testing.assert_array_equal(
            padded[n_id >= 0], rows.astype(dtype)[n_id[n_id >= 0]])


def _trainer(dtype, width):
    """A one-device fused step whose frontier is ``width`` lanes wide."""
    topo = quiver.CSRTopo(edge_index=generate_pareto_graph(1500, 6.0, seed=0))
    sampler = quiver.GraphSageSampler(topo, [6, 4], frontier_caps=[128, width])
    rows = np.random.default_rng(0).normal(
        size=(topo.node_count, 8)).astype(np.float32)
    store = Feature(device_cache_size="1M", dtype=dtype).from_cpu_tensor(rows)
    from quiver_tpu.models.sage import GraphSAGE

    trainer = DistributedTrainer(
        make_mesh(data=1, feature=1, devices=jax.devices()[:1]), sampler,
        store, GraphSAGE(hidden=8, num_classes=4, num_layers=2),
        optax.adam(1e-2), local_batch=32)
    params, opt_state = trainer.init(jax.random.PRNGKey(0))
    labels = jnp.asarray(np.random.default_rng(1).integers(
        0, 4, topo.node_count), jnp.int32)
    return trainer, params, opt_state, labels


@pytest.mark.parametrize("dtype", DTYPES, ids=str)
@pytest.mark.parametrize("width", [1024, 960])
def test_fused_step_trains_on_the_unpadded_rows_bit_for_bit(
        monkeypatch, dtype, width):
    """The trainer's fused step with the pad and without it: the same loss
    and the same parameters after a step, bit for bit, from a frontier
    of padded lanes (-1) and rows; ``feature.gather_pad_lanes`` reads the
    pad once the step is built, a host value and no output of the step."""
    out = {}
    for padded in (True, False):
        if not padded:
            _unpadded(monkeypatch)
        trainer, params, opt_state, labels = _trainer(dtype, width)
        seeds = np.arange(trainer.global_batch)
        params, opt_state, loss = trainer.step(
            params, opt_state, seeds, labels, jax.random.PRNGKey(3))
        out[padded] = (np.asarray(loss), jax.tree_util.tree_leaves(params))
        pad = trainer.metrics.value(GATHER_PAD_LANES)
        assert isinstance(pad, np.integer)
        assert int(pad) == (gather.tile_pad(width) if padded else 0)
    (loss_p, leaves_p), (loss_u, leaves_u) = out[True], out[False]
    assert np.isfinite(loss_p)
    np.testing.assert_array_equal(loss_p.view(np.uint32),
                                  loss_u.view(np.uint32))
    for a, b in zip(leaves_p, leaves_u):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
