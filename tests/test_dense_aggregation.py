"""Dense (fanout) vs segment aggregation parity.

Every sampler-built Adj now carries its static ``fanout``, switching the
model convs to dense masked reductions over each target's ``fanout`` lanes —
zero scatters (on a v5e a scatter costs 4.4x a sort of the same lanes:
PERF.md, PR 26).
These tests pin the invariant that the dense path is numerically the
segment path: same Adj, same params, fanout set vs stripped, outputs must
agree to float tolerance for all four homogeneous conv families plus the
layer primitives.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from quiver_tpu import CSRTopo, GraphSageSampler
from quiver_tpu.sampling.sampler import Adj


@pytest.fixture(scope="module")
def sampled():
    rng = np.random.default_rng(5)
    ei = rng.integers(0, 300, size=(2, 4500)).astype(np.int64)
    topo = CSRTopo(edge_index=ei)
    s = GraphSageSampler(topo, [7, 5], seed_capacity=64, seed=3)
    out = s.sample(rng.integers(0, 300, 64))
    x = rng.normal(size=(out.n_id.shape[0], 32)).astype(np.float32)
    return out, jnp.asarray(x)


def _strip_fanout(adjs):
    return [Adj(a.edge_index, a.e_id, a.size, fanout=None) for a in adjs]


def test_sampler_adjs_carry_fanout(sampled):
    out, _ = sampled
    assert [a.fanout for a in out.adjs] == [5, 7]  # deepest first
    for a in out.adjs:
        assert a.edge_index.shape[1] == a.size[1] * a.fanout


def test_adj_pytree_roundtrip_preserves_fanout(sampled):
    out, _ = sampled
    leaves, treedef = jax.tree_util.tree_flatten(out.adjs)
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert [a.fanout for a in rebuilt] == [5, 7]
    assert [a.size for a in rebuilt] == [a.size for a in out.adjs]


@pytest.mark.parametrize("family", ["sage", "gcn", "gin", "gat"])
def test_dense_matches_segment(sampled, family):
    from quiver_tpu.models import GAT, GCN, GIN, GraphSAGE

    out, x = sampled
    model = {
        "sage": lambda: GraphSAGE(hidden=16, num_classes=4, num_layers=2),
        "gcn": lambda: GCN(hidden=16, num_classes=4, num_layers=2),
        "gin": lambda: GIN(hidden=16, num_classes=4, num_layers=2),
        "gat": lambda: GAT(hidden=16, num_classes=4, num_layers=2, heads=2),
    }[family]()
    params = model.init(jax.random.PRNGKey(0), x, out.adjs)
    y_dense = model.apply(params, x, out.adjs)
    y_seg = model.apply(params, x, _strip_fanout(out.adjs))
    np.testing.assert_allclose(
        np.asarray(y_dense), np.asarray(y_seg), rtol=2e-4, atol=2e-5
    )


# (num_dst, fanout) of a hand-built regular block: 37 is no multiple of 8
# (the fanout-major view is then no view of whole tiles: same sums)
FANOUT_BLOCKS = [(64, 15), (64, 10), (40, 5), (37, 5), (8, 25)]


def _regular_block(num_dst, fanout, rows, seed=0):
    """``edge_index`` (2, num_dst * fanout) in the sampler's layout with
    padded lanes, a target with none but padded lanes, one with none
    padded, a source repeated within a target and one across targets."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, rows, (num_dst, fanout)).astype(np.int32)
    src[rng.random(src.shape) < 0.3] = -1
    src[1] = -1
    src[2] = rng.integers(0, rows, fanout)
    src[3, :2] = 7
    src[4:7, 0] = 9
    dst = np.repeat(np.arange(num_dst, dtype=np.int32), fanout)
    src = src.reshape(-1)
    return jnp.asarray(np.stack([src, np.where(src >= 0, dst, -1)]))


@pytest.mark.parametrize("num_dst,fanout", FANOUT_BLOCKS)
def test_fanout_gather_sum_is_the_segment_sum(num_dst, fanout):
    """The fanout-major gather and sum against ``segment_sum`` over the
    same lanes: totals, counts, and the gradient of the rows."""
    from quiver_tpu.models.layers import fanout_gather_sum

    rows, width = 3 * num_dst, 12
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(rows, width)).astype(np.float32))
    weight = jnp.asarray(rng.normal(size=(num_dst, width)).astype(np.float32))
    src, dst = _regular_block(num_dst, fanout, rows)
    valid = src >= 0
    seg = jnp.where(valid, dst, num_dst)

    def dense(x):
        total, cnt = fanout_gather_sum(x, src, num_dst, fanout)
        return (total * weight).sum(), (total, cnt)

    def segment(x):
        msgs = jnp.where(valid[:, None], x[jnp.clip(src, 0)], 0.0)
        total = jax.ops.segment_sum(msgs, seg, num_dst + 1)[:num_dst]
        cnt = jax.ops.segment_sum(valid.astype(jnp.int32), seg, num_dst + 1)
        return (total * weight).sum(), (total, cnt[:num_dst])

    (_, (total, cnt)), grad = jax.value_and_grad(dense, has_aux=True)(x)
    (_, (want, want_cnt)), want_grad = jax.value_and_grad(
        segment, has_aux=True)(x)
    _, jitted = jax.jit(jax.value_and_grad(dense, has_aux=True))(x)
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(want_cnt))
    assert cnt.dtype == jnp.int32 and int(cnt[1]) == 0 and int(cnt[2]) == fanout
    assert not np.asarray(total)[1].any()
    np.testing.assert_allclose(
        np.asarray(total), np.asarray(want), rtol=1e-5, atol=1e-5)
    for g in (grad, jitted):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(want_grad), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("relations,dtype", [
    (0, np.float32), (5, np.float32), (5, np.float16)])
def test_padded_lanes_give_the_unpadded_sums_bit_for_bit(
        relations, dtype, monkeypatch):
    """128 targets x 8 fill one whole 1,024-word tile, so the lanes are
    padded (``layers._target_pad``): the sums and the counts are the
    unpadded gather's bit for bit (a pad lane enters no sum, and each
    target's terms are added in the same order), the gradient of the rows
    the same to float32 round-off; masked lanes, a target with none but
    masked lanes, every relation present."""
    from quiver_tpu.models import layers

    num_dst, fanout, rows, width = 128, 8, 300, 12
    assert layers._target_pad(num_dst, fanout) > 0
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(rows, width)).astype(dtype))
    src = _regular_block(num_dst, fanout, rows, seed=5)[0]
    relation = jnp.where(
        src.reshape(num_dst, fanout).T >= 0,
        jnp.asarray(rng.integers(0, max(relations, 1), (fanout, num_dst))),
        -1).astype(jnp.int32)
    weight = jnp.asarray(
        rng.normal(size=(max(relations, 1), num_dst, width)).astype(np.float32))

    def scalar(x):
        if relations:
            sums, counts = layers.fanout_relation_sums(
                x, src, relation, num_dst, fanout, relations)
        else:
            total, count = layers.fanout_gather_sum(x, src, num_dst, fanout)
            sums, counts = (total,), count[None]
        return sum((t * w).sum() for t, w in zip(sums, weight)), (
            sums, counts)

    (_, (sums, counts)), grad = jax.value_and_grad(scalar, has_aux=True)(x)
    monkeypatch.setattr(layers, "_target_pad", lambda num_dst, fanout: 0)
    (_, (want, want_counts)), want_grad = jax.value_and_grad(
        scalar, has_aux=True)(x)
    assert (np.asarray(want_counts) > 0).any(axis=1).all()
    assert int(np.asarray(want_counts)[:, 1].sum()) == 0
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(want_counts))
    for a, b in zip(sums, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(np.asarray(grad, np.float32),
                               np.asarray(want_grad, np.float32),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("num_dst,fanout", FANOUT_BLOCKS)
def test_sageconv_dense_path_is_the_segment_path(num_dst, fanout):
    """``SAGEConv`` with the block's fanout against the same block with
    none: the layer's values and its gradients with respect to the rows
    and every weight."""
    from quiver_tpu.models.sage import SAGEConv

    rows, width = 3 * num_dst, 12
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(rows, width)).astype(np.float32))
    edge_index = _regular_block(num_dst, fanout, rows, seed=3)
    conv = SAGEConv(8)
    params = conv.init(jax.random.PRNGKey(0), x, edge_index, num_dst, fanout)
    weight = jnp.asarray(rng.normal(size=(num_dst, 8)).astype(np.float32))

    def loss(params, x, fanout):
        y = conv.apply(params, x, edge_index, num_dst, fanout)
        return (y * weight).sum(), y

    with jax.default_matmul_precision("highest"):
        (_, y), grads = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)(params, x, fanout)
        (_, want), want_grads = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)(params, x, None)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(want), rtol=1e-5, atol=1e-5)
    for g, w in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=1e-5, atol=1e-5)


def test_sageconv_dense_forward_gathers_fanout_major():
    """The traced dense forward of ``SAGEConv`` holds ONE gather, straight
    into ``(fanout, num_dst, F)``, and a sum over axis 0 of that array;
    nothing is reshaped to ``(num_dst, fanout, F)``, the view a v5e can
    only have as a padded copy of every gathered row (PERF.md, PR 36). The
    segment path keeps its ``(E, F)`` gather."""
    from quiver_tpu.models.sage import SAGEConv
    from quiver_tpu.tools.audit.ir import iter_eqns

    num_dst, fanout, width = 16, 5, 12
    x = jnp.zeros((3 * num_dst, width), jnp.float32)
    edge_index = _regular_block(num_dst, fanout, x.shape[0])
    conv = SAGEConv(8)
    params = conv.init(jax.random.PRNGKey(0), x, edge_index, num_dst, fanout)

    def traced(fanout):
        jaxpr = jax.make_jaxpr(
            lambda p, x: conv.apply(p, x, edge_index, num_dst, fanout))(
                params, x)
        return [eqn for eqn, _ in iter_eqns(jaxpr)]

    slabs = (fanout, num_dst, width)
    eqns = traced(fanout)
    gathers = [e for e in eqns if e.primitive.name == "gather"]
    assert [e.outvars[0].aval.shape for e in gathers] == [slabs]
    sums = [e for e in eqns if e.primitive.name == "reduce_sum"
            and e.invars[0].aval.shape == slabs]
    assert [e.params["axes"] for e in sums] == [(0,)]
    by_lane = {(num_dst, fanout, width), (num_dst * fanout, width)}
    for e in eqns:
        shapes = [v.aval.shape for v in list(e.invars) + list(e.outvars)
                  if hasattr(v.aval, "shape")]
        assert not by_lane & set(shapes), (e.primitive.name, shapes)
    assert not any(e.primitive.name.startswith("scatter") for e in eqns)
    segment = [e.outvars[0].aval.shape for e in traced(None)
               if e.primitive.name == "gather"]
    assert segment == [(num_dst * fanout, width)]


@pytest.fixture(scope="module")
def one_chip():
    """A described (not attached) v5e chip's sharding: the TPU's compiler
    runs where there is no TPU. Described inside the fixture, by the worker
    that runs this file alone."""
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _relayouts(compiled, elements):
    """The ENTRY computation's ``copy`` / ``reshape`` / ``transpose`` /
    ``slice`` instructions of ``elements`` elements or more: the ops that
    move an array into another layout or a part of it into one of its own
    (a view is a ``bitcast``)."""
    import re

    text = compiled.as_text()
    found = []
    for line in text[text.index("\nENTRY "):].splitlines():
        m = re.match(
            r"\s+(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* ([a-z\-]+)\(", line)
        if m and m.group(2) in ("copy", "reshape", "transpose", "slice"):
            if np.prod([int(d) for d in m.group(1).split(",") if d]) >= elements:
                found.append(line.strip()[:120])
    return found


def _row_gathers(text):
    """(rows in flight, lanes) of every row-gather fusion of a compiled
    program: the ``"integer"`` of its backend config, and its output's
    leading dimension"""
    import re

    return [(int(re.search(r'"integer":"(\d+)"', line).group(1)),
             int(re.search(r"= \w+\[(\d+)", line).group(1)))
            for line in text.splitlines()
            if "kind=kCustom" in line and "/gather" in line
            and '"integer"' in line]


@pytest.mark.parametrize(
    "num_dst,fanout,width", [(1024, 10, 256), (1024, 5, 100), (1000, 25, 602)])
def test_a_v5e_gets_the_fanout_major_rows_without_a_copy(
        one_chip, num_dst, fanout, width):
    """Compiled for a described v5e, value and gradient: behind the
    fanout-major gather no instruction re-lays out an array of the
    gathered rows' size, forward or transposed; the same rows gathered in
    lane order and summed by ``fanout_sum_aggregate`` cost ``reshape``s of
    that size both ways (which also shows that the check can see one).
    Nothing runs; this says nothing of results or times."""
    from quiver_tpu.models.layers import (
        fanout_gather_sum, fanout_sum_aggregate, gather_src)

    def fanout_major(x, src, weight):
        total, _ = fanout_gather_sum(x, src, num_dst, fanout)
        return (total * weight).sum()

    def lane_order(x, src, weight):
        msgs, valid = gather_src(x, src)
        return (fanout_sum_aggregate(msgs, valid, num_dst, fanout)
                * weight).sum()

    def relayouts(fn):
        shapes = [((4 * num_dst, width), jnp.float32),
                  ((num_dst * fanout,), jnp.int32),
                  ((num_dst, width), jnp.float32)]
        compiled = jax.jit(jax.value_and_grad(fn)).lower(*[
            jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]).compile()
        return _relayouts(compiled, num_dst * fanout * width)

    assert relayouts(fanout_major) == []
    assert len(relayouts(lane_order)) >= 2


def _parent_gather_sum(x, src, num_dst, fanout):
    """``fanout_gather_sum`` as it was before its lanes were padded"""
    idx = src.reshape(num_dst, fanout).T
    valid = idx >= 0
    rows = jnp.where(valid[..., None], x[jnp.clip(idx, 0)], 0)
    return rows.sum(axis=0), valid.sum(axis=0, dtype=jnp.int32)


# (table rows, targets, fanout, width, dtype, relations) of a cell's layer:
# reddit-sage's and mag240m-rsage's conv0 fill whole 1,024-word tiles,
# products-sage's does not; a conv2's 1,024 x 15 lanes over a hidden
# layer's rows, differentiated (a table this size stays in HBM)
V5E_GATHERS = {
    "reddit conv0": (195_328, 30_208, 10, 602, jnp.float32, 0),
    "mag240m conv0": (425_984, 26_624, 15, 768, jnp.float16, 5),
    "products conv0": (672_384, 142_080, 5, 100, jnp.float32, 0),
    "conv2": (163_840, 1_024, 15, 256, jnp.float32, 0),
}


@pytest.mark.parametrize("case", list(V5E_GATHERS))
def test_a_v5e_gathers_the_fanout_major_rows_256_in_flight(
        one_chip, case, monkeypatch):
    """``fanout_gather_sum`` / ``fanout_relation_sums`` at a cell's shapes,
    value and gradient compiled for a described v5e: the one row gather
    keeps 256 rows in flight, over the lanes padded by
    ``layers._target_pad`` (which says so once), no instruction copies,
    re-lays out or slices an array of the gathered rows' size, and the
    temporaries are the unpadded program's to 2 % of the gathered rows'
    bytes. Unpadded, the lanes that fill whole 1,024-word tiles keep 128
    in flight (10 ns a row against 4 on the chip: PERF.md section 6), which
    also shows that the check can see it; products' lanes are not padded
    and lower as before. Nothing runs; this says nothing of results or
    times."""
    from quiver_tpu.models import layers

    rows, num_dst, fanout, width, dtype, relations = V5E_GATHERS[case]
    logged = []
    monkeypatch.setattr(
        layers, "info_once", lambda key, msg, *args: logged.append(args))

    def loss(aggregate):
        def scalar(x, src, kernel, relation):
            if relations:
                sums, counts = layers.fanout_relation_sums(
                    x, src, relation, num_dst, fanout, relations)
            else:
                total, count = aggregate(x, src, num_dst, fanout)
                sums, counts = (total,), count[None]
            return sum(((t / jnp.maximum(c, 1)[:, None]) @ k).sum()
                       for t, c, k in zip(sums, counts, kernel))
        return scalar

    shapes = [((rows, width), dtype), ((num_dst * fanout,), jnp.int32),
              ((max(relations, 1), width, 16), jnp.float32),
              ((fanout, num_dst), jnp.int32)]
    # the input layer's rows are data; a hidden layer's are differentiated
    argnums = (0, 2) if case == "conv2" else 2

    def lowered(aggregate=layers.fanout_gather_sum):
        return jax.jit(jax.value_and_grad(loss(aggregate), argnums)).lower(*[
            jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
            for shape, dt in shapes])

    compiled = lowered().compile()
    traced = list(logged)
    pad = layers._target_pad(num_dst, fanout)
    lanes = fanout * (num_dst + pad)
    assert traced == ([(fanout, num_dst, pad)] if pad else [])
    assert _row_gathers(compiled.as_text()) == [(256, lanes)]
    assert _relayouts(compiled, lanes * width) == []
    if not pad:
        assert lowered().as_text() == lowered(_parent_gather_sum).as_text()

    monkeypatch.setattr(layers, "_target_pad", lambda num_dst, fanout: 0)
    plain = lowered().compile()
    gathered = lanes * width * jnp.dtype(dtype).itemsize
    assert (compiled.memory_analysis().temp_size_in_bytes
            - plain.memory_analysis().temp_size_in_bytes) < 0.02 * gathered
    if pad:
        assert _row_gathers(plain.as_text()) == [(128, fanout * num_dst)]


@pytest.mark.parametrize("nodes,edges,targets,fanout", [
    (232_965, 114_615_892, 30_208, 10),    # reddit-sage, hop 1
    (2_449_029, 123_718_280, 142_080, 5),  # products-sage, hop 2
])
def test_a_v5e_reads_the_neighbour_ids_as_rows_256_in_flight(
        one_chip, nodes, edges, targets, fanout):
    """``sample_layer`` at a cell's widest hop, compiled for a described
    v5e: the edge array's 2-D view is a bitcast of the argument, the one
    gather from it is a row gather that keeps 256 rows in flight, and the
    hop's temporaries are the gathered blocks once, not a padded copy of
    them. ``ops/sample.py::_gather_indices`` pads its lanes to 512 past a
    multiple of 1,024 for that: the same row gather over exactly
    ``targets * fanout`` lanes (reddit's fill whole 1,024-word tiles)
    keeps 128 in flight, 10 ns a row against 4 on the chip (PERF.md
    section 6, PR 38), which also shows that the check can see it.
    Nothing runs; this says nothing of results or times."""
    import re

    from quiver_tpu.core.topology import DeviceTopology
    from quiver_tpu.ops.sample import sample_layer

    def shape(dims, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    words = -(-edges // 128) * 128
    topo = DeviceTopology(shape((nodes + 1,)), shape((words,)),
                          edge_count=edges)
    compiled = jax.jit(
        lambda t, s, n, key: sample_layer(t, s, n, fanout, key)
    ).lower(topo, shape((targets,)), shape(()), shape((2,), jnp.uint32)
            ).compile()

    def row_gathers(text):
        """rows in flight of every gather fusion over the 2-D view"""
        return [int(re.search(r'"integer":"(\d+)"', line).group(1))
                for line in text.splitlines()
                if "kind=kCustom" in line and "/gather" in line
                and re.search(r"= s32\[\d+,128\]", line)]

    text = compiled.as_text()
    assert f"s32[{words // 128},128]" in text
    assert not re.search(
        rf"= s32\[{words}\]\S* (copy|pad|slice|concatenate)\(", text)
    assert not re.search(
        rf"= s32\[{words // 128},128\]\S* (copy|pad|slice|reshape)\(", text)
    assert row_gathers(text) == [256]
    lanes = targets * fanout
    assert compiled.memory_analysis().temp_size_in_bytes < 1.1 * lanes * 512

    plain = jax.jit(lambda blocks, blk: blocks[blk]).lower(
        shape((words // 128, 128)), shape((lanes,))).compile()
    expected = 128 if lanes % 1024 == 0 else 256
    assert row_gathers(plain.as_text()) == [expected]


# (table rows, width, dtype) of each one-chip cell's store, by the lanes of
# its frontier: mag240m's fill whole 1,024-word tiles; products-sage's,
# reddit-sage's and products-gat's fall short of them by 128 words or more
V5E_STORES = {
    425_984: (3_815_008, 768, jnp.float16),   # mag240m-rsage, mag240m-rgat
    672_384: (2_449_029, 100, jnp.float32),   # products-sage
    195_328: (232_965, 602, jnp.float32),     # reddit-sage
    447_104: (2_449_029, 100, jnp.float32),   # products-gat
}


@pytest.mark.parametrize("lanes", list(V5E_STORES))
def test_a_v5e_gathers_the_store_rows_256_in_flight(
        one_chip, lanes, monkeypatch):
    """``tiered_lookup`` over a plain hot tier, as ``Feature[...]`` and the
    fused step call it, compiled for a described v5e at a cell's lanes: the
    one ``tier_hot`` row gather keeps 256 rows in flight over the lanes
    padded by ``ops.gather.tile_pad`` (which says so once), and no copy or
    slice of the padded rows stands outside a fusion; the padded rows are
    the one temporary of the lone lookup (in a cell's whole step they fit
    under its peak: PERF.md section 6). Unpadded, mag's lanes keep 128 in flight (PERF.md
    section 7 (3)), which also shows that the check can see it. Lanes that
    take no pad lower to the unpadded gather's text. Nothing runs; this
    says nothing of results or times."""
    from quiver_tpu.feature.feature import tiered_lookup
    from quiver_tpu.ops import gather

    rows, width, dtype = V5E_STORES[lanes]
    logged = []
    monkeypatch.setattr(
        gather, "info_once", lambda key, msg, *args: logged.append(args))

    def lowered(hot_gather):
        return jax.jit(lambda table, n_id: tiered_lookup(
            n_id, None, rows, lambda ids: hot_gather(table, ids), None,
        )).lower(jax.ShapeDtypeStruct((rows, width), dtype, sharding=one_chip),
                 jax.ShapeDtypeStruct((lanes,), jnp.int32, sharding=one_chip))

    def plain(table, ids):
        return table[ids]

    padded = lowered(gather.padded_row_gather)
    pad = gather.tile_pad(lanes)
    if not pad:
        assert logged == []
        assert padded.as_text() == lowered(plain).as_text()
        return
    assert logged == [(lanes, pad)]
    compiled = padded.compile()
    assert _row_gathers(compiled.as_text()) == [(256, lanes + pad)]
    assert _relayouts(compiled, lanes * width) == []
    assert (compiled.memory_analysis().temp_size_in_bytes
            < 1.01 * (lanes + pad) * width * jnp.dtype(dtype).itemsize)
    assert _row_gathers(lowered(plain).compile().as_text()) == [(128, lanes)]


def test_fanout_softmax_matches_segment_softmax():
    """The dense softmax over each target's ``fanout`` lanes and its self
    term against the segment softmax over the same lanes with one self
    edge per target appended."""
    from quiver_tpu.models.layers import fanout_softmax, segment_softmax

    rng = np.random.default_rng(0)
    S, K, H = 12, 6, 3
    logits = jnp.asarray(rng.normal(size=(S * K, H)).astype(np.float32))
    own = jnp.asarray(rng.normal(size=(S, H)).astype(np.float32))
    valid = np.asarray(rng.random(S * K) < 0.7)
    valid[2 * K:3 * K] = False  # a target with no valid lane
    valid = jnp.asarray(valid)
    dst = jnp.repeat(jnp.arange(S), K)
    seg = jnp.concatenate([jnp.where(valid, dst, S), jnp.arange(S)])
    a_seg = segment_softmax(
        jnp.concatenate([logits, own]), seg,
        jnp.concatenate([valid, jnp.ones(S, bool)]), S)
    a_dense, a_own = fanout_softmax(logits, own, valid, S, K)
    # compare on valid lanes only (invalid lanes: dense gives 0, segment
    # gives exp(min)/tiny garbage that callers mask anyway)
    m = np.asarray(valid)
    np.testing.assert_allclose(
        np.asarray(a_dense)[m], np.asarray(a_seg)[:S * K][m],
        rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(a_own), np.asarray(a_seg)[S * K:], rtol=1e-5, atol=1e-6)
    assert np.all(np.asarray(a_dense)[~m] == 0)
    # each target's weights, its own among them, sum to 1; alone it is 1
    sums = np.asarray(a_own).copy()
    np.add.at(sums, np.asarray(dst)[m], np.asarray(a_dense)[m])
    np.testing.assert_allclose(sums, 1.0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(a_own)[2], 1.0, atol=1e-6)


# index blocks (targets, fanout) over ROWS rows for ``gather_lane_rows``,
# NUM_DST of them targets; -1 is a padded lane
ROWS, NUM_DST = 20, 6


def _lanes_random(rng):
    idx = rng.integers(0, ROWS - 2, (NUM_DST, 5)).astype(np.int32)
    idx[rng.random(idx.shape) < 0.35] = -1
    return idx


def _lanes_of(*rows):
    return lambda rng: np.asarray(rows, np.int32)


LANE_BLOCKS = {
    "padded lanes and repeats": _lanes_random,
    "one source twice within a target": _lanes_of(
        [7, 7, -1], [8, 9, -1], [10, -1, -1]),
    "one source across targets": _lanes_of(
        [7, 8, -1], [7, 9, -1], [-1, 7, 8]),
    "a source below num_dst": _lanes_of(
        [2, 7, -1], [0, 2, 2], [5, -1, 0]),
    "a row no lane names": _lanes_of([7, 9], [9, 11]),
    "every lane padded": _lanes_of([-1, -1, -1], [-1, -1, -1]),
    "no lane padded": _lanes_of(
        [7, 8, 9], [7, 10, 11], [12, 7, 19]),
    # 7 repeats, chunks of 4 and of 3: the last trip is part empty
    "repeats not a multiple of the chunk": _lanes_of(
        [7, 7, 7, 7], [7, 8, 8, 8], [9, 9, -1, 10]),
}


@pytest.mark.parametrize("chunk", [3, 4, 1024])
@pytest.mark.parametrize("case", list(LANE_BLOCKS))
def test_gather_lane_rows_transposes_as_the_plain_gather(
        case, chunk, monkeypatch):
    """``gather_lane_rows`` is ``h[clip(idx, 0)]`` and ``h[:num_head]``
    forward, and its rule (one lane a row by a row gather, the head's
    cotangent added in the same pass, the repeats scatter-added in chunks,
    padded lanes dropped) gives the gradient ``jax.grad`` of the plain
    gather and slice gives once the padded lanes are masked, to 1e-6:
    whatever the repeats' number is to the chunk, compiled or not."""
    from quiver_tpu.models import layers

    monkeypatch.setattr(layers, "_REPEAT_CHUNK", chunk)
    rng = np.random.default_rng(5)
    idx = jnp.asarray(LANE_BLOCKS[case](rng))
    h = jnp.asarray(rng.normal(size=(ROWS, 2, 3)).astype(np.float32))
    weight = jnp.asarray(
        rng.normal(size=idx.shape + (2, 3)).astype(np.float32))
    head_weight = jnp.asarray(
        rng.normal(size=(NUM_DST, 2, 3)).astype(np.float32))
    live = (idx >= 0)[..., None, None]

    def through(gather):
        def scalar(h):
            rows, head = gather(h)
            return ((jnp.where(live, rows, 0.0) * weight).sum()
                    + (head * head_weight).sum()), (rows, head)
        return scalar

    new = through(lambda h: layers.gather_lane_rows(h, idx, NUM_DST))
    plain = through(lambda h: (h[jnp.clip(idx, 0)], h[:NUM_DST]))
    with jax.default_matmul_precision("highest"):
        (_, want_out), want = jax.value_and_grad(plain, has_aux=True)(h)
        (_, out), got = jax.value_and_grad(new, has_aux=True)(h)
        _, jitted = jax.jit(jax.value_and_grad(new, has_aux=True))(h)
    for a, b in zip(out, want_out):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for grad in (got, jitted):
        np.testing.assert_allclose(
            np.asarray(grad), np.asarray(want), rtol=1e-6, atol=1e-6)
    # a row that no lane names and that is no target gets exactly nothing
    unnamed = np.setdiff1d(
        np.arange(NUM_DST, ROWS), np.asarray(idx).reshape(-1))
    assert unnamed.size and not np.asarray(got)[unnamed].any()


def test_gather_lane_rows_plan_names_every_row_once():
    """The two sorts' plan: each named row gets one of its own lanes, the
    other valid lanes are the repeats, each once, and the padding of the
    compacted arrays points out of range."""
    from quiver_tpu.models.layers import _lane_plan

    rng = np.random.default_rng(6)
    flat = rng.integers(-1, 9, 40).astype(np.int32)
    lane_of, rep_rows, rep_lanes, num_rep = map(
        np.asarray, _lane_plan(jnp.asarray(flat), 12, 8))
    named = np.unique(flat[flat >= 0])
    assert set(np.flatnonzero(lane_of >= 0)) == set(named)
    assert all(flat[lane_of[r]] == r for r in named)
    n = int(num_rep)
    assert n == (flat >= 0).sum() - named.size
    assert rep_rows.size == rep_lanes.size == 48 and rep_rows.size % 8 == 0
    assert (flat[rep_lanes[:n]] == rep_rows[:n]).all()
    assert (rep_rows[n:] == 12).all()
    lanes = np.concatenate([lane_of[lane_of >= 0], rep_lanes[:n]])
    assert sorted(lanes) == sorted(np.flatnonzero(flat >= 0))


def test_gather_lane_rows_refuses_lanes_its_keys_cannot_hold():
    from quiver_tpu.models.layers import _lane_plan

    with pytest.raises(ValueError, match="packed int32 keys"):
        _lane_plan(jnp.zeros(4, jnp.int32), 2 ** 30, 4)


def test_zero_scatter_counts_matches_bincount():
    from quiver_tpu.models.layers import zero_scatter_counts

    rng = np.random.default_rng(1)
    ids = rng.integers(0, 50, 1000)
    valid = rng.random(1000) < 0.8
    got = np.asarray(zero_scatter_counts(
        jnp.asarray(ids), jnp.asarray(valid), 50))
    want = np.bincount(ids[valid], minlength=50)
    np.testing.assert_array_equal(got, want.astype(np.float32))


def test_trainer_fused_step_rebuilds_fanout_correctly():
    """The fused-step Adj rebuild must restore each layer's OWN fanout
    (regression: stacked arrays lose the static metadata, and a wrong
    pairing silently falls back to the scatter path)."""
    rng = np.random.default_rng(7)
    topo = CSRTopo(edge_index=rng.integers(0, 400, (2, 6000)).astype(np.int64))
    sampler = GraphSageSampler(topo, [9, 4], seed_capacity=32, seed=0)
    out = sampler.sample(np.arange(32))
    caps = tuple(a.size[0] for a in out.adjs)[::-1]  # seeds-outward order

    # replicate _compiled_step's rebuild: deepest-first sizes + caps
    from quiver_tpu.parallel.trainer import DataParallelTrainer

    adj_sizes = DataParallelTrainer._adj_sizes(
        type("T", (), {"local_batch": 32})(), caps
    )
    fanouts = tuple(sampler.sizes)[::-1]
    for a, sz, f in zip(out.adjs, adj_sizes, fanouts):
        rebuilt = Adj(a.edge_index, None, sz, fanout=f)
        # the dense-path gate must hold for every rebuilt layer
        assert rebuilt.edge_index.shape[1] == rebuilt.size[1] * rebuilt.fanout
        assert rebuilt.size == a.size and rebuilt.fanout == a.fanout


def test_occurrence_counts_strategies_agree(monkeypatch):
    from quiver_tpu.models import layers

    rng = np.random.default_rng(2)
    ids = jnp.asarray(rng.integers(0, 40, 500))
    valid = jnp.asarray(rng.random(500) < 0.6)
    # the strategy is pinned once per process (ADVICE #1: no trace-time env
    # reads inside jitted model code), so flipping QUIVER_COUNTS requires
    # resetting the cache — which is exactly what a live model can NOT do
    monkeypatch.setenv("QUIVER_COUNTS", "scan")
    monkeypatch.setattr(layers, "_counts_strategy", None)
    a = np.asarray(layers.occurrence_counts(ids, valid, 40))
    assert layers.resolve_counts_strategy() == "scan"
    monkeypatch.setenv("QUIVER_COUNTS", "scatter")
    # without a reset the pinned strategy stays — env after first trace is
    # inert by contract
    assert layers.resolve_counts_strategy() == "scan"
    monkeypatch.setattr(layers, "_counts_strategy", None)
    b = np.asarray(layers.occurrence_counts(ids, valid, 40))
    assert layers.resolve_counts_strategy() == "scatter"
    np.testing.assert_array_equal(a, b)
    monkeypatch.setattr(layers, "_counts_strategy", None)  # leave no pin
