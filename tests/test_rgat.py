"""The MAG240M R-GAT cell of the benchmark and its layer, on the CPU at tiny
sizes.

``models/rgat.py`` takes one softmax per target, relation and head over the
sampled lanes (``layers.fanout_relation_softmax``), aggregates the rows
before it transforms them, and agrees with the plain reference
(``chipbench/reference/rgat.py``, which projects, then attends, per
relation) on the loss and on every gradient leaf; the cell runs through the
harness and counts its non-empty groups (``sample.relation_targets``).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench import check
from quiver_tpu.models.layers import (
    _fanout_index, _target_pad, fanout_relation_softmax, segment_softmax)
from quiver_tpu.models.rgat import RGAT, RelGATConv
from tests.test_relational import cell_run, reference_block, sampled

CELL = "mag240m-rgat.hbm"
CFG = {"feature_dim": 16, "hidden": 8, "heads": 2, "classes": 4,
       "relations": 5, "layers": 2, "dropout": 0.0}


def test_the_grouped_softmax_is_a_segment_softmax_per_target_and_relation():
    """Against ``segment_softmax`` over the lanes keyed by ``t * R + r``:
    the same weights, each group's summing to 1, and 0 on a lane in no
    group; a group with no lane (target 0 has none of relation 4, target 1
    no valid lane at all) leaves nothing behind."""
    R, H, K, T = 5, 3, 6, 40
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(H, K, T)) * 4, jnp.float32)
    relation = rng.integers(0, R, (K, T)).astype(np.int8)
    relation[rng.random((K, T)) < 0.3] = -1
    relation[:, 0] = np.where(relation[:, 0] == 4, 3, relation[:, 0])
    relation[:, 1] = -1
    got = np.asarray(fanout_relation_softmax(logits, jnp.asarray(relation), R))
    valid = relation >= 0
    key = np.where(valid, np.arange(T)[None] * R + relation, 0)
    want = segment_softmax(logits.reshape(H, -1).T, jnp.asarray(key.reshape(-1)),
                           jnp.asarray(valid.reshape(-1)), T * R)
    want = np.where(valid.reshape(-1), np.asarray(want).T, 0).reshape(H, K, T)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)
    assert not got[:, ~valid].any()
    for t in range(T):
        for r in set(relation[:, t][valid[:, t]].tolist()):
            np.testing.assert_allclose(
                got[:, relation[:, t] == r, t].sum(axis=1), 1, rtol=1e-6)
    assert not got[:, :, 1].any()
    # no NaN in the gradient, whatever the empty groups
    grad = jax.grad(lambda g: (fanout_relation_softmax(
        g, jnp.asarray(relation), R) ** 2).sum())(logits)
    assert np.isfinite(np.asarray(grad)).all()
    assert not np.asarray(grad)[:, ~valid].any()


def block_with_everything(seed):
    """A sampled block (``test_relational.sampled``) that has what the
    comparison must cover: padded targets, no lane of relation 3 anywhere,
    a target with no lane of a relation that the layer carries, and an
    input layer of 4 x 256 lanes (whole 1,024-word tiles: its row gather is
    padded)."""
    out, x, rows = sampled(seed=seed, absent=(3,))
    adjs = out.adjs
    assert [(a.fanout, a.size[1]) for a in adjs] == [(4, 256), (6, 128)]
    assert _target_pad(256, 4) > 0
    for adj in adjs:
        relation = np.asarray(adj.relation)
        count = int(adj.dst_count)
        assert count < adj.size[1]                      # padded targets
        assert not (relation == 3).any()                # a relation absent
        has = [(relation[:, :count] == r).any(axis=0) for r in (0, 2)]
        assert any(h.any() and not h.all() for h in has)
    return out, x, rows


def program_loss_and_grads(out, x, labels, weights):
    from chipbench.models import rgat as program_side
    from quiver_tpu.parallel.train import cross_entropy_on_seeds

    model = program_side.build(CFG)
    T = out.adjs[-1].size[1]

    def loss_fn(params):
        logits = model.apply({"params": params}, x, out.adjs)
        return cross_entropy_on_seeds(
            logits[:T], labels[jnp.clip(out.n_id[:T], 0)],
            jnp.arange(T) < out.batch_size)

    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(loss_fn)(
            program_side.to_program_tree(weights))
    return loss, program_side.from_program_tree(grads, 2)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_model_agrees_with_the_plain_reference(seed):
    """Seeded random weights through the harness's own trees: the program's
    loss and every gradient leaf against ``reference/rgat.py``'s; the leaves
    of the relation no lane carries stay zero on both sides, those of a
    relation with lanes move."""
    from chipbench.reference import rgat as plain

    out, x, rows = block_with_everything(seed)
    weights = plain.make_weights(CFG, np.random.default_rng(seed))
    labels = jnp.asarray(np.random.default_rng(seed).integers(0, 4, 400),
                         jnp.int32)
    loss, grads = program_loss_and_grads(out, x, labels, weights)
    want_loss, want_grads = plain.loss_and_grads(
        weights, rows, labels, reference_block(out))
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    got, want = plain.leaf_norms(grads), plain.leaf_norms(want_grads)
    assert set(got) == set(want)
    assert check.worst_leaf_gap(got, want) < 1e-4
    for layer in ("layer0", "layer1"):
        for name in ("w_rel", "a_src", "a_dst", "b_rel"):
            assert got[f"{layer}.{name}3"] == want[f"{layer}.{name}3"] == 0
        assert want[f"{layer}.w_rel0"] > 0 and want[f"{layer}.a_src2"] > 0


def combined(model, params, x, adjs):
    """What each layer hands its batch norm."""
    _, state = model.apply({"params": params}, x, adjs,
                           mutable="intermediates")
    return [np.asarray(state["intermediates"][f"conv{i}"]["combined"][0])
            for i in range(len(adjs))]


def test_an_absent_relation_adds_no_bias_and_a_missing_one_its_bias_alone():
    """Relation 3 occurs on no edge: its bias moves nothing. Relation 0
    occurs in the layer: its bias reaches every target, those with no lane
    of it too (a zero message plus the bias), as the batch norm's input
    shows."""
    out, x, _ = block_with_everything(4)
    model = RGAT(hidden=8, heads=2, num_classes=4, num_relations=5)
    params = model.init(jax.random.PRNGKey(0), x, out.adjs)["params"]

    def with_bias(r, value):
        p = jax.tree_util.tree_map(lambda a: a, params)
        for conv in ("conv0", "conv1"):
            p[conv]["rel_bias"] = p[conv]["rel_bias"].at[r].set(value)
        return combined(model, p, x, out.adjs)

    for got, want in zip(with_bias(3, 5.0), with_bias(3, 0.0)):
        np.testing.assert_array_equal(got, want)
    for got, want in zip(with_bias(0, 5.0), with_bias(0, 0.0)):
        np.testing.assert_allclose(got - want, 5.0, rtol=1e-5)


def lane_by_lane(params, x, adj, heads):
    """The layer's input to its batch norm in the literal order, on the
    fanout lanes: each lane's row projected by its relation's weight, the
    grouped softmax, the weighted projected rows summed over the fanout."""
    conv = params
    T, K = adj.size[1], adj.fanout
    R, F, out = conv["rel_kernel"].shape
    idx = _fanout_index(adj.edge_index[0], T, K, x.shape[0])[:, :T]
    rel = jnp.where(idx >= 0, adj.relation, -1)
    rows = x[jnp.clip(idx, 0)].astype(jnp.float32)                # (K, T, F)
    kernel = conv["rel_kernel"][jnp.clip(rel, 0)]                 # (K, T, F, out)
    z = jnp.einsum("ktf,ktfo->kto", rows, kernel).reshape(K, T, heads, -1)
    w_t = conv["rel_kernel"].reshape(R, F, heads, -1)
    z_t = jnp.einsum("tf,rfhc->rthc", x[:T].astype(jnp.float32), w_t)
    src_att = conv["att_src"][jnp.clip(rel, 0)]                   # (K, T, H, C)
    dst_att = conv["att_dst"][jnp.clip(rel, 0)]
    logits = ((z * src_att).sum(-1)
              + (z_t[jnp.clip(rel, 0), jnp.arange(T)[None]] * dst_att).sum(-1))
    alpha = fanout_relation_softmax(
        jax.nn.leaky_relu(logits, 0.2).transpose(2, 0, 1), rel, R)
    msg = (alpha.transpose(1, 2, 0)[..., None] * z).sum(axis=0).reshape(T, out)
    present = jnp.stack([(rel == r).any() for r in range(R)])
    skip = x[:T].astype(jnp.float32) @ conv["skip"]["kernel"] + conv["skip"]["bias"]
    return msg + jnp.where(present[:, None], conv["rel_bias"], 0).sum(0) + skip


def test_aggregating_first_equals_projecting_each_lane():
    """The program's order (the rows summed by ``(target, relation, head)``,
    then each sum times its block of ``W_r``; the logits' source terms from
    ``W_r,h a_src[r,h]``) against the literal order on the same lanes: the
    same numbers to float32 round-off, at both layers."""
    out, x, _ = block_with_everything(5)
    model = RGAT(hidden=8, heads=2, num_classes=4, num_relations=5)
    params = model.init(jax.random.PRNGKey(1), x, out.adjs)["params"]
    conv = RelGATConv(8, 2, 5)
    h = x
    with jax.default_matmul_precision("highest"):
        for i, adj in enumerate(out.adjs):
            layer = {"params": params[f"conv{i}"]}
            after, state = conv.apply(layer, h, adj, mutable="intermediates")
            want = lane_by_lane(params[f"conv{i}"], h, adj, 2)
            np.testing.assert_allclose(
                np.asarray(state["intermediates"]["combined"][0]),
                np.asarray(want), rtol=2e-5, atol=2e-5)
            h = after


# -- the benchmark's cell, by its files --------------------------------------

def test_the_cell_is_correct_and_counts_its_groups(monkeypatch):
    """The tiny cell through the harness is correct, and the trainer's
    registry counts each hop's valid targets with a lane of each relation:
    at the seeds' hop (papers) only relations 0 and 2 have one, and no hop
    has more such targets than lanes of the relation."""
    from chipbench.adapter import Program

    seen = []
    real = Program.step

    def step(self, seeds, key):
        loss = real(self, seeds, key)
        value = self.trainer.metrics.value
        seen.append((np.asarray(value("sample.relation_targets")),
                     np.asarray(value("sample.relation_lanes"))))
        return loss

    monkeypatch.setattr(Program, "step", step)
    result = cell_run(monkeypatch, cell=CELL)
    assert result["correct"] is True, result["compared"]
    targets, lanes = seen[0]
    assert targets.shape == (2, 5)
    assert targets[0, [1, 3, 4]].sum() == 0 and targets[0, [0, 2]].all()
    assert targets[0].max() <= 64
    assert (targets <= lanes).all() and ((targets > 0) == (lanes > 0)).all()


def test_a_bent_relation_comes_out_not_correct(monkeypatch):
    """A lane's relation altered where the program produced it: the graph
    file's ``lane_faults`` finds it, ``block_faults`` counts it and the run
    is not correct."""
    from chipbench.adapter import Program

    real = Program.blocks

    def altered(self, seeds, key):
        blocks = real(self, seeds, key)
        src = blocks[0].layers[1][0]
        carried = blocks[0].lane_data[1]["relation"]
        lane = int(np.flatnonzero(src >= 0)[0])
        carried[lane] = (carried[lane] + 2) % 5
        return blocks

    monkeypatch.setattr(Program, "blocks", altered)
    result = cell_run(monkeypatch, cell=CELL)
    assert result["correct"] is False
    failed = {n for n, r in result["compared"].items()
              if r["value"] > r["limit"]}
    assert "block_faults" in failed
