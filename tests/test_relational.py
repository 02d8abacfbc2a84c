"""Edge relations from the CSR to the model, and the MAG240M R-GraphSAGE
cell of the benchmark, on the CPU at tiny sizes.

Each sampled lane carries its edge's relation, read from the packed edge
word the sampler fetches (``core/topology.py``, ``ops/sample.py``) and handed
on fanout-major in ``Adj.relation``; ``models/rsage.py`` takes a mean per
relation and normalises over the valid targets; the program agrees with the
plain reference (``chipbench/reference/rsage.py``) on the loss and on every
gradient leaf; a topology without relations lowers to the text it did.
"""

import argparse
import hashlib
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import quiver_tpu as quiver
from chipbench import check, inputs, spec
from chipbench import run as harness
from chipbench.tests import tiny
from quiver_tpu.models.layers import masked_batch_norm
from quiver_tpu.models.rsage import RGraphSAGE
from quiver_tpu.sampling.sampler import multilayer_sample

CELL = "mag240m-rsage.hbm"


def typed_graph(nodes=400, relations=5, seed=0):
    rng = np.random.default_rng(seed)
    deg = rng.integers(1, 20, nodes)
    indptr = np.zeros(nodes + 1, np.int64)
    np.cumsum(deg, out=indptr[1:])
    indices = rng.integers(0, nodes, int(indptr[-1])).astype(np.int32)
    relation = rng.integers(0, relations, indices.shape[0]).astype(np.int8)
    return indptr, indices, relation


def csr_faults(indptr, indices, relation, n_id, adj):
    """Lanes whose (target, source, relation) is no edge of the CSR, and
    padded lanes that carry a relation."""
    src, dst = np.asarray(adj.edge_index)
    carried = np.asarray(adj.relation).T.reshape(-1)
    bad = int((carried[src < 0] != -1).sum())
    for s, d, r in zip(src[src >= 0], dst[src >= 0], carried[src >= 0]):
        row = slice(indptr[n_id[d]], indptr[n_id[d] + 1])
        bad += not ((indices[row] == n_id[s]) & (relation[row] == r)).any()
    return bad


@pytest.mark.parametrize("mode", ["HBM", "HOST"])
def test_every_lane_carries_its_edges_relation(mode):
    indptr, indices, relation = typed_graph()
    topo = quiver.CSRTopo(indptr=indptr, indices=indices,
                          edge_relation=relation)
    sampler = quiver.GraphSageSampler(topo, [6, 4], mode=mode,
                                      frontier_caps=[256, 400])
    out = sampler.sample(np.arange(40))
    n_id = np.asarray(out.n_id)
    assert [a.relation.shape for a in out.adjs] == [(4, 256), (6, 128)]
    assert [int(a.dst_count) for a in out.adjs] == [
        int(out.adjs[1].edge_index[0].max()) + 1, 40]
    for adj in out.adjs:
        assert adj.relation.dtype == jnp.int8
        assert csr_faults(indptr, indices, relation, n_id, adj) == 0
    # the node ids are the plain sampler's, draw for draw
    plain = quiver.GraphSageSampler(
        quiver.CSRTopo(indptr=indptr, indices=indices), [6, 4], mode=mode,
        frontier_caps=[256, 400])
    plain._call = sampler._call - 1
    again = plain.sample(np.arange(40))
    assert np.array_equal(np.asarray(again.n_id), n_id)
    assert all(a.relation is None and a.dst_count is None for a in again.adjs)


def test_relations_follow_the_edges_through_time_sort_and_save(tmp_path):
    indptr, indices, relation = typed_graph(nodes=50)
    topo = quiver.CSRTopo(indptr=indptr, indices=indices,
                          edge_relation=relation)
    pairs = set(zip(np.repeat(np.arange(50), np.diff(indptr)), indices,
                    relation))
    topo.set_edge_time(np.random.default_rng(1).random(topo.edge_count),
                       coo_order=False)
    rows = np.repeat(np.arange(50), np.diff(topo.indptr))
    assert set(zip(rows, topo.indices, topo.edge_relation)) == pairs
    for fmt in ("npz", "raw"):
        path = str(tmp_path / f"topo_{fmt}")
        topo.save(path, format=fmt)
        back = quiver.CSRTopo.load(path)
        assert np.array_equal(back.edge_relation, topo.edge_relation)
    with pytest.raises(ValueError):
        quiver.CSRTopo(indptr=indptr, indices=indices,
                       edge_relation=relation.astype(np.int32) + 200)


def test_what_cannot_carry_relations_refuses_them():
    indptr, indices, relation = typed_graph(nodes=50)
    topo = quiver.CSRTopo(indptr=indptr, indices=indices,
                          edge_relation=relation)
    with pytest.raises(ValueError, match="relations"):
        quiver.GraphSageSampler(topo, [3], kernel="pallas")
    with pytest.raises(ValueError, match="relations"):
        topo._publish_mutation(topo.indptr, topo.indices)
    with pytest.raises(ValueError):
        quiver.CSRTopo(indptr=indptr, indices=indices).to_device(
            with_relations=True)


def test_a_topology_without_relations_lowers_to_the_text_it_did():
    """The sampler over a topology that carries no relation lowers to the
    StableHLO it lowered to before relations existed (digest of the text at
    the parent tree): the relation is compiled in only where the topology
    has one."""
    from quiver_tpu.utils.graphgen import generate_pareto_graph

    topo = quiver.CSRTopo(edge_index=generate_pareto_graph(300, 6.0, seed=0))
    dev = topo.to_device()

    def sample(t, seeds, key):
        return multilayer_sample(t, seeds, jnp.int32(8), key, (3, 2),
                                 (32, 64))

    text = jax.jit(sample).lower(dev, jnp.arange(8, dtype=jnp.int32),
                                 jax.random.PRNGKey(0)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "5933ea5d6da6314cdab9b445e968d6b9999c96734e49aa156405019040a93190")


def test_batch_norm_leaves_the_padded_targets_out():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(12, 5)), jnp.float32)
    valid = jnp.arange(12) < 7
    scale, bias = jnp.full((5,), 1.5), jnp.full((5,), 0.25)
    got = masked_batch_norm(x, valid, scale, bias)
    rows = np.asarray(x[:7], np.float64)
    want = ((rows - rows.mean(0)) / np.sqrt(rows.var(0) + 1e-5)) * 1.5 + 0.25
    np.testing.assert_allclose(np.asarray(got[:7]), want, rtol=1e-5,
                               atol=1e-5)
    assert not np.asarray(got[7:]).any()
    # the padded rows' values move nothing
    moved = masked_batch_norm(x.at[7:].set(1e3), valid, scale, bias)
    np.testing.assert_array_equal(np.asarray(moved), np.asarray(got))


def sampled(relations=5, caps=(256, 400), seed=0, absent=()):
    indptr, indices, relation = typed_graph(relations=relations, seed=seed)
    for r in absent:
        relation[relation == r] = 0
    topo = quiver.CSRTopo(indptr=indptr, indices=indices,
                          edge_relation=relation)
    sampler = quiver.GraphSageSampler(topo, [6, 4], frontier_caps=list(caps))
    out = sampler.sample(np.arange(40))
    rows = jnp.asarray(np.random.default_rng(seed + 1).normal(
        size=(400, 16)).astype(np.float16))
    x = jnp.where((out.n_id >= 0)[:, None], rows[jnp.clip(out.n_id, 0)], 0)
    return out, x, rows


def test_a_relation_no_lane_carries_adds_no_bias():
    """Relation 3 occurs on no edge: whatever its bias, what each layer
    hands its batch norm is the same; a relation that occurs adds its bias
    to every target (which the batch norm then cancels)."""
    out, x, _ = sampled(absent=(3,))
    model = RGraphSAGE(hidden=8, num_classes=4, num_relations=5)
    params = model.init(jax.random.PRNGKey(0), x, out.adjs)["params"]

    def combined(r, value):
        p = jax.tree_util.tree_map(lambda a: a, params)
        for conv in ("conv0", "conv1"):
            p[conv]["rel_bias"] = p[conv]["rel_bias"].at[r].set(value)
        _, state = model.apply({"params": p}, x, out.adjs,
                               mutable="intermediates")
        return [np.asarray(state["intermediates"][conv]["combined"][0])
                for conv in ("conv0", "conv1")]

    base = combined(3, 0.0)
    for got, want in zip(combined(3, 5.0), base):
        np.testing.assert_array_equal(got, want)
    for got, want in zip(combined(1, 5.0), combined(1, 0.0)):
        np.testing.assert_allclose(got - want, 5.0, rtol=1e-5)


def reference_block(out):
    from chipbench.reference.graph import Block

    block = Block(np.asarray(out.n_id), [], int(out.batch_size))
    for adj in out.adjs:
        src, dst = np.asarray(adj.edge_index)
        block.layers.append((src, dst, int(adj.size[1])))
        block.lane_data.append(
            {"relation": np.asarray(adj.relation).T.reshape(-1)})
    return block


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_model_agrees_with_the_plain_reference(seed):
    """Seeded random weights through the harness's own trees: the program's
    loss and every gradient leaf against ``reference/rsage.py``'s, on a
    block whose targets are padded and whose every relation occurs."""
    from chipbench.models import rsage as program_side
    from chipbench.reference import rsage as plain
    from quiver_tpu.parallel.train import cross_entropy_on_seeds

    out, x, rows = sampled(seed=seed)
    cfg = {"feature_dim": 16, "hidden": 8, "classes": 4, "relations": 5,
           "layers": 2, "dropout": 0.0}
    weights = plain.make_weights(cfg, np.random.default_rng(seed))
    labels = jnp.asarray(np.random.default_rng(seed).integers(0, 4, 400),
                         jnp.int32)
    model = program_side.build(cfg)
    n_id = out.n_id

    def loss_fn(params):
        logits = model.apply({"params": params}, x, out.adjs)
        seeds = jnp.arange(out.adjs[-1].size[1]) < out.batch_size
        return cross_entropy_on_seeds(
            logits[:out.adjs[-1].size[1]],
            labels[jnp.clip(n_id[:out.adjs[-1].size[1]], 0)], seeds)

    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(loss_fn)(
            program_side.to_program_tree(weights))
    want_loss, want_grads = plain.loss_and_grads(
        weights, rows, labels, reference_block(out))
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    got = plain.leaf_norms(program_side.from_program_tree(grads, 2))
    want = plain.leaf_norms(want_grads)
    assert set(got) == set(want)
    assert check.worst_leaf_gap(got, want) < 1e-4
    # a relation with lanes at some layer moves its weights there
    assert want["layer0.w_rel4"] > 0 and want["layer1.w_rel2"] > 0


# -- the benchmark's cell, by its files --------------------------------------

def cell_run(monkeypatch, seed=11, cell=CELL):
    monkeypatch.setattr(spec, "load_config", tiny.tiny_config)
    args = argparse.Namespace(workload=cell, seed=seed, seconds=0.5, trace=0)
    return harness.run(args, jax.devices()[:1], harness.CompileMeter(),
                       time.perf_counter(), {})


def test_the_graph_is_three_types_and_five_relations_with_their_transposes():
    cfg = tiny.tiny_config("mag240m-rsage")
    data = inputs.make_inputs(cfg, 5)
    graph = spec.load_graph("mag240m")
    sizes = graph._sizes(cfg)
    papers, authors = sizes["papers"], sizes["authors"]
    assert data.features.dtype == np.float16
    assert data.features.shape == (3000, 768)
    assert data.indptr[-1] == 60000 == data.indices.shape[0]
    kind = np.repeat([0, 1, 2], [papers, authors, sizes["institutions"]])
    rows = np.repeat(np.arange(3000), np.diff(data.indptr))
    pair = {(0, 0): 0, (1, 0): 1, (0, 1): 2, (1, 2): 3, (2, 1): 4}
    rel = data.edge_data["relation"]
    want = np.array([pair.get((a, b), -1) for a, b in
                     zip(kind[rows], kind[data.indices])])
    assert np.array_equal(rel, want)
    # every relation is its partner's transpose, edge for edge
    keys = lambda r: np.sort(rows[rel == r] * 3000 + data.indices[rel == r])
    flip = lambda r: np.sort(data.indices[rel == r].astype(np.int64) * 3000
                             + rows[rel == r])
    assert np.array_equal(keys(0), flip(0))
    assert np.array_equal(keys(1), flip(2)) and np.array_equal(keys(3), flip(4))
    seeds = data.seed_nodes
    assert seeds.shape == (512,) and seeds.max() < papers
    bumped = data.features[seeds, data.labels[seeds]].astype(np.float32)
    assert bumped.mean() > 2.5


@pytest.mark.parametrize("bent", ["one lane's relation", "no lane carries any"])
def test_a_bent_relation_comes_out_not_correct(monkeypatch, bent):
    """A lane's relation altered where the program produced it: the graph
    file's ``lane_faults`` finds it, ``block_faults`` counts it and the run
    is not correct (on the relation alone)."""
    from chipbench.adapter import Program

    real = Program.blocks

    def altered(self, seeds, key):
        blocks = real(self, seeds, key)
        if bent == "no lane carries any":
            blocks[0].lane_data = []
            return blocks
        src = blocks[0].layers[1][0]
        carried = blocks[0].lane_data[1]["relation"]
        lane = int(np.flatnonzero(src >= 0)[0])
        carried[lane] = (carried[lane] + 2) % 5
        return blocks

    monkeypatch.setattr(Program, "blocks", altered)
    result = cell_run(monkeypatch)
    assert result["correct"] is False
    assert result["compared"]["block_faults"]["value"] > 0
    failed = {n for n, r in result["compared"].items()
              if r["value"] > r["limit"]}
    assert "block_faults" in failed


def test_the_cell_reports_its_relation_lanes(monkeypatch):
    """The trainer's registry counts the lanes of each relation at each
    hop, from the lanes the model gets: at the seeds' hop (papers) only
    relations 0 and 2 occur."""
    from chipbench.adapter import Program

    seen = []
    real = Program.step

    def step(self, seeds, key):
        loss = real(self, seeds, key)
        seen.append(np.asarray(self.trainer.metrics.value(
            "sample.relation_lanes")))
        return loss

    monkeypatch.setattr(Program, "step", step)
    result = cell_run(monkeypatch)
    assert result["correct"] is True, result["compared"]
    lanes = seen[0]
    assert lanes.shape == (2, 5)
    assert lanes[0, [1, 3, 4]].sum() == 0 and lanes[0, [0, 2]].all()
    assert lanes[1, 4] == 0 and lanes[1, :4].all()
