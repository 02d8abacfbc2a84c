"""Tests of the benchmark itself, on the CPU at a tiny size.

    python3 -m pytest chipbench/tests -q

They show that the data files load and agree with ``BENCHMARK.json``, that
no shape depends on the seed, that the counting functions and the trace
reduction compute what they say, that the plain reference agrees with
``DistributedTrainer.step``, and that the comparison fails what it has to
fail: the control (the reference in bfloat16 in the program's place) and
each fault a training cell can have, planted under a whole run.
"""

import argparse
import glob
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from chipbench import check, inputs, readers, spec, work, xplane
from chipbench import run as harness
from chipbench.tests import tiny

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
BENCH = spec.load_benchmark()


# -- the data files ----------------------------------------------------------

ADDED = "reddit-sage-wide.hbm-long-warm"
# a second graph, a second assembly and the configuration that names both
TYPED = "products-typed.hbm"

# what the copy's second model says otherwise than the first: the scope its
# ops carry (a flax class of another name) and the names of its weights'
# leaves ...
SECOND_PROGRAM = (
    ('SCOPE = "GraphSAGE"', 'SCOPE = "GraphSAGE2"'),
    ("    return GraphSAGE(", "    return type(SCOPE, (GraphSAGE,), {})("),
)
SECOND_LEAVES = (("w_neigh", "neigh"), ("w_self", "root"))
# ... and, on its plain side, a count that `work.WORK` does not have
SECOND_COUNT = '''

def sage2_aggregate_bytes(counts: dict) -> float:
    """Per layer: the input row of every valid edge read and every target's
    aggregated row written, 4 bytes a float."""
    return float(sum(
        4 * d_in * (hop["edges"] + hop["targets"])
        for (d_in, _), hop in zip(counts["layer_dims"], counts["hops"][::-1])))
'''


def rewritten(path, pairs) -> str:
    with open(path) as f:
        text = f.read()
    for old, new in pairs:
        assert old in text, (str(path), old)
        text = text.replace(old, new)
    return text


@pytest.fixture()
def added(tmp_path, monkeypatch):
    """What the README's worked examples add, added in a copy: nothing
    that is there is edited, and the harness finds each by its name. The
    second model is not the first: ``sage2``'s files declare a scope, a
    weights' tree and a byte count of their own; the second graph
    (``data/typed_graph.py``) has two node types, a relation on every edge
    and seeds of the first type alone, and the second assembly
    (``data/typed_assembly.py``) hands each lane's relation on."""
    import chipbench.assemblies
    import chipbench.graphs
    import chipbench.models
    import chipbench.reference

    root = tmp_path / "checkout"
    here = root / "chipbench"
    shutil.copytree(spec.HERE, here,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads(json.dumps(BENCH))
    (here / "models" / "sage2.py").write_text(rewritten(
        here / "models" / "sage.py", SECOND_PROGRAM + SECOND_LEAVES))
    (here / "reference" / "sage2.py").write_text(rewritten(
        here / "reference" / "sage.py", SECOND_LEAVES) + SECOND_COUNT)
    for kind in ("graph", "assembly"):
        shutil.copy(os.path.join(DATA, f"typed_{kind}.py"),
                    here / {"graph": "graphs", "assembly": "assemblies"}[kind]
                    / "typed.py")
    typed = spec.load_config("products-sage")
    typed["name"], typed["assembly"] = "products-typed", "typed"
    typed["graph"].update(generator="typed", type_shares=[0.25, 0.75],
                          relations=4)
    cfg = spec.load_config("reddit-sage")
    cfg["name"], cfg["fanout"] = "reddit-sage-wide", [25, 15]
    cfg["model"], cfg["graph"]["endpoints"] = "sage2", "degree"
    traffic = spec.load_traffic("train-hbm")
    traffic["name"], traffic["warmup_steps"] = "train-hbm-long-warm", 5
    metric = spec.load_metric("sample_device_ms")
    metric.update(name="sample_hop1_device_ms",
                  args={"pattern": r"sample_layer_1"}, workloads=[ADDED])
    roofline = dict(
        metric, name="sage2_aggregate_roofline", unit="%", better="higher",
        layer="model + optimizer (models/sage.py, optax)", reader="roofline",
        args={"pattern": r"/jvp\({model_scope}\)/conv\d+/",
              "work": "sage2_aggregate_bytes", "peak": "hbm_gbps"})
    for kind, item in (("configs", cfg), ("configs", typed),
                       ("traffic", traffic),
                       ("metrics", metric), ("metrics", roofline)):
        with open(here / kind / f"{item['name']}.json", "w") as f:
            json.dump(item, f)
    entries = {
        "configs": [{
            "name": "reddit-sage-wide", "source": cfg["source"],
            "file": "chipbench/configs/reddit-sage-wide.json",
            "reduced": cfg["reduced"], "why": "an example"}, {
            "name": "products-typed", "source": typed["source"],
            "file": "chipbench/configs/products-typed.json",
            "reduced": typed["reduced"], "why": "an example"}],
        "workloads": [{
            "name": ADDED, "config": "reddit-sage-wide",
            "traffic": "train-hbm-long-warm", "chips": 1,
            "why": "an example"}, {
            "name": TYPED, "config": "products-typed",
            "traffic": "train-hbm", "chips": 1, "why": "an example"}],
        "per_layer": [
            {k: m[k] for k in ("name", "unit", "better", "source", "layer",
                               "moves", "workloads")}
            for m in (metric, roofline)],
    }
    for group, new in entries.items():
        # a later PR may take an example's name for its own entry: in the
        # copy the example stands in its place
        names = {e["name"] for e in new}
        bench[group] = [e for e in bench[group]
                        if e["name"] not in names] + new
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    monkeypatch.setattr(spec, "HERE", str(here))
    monkeypatch.setattr(spec, "ROOT", str(root))
    # the copy's model, graph and assembly files are found as a checkout's
    # own are
    for package in (chipbench.models, chipbench.reference, chipbench.graphs,
                    chipbench.assemblies):
        side = package.__name__.rsplit(".", 1)[1]
        monkeypatch.setattr(package, "__path__",
                            list(package.__path__) + [str(here / side)])
    yield spec.load_benchmark()
    for module in ("models.sage2", "reference.sage2", "graphs.typed",
                   "assemblies.typed"):
        sys.modules.pop(f"chipbench.{module}", None)


@pytest.fixture(params=["as it is", "with a second model"])
def bench(request):
    """``BENCHMARK.json`` as the checkout has it, and as the ``added``
    fixture's copy has it: what holds of the benchmark's files holds of
    both, so a PR that adds a model by files and entries keeps it."""
    if request.param == "as it is":
        return BENCH
    return request.getfixturevalue("added")


def model_of(bench: dict, workload: str) -> str:
    return spec.load_config(spec.cell(bench, workload)["config"])["model"]


def fill_outside_a_cell(bench: dict) -> str:
    """What fills ``{model_scope}`` outside a cell, by the rule: the scope
    of every model that a configuration names, one as the bare name,
    several as ``(?:a|b)``, sorted; each matched as text."""
    names = sorted({re.escape(spec.load_model(
        spec.load_config(c["name"])["model"], "models").SCOPE)
        for c in bench["configs"]})
    return names[0] if len(names) == 1 else "(?:" + "|".join(names) + ")"


def test_benchmark_names_units_and_files(bench):
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in bench[k]]
    names += [w["config"] for w in bench["workloads"]]
    names += [w["traffic"] for w in bench["workloads"]]
    names += [k for c in bench["configs"] for k in c["reduced"]]
    assert all(spec.NAME.match(n) for n in names)
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            assert spec.UNIT.match(m["unit"]), m
            assert m["better"] in ("lower", "higher")
        assert len({m["name"] for m in bench[group]}) == len(bench[group])
    for w in bench["workloads"]:
        assert w["name"] == f"{w['config']}.{w['traffic'].split('-', 1)[1]}"
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for c in bench["configs"]:
        cfg = spec.load_config(c["name"])
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        assert set(cfg["reduced"]) <= set(cfg["published"])
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(bench["workloads"]) // 4)


@pytest.mark.parametrize("kind", ["configs", "traffic", "metrics"])
def test_every_data_file_loads_under_its_own_name(bench, kind):
    files = glob.glob(os.path.join(spec.HERE, kind, "*.json"))
    assert files
    for path in files:
        with open(path) as f:
            assert json.load(f)["name"] == os.path.basename(path)[:-5]
    named = {"configs": [c["name"] for c in bench["configs"]],
             "traffic": [w["traffic"] for w in bench["workloads"]],
             "metrics": [m["name"] for m in bench["per_layer"]]}[kind]
    assert set(named) <= {os.path.basename(p)[:-5] for p in files}


def test_metric_files_agree_with_benchmark_json(bench):
    end_to_end = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for entry in bench["per_layer"]:
        file = spec.load_metric(entry["name"])
        assert file["reader"] in readers.READERS
        for key in ("unit", "better", "source", "layer", "moves"):
            assert file[key] == entry[key], (entry["name"], key)
        assert file.get("workloads") == entry.get("workloads")
        assert entry["moves"] in end_to_end
        assert set(entry.get("workloads", [])) <= cells
        if file["reader"] in ("roofline", "mfu"):
            # a count of `WORK`, or the plain side's own, in every cell
            # that reports the metric
            for cell in entry.get("workloads", cells):
                assert callable(work.counting(
                    file["args"]["work"], model_of(bench, cell),
                    entry["name"]))
            assert file["args"]["peak"] in spec.load_peaks("TPU v5 lite")
    for w in bench["workloads"]:
        assert spec.metrics_of(bench, w["name"], "per_layer")
        assert len(spec.metrics_of(bench, w["name"], "end_to_end")) >= 2


with open(os.path.join(DATA, "metrics.loaded.json")) as _f:
    LOADED = json.load(_f)


@pytest.mark.parametrize("metric", sorted(LOADED))
def test_an_accepted_metric_file_loads_as_it_did(metric):
    """Each of the 31 metric files that commit 09e71fa had loads, in a cell
    of that commit's one model, to what its ``spec.load_metric`` gave (it
    wrote ``data/metrics.loaded.json``): every accepted pattern, ``work``
    and ``peak`` reads what it read. Outside a cell it loads to the same
    with the rule's fill in the model's place, which is the same string
    for as long as the benchmark has that one scope."""
    assert spec.load_metric(metric, "GraphSAGE") == LOADED[metric]
    outside = json.loads(json.dumps(LOADED[metric]))
    if "pattern" in outside.get("args", {}):
        outside["args"]["pattern"] = outside["args"]["pattern"].replace(
            "GraphSAGE", fill_outside_a_cell(BENCH))
    assert spec.load_metric(metric) == outside


def test_unknown_device_kind_has_no_peaks():
    with pytest.raises(KeyError):
        spec.load_peaks("TPU v99")


def test_a_cell_a_mix_a_metric_and_a_configuration_are_files_alone(added):
    cell = spec.cell(added, ADDED)
    assert spec.load_config(cell["config"])["fanout"] == [25, 15]
    assert spec.load_traffic(cell["traffic"])["warmup_steps"] == 5
    mine = {m["name"] for m in spec.metrics_of(added, cell["name"], "per_layer")}
    assert "sample_hop1_device_ms" in mine and "collective_exposed_ms" not in mine
    assert "sample_hop1_device_ms" not in {
        m["name"] for m in spec.metrics_of(added, "reddit-sage.hbm", "per_layer")}
    trace = xplane.Trace([
        xplane.Op("/device:TPU:0", "XLA Ops", "fusion.1", "a/sample_layer_1/x", 0, 40),
        xplane.Op("/device:TPU:0", "XLA Ops", "fusion.2", "a/sample_layer_0/x", 50, 60),
    ], [])
    ctx = {"trace": trace, "steps": 2}
    assert readers.read(spec.load_metric("sample_hop1_device_ms"), ctx) == \
        pytest.approx(40 / 2 * 1e-6)


def test_a_model_is_two_files_and_a_configuration_names_it(added, monkeypatch):
    """``models/sage2.py``, ``reference/sage2.py`` and a configuration
    that names them, on a graph whose endpoints follow the degrees: the
    whole run, the chip left out, comes out correct through those files,
    whose scope and whose leaves are not the first model's."""
    import jax

    monkeypatch.setattr(spec, "load_config", tiny.tiny_config)
    args = argparse.Namespace(workload=ADDED, seed=11, seconds=0.5, trace=0)
    result = harness.run(args, jax.devices()[:1], harness.CompileMeter(),
                         time.perf_counter(), {})
    assert result["correct"] is True, result["compared"]
    for name, row in result["compared"].items():
        assert row["limit"] is not None and row["value"] <= row["limit"], name
    for side in spec.MODEL_SIDES:
        module = sys.modules[f"chipbench.{side}.sage2"]
        assert module.__file__.startswith(spec.HERE)
    cfg = spec.load_config("reddit-sage-wide")
    assert (cfg["model"], cfg["graph"]["endpoints"]) == ("sage2", "degree")
    first, second = (spec.load_model(m, "models") for m in ("sage", "sage2"))
    assert (first.SCOPE, second.SCOPE) == ("GraphSAGE", "GraphSAGE2")
    assert type(second.build(cfg)).__name__ == "GraphSAGE2"
    leaves = [set(layer) for layer in inputs.make_weights(cfg, 11)]
    assert leaves == [{"neigh", "b", "root"}] * 2
    assert not hasattr(spec.load_model("sage", "reference"), "sage2_aggregate_bytes")


def typed_run(monkeypatch, seed: int = 13) -> tuple:
    """The ``added`` copy's typed cell through the whole run, the chip left
    out: the result, the seeds every step was fed, and the blocks that went
    to the comparison."""
    import jax

    from chipbench.adapter import Program

    fed, compared = [], []
    real_step, real_compare = Program.step, check.compare

    def step(self, seeds, key):
        fed.append(np.asarray(seeds))
        return real_step(self, seeds, key)

    def compare(cfg, data, weights0, obs, seed):
        compared.extend(b for blocks in obs.blocks for b in blocks)
        return real_compare(cfg, data, weights0, obs, seed)

    monkeypatch.setattr(Program, "step", step)
    monkeypatch.setattr(check, "compare", compare)
    monkeypatch.setattr(spec, "load_config", tiny.tiny_config)
    args = argparse.Namespace(workload=TYPED, seed=seed, seconds=0.5, trace=0)
    result = harness.run(args, jax.devices()[:1], harness.CompileMeter(),
                         time.perf_counter(), {})
    return result, fed, compared


def test_a_graph_and_an_assembly_are_files_and_a_configuration_names_them(
        added, monkeypatch):
    """``graphs/typed.py``, ``assemblies/typed.py`` and a configuration that
    names both: two node types of which the first alone is fed as seeds, an
    ``int8`` relation on every edge, and every sampled lane carrying its
    edge's relation to the comparison; the whole run, the chip left out,
    comes out correct through those files."""
    result, fed, compared = typed_run(monkeypatch)
    assert result["correct"] is True, result["compared"]
    for kind in ("graphs", "assemblies"):
        module = sys.modules[f"chipbench.{kind}.typed"]
        assert module.__file__.startswith(spec.HERE)
    cfg = spec.load_config("products-typed")
    assert (cfg["graph"]["generator"], cfg["assembly"]) == ("typed", "typed")
    data = inputs.make_inputs(cfg, 13)
    first = 3000 // 4
    assert np.array_equal(data.seed_nodes, np.arange(first))
    relation = data.edge_data["relation"]
    assert relation.dtype == np.int8 and relation.shape == data.indices.shape
    assert set(np.unique(relation)) == {0, 1, 2, 3}
    assert len(fed) > 3 and all(
        seeds.shape == (64,) and seeds.max() < first for seeds in fed)
    assert len(compared) == 3
    for block in compared:
        assert len(block.lane_data) == len(block.layers)
        for (src, _, _), carried in zip(block.layers, block.lane_data):
            assert carried["relation"].shape == src.shape
            assert (carried["relation"][src >= 0] >= 0).all()
    # the first graph says nothing more than it did
    plain = inputs.make_inputs(tiny.tiny_config("products-sage"), 13)
    assert plain.seed_nodes is None and plain.edge_data == {}
    assert not hasattr(spec.load_graph("lomax"), "lane_faults")
    assert np.array_equal(plain.indices, data.indices)


@pytest.mark.parametrize("bent", ["one lane's relation", "no lane carries any"])
def test_a_bent_relation_comes_out_not_correct(added, monkeypatch, bent):
    """A lane's payload altered where it is produced: the graph file's
    ``lane_faults`` finds a relation that no edge between the lane's two
    nodes has, ``block_faults`` counts it and the run is not correct."""
    from chipbench.adapter import Program

    real = Program.blocks

    def altered(self, seeds, key):
        blocks = real(self, seeds, key)
        if bent == "no lane carries any":
            blocks[0].lane_data = []
            return blocks
        carried = blocks[0].lane_data[0]["relation"]
        lane = int(np.flatnonzero(blocks[0].layers[0][0] >= 0)[0])
        carried[lane] = 4  # the graph has relations 0..3
        return blocks

    monkeypatch.setattr(Program, "blocks", altered)
    result, _, _ = typed_run(monkeypatch)
    assert result["correct"] is False
    assert result["compared"]["block_faults"]["value"] > 0
    failed = {n for n, r in result["compared"].items()
              if r["value"] > r["limit"]}
    assert failed == {"block_faults"}


@pytest.mark.parametrize("broken, named", [
    (lambda cfg: cfg["graph"].pop("generator"), "`graph.generator`"),
    (lambda cfg: cfg.pop("assembly"), "`assembly`"),
    (lambda cfg: cfg["graph"].update(generator="no_such_graph"),
     "`graph.generator` 'no_such_graph'"),
    (lambda cfg: cfg.update(assembly="no_such_assembly"),
     "`assembly` 'no_such_assembly'"),
])
def test_a_configuration_names_its_graph_and_its_assembly(
        added, broken, named):
    """Neither key has a default: a file without one, or one that names a
    graph or an assembly that has no file, fails in ``spec.load_config``
    with the key's name, before anything is built."""
    cfg = spec.load_config("products-typed")
    broken(cfg)
    with open(os.path.join(spec.HERE, "configs", "products-typed.json"),
              "w") as f:
        json.dump(cfg, f)
    with pytest.raises((KeyError, FileNotFoundError)) as raised:
        spec.load_config("products-typed")
    assert named in str(raised.value)


def test_a_models_plain_side_counts_its_own_bytes(added):
    """A roofline whose ``work`` is not one of ``WORK`` reads the function
    of that name on the plain side of the cell's model, with the same
    counts; a name that neither has fails with the metric's and the
    model's names, and does not read as nothing."""
    counts = {
        "hops": [{"fanout": 3, "targets": 4, "edges": 11, "unique": 10},
                 {"fanout": 2, "targets": 10, "edges": 18, "unique": 21}],
        "gathered_rows": 21, "feature_dim": 8, "feature_itemsize": 4,
        "model": model_of(added, ADDED), "layer_dims": [(8, 16), (16, 5)],
    }
    second = spec.load_model("sage2", "models")
    file = spec.load_metric("sage2_aggregate_roofline", second.SCOPE)
    assert "sage2_aggregate_bytes" not in work.WORK
    assert file["args"]["pattern"] == r"/jvp\(GraphSAGE2\)/conv\d+/"
    trace = xplane.Trace([
        xplane.Op("/device:TPU:0", "XLA Ops", "fusion.1",
                  "jit(body)/jvp(GraphSAGE2)/conv0/reduce_sum", 0, 3000),
        xplane.Op("/device:TPU:0", "XLA Ops", "fusion.2",
                  "jit(body)/jvp(GraphSAGE)/conv0/reduce_sum", 3000, 4000),
    ], [])
    ctx = {"trace": trace, "steps": 2, "work": counts,
           "peaks": {"hbm_gbps": 2.0}}
    own = 4 * 8 * (18 + 10) + 4 * 16 * (11 + 4)
    assert readers.read(file, ctx) == pytest.approx(
        100.0 * (own / 2e9) / (3000e-9 / 2))
    # an accepted count reads through `WORK` as it did
    assert work.counting("gather_bytes", "sage2") is work.gather_bytes
    nothing_to_read = dict(ctx, trace=xplane.Trace([], []))
    assert readers.read(file, nothing_to_read) is None
    for model, name in (("sage2", "no_such_bytes"), ("sage", "sage2_aggregate_bytes"),
                        ("sage2", "np"), ("sage2", "Block")):
        file["args"]["work"], counts["model"] = name, model
        with pytest.raises(KeyError) as raised:
            readers.read(file, nothing_to_read)
        for said in (name, "sage2_aggregate_roofline", f"reference/{model}.py"):
            assert said in str(raised.value)


@pytest.mark.parametrize("broken, named", [
    (lambda cfg: cfg.pop("model"), "`model`"),
    (lambda cfg: cfg["graph"].pop("endpoints"), "`graph.endpoints`"),
    (lambda cfg: cfg.update(model="no_such_model"), "models/no_such_model.py"),
    (lambda cfg: cfg["graph"].update(endpoints="zipf"), "`graph.endpoints`"),
])
def test_a_configuration_names_its_model_and_its_endpoint_law(
        added, broken, named):
    """Neither key has a default: a file without one, or with a name that
    nothing answers to, fails with the key's name before anything is built."""
    cfg = spec.load_config("reddit-sage-wide")
    broken(cfg)
    with open(os.path.join(spec.HERE, "configs", "reddit-sage-wide.json"),
              "w") as f:
        json.dump(cfg, f)
    with pytest.raises((KeyError, FileNotFoundError, ValueError)) as raised:
        inputs.make_inputs(tiny.tiny_config("reddit-sage-wide"), 1)
    assert named in str(raised.value)


@pytest.mark.parametrize("metric, filled", [
    ("forward_device_ms", r"/jvp\(GraphSAGE\)"),
    ("backward_device_ms", r"transpose\(jvp\(GraphSAGE\)\)"),
    ("model_device_ms", r"GraphSAGE|adam|optax|apply_updates|scale_by"),
])
def test_model_scope_in_a_pattern_is_filled_from_the_model_file(
        bench, metric, filled):
    """The patterns name no class: the cell's model file declares the scope
    its ops carry, which is the flax module's class name. Outside a cell
    the pattern is filled by a rule, with the scope of every model that a
    configuration of the benchmark names: one scope reads as the bare name,
    several as ``(?:a|b)``, sorted. The rule is held here, worked out from
    the benchmark's configurations, and not today's value of it."""
    for c in bench["configs"]:
        cfg = spec.load_config(c["name"])
        model = spec.load_model(cfg["model"], "models")
        assert type(model.build(cfg)).__name__ == model.SCOPE
    with open(os.path.join(spec.HERE, "metrics", metric + ".json")) as f:
        assert spec.MODEL_SCOPE in json.load(f)["args"]["pattern"]
    first = spec.load_model("sage", "models")
    assert spec.load_metric(metric, first.SCOPE)["args"]["pattern"] == filled
    assert spec.load_metric(metric, "GAT")["args"]["pattern"] == \
        filled.replace("GraphSAGE", "GAT")
    assert spec.load_metric(metric)["args"]["pattern"] == \
        filled.replace("GraphSAGE", fill_outside_a_cell(bench))


def test_two_models_fill_a_pattern_with_both_scopes(added):
    second = spec.load_model("sage2", "models")
    assert second.SCOPE == "GraphSAGE2"  # the file's own, nothing patched
    # a benchmark of these two models, whatever later PRs have added
    pair = dict(added, configs=[c for c in added["configs"] if c["name"] in (
        "reddit-sage", "reddit-sage-wide")])
    with open(os.path.join(spec.ROOT, "BENCHMARK.json"), "w") as f:
        json.dump(pair, f)
    filled = spec.load_metric("forward_device_ms")["args"]["pattern"]
    assert filled == r"/jvp\((?:GraphSAGE|GraphSAGE2)\)"
    both = re.compile(filled)
    assert both.search("jit(body)/jvp(GraphSAGE)/conv0/dot_general")
    assert both.search("jit(body)/jvp(GraphSAGE2)/conv0/dot_general")
    assert not both.search("jit(body)/jvp(GraphSAGE20)/conv0/dot_general")
    assert not both.search("jit(body)/jvp(seed_loss)/gather")
    assert spec.load_metric("forward_device_ms", second.SCOPE)[
        "args"]["pattern"] == r"/jvp\(GraphSAGE2\)"
    # a scope is matched as text, whatever it holds
    assert spec.load_metric("forward_device_ms", "Graph.SAGE2")[
        "args"]["pattern"] == r"/jvp\(Graph\.SAGE2\)"


# -- shapes from data, values from the seed ----------------------------------

def test_two_seeds_give_the_same_shapes_and_other_values():
    cfg = tiny.tiny_config("products-sage")
    a, b = inputs.make_inputs(cfg, 3), inputs.make_inputs(cfg, 2**31 + 5)
    for name in ("indptr", "indices", "features", "labels"):
        x, y = getattr(a, name), getattr(b, name)
        assert x.shape == y.shape and x.dtype == y.dtype
        assert not np.array_equal(x, y)
    g = cfg["graph"]
    for data in (a, b):
        deg = np.diff(data.indptr)
        assert data.indptr[-1] == g["edges"] == data.indices.shape[0]
        assert deg.max() == g["max_degree"] and deg.min() >= 1
        assert data.features.shape == (g["nodes"], cfg["feature_dim"])
    again = inputs.make_inputs(cfg, 3)
    assert np.array_equal(a.indices, again.indices)
    assert np.array_equal(a.features, again.features)
    wa, wb = inputs.make_weights(cfg, 3), inputs.make_weights(cfg, 4)
    assert [{k: v.shape for k, v in l.items()} for l in wa] == \
        [{k: v.shape for k, v in l.items()} for l in wb]
    fa, fb = inputs.Feed(3000, 64, 3), inputs.Feed(3000, 64, 4)
    assert fa.seeds(0).shape == fb.seeds(0).shape == (64,)
    assert fa.key(0).shape == (2,) and fa.key(0).dtype == np.uint32
    assert not np.array_equal(fa.seeds(0), fa.seeds(1))


# digests of the parent's arrays (commit c9b5f11, before the endpoint law and
# the model became data): `"uniform"` and `"model": "sage"` are that draw,
# byte for byte
GOLDEN = {
    ("products-sage", 3): {
        "indptr": "8db897232b37d72bfeb05ac0409d1125a11243c46b71f526cc053961b957b578",
        "indices": "2929502b4d50bf449731128c435c57520371206a110aa8d831e410775d07e207",
        "features": "d731f3d6ef5e6ab7960db52bf57236b3e6c8fb077f4cb67c57ae3ad53496fd9b",
        "labels": "2319b06686ec5b1697c2e72e2613accb30e1b3a60c855f558536ae5bbd850404",
        "weights": "dba5571f14cd6e8b1ee0687394099c958b2eb3159743045792ac8373e3245907",
        "feed": "6bb71d629eb463131975c985fb1c2c1d0383e31057bc6ccbfe46591f9cab9973"},
    ("products-sage", 2**31 + 5): {
        "indptr": "e7f03a3b5ff8de69752fdfe260e8d6f34fe2aad74fbcdde825815a50d4ba0321",
        "indices": "ac6299385047ee5be6d0d6c17a8cda00016a749a8cc77bbff33d436672be15c1",
        "features": "0946eedac5e21437e266085858ba93aaff401b0c29451acb2622ad8fce248116",
        "labels": "05aa8d6680de14f463295c0a8cb540e7391b7d590635664c13d0357047fbb68e",
        "weights": "01d372591b2f50b8c33d3fb77d0b28689f29e58a2ccb93cf7ac33037970b5635",
        "feed": "462e13ef8e24dc25b102441334b23daa09036c1b02f987009229754b46129b28"},
    ("reddit-sage", 3): {
        "indptr": "8db897232b37d72bfeb05ac0409d1125a11243c46b71f526cc053961b957b578",
        "indices": "2929502b4d50bf449731128c435c57520371206a110aa8d831e410775d07e207",
        "features": "ea49cd4ab7c3c4e293e06ae5b95bcafd3a775914ce96563e2929ad41ed2848d4",
        "labels": "9674b9f79e11f8cc01bcf0e8c2015ffee515111bd09cf7d04f2bbd5790efe5cd",
        "weights": "6b9b51ff80f9da6589c1bdfd0a818031dcf2f611d2d28ee7f46d0f92509be803",
        "feed": "6bb71d629eb463131975c985fb1c2c1d0383e31057bc6ccbfe46591f9cab9973"},
    ("reddit-sage", 2**31 + 5): {
        "indptr": "e7f03a3b5ff8de69752fdfe260e8d6f34fe2aad74fbcdde825815a50d4ba0321",
        "indices": "ac6299385047ee5be6d0d6c17a8cda00016a749a8cc77bbff33d436672be15c1",
        "features": "793a2b75cf56932a203385fc910141afae1bf176a26abc81f4bff242f2216ad8",
        "labels": "5cd46ee5ffb42eecbac05273b78e07e457f8e05a52b108af096db4c05a732d30",
        "weights": "d36a3d5f370f50481fea168e55fa99d52942bb5fa119f6a8b786185d3cce811f",
        "feed": "462e13ef8e24dc25b102441334b23daa09036c1b02f987009229754b46129b28"},
}


def sha256(*arrays) -> str:
    return hashlib.sha256(b"".join(
        np.ascontiguousarray(a).tobytes() for a in arrays)).hexdigest()


@pytest.mark.parametrize("config, seed", list(GOLDEN))
def test_inputs_are_the_bytes_they_were(config, seed):
    cfg = tiny.tiny_config(config)
    data = inputs.make_inputs(cfg, seed)
    weights = inputs.make_weights(cfg, seed)
    feed = inputs.Feed(cfg["graph"]["nodes"], cfg["batch"], seed)
    got = {name: sha256(getattr(data, name))
           for name in ("indptr", "indices", "features", "labels")}
    got["weights"] = sha256(*(layer[leaf] for layer in weights
                              for leaf in sorted(layer)))
    got["feed"] = sha256(feed.seeds(0), feed.seeds(1), feed.key(0), feed.key(1))
    assert got == GOLDEN[config, seed]


def test_a_feed_over_every_node_is_the_golden_feed_and_over_seed_nodes_draws_only_those():
    """``Feed.of`` over inputs that name no ``seed_nodes`` is the feed the
    golden digests hold, to the byte; over a subset it cuts a permutation
    of the subset and nothing else."""
    cfg = tiny.tiny_config("products-sage")
    data = inputs.make_inputs(cfg, 3)
    assert data.seed_nodes is None
    feed = inputs.Feed.of(data, cfg["batch"], 3)
    assert sha256(feed.seeds(0), feed.seeds(1), feed.key(0), feed.key(1)) \
        == GOLDEN["products-sage", 3]["feed"]
    assert feed.steps_per_epoch == 3000 // 64
    data.seed_nodes = np.arange(5, 3000, 7, dtype=np.int32)  # 428 nodes
    some = inputs.Feed.of(data, cfg["batch"], 3)
    assert some.steps_per_epoch == 428 // 64
    epoch = np.concatenate([some.seeds(i) for i in range(6)])
    assert epoch.dtype == np.int32 and np.unique(epoch).shape == (6 * 64,)
    assert np.isin(epoch, data.seed_nodes).all()
    assert np.array_equal(some.seeds(6), some.seeds(0))  # the epoch wraps
    assert not np.array_equal(epoch, np.sort(epoch))
    with pytest.raises(ValueError):
        inputs.Feed.of(data, 429, 3)


def test_more_classes_than_feature_columns_share_the_bumps():
    """ogbn-papers100M has 172 classes and 128-d rows: the label's bump
    goes to column ``label % width``, and every label is still drawn."""
    cfg = tiny.tiny_config("products-sage")
    cfg.update(classes=172, feature_dim=128)
    data = inputs.make_inputs(cfg, 3)
    assert data.labels.max() == 171 and data.features.shape[1] == 128
    bumped = data.features[np.arange(3000), data.labels % 128]
    assert bumped.mean() > 2.5 and abs(data.features.mean() - 3 / 128) < 0.02
    assert len(inputs.make_weights(cfg, 3)[-1]["b"]) == 172


# -- the endpoint law ---------------------------------------------------------

@pytest.fixture(scope="module", params=["degree", "uniform"])
def law_graph(request):
    """ogbn-products' degree law at 200 k nodes, under each endpoint law."""
    cfg = spec.load_config("products-sage")
    mean = cfg["graph"]["edges"] / cfg["graph"]["nodes"]
    cfg["graph"].update(nodes=200_000, edges=int(200_000 * mean),
                        endpoints=request.param)
    return request.param, cfg, inputs.make_inputs(cfg, 7)


def test_endpoints_follow_the_law(law_graph):
    """The share of endpoints on the top fifth of the nodes by degree is
    those nodes' share of the edge slots under ``"degree"``, a fifth under
    ``"uniform"``; to sampling error."""
    law, cfg, data = law_graph
    nodes, edges = cfg["graph"]["nodes"], cfg["graph"]["edges"]
    deg = np.diff(data.indptr)
    top = np.zeros(nodes, bool)
    top[np.argsort(-deg, kind="stable")[:nodes // 5]] = True
    slots = deg[top].sum() / edges
    assert 0.6 < slots < 0.75
    want = slots if law == "degree" else 0.2
    share = top[data.indices].mean()
    assert abs(share - want) < 5 * (want * (1 - want) / edges) ** 0.5
    if law == "degree":
        received = np.bincount(data.indices, minlength=nodes)
        assert np.corrcoef(received, deg)[0, 1] > 0.99


def test_a_cache_of_a_fifth_of_the_rows_hits_by_the_law(law_graph):
    """Through the program's own placement and tier merge: the hot fifth
    by degree holds over half of a batch's distinct rows when endpoints
    follow the degrees, and a fifth of them when they do not. Two hops of a
    batch of 256 reach a ninth of this graph's nodes; a frontier that
    covers more of the graph runs out of hubs (1,024 seeds reach 30 % of
    the nodes, and the hot share is 0.49)."""
    import jax.numpy as jnp
    import quiver_tpu
    from quiver_tpu.feature.feature import tiered_lookup

    from chipbench import plan_caps

    law, cfg, data = law_graph
    nodes, width = data.features.shape
    topo = quiver_tpu.CSRTopo(indptr=data.indptr, indices=data.indices)
    feature = quiver_tpu.Feature(
        device_cache_size=nodes // 5 * width * 4,
        csr_topo=topo).from_cpu_tensor(data.features)
    assert feature.hot_rows == nodes // 5
    feed = inputs.Feed(nodes, 256, 7)
    n_id = plan_caps.frontiers(data.indptr, data.indices, feed.seeds(0),
                               cfg["fanout"][:2], np.random.default_rng(7))[-1]
    hot, cold = jnp.asarray(feature.hot), jnp.asarray(np.asarray(feature.cold))
    rows, hits = tiered_lookup(
        jnp.asarray(n_id, jnp.int32), feature.feature_order, feature.hot_rows,
        lambda ids: hot[ids], lambda ids: cold[ids], with_hits=True)
    assert np.array_equal(np.asarray(rows), data.features[n_id])
    rep, in_hot, in_cold = (int(h) for h in hits)
    assert rep == 0 and in_hot + in_cold == n_id.shape[0]
    share = in_hot / n_id.shape[0]
    print(f"{law}: {n_id.shape[0]} distinct rows, hot share {share:.3f}")
    if law == "degree":
        assert share > 0.5
    else:
        assert abs(share - 0.2) < 0.02


# -- the counting functions --------------------------------------------------

def test_work_counts_against_hand_worked_values():
    # two hops, seeds outward: 4 seeds x fanout 3, then 10 targets x fanout 2
    counts = {
        "hops": [
            {"fanout": 3, "targets": 4, "edges": 11, "unique": 10},
            {"fanout": 2, "targets": 10, "edges": 18, "unique": 21},
        ],
        "gathered_rows": 21, "feature_dim": 8, "feature_itemsize": 4,
        "model": "sage", "layer_dims": [(8, 16), (16, 5)],
    }
    assert work.sample_bytes(counts) == 4 * (8 + 24) + 10 * (8 + 16)
    assert work.reindex_bytes(counts) == \
        4 * (2 * 16 + 10) + 4 * (2 * 30 + 21)
    assert work.gather_bytes(counts) == 21 * (2 * 32 + 4)
    input_layer = 2 * 10 * 8 * 16   # one matmul over hop 1's 10 targets
    output_layer = 2 * 4 * 16 * 5   # one matmul over the 4 seeds
    assert work.step_flops(counts) == (
        4 * input_layer + 2 * 18 * 8            # fwd + weight grads + mean
        + 6 * output_layer + 2 * 11 * 16)       # and the input gradients


# -- the trace reduction -----------------------------------------------------

def synthetic_trace():
    ops = [
        # a while that spans two body ops, then a gap, then a free op
        xplane.Op("/device:TPU:0", "XLA Ops", "while.1", "jit(f)/reindex_layer_0/while", 100, 200),
        xplane.Op("/device:TPU:0", "XLA Ops", "fusion.1", "jit(f)/reindex_layer_0/while/body/sort", 110, 150),
        xplane.Op("/device:TPU:0", "XLA Ops", "fusion.2", "jit(f)/sample_layer_0/gather", 160, 190),
        xplane.Op("/device:TPU:0", "XLA Ops", "copy.3", "", 300, 350),
        xplane.Op("/device:TPU:0", "XLA Ops", "all-reduce.4", "jit(f)/pmean", 350, 400),
    ]
    spans = [xplane.Span("chipbench.window", 0, 500),
             xplane.Span("chipbench.step", 0, 120),
             xplane.Span("chipbench.loss_read", 120, 480)]
    return xplane.Trace(ops, spans)


def test_trace_arithmetic_on_known_intervals():
    t = synthetic_trace()
    assert t.window_s() == pytest.approx(500e-9)
    assert t.busy_s() == pytest.approx((100 + 100) * 1e-9)
    assert t.scope_s(r"reindex_layer_\d+") == pytest.approx((30 + 40) * 1e-9)
    assert t.scope_s(r"sample_layer_\d+") == pytest.approx(30e-9)
    claimed = [r"reindex_layer_\d+", r"sample_layer_\d+"]
    assert t.unclaimed_s(claimed) == pytest.approx((50 + 50) * 1e-9)
    assert t.scope_s("reindex") + t.scope_s("sample") + t.unclaimed_s(claimed) \
        == pytest.approx(t.busy_s())
    assert t.collective_exposed_s() == pytest.approx(50e-9)
    assert dict(t.idle_gaps()) == pytest.approx({
        "chipbench.step": 100e-9, "chipbench.loss_read": 180e-9,
        "outside any span": 20e-9})
    ctx = {"trace": t, "steps": 2}
    assert readers.idle_share(ctx) == pytest.approx(60.0)
    assert readers.device_time_by_scope(ctx, "nothing_here") is None


def _message(*fields) -> bytes:
    """A protobuf message of length-delimited fields: (number, bytes)."""
    out = b""
    for number, payload in fields:
        size, head = len(payload), b""
        while True:
            head += bytes([(size & 0x7F) | (0x80 if size > 0x7F else 0)])
            size >>= 7
            if not size:
                break
        out += bytes([(number << 3) | 2]) + head + payload
    return out


def test_scope_paths_are_read_from_the_hlo_proto_in_the_trace():
    def instruction(name, path):
        return _message((1, name), (2, b"fusion"),
                        (7, _message((1, b"gather"), (2, path))))

    module = _message((1, b"jit_body"), (3, _message(
        (1, b"main"),
        (2, instruction(b"fusion.7", b"jit(body)/sample_layer_0/gather")),
        (2, instruction(b"fusion.8", b"jit(body)/" + b"x" * 300)),
        (2, _message((1, b"copy.1"))))))
    stat = _message((6, _message((1, module))))
    plane = _message((2, b"/host:metadata"),
                     (4, _message((2, _message((2, b"jit_body"), (5, stat))))))
    other = _message((2, b"/device:TPU:0"),
                     (4, _message((2, _message((5, stat))))))
    assert xplane.hlo_scope_paths(_message((1, other), (1, plane))) == {
        "fusion.7": "jit(body)/sample_layer_0/gather",
        "fusion.8": "jit(body)/" + "x" * 300}
    assert xplane.hlo_scope_paths(_message((1, other))) == {}


def test_recorded_trace_reduces_to_the_recorded_numbers():
    """A trace recorded on the chip (a few steps of reddit-sage.hbm, cut to
    its ops and spans) and the numbers read off it when it was recorded."""
    with open(os.path.join(DATA, "recorded.expected.json")) as f:
        want = json.load(f)
    t = xplane.load_json(os.path.join(DATA, "recorded.json.gz"))
    assert t.devices == want["devices"]
    assert t.window_s() == pytest.approx(want["window_s"])
    assert t.busy_s() == pytest.approx(want["busy_s"])
    assert 0 < t.busy_s() <= t.window_s()
    claims = want["claims"]
    by_scope = {p: t.scope_s(p) for p in claims}
    assert by_scope == pytest.approx(want["by_scope"])
    assert all(v > 0 for v in by_scope.values())
    assert t.unclaimed_s(claims) == pytest.approx(want["unclaimed_s"])
    # self times: every op counted once, so the parts make up the whole
    assert sum(by_scope.values()) + t.unclaimed_s(claims) == pytest.approx(
        t.time_by(lambda op: True))
    assert t.time_by(lambda op: True) <= t.busy_s() * 1.0001


# -- a whole run, the chip left out ------------------------------------------

@pytest.fixture()
def small(monkeypatch):
    monkeypatch.setattr(spec, "load_config", tiny.tiny_config)
    monkeypatch.setattr(spec, "load_peaks",
                        lambda kind: {"bf16_tflops": 1.0, "hbm_gbps": 1.0})


def drive(workload: str, seed: int = 7, trace: int = 0) -> dict:
    import jax

    chips = spec.cell(BENCH, workload)["chips"]
    args = argparse.Namespace(workload=workload, seed=seed, seconds=0.5,
                              trace=trace)
    return harness.run(args, jax.devices()[:chips], harness.CompileMeter(),
                       time.perf_counter(), {})


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_reference_agrees_with_the_trainer_step(small, workload, capfd):
    result = drive(workload, seed=2**31 + 9)
    assert result["correct"] is True, result["compared"]
    assert list(result)[-1] == "compared"
    assert result["attempted"] >= 2 and result["failed"] == 0
    wanted = {m["name"] for m in spec.metrics_of(BENCH, workload, "end_to_end")}
    assert set(result["metrics"]) == wanted
    for name, row in result["compared"].items():
        assert row["limit"] is not None and row["value"] <= row["limit"], name
    assert "compared loss_gap_1 = " in capfd.readouterr().err


def test_traced_run_reports_only_what_it_could_read(small):
    result = drive("reddit-sage.hbm", trace=1)
    assert result["correct"] is True
    # the CPU backend's trace has no device plane: host spans, counters and
    # stages are read, every device reader returns nothing and is left out
    assert {"setup_inputs_s", "setup_place_s", "compile_s", "cache_misses",
            "host_dispatch_ms"} <= set(result["metrics"])
    assert not {"sample_roofline", "step_mfu", "sample_device_ms",
                "device_idle_share"} & set(result["metrics"])
    assert result["metrics"]["cache_misses"]["value"] >= 0


def broken_step(kind):
    from chipbench.adapter import Program

    real = Program.step

    def unchanged(self, seeds, key):
        params, opt_state = self.params, self.opt_state
        loss = real(self, seeds, key)
        self.params, self.opt_state = params, opt_state
        return loss

    def half_batch(self, seeds, key):
        return real(self, seeds[:len(seeds) // 2], key)

    return {"unchanged": unchanged, "half_batch": half_batch}[kind]


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_a_broken_step_comes_out_not_correct(small, monkeypatch, fault):
    from chipbench.adapter import Program

    monkeypatch.setattr(Program, "step", broken_step(fault))
    result = drive("reddit-sage.hbm")
    assert result["correct"] is False
    failed = {n for n, r in result["compared"].items()
              if r["value"] > r["limit"]}
    assert failed and "block_faults" not in failed


def test_the_exchange_between_chips_left_out_comes_out_not_correct(
        small, monkeypatch):
    import jax

    monkeypatch.setattr(jax.lax, "pmean", lambda x, axis_name: x)
    result = drive("products-sage.clique2x2")
    assert result["correct"] is False


def test_an_altered_block_comes_out_not_correct(small, monkeypatch):
    """A sampled neighbour altered where it is produced: the block check
    finds an edge that the graph does not have."""
    from chipbench.adapter import Program

    real = Program.blocks

    def altered(self, seeds, key):
        blocks = real(self, seeds, key)
        n_id = blocks[0].n_id.copy()
        last = int((n_id >= 0).sum()) - 1
        n_id[last] = (n_id[last] + 1) % 3000
        blocks[0].n_id = n_id
        return blocks

    monkeypatch.setattr(Program, "blocks", altered)
    result = drive("reddit-sage.hbm")
    assert result["correct"] is False
    assert result["compared"]["block_faults"]["value"] > 0


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_control_in_bfloat16_comes_out_not_correct(seed):
    """The reference, computed in bfloat16 and put in the program's place,
    fails at least one number; computed in float32 it passes all."""
    import jax.numpy as jnp

    cfg = tiny.tiny_config("reddit-sage")
    sage = spec.load_model(cfg["model"], "reference")
    data = inputs.make_inputs(cfg, seed)
    weights0 = inputs.make_weights(cfg, seed)
    feed = inputs.Feed(cfg["graph"]["nodes"], cfg["batch"], seed)
    rng = np.random.default_rng(seed)
    steps = [[host_block(data, feed.seeds(i), cfg["fanout"], rng)]
             for i in range(3)]
    feats, labels = jnp.asarray(data.features), jnp.asarray(data.labels)
    ref = sage.train(weights0, feats, labels, steps, cfg["optimizer"])
    for dtype, passes in ((jnp.float32, True), (jnp.bfloat16, False)):
        side = sage.train(weights0, feats, labels, steps, cfg["optimizer"],
                          param_dtype=dtype, compute_dtype=dtype)
        values = check.numbers(sage, *side, *ref, weights0)
        ok, table = check.verdict(values, tiny.LIMITS)
        assert ok is passes, table


def host_block(data, seeds, fanout, rng):
    """A sampled block made with numpy alone, for tests that need no
    program: ``min(deg, k)`` neighbours of every target."""
    from chipbench.reference.graph import Block

    n_id = list(dict.fromkeys(int(s) for s in seeds))
    local = {n: i for i, n in enumerate(n_id)}
    layers, targets = [], len(n_id)
    for k in fanout:
        src, dst = [], []
        for t in range(targets):
            row = data.indices[data.indptr[n_id[t]]:data.indptr[n_id[t] + 1]]
            for n in rng.choice(row, size=min(len(row), k), replace=False):
                if int(n) not in local:
                    local[int(n)] = len(n_id)
                    n_id.append(int(n))
                src.append(local[int(n)])
                dst.append(t)
        layers.append((np.asarray(src, np.int32), np.asarray(dst, np.int32),
                       targets))
        targets = len(n_id)
    return Block(np.asarray(n_id, np.int32), layers[::-1], len(seeds))


def test_host_block_passes_the_block_check_and_a_bent_one_does_not():
    from chipbench.reference import graph

    cfg = tiny.tiny_config("reddit-sage")
    data = inputs.make_inputs(cfg, 5)
    seeds = inputs.Feed(3000, 64, 5).seeds(0)
    rng = np.random.default_rng(5)
    block = host_block(data, seeds, cfg["fanout"], rng)
    assert sum(graph.block_faults(data.indptr, data.indices, seeds, block,
                                  cfg["fanout"], rng).values()) == 0
    src, dst, n = block.layers[0]
    block.layers[0] = (src[1:], dst[1:], n)  # one sampled edge dropped
    assert graph.block_faults(data.indptr, data.indices, seeds, block,
                              cfg["fanout"], rng)["wrong_counts"] == 1


# -- off the chip ------------------------------------------------------------

def test_run_exits_non_zero_off_the_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "reddit-sage.hbm", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert done.returncode != 0
    assert "needs 1 TPU chip" in done.stderr
    assert not done.stdout.strip().startswith("{")
