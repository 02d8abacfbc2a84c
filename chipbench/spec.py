"""The benchmark's data files, found by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; a per-layer metric names
itself; a configuration names its model, its graph's generator and the
program's assembly. Each is one file under this directory (a model two: the
program's side and the plain reference), so a later PR adds a cell, a mix, a
metric, a model, a graph or an assembly by adding files and entries and
edits nothing that is here.
"""

from __future__ import annotations

import importlib
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
MODEL_SIDES = ("models", "reference")
MODEL_SCOPE = "{model_scope}"  # what a `device_time_by_scope` pattern may hold


def _load(kind: str, name: str) -> dict:
    if not NAME.match(name):
        raise ValueError(f"not a name the benchmark allows: {name!r}")
    path = os.path.join(HERE, kind, name + ".json")
    with open(path) as f:
        return json.load(f)


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_config(name: str) -> dict:
    """The configuration's file. Its model, its graph's generator and
    endpoint law and the program's assembly have no default: a file that
    lacks one of the keys, or names a model, a generator or an assembly
    that has no file, is an error here, before anything is built."""
    cfg = _load("configs", name)
    if "model" not in cfg:
        raise KeyError(
            f"configuration {name!r} has no `model`; name a file of "
            "chipbench/models/ and chipbench/reference/, there is no default")
    if "endpoints" not in cfg.get("graph", {}):
        raise KeyError(
            f"configuration {name!r} has no `graph.endpoints`; give the law "
            "its edges' endpoints follow (the graph's file reads it), there "
            "is no default")
    if "generator" not in cfg["graph"]:
        raise KeyError(
            f"configuration {name!r} has no `graph.generator`; name a file "
            "of chipbench/graphs/, there is no default")
    if "assembly" not in cfg:
        raise KeyError(
            f"configuration {name!r} has no `assembly`; name a file of "
            "chipbench/assemblies/, there is no default")
    files = [("model", side, cfg["model"]) for side in MODEL_SIDES] + [
        ("graph.generator", "graphs", cfg["graph"]["generator"]),
        ("assembly", "assemblies", cfg["assembly"])]
    for key, kind, named in files:
        if not (isinstance(named, str) and NAME.match(named)
                and os.path.isfile(os.path.join(HERE, kind, named + ".py"))):
            raise FileNotFoundError(
                f"`{key}` {named!r} of configuration {name!r} has no file "
                f"chipbench/{kind}/{named}.py")
    return cfg


def _module(kind: str, name: str):
    if not NAME.match(name):
        raise ValueError(f"not a name the benchmark allows: {kind}/{name!r}")
    return importlib.import_module(f"{__package__}.{kind}.{name}")


def load_model(name: str, side: str):
    """The module of that name: ``side`` is ``models`` (the program's side:
    the flax module, the weights' tree, ``SCOPE``) or ``reference`` (the
    plain side: ``layer_dims``, ``make_weights``, ``train``, ``leaf_norms``,
    ``step_flops``, and any count of the bytes of its own mechanisms that a
    roofline's ``work`` names: ``work.counting`` looks there for a name
    that ``work.WORK`` does not have)."""
    if side not in MODEL_SIDES:
        raise ValueError(f"not a model the benchmark allows: {side}/{name!r}")
    return _module(side, name)


def load_graph(name: str):
    """The graph file of that name (``graphs/<name>.py``): ``make(cfg,
    seed)`` draws the configuration's ``inputs.Inputs`` from the seed,
    ``describe(cfg)`` gives them at the same shapes without values, and a
    file *may* have ``lane_faults(data, seeds, block)``, whose counts
    ``check.compare`` adds to ``block_faults``."""
    return _module("graphs", name)


def load_assembly(name: str):
    """The assembly file of that name (``assemblies/<name>.py``), which
    calls the program's constructors: ``build(cfg, traffic, data, mesh)``
    returns the parts that ``DistributedTrainer`` takes (``.sampler``,
    ``.feature``), ``blocks(parts, cfg, seeds, key, workers)`` draws again
    the blocks that a step trains on."""
    return _module("assemblies", name)


def load_traffic(name: str) -> dict:
    return _load("traffic", name)


def load_metric(name: str, model_scope: str | None = None) -> dict:
    """The metric's file, ``{model_scope}`` in its pattern filled with the
    scope that the cell's model file declares (``models/<name>.py``,
    ``SCOPE``). Asked outside any cell, the pattern reads as it does over
    all cells: filled with the scope of every model that a configuration
    of ``BENCHMARK.json`` names."""
    metric = _load("metrics", name)
    args = metric.get("args", {})
    if MODEL_SCOPE in args.get("pattern", ""):
        scopes = [model_scope] if model_scope is not None else sorted({
            load_model(load_config(c["name"])["model"], "models").SCOPE
            for c in load_benchmark()["configs"]})
        fill = "|".join(map(re.escape, scopes))
        args["pattern"] = args["pattern"].replace(
            MODEL_SCOPE, fill if len(scopes) == 1 else f"(?:{fill})")
    return metric


def load_peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    if device_kind not in peaks:
        raise KeyError(
            f"no peaks for device_kind {device_kind!r} in chipbench/peaks.json;"
            " add the device with its source, there is no default"
        )
    return peaks[device_kind]


def cell(bench: dict, workload: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def metrics_of(bench: dict, workload: str, group: str) -> list[dict]:
    """The entries of ``end_to_end`` or ``per_layer`` that this cell
    reports: those with no ``workloads`` key, and those that list it."""
    return [m for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]]
