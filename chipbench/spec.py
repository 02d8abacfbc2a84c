"""The benchmark's data files, found by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; a per-layer metric names
itself. Each is one file under this directory, so a later PR adds a cell, a
mix or a metric by adding files and entries and edits nothing that is here.
"""

from __future__ import annotations

import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


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
    return _load("configs", name)


def load_traffic(name: str) -> dict:
    return _load("traffic", name)


def load_metric(name: str) -> dict:
    return _load("metrics", name)


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
