"""graftmem acceptance: the static per-device memory estimate reconciles
with XLA's buffer assignment on the audit mesh, every registered program
declares (and fits) an ``hbm_budget``, the CostModel carries the peaks
into controller-facing predictions, and the registry's ``sources``
claims actually cover the modules its builders trace.

Fast lane compiles exactly one tiny target (routed_gather) for the
arg/out exactness check; the full-registry XLA tolerance sweep — the
only part that compiles all fourteen programs — rides the slow lane.
"""

import ast
import pathlib

import pytest

from quiver_tpu.control.cost import CostModel
from quiver_tpu.tools.audit import mem
from quiver_tpu.tools.audit.audit_targets import REGISTRY, build

_ROOT = pathlib.Path(__file__).resolve().parent.parent

# graftmem's estimate is a fusion-blind upper-shape: XLA fuses
# intermediates away below it and pads/aligns small buffers above it.
# The band is the measured envelope across the 14-target registry on
# jax's CPU backend (min 0.39 routed_gather, max 1.96
# mmap_tiered_gather), with margin so only a real accounting regression
# trips it.
_PEAK_RATIO_BAND = (0.33, 2.2)

# Targets whose argument byte total reproduces XLA exactly (the rest
# differ by XLA's sub-8-byte scalar padding on epoch/metrics operands).
_ARG_EXACT = frozenset({
    "routed_gather", "sample_hop", "serve_forward", "serve_sample",
    "pallas_fused_interp", "serve_fleet_forward", "mmap_tiered_gather",
})


def _estimate(name):
    built = build(name)
    return mem.estimate_peak(built.jaxpr, built.mlir), built


def test_arg_and_out_bytes_exact_on_routed_gather():
    """Fast-lane exactness anchor: on the canonical routed gather the
    static accounting reproduces XLA's argument AND output totals to the
    byte, and the peak lands inside the stated band."""
    est, _ = _estimate("routed_gather")
    stats = mem.xla_memory_stats(REGISTRY["routed_gather"])
    assert stats is not None, "CPU backend stopped exposing memory_analysis"
    assert est.arg_bytes == stats["argument_bytes"]
    assert est.out_bytes == stats["output_bytes"]
    lo, hi = _PEAK_RATIO_BAND
    assert lo <= est.peak_bytes / stats["peak_bytes"] <= hi


@pytest.mark.slow
def test_peak_estimate_tracks_xla_across_registry():
    """The acceptance tolerance: every registry program's static peak is
    within the stated band of XLA's buffer-assignment peak; argument
    bytes are exact on the listed targets and output bytes are exact on
    ALL of them (the tuple-table correction included)."""
    lo, hi = _PEAK_RATIO_BAND
    for name, target in REGISTRY.items():
        est, _ = _estimate(name)
        stats = mem.xla_memory_stats(target)
        assert stats is not None, name
        ratio = est.peak_bytes / max(stats["peak_bytes"], 1)
        assert lo <= ratio <= hi, (
            f"{name}: est {est.peak_bytes} vs xla {stats['peak_bytes']} "
            f"(ratio {ratio:.2f} outside {lo}..{hi})")
        assert est.out_bytes == stats["output_bytes"], name
        if name in _ARG_EXACT:
            assert est.arg_bytes == stats["argument_bytes"], name
        # the donation discount must match XLA's aliased bytes when the
        # program donates at all
        if target.meta.get("donation") == "epoch_state":
            assert est.aliased_bytes == stats["alias_bytes"] > 0


def test_every_target_declares_hbm_budget():
    """Acceptance: no registry program enters unpriced — the
    peak-hbm-budget rule treats a missing budget as a finding, so this
    is the same invariant checked without building anything."""
    for name, target in REGISTRY.items():
        budget = target.meta.get("hbm_budget")
        assert isinstance(budget, int) and budget > 0, (
            f"{name} has no usable hbm_budget: {budget!r}")


def test_fleet_target_joined_warm_from_aot():
    """Satellite target contract: the serve_fleet_forward builder grows
    the fleet by a warm replica and records its cold-start ledger —
    every executable loaded from the AOT cache, zero compiles."""
    build("serve_fleet_forward")
    warm = REGISTRY["serve_fleet_forward"].meta["warm_join"]
    assert warm["loaded"] > 0
    assert warm["compiled"] == 0


def test_cost_model_hbm_surface():
    model = CostModel(local_len=16, num_shards=2)
    assert not model.hbm_calibrated
    assert model.calibrate_hbm({}) is False
    assert not model.hbm_calibrated

    assert model.calibrate_hbm({"serve_forward": 9384}) is True
    assert model.hbm_calibrated
    fits = model.predict_hbm("serve_forward", budget_bytes=24 * 1024)
    assert fits == {"target": "serve_forward", "known": True,
                    "peak_bytes": 9384, "budget_bytes": 24 * 1024,
                    "headroom_bytes": 24 * 1024 - 9384, "fits": True}
    tight = model.predict_hbm("serve_forward", budget_bytes=9000)
    assert tight["fits"] is False and tight["headroom_bytes"] < 0
    unknown = model.predict_hbm("nope", budget_bytes=1)
    assert unknown["known"] is False and unknown["fits"] is None
    # without a budget the peak is reported but nothing is judged
    bare = model.predict_hbm("serve_forward")
    assert bare["peak_bytes"] == 9384 and bare["fits"] is None


# slow lane: the budget table builds (traces) all 14 registry programs;
# the CI memory-audit job runs this file unfiltered on every push, and
# the peak-hbm-budget rule gates the same headroom in the audit job —
# tier-1 keeps the meta-only budgets-declared check above
@pytest.mark.slow
def test_peak_table_budgets_all_in_headroom():
    """The CLI/scoreboard budget table: every row priced, every row in
    positive headroom (the repo's own programs fit their declared
    budgets), and the rendered table carries one line per target."""
    rows = mem.peak_table()
    assert {r["target"] for r in rows} == set(REGISTRY)
    for r in rows:
        assert r["hbm_budget"] is not None, r["target"]
        assert r["headroom_bytes"] >= 0, r
    rendered = mem.format_peak_table(rows)
    assert len(rendered.splitlines()) == len(rows) + 1


# -- sources coverage (the --changed contract) --------------------------------

# Modules a builder's import closure reaches that no target lists as a
# source, each with a reason the --changed contract tolerates it:
# host-side construction/observability/controller code that shapes no
# lowered program (the traced surfaces — cost.py, obs/registry.py —
# ARE in sources), and the resilience/utils layers no registry program
# exercises. quiver_tpu/tools/** is excluded structurally: editing the
# auditor re-audits every target already (runner.select_targets).
_SOURCES_EXEMPT = frozenset({
    "quiver_tpu/control/controller.py",
    "quiver_tpu/control/freq.py",
    "quiver_tpu/core/config.py",
    "quiver_tpu/core/memory.py",
    "quiver_tpu/core/sharded_topology.py",
    "quiver_tpu/obs/compile_watch.py",
    "quiver_tpu/obs/endpoint.py",
    "quiver_tpu/obs/export.py",
    "quiver_tpu/obs/timeline.py",
    "quiver_tpu/obs/tracing.py",
    "quiver_tpu/ops/reindex.py",
    "quiver_tpu/resilience/elastic.py",
    "quiver_tpu/resilience/faults.py",
    "quiver_tpu/resilience/guard.py",
    "quiver_tpu/resilience/integrity.py",
    "quiver_tpu/serving/coalesce.py",
    "quiver_tpu/utils/backend.py",
    "quiver_tpu/utils/checkpoint.py",
    "quiver_tpu/utils/reorder.py",
    "quiver_tpu/utils/trace.py",
})


def _module_file(parts):
    p = _ROOT.joinpath(*parts).with_suffix(".py")
    if p.is_file():
        return p
    p = _ROOT.joinpath(*parts) / "__init__.py"
    return p if p.is_file() else None


def _imports_of(path):
    """quiver_tpu module files imported anywhere in ``path`` — including
    the function-level imports the lazy builders use."""
    pkg = list(path.relative_to(_ROOT).parts[:-1])
    out = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            for alias in node.names:
                parts = alias.name.split(".")
                if parts[0] == "quiver_tpu":
                    f = _module_file(parts)
                    if f:
                        out.add(f)
        elif isinstance(node, ast.ImportFrom):
            if node.level:  # relative: level 1 is the containing package
                base = pkg[:len(pkg) - (node.level - 1)]
            else:
                base = []
            full = base + (node.module.split(".") if node.module else [])
            if not full or full[0] != "quiver_tpu":
                continue
            for alias in node.names:
                f = _module_file(full + [alias.name]) or _module_file(full)
                if f:
                    out.add(f)
    return out


def _builder_import_closure():
    seed = _ROOT / "quiver_tpu/tools/audit/audit_targets.py"
    seen, todo = set(), [seed]
    while todo:
        p = todo.pop()
        if p in seen:
            continue
        seen.add(p)
        todo.extend(_imports_of(p) - seen)
    rels = {str(p.relative_to(_ROOT)) for p in seen}
    return {r for r in rels
            if not r.endswith("__init__.py")
            and not r.startswith("quiver_tpu/tools/")}


def test_builder_import_closure_covered_by_sources():
    """Every quiver_tpu module a registry builder (transitively) traces
    appears in some target's ``sources`` — so ``--changed`` re-audits
    the right programs — except the explicitly reasoned exemptions. The
    newer subsystems must be covered, not exempted."""
    closure = _builder_import_closure()
    union = {s for t in REGISTRY.values() for s in t.sources
             if s.startswith("quiver_tpu/")}

    missing = closure - union - _SOURCES_EXEMPT
    assert not missing, (
        f"builder-traced modules invisible to --changed: {sorted(missing)}; "
        f"add them to a target's sources or (with a reason) to "
        f"_SOURCES_EXEMPT")
    # exemptions must not rot: anything now covered leaves the list
    stale = _SOURCES_EXEMPT & union
    assert not stale, f"exempt modules now in sources: {sorted(stale)}"
    # the PR 16-18 subsystems are load-bearing sources, never exemptions
    required = {
        "quiver_tpu/serving/aot.py",
        "quiver_tpu/serving/fleet.py", "quiver_tpu/ooc/store.py",
        "quiver_tpu/ooc/format.py", "quiver_tpu/ooc/stager.py",
    }
    assert required <= union
    assert not required & _SOURCES_EXEMPT
