"""graftaudit acceptance: the tree audits clean, every rule family
catches its seeded positive fixture, and the comm-budget rule reconciles
the lowered epoch body with the analytic model exactly at alpha 1 and 2.

Everything here is trace/lower only — no program executes a step (the
whole point: these invariants used to need slow execution differentials;
two of those are demoted to the slow lane in this PR)."""

import json

import pytest

from quiver_tpu.tools.audit import audit_targets
from quiver_tpu.tools.audit.audit_targets import REGISTRY, build, build_from
from quiver_tpu.tools.audit.cli import main
from quiver_tpu.tools.audit.rules import RULES, family_of, rule_docs
from quiver_tpu.tools.audit.runner import run_audit, select_targets

from audit_fixtures import (
    comm_fixtures,
    constant_fixtures,
    donation_fixtures,
    dtype_fixtures,
    hbm_fixtures,
    metrics_fixtures,
    padding_fixtures,
    parity_fixtures,
    replication_fixtures,
    vmem_fixtures,
)

_FIXTURES = {
    "collective-parity": parity_fixtures,
    "metrics-strip": metrics_fixtures,
    "donation-audit": donation_fixtures,
    "dtype-discipline": dtype_fixtures,
    "constant-bloat": constant_fixtures,
    "comm-budget": comm_fixtures,
    "peak-hbm-budget": hbm_fixtures,
    "no-silent-replication": replication_fixtures,
    "vmem-budget": vmem_fixtures,
    "padding-waste": padding_fixtures,
}


def _audit_fixture_set(rule, module):
    """Build a fixture module's targets and run one rule over each,
    resolving metrics pairs within the set."""
    pairs = [(t, build_from(t), fire) for t, fire in module.targets()]
    by_name = {t.name: b for t, b, _ in pairs}
    results = {}
    for t, built, fire in pairs:
        findings = RULES[rule](t, built, by_name.__getitem__)
        results[t.name] = (findings, fire)
    return results


@pytest.mark.parametrize("rule", sorted(_FIXTURES))
def test_rule_catches_its_positive_fixture(rule):
    for name, (findings, fire) in _audit_fixture_set(
            rule, _FIXTURES[rule]).items():
        if fire:
            assert findings, f"{rule} missed seeded positive {name}"
            assert all(f.rule == rule for f in findings)
        else:
            assert not findings, (
                f"{rule} false-positive on {name}: "
                f"{[f.message for f in findings]}"
            )


# slow lane: tracing + lowering all 14 registry programs is ~20s, and the
# CI audit job already gates the full registry twice per push (the
# authoritative `python -m quiver_tpu.tools.audit --sarif` run plus this
# file with no marker filter) — tier-1 keeps the per-rule fixture tests
# and the exactness differentials, which build only what they audit
@pytest.mark.slow
def test_repo_audits_clean():
    """The acceptance gate: every registered program upholds every rule
    family — 0 findings, nothing waived away silently."""
    result = run_audit()
    assert result.exit_code == 0
    assert result.findings == []
    assert set(result.targets) == set(REGISTRY)


def test_comm_budget_exact_at_alpha_1_and_2():
    """The lowered epoch body's all_to_all lanes == routed_lanes_per_hop
    EXACTLY at alpha in {1, 2} on the 2-device mesh — and the reconciled
    shapes are the ids + payload hops, not vacuous."""
    from quiver_tpu.control.cost import routed_lanes_per_hop
    from quiver_tpu.tools.audit.ir import collectives_of

    for name in ("epoch_body_alpha1", "epoch_body_alpha2"):
        built = build(name)
        comm = built.meta["comm"]
        model = routed_lanes_per_hop(
            comm["local_len"], comm["feature_shards"], comm["alpha"])
        a2a = [c for c in collectives_of(built.jaxpr)
               if c.prim == "all_to_all"]
        assert len(a2a) == 2, [str(c) for c in a2a]  # ids hop + payload hop
        for c in a2a:
            assert c.shape[:2] == (comm["feature_shards"],
                                   int(model["cap"]))
            assert c.lanes == int(model["lanes_per_hop"])
        assert not RULES["comm-budget"](REGISTRY[name], built, build)


def test_donating_epoch_donates_exactly_its_claim():
    """donate_epoch_state=True lowers a donation attr on every params+opt
    leaf (scan-carried state rides jax.buffer_donor) with zero
    unusable-donation warnings; the default epoch donates nothing."""
    from quiver_tpu.tools.audit.ir import main_arg_attrs

    donating = build("epoch_donating")
    attrs = main_arg_attrs(donating.mlir)
    donated = sum(1 for a in attrs if a["aliased"] or a["donor"])
    assert donated == REGISTRY["epoch_donating"].meta["donated_leaves"] > 0
    assert donating.donation_warnings == ()

    plain = build("epoch_body_alpha2")
    assert all(not (a["aliased"] or a["donor"])
               for a in main_arg_attrs(plain.mlir))


def test_donation_parser_pairs_operands_to_results():
    """main_arg_attrs against zero/partial/full donation: not just the
    donated COUNT but the operand<->result pairing — a pre-aliased arg's
    ``alias_output`` names the flattened result it writes into, tracking
    the matching result's POSITION, and an unusable donation leaves no
    attr (it surfaces as a warning only)."""
    import warnings

    import jax
    import jax.numpy as jnp

    from quiver_tpu.tools.audit.ir import main_arg_attrs

    a = jax.ShapeDtypeStruct((8, 4), jnp.float32)
    b = jax.ShapeDtypeStruct((16,), jnp.float32)

    def f(x, y):
        return x * 2.0, jnp.concatenate([y, y])

    def g(x, y):  # same programs, result order flipped
        return jnp.concatenate([y, y]), x * 2.0

    def attrs_of(fn, donate):
        with warnings.catch_warnings(record=True) as wlist:
            warnings.simplefilter("always")
            txt = jax.jit(fn, donate_argnums=donate).trace(
                a, b).lower().as_text()
        warned = [w for w in wlist if "donat" in str(w.message).lower()]
        return main_arg_attrs(txt), warned

    # zero donation: no attrs at all
    none, warned = attrs_of(f, ())
    assert len(none) == 2 and not warned
    assert all(not x["aliased"] and not x["donor"]
               and x["alias_output"] is None for x in none)

    # partial: x pre-aliases the same-shaped result — at index 0 in f,
    # index 1 in g: the parser reports the PAIRING, not a bare count
    part_f, warned = attrs_of(f, (0,))
    assert not warned
    assert (part_f[0]["aliased"], part_f[0]["alias_output"]) == (True, 0)
    assert part_f[1] == {"aliased": False, "donor": False,
                         "alias_output": None}
    part_g, _ = attrs_of(g, (0,))
    assert (part_g[0]["aliased"], part_g[0]["alias_output"]) == (True, 1)

    # full donation: y has no same-shaped result, so its donation is
    # UNUSABLE — no attr lowers for it, only the build warning (exactly
    # what the donation-audit rule counts on)
    full, warned = attrs_of(f, (0, 1))
    assert (full[0]["aliased"], full[0]["alias_output"]) == (True, 0)
    assert not full[1]["aliased"] and not full[1]["donor"]
    assert warned, "unusable donation must surface as a warning"


def test_changed_scoping_and_target_selection():
    assert select_targets(changed=set()) == []
    hit = select_targets(changed={"quiver_tpu/serving/ladder.py"})
    assert set(hit) == {"serve_forward", "serve_sample",
                        "serve_fleet_forward"}
    # PR 16-18 modules now scope to the targets that trace them
    assert "mmap_tiered_gather" in select_targets(
        changed={"quiver_tpu/ooc/store.py"})
    assert "serve_fleet_forward" in select_targets(
        changed={"quiver_tpu/serving/aot.py"})
    assert "pallas_fused_interp" in select_targets(
        changed={"quiver_tpu/ops/pallas/fused.py"})
    # editing the auditor itself re-audits everything
    assert set(select_targets(
        changed={"quiver_tpu/tools/audit/rules.py"})) == set(REGISTRY)
    with pytest.raises(ValueError):
        select_targets(names=["nope"])


def test_waivers_suppress_with_reason():
    t = REGISTRY["pallas_fused_interp"]
    assert "constant-bloat" in t.waivers  # reasoned registry-side waiver
    result = run_audit(targets=["pallas_fused_interp"])
    assert result.exit_code == 0
    assert ("pallas_fused_interp", "constant-bloat",
            t.waivers["constant-bloat"]) in result.waivers


def test_cli_json_and_sarif(tmp_path, capsys):
    sarif = tmp_path / "audit.sarif"
    rc = main(["--targets", "routed_gather", "--json",
               "--sarif", str(sarif)])
    assert rc == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["targets_audited"] == ["routed_gather"]
    assert payload["findings"] == []
    doc = json.loads(sarif.read_text())
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "graftaudit"
    assert {r["id"] for r in run["tool"]["driver"]["rules"]} == (
        set(RULES) | {"audit-error"})


def test_cli_usage_errors():
    assert main(["--select", "no-such-rule"]) == 2
    assert main(["--targets", "no-such-target"]) == 2


def test_sarif_merge(tmp_path):
    from quiver_tpu.tools.sarif import merge_sarif_files

    a = tmp_path / "lint.sarif"
    b = tmp_path / "audit.sarif"
    out = tmp_path / "analysis.sarif"
    doc = {"$schema": "s", "version": "2.1.0",
           "runs": [{"tool": {"driver": {"name": "graftlint"}},
                     "results": []}]}
    a.write_text(json.dumps(doc))
    doc["runs"][0]["tool"]["driver"]["name"] = "graftaudit"
    b.write_text(json.dumps(doc))
    merge_sarif_files([str(a), str(b), str(tmp_path / "missing.sarif")],
                      str(out))
    merged = json.loads(out.read_text())
    assert [r["tool"]["driver"]["name"] for r in merged["runs"]] == [
        "graftlint", "graftaudit"]


def test_rule_docs_cover_families():
    docs = rule_docs()
    for rule in RULES:
        assert docs[rule], f"{rule} has no doc"
        assert family_of(rule) != "meta"
    assert family_of("audit-error") == "meta"
