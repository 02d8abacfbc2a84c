"""chip_smoke.py: refuses the CPU backend, and its stages hold together.

The smoke itself only means something on the chip; here its control flow
runs at a tiny node count on the CPU test mesh (kernel stage interpreted),
so a refactor that breaks a stage is caught in tier-1.
"""

import pytest

import chip_smoke
from quiver_tpu.parallel.mesh import make_mesh

TINY = chip_smoke.Config(
    nodes=3000, avg_degree=12.0, hidden=32, batch=64, steps=10, scan_steps=2,
    check_rows=40, interpret=True,
)


def test_refuses_the_cpu_backend(monkeypatch, capsys):
    ran = []
    for name in ("build_inputs", "one_device_stages", "stage_per_call",
                 "stage_trainer", "stage_kernels", "stage_multichip"):
        monkeypatch.setattr(chip_smoke, name,
                            lambda *a, _n=name, **k: ran.append(_n))
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main()
    assert exc.value.code not in (0, None)
    assert not ran  # no stage executed
    captured = capsys.readouterr()
    assert captured.out == ""  # no result, no metric
    assert "needs a TPU" in captured.err


def test_stages_at_tiny_size(capsys):
    meter = chip_smoke.CompileMeter()
    topo, feat, labels = chip_smoke.build_inputs(TINY)
    sampler, feature, out = chip_smoke.stage_per_call(TINY, topo, feat, meter)
    assert sampler._frontier_caps is not None  # auto caps planned
    chip_smoke.stage_trainer(
        TINY, "trainer", make_mesh(1), sampler, feature, labels, meter
    )
    chip_smoke.stage_kernels(TINY, sampler, out, meter)
    # the multi-device stage on the virtual mesh: placement and the
    # no-implicit-transfer guard are real there, memory statistics are not
    chip_smoke.stage_multichip(TINY, topo, feat, labels, sampler, meter)
    printed = capsys.readouterr().out
    for stage in ("per-call", "trainer", "kernels", "trainer, sharded feature"):
        assert f"[{stage}] setup" in printed
    assert "step() compiled once" in printed
    assert "operands span the 8-device mesh" in printed
    assert meter.compiles > 0


def test_a_failed_check_raises():
    with pytest.raises(chip_smoke.CheckFailed, match="overflow"):
        chip_smoke.check(False, "overflow == 0")
