"""A configuration cut to a size a test run can hold: the graph's scale and
the batch shrink, nothing else that a chip run depends on."""

from chipbench import spec

_load_config = spec.load_config  # the real one, before a test patches it

LIMITS = {"block_faults": 0, "block_overflow": 0, "nonfinite_losses": 0,
          "loss_gap": 1e-4, "grad_gap": 1e-3, "update_gap": 1e-3}


def shrink(cfg: dict) -> dict:
    cfg = dict(cfg)
    cfg["graph"] = dict(cfg["graph"], nodes=3000, edges=60000,
                        degree_alpha=2.0, max_degree=400)
    cfg["batch"] = 64
    cfg["frontier_caps"] = [3072] * len(cfg["fanout"])
    cfg["limits"] = dict(LIMITS)
    return cfg


def tiny_config(name: str) -> dict:
    return shrink(_load_config(name))
