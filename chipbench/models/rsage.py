"""R-GraphSAGE as the program has it (``quiver_tpu.models.rsage``: OGB-LSC's
MAG240M baseline, ``rgnn.py --model rgraphsage``): the flax module built
from a configuration, and the harness's weights (``reference/rsage.py``
names them: one dict per relational layer, then one for the head) in that
module's tree and back."""

from __future__ import annotations

import numpy as np

__all__ = ["SCOPE", "build", "to_program_tree", "from_program_tree"]

# the name the module's ops carry in the step (``jvp(RGraphSAGE)``): flax
# scopes a module's ops by its class name; fills ``{model_scope}`` in the
# patterns of the per-layer metrics
SCOPE = "RGraphSAGE"


def build(cfg: dict):
    from quiver_tpu.models.rsage import RGraphSAGE

    return RGraphSAGE(
        hidden=int(cfg["hidden"]), num_classes=int(cfg["classes"]),
        num_relations=int(cfg["relations"]), num_layers=int(cfg["layers"]),
        dropout=float(cfg["dropout"]),
    )


def _relations(layer: dict) -> int:
    return sum(name.startswith("w_rel") for name in layer)


def to_program_tree(weights: list) -> dict:
    *convs, head = weights
    tree = {
        f"conv{i}": {
            "rel_kernel": np.stack([w[f"w_rel{r}"]
                                    for r in range(_relations(w))]),
            "rel_bias": np.stack([w[f"b_rel{r}"]
                                  for r in range(_relations(w))]),
            "skip": {"kernel": w["w_skip"], "bias": w["b_skip"]},
            "norm": {"scale": w["gamma"], "bias": w["beta"]},
        }
        for i, w in enumerate(convs)
    }
    tree.update(
        lin0={"kernel": head["w0"], "bias": head["b0"]},
        norm={"scale": head["gamma"], "bias": head["beta"]},
        lin1={"kernel": head["w1"], "bias": head["b1"]})
    return tree


def from_program_tree(tree, layers: int) -> list:
    out = []
    for i in range(layers):
        conv = tree[f"conv{i}"]
        layer = {}
        for r, (w, b) in enumerate(zip(np.asarray(conv["rel_kernel"]),
                                       np.asarray(conv["rel_bias"]))):
            layer[f"w_rel{r}"], layer[f"b_rel{r}"] = w, b
        layer.update(
            w_skip=np.asarray(conv["skip"]["kernel"]),
            b_skip=np.asarray(conv["skip"]["bias"]),
            gamma=np.asarray(conv["norm"]["scale"]),
            beta=np.asarray(conv["norm"]["bias"]))
        out.append(layer)
    out.append({
        "w0": np.asarray(tree["lin0"]["kernel"]),
        "b0": np.asarray(tree["lin0"]["bias"]),
        "gamma": np.asarray(tree["norm"]["scale"]),
        "beta": np.asarray(tree["norm"]["bias"]),
        "w1": np.asarray(tree["lin1"]["kernel"]),
        "b1": np.asarray(tree["lin1"]["bias"]),
    })
    return out
