"""R-GAT as the program has it (``quiver_tpu.models.rgat``: OGB-LSC's
MAG240M baseline, ``rgnn.py --model rgat``): the flax module built from a
configuration, and the harness's weights (``reference/rgat.py`` names them:
one dict per relational layer, then one for the head) in that module's tree
and back."""

from __future__ import annotations

import numpy as np

__all__ = ["SCOPE", "build", "to_program_tree", "from_program_tree"]

# the name the module's ops carry in the step (``jvp(RGAT)``): flax scopes
# a module's ops by its class name; fills ``{model_scope}`` in the patterns
# of the per-layer metrics
SCOPE = "RGAT"

# per relation: the reference's name and the program's stacked leaf
_PER_RELATION = (("w_rel", "rel_kernel"), ("a_src", "att_src"),
                 ("a_dst", "att_dst"), ("b_rel", "rel_bias"))


def build(cfg: dict):
    from quiver_tpu.models.rgat import RGAT

    return RGAT(
        hidden=int(cfg["hidden"]), heads=int(cfg["heads"]),
        num_classes=int(cfg["classes"]), num_relations=int(cfg["relations"]),
        num_layers=int(cfg["layers"]), dropout=float(cfg["dropout"]),
    )


def _relations(layer: dict) -> int:
    return sum(name.startswith("w_rel") for name in layer)


def to_program_tree(weights: list) -> dict:
    *convs, head = weights
    tree = {}
    for i, w in enumerate(convs):
        conv = {leaf: np.stack([w[f"{name}{r}"]
                                for r in range(_relations(w))])
                for name, leaf in _PER_RELATION}
        conv.update(skip={"kernel": w["w_skip"], "bias": w["b_skip"]},
                    norm={"scale": w["gamma"], "bias": w["beta"]})
        tree[f"conv{i}"] = conv
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
        for name, leaf in _PER_RELATION:
            for r, value in enumerate(np.asarray(conv[leaf])):
                layer[f"{name}{r}"] = value
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
