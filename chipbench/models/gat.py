"""GAT as the program has it (``quiver_tpu.models.gat``: the ogbn-products
recipe of PyTorch Geometric's example): the flax module built from a
configuration, and the harness's per-layer weights (``reference/gat.py``
names them) in that module's tree and back."""

from __future__ import annotations

import numpy as np

__all__ = ["SCOPE", "build", "to_program_tree", "from_program_tree"]

# the name the module's ops carry in the step (``jvp(GAT)``): flax scopes a
# module's ops by its class name; fills ``{model_scope}`` in the patterns of
# the per-layer metrics
SCOPE = "GAT"


def build(cfg: dict):
    from quiver_tpu.models.gat import GAT

    return GAT(
        hidden=int(cfg["hidden"]), num_classes=int(cfg["classes"]),
        num_layers=int(cfg["layers"]), heads=int(cfg["heads"]),
        dropout=float(cfg["dropout"]),
    )


def to_program_tree(weights: list) -> dict:
    return {
        f"conv{i}": {
            "lin": {"kernel": w["w"]},
            "att_l": w["a_src"],
            "att_r": w["a_dst"],
            "bias": w["b"],
            "skip": {"kernel": w["w_skip"], "bias": w["b_skip"]},
        }
        for i, w in enumerate(weights)
    }


def from_program_tree(tree, layers: int) -> list:
    return [
        {
            "w": np.asarray(tree[f"conv{i}"]["lin"]["kernel"]),
            "a_src": np.asarray(tree[f"conv{i}"]["att_l"]),
            "a_dst": np.asarray(tree[f"conv{i}"]["att_r"]),
            "b": np.asarray(tree[f"conv{i}"]["bias"]),
            "w_skip": np.asarray(tree[f"conv{i}"]["skip"]["kernel"]),
            "b_skip": np.asarray(tree[f"conv{i}"]["skip"]["bias"]),
        }
        for i in range(layers)
    ]
