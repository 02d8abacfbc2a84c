"""GraphSAGE as the program has it (``quiver_tpu.models.sage``): the flax
module built from a configuration, and the harness's per-layer weights
(``reference/sage.py`` names them) in that module's tree and back."""

from __future__ import annotations

import numpy as np

__all__ = ["SCOPE", "build", "to_program_tree", "from_program_tree"]

# the name the module's ops carry in the step (``jvp(GraphSAGE)``): flax
# scopes a module's ops by its class name; fills ``{model_scope}`` in the
# patterns of the per-layer metrics
SCOPE = "GraphSAGE"


def build(cfg: dict):
    from quiver_tpu.models.sage import GraphSAGE

    return GraphSAGE(
        hidden=int(cfg["hidden"]), num_classes=int(cfg["classes"]),
        num_layers=int(cfg["layers"]), dropout=float(cfg["dropout"]),
    )


def to_program_tree(weights: list) -> dict:
    return {
        f"conv{i}": {
            "lin_l": {"kernel": w["w_neigh"], "bias": w["b"]},
            "lin_r": {"kernel": w["w_self"]},
        }
        for i, w in enumerate(weights)
    }


def from_program_tree(tree, layers: int) -> list:
    return [
        {
            "w_neigh": np.asarray(tree[f"conv{i}"]["lin_l"]["kernel"]),
            "b": np.asarray(tree[f"conv{i}"]["lin_l"]["bias"]),
            "w_self": np.asarray(tree[f"conv{i}"]["lin_r"]["kernel"]),
        }
        for i in range(layers)
    ]
