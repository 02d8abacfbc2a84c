"""Relational GCN (R-GCN) over padded hetero layers.

The model family for the heterogeneous configs (BASELINE.json config 5:
MAG240M-style R-GCN). Schlichtkrull et al.'s R-GCN layer, adapted to the
typed padded-Adj format of sampling/hetero.py:

    h'_v = act( W_self^{type(v)} h_v
                + sum_rel mean_{u in N_rel(v)} W_rel h_u )

Per-relation weights support optional basis decomposition (num_bases > 0,
the paper's regularization for many-relation graphs): W_rel = sum_b
a_{rel,b} B_b, with the bases shared across relations of the same layer.

Each layer consumes one HeteroLayer (deepest first) and shrinks every
type's frontier to its dst capacity, exactly like the homogeneous models'
``x[:num_dst]`` convention.
"""

from __future__ import annotations

from typing import Sequence

import jax.numpy as jnp
import flax.linen as nn

from .layers import gather_mean_aggregate

__all__ = ["RGCNLayer", "RGCN"]


def _rel_name(et) -> str:
    s, r, d = et
    return f"{s}__{r}__{d}"


class RGCNLayer(nn.Module):
    features: int
    num_bases: int = 0  # 0 = full per-relation weights
    dtype: str | None = None  # "bfloat16" = mixed-precision compute

    @nn.compact
    def __call__(self, x_dict: dict, layer) -> dict:
        """x_dict: {type: (src_cap_t, F)}; layer: HeteroLayer."""
        if self.dtype is not None:
            x_dict = {t: v.astype(self.dtype) for t, v in x_dict.items()}
        out = {}
        for t, cap in layer.dst_caps.items():
            if t in x_dict:
                out[t] = nn.Dense(
                    self.features, dtype=self.dtype, name=f"self_{t}"
                )(x_dict[t][:cap])

        rel_keys = sorted(layer.adjs, key=str)
        # one basis set per distinct source feature width (node types may
        # carry different-dimensional features)
        bases_by_dim: dict[int, jnp.ndarray] = {}
        for et in rel_keys:
            s_t, _, d_t = et
            adj = layer.adjs[et]
            if self.num_bases > 0:
                in_dim = x_dict[s_t].shape[-1]
                if in_dim not in bases_by_dim:
                    bases_by_dim[in_dim] = self.param(
                        f"bases_{in_dim}",
                        nn.initializers.lecun_normal(),
                        (self.num_bases, in_dim, self.features),
                    )
                coef = self.param(
                    f"coef_{_rel_name(et)}",
                    nn.initializers.normal(1.0 / max(self.num_bases, 1)),
                    (self.num_bases,),
                )
                w = jnp.einsum("b,bif->if", coef, bases_by_dim[in_dim])
                if self.dtype is not None:
                    # the basis combination stays f32 (params), but the big
                    # per-relation matmul must hit the MXU in bf16 like the
                    # Dense branch does
                    w = w.astype(self.dtype)
                h = x_dict[s_t] @ w
            else:
                h = nn.Dense(
                    self.features, use_bias=False, dtype=self.dtype,
                    name=f"rel_{_rel_name(et)}",
                )(x_dict[s_t])
            src, dst = adj.edge_index
            agg = gather_mean_aggregate(
                h, src, dst, layer.dst_caps[d_t],
                fanout=getattr(adj, "fanout", None),
            )
            out[d_t] = out[d_t] + agg
        return out


class RGCN(nn.Module):
    """Multi-layer R-GCN consuming HeteroGraphSampler output.

    Produces log-probabilities for the first ``dst_cap`` rows of
    ``target_type`` after the last layer (the seed rows, by the
    seeds-first frontier contract).
    """

    hidden: int
    num_classes: int
    target_type: str
    num_layers: int = 2
    num_bases: int = 0
    dropout: float = 0.5
    dtype: str | None = None  # "bfloat16" = mixed-precision compute

    @nn.compact
    def __call__(self, x_dict: dict, layers: Sequence, *, train: bool = False):
        if len(layers) != self.num_layers:
            raise ValueError(
                f"model has {self.num_layers} layers but got {len(layers)} "
                "hetero layers; sampler sizes and num_layers must match"
            )
        for i, layer in enumerate(layers):
            feats = (
                self.num_classes if i == self.num_layers - 1 else self.hidden
            )
            x_dict = RGCNLayer(
                feats, num_bases=self.num_bases, dtype=self.dtype,
                name=f"conv{i}",
            )(x_dict, layer)
            if i != self.num_layers - 1:
                x_dict = {t: nn.relu(v) for t, v in x_dict.items()}
                drop = nn.Dropout(self.dropout, deterministic=not train)
                x_dict = {t: drop(v) for t, v in x_dict.items()}
        # log-softmax in f32: bf16 has too little mantissa for stable NLL
        return nn.log_softmax(
            x_dict[self.target_type].astype(jnp.float32), axis=-1
        )
