"""GraphSAGE in flax over padded Adj blocks.

Functional parity with the SAGE model of the reference's acceptance example
(torch-quiver examples/pyg/reddit_quiver.py:42-65: per-layer SAGEConv, ReLU +
dropout between layers, log-softmax head; layers consumed deepest-first with
``x_target = x[:size[1]]``). PyG's SAGEConv(mean) is
``W_l · mean(neighbors) + W_r · x_self``; we keep that form so accuracy
comparisons carry over.
"""

from __future__ import annotations

from typing import Sequence

import jax.numpy as jnp
import flax.linen as nn

from .layers import gather_mean_aggregate

__all__ = ["SAGEConv", "GraphSAGE"]


class SAGEConv(nn.Module):
    features: int
    # computation dtype (None = float32): "bfloat16" runs the matmuls and
    # aggregation in bf16 on the MXU while params stay float32 — the
    # standard TPU mixed-precision recipe. The reference is fp32-only.
    dtype: str | None = None

    def setup(self):
        # attribute names keep the original compact-module param tree
        # ("lin_l"/"lin_r"), so existing checkpoints/params stay valid
        self.lin_l = nn.Dense(self.features, dtype=self.dtype, name="lin_l")
        self.lin_r = nn.Dense(
            self.features, use_bias=False, dtype=self.dtype, name="lin_r"
        )

    def combine(self, agg, x_self):
        """W_l · aggregated-neighbors + W_r · x_self — exposed separately so
        full-graph layer-wise inference (models/inference.py) can reuse the
        trained weights on aggregates it computed itself."""
        return self.lin_l(agg) + self.lin_r(x_self)

    def __call__(self, x, edge_index, num_dst: int, fanout: int | None = None):
        src, dst = edge_index[0], edge_index[1]
        agg = gather_mean_aggregate(x, src, dst, num_dst, fanout=fanout)
        return self.combine(agg, x[:num_dst])


class GraphSAGE(nn.Module):
    """Multi-layer GraphSAGE consuming sampler output (adjs deepest-first)."""

    hidden: int
    num_classes: int
    num_layers: int = 2
    dropout: float = 0.5
    dtype: str | None = None  # "bfloat16" = mixed-precision compute

    @nn.compact
    def __call__(self, x, adjs: Sequence, *, train: bool = False):
        if len(adjs) != self.num_layers:
            raise ValueError(
                f"model has {self.num_layers} layers but got {len(adjs)} adjs; "
                "sampler sizes and num_layers must match"
            )
        if self.dtype is not None:
            x = x.astype(self.dtype)
        for i, adj in enumerate(adjs):
            num_dst = adj.size[1]
            feats = self.num_classes if i == self.num_layers - 1 else self.hidden
            x = SAGEConv(feats, dtype=self.dtype, name=f"conv{i}")(
                x, adj.edge_index, num_dst, getattr(adj, "fanout", None)
            )
            if i != self.num_layers - 1:
                x = nn.relu(x)
                x = nn.Dropout(self.dropout, deterministic=not train)(x)
        # log-softmax in f32: bf16 has too little mantissa for stable NLL
        return nn.log_softmax(x.astype(jnp.float32), axis=-1)
