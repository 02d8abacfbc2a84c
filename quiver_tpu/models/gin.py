"""Graph Isomorphism Network over padded Adj blocks.

The reference delegates modeling to PyG (its examples are SAGE/GAT
configs); quiver-tpu ships a TPU-native GINConv for API breadth — GIN (Xu
et al., "How Powerful are Graph Neural Networks?") is the standard
expressiveness-maximal aggregator a torch-quiver user would bring along.
Semantics follow PyG ``GINConv``:

    h_i' = MLP( (1 + eps) · x_i  +  Σ_{j ∈ N(i)} x_j )

with SUM aggregation (no normalization — that is the point of GIN) and the
customary 2-layer MLP (Dense → ReLU → Dense). ``eps`` is 0 and fixed by
default (PyG's default); ``train_eps=True`` makes it a learnable scalar.

All shapes static: the self term is ``x[:num_dst]`` by the seeds-first
frontier contract (destination i has source-local id i), and the neighbor
sum is a ``segment_sum`` with the usual overflow bucket for padding lanes.
On a block that covers the full graph this is exactly full-graph GIN,
which :func:`quiver_tpu.models.inference.gin_layerwise_inference` computes
layer-wise with global degrees.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import flax.linen as nn

from .layers import fanout_gather_sum

__all__ = ["GINConv", "GIN"]


class GINConv(nn.Module):
    features: int
    mlp_hidden: int | None = None  # default: same as features
    train_eps: bool = False
    eps_init: float = 0.0
    dtype: str | None = None  # "bfloat16" = mixed-precision compute

    def setup(self):
        width = self.mlp_hidden or self.features
        self.lin1 = nn.Dense(width, dtype=self.dtype, name="lin1")
        self.lin2 = nn.Dense(self.features, dtype=self.dtype, name="lin2")
        if self.train_eps:
            self.eps = self.param("eps", nn.initializers.constant(self.eps_init), ())
        else:
            self.eps = self.eps_init

    def combine(self, z):
        """MLP((1+eps)x + Σ neighbors) — exposed for layer-wise inference,
        which builds the aggregate itself."""
        return self.lin2(nn.relu(self.lin1(z)))

    def __call__(self, x, edge_index, num_dst: int, fanout: int | None = None):
        src, dst = edge_index[0], edge_index[1]
        valid = (src >= 0) & (dst >= 0)

        if fanout is not None and src.shape[0] == num_dst * fanout:
            # regular sampler layout: dense reduction, zero scatters
            agg, _ = fanout_gather_sum(
                x, jnp.where(valid, src, -1), num_dst, fanout)
        else:
            msgs = jnp.where(valid[:, None], x[jnp.clip(src, 0)], 0.0)
            dst_safe = jnp.where(valid, dst, num_dst)  # padding -> overflow
            agg = jax.ops.segment_sum(
                msgs, dst_safe, num_segments=num_dst + 1)[:num_dst]
        z = agg + (1.0 + self.eps) * x[:num_dst]
        return self.combine(z)


class GIN(nn.Module):
    """Multi-layer GIN consuming sampler output (adjs deepest-first)."""

    hidden: int
    num_classes: int
    num_layers: int = 2
    dropout: float = 0.5
    train_eps: bool = False
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
            x = GINConv(feats, mlp_hidden=self.hidden,
                        train_eps=self.train_eps, dtype=self.dtype,
                        name=f"conv{i}")(x, adj.edge_index, num_dst,
                                   getattr(adj, "fanout", None))
            if i != self.num_layers - 1:
                x = nn.relu(x)
                x = nn.Dropout(self.dropout, deterministic=not train)(x)
        # log-softmax in f32: bf16 has too little mantissa for stable NLL
        return nn.log_softmax(x.astype(jnp.float32), axis=-1)
