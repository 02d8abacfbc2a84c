"""Graph Convolutional Network over padded Adj blocks.

The reference delegates modeling to PyG (its examples are SAGE/GAT configs);
quiver-tpu ships a TPU-native GCNConv for API breadth — GCN is the most
common GNN a torch-quiver user would bring along. Semantics follow Kipf &
Welling with the standard mini-batch adaptation (DGL GraphConv
``norm='both'`` on blocks): self-loops added per destination, symmetric
normalization by in-block degrees,

    h_i' = b + W · Σ_{j ∈ N(i) ∪ {i}}  h_j / sqrt(d_j · d_i)

where d are degrees of the self-loop-augmented block. On a block that
covers the full graph (every node a seed, full fanout) this is exactly
full-graph GCN, which is what :func:`gcn_layerwise_inference` computes
layer-wise with global degrees.

All shapes static: the self-loop edges are a fixed (num_dst,) append — the
seeds-first frontier contract guarantees destination i has source-local id
i — and degrees come from ``segment_sum`` with the usual overflow bucket.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import flax.linen as nn

from .layers import fanout_gather_sum, occurrence_counts

__all__ = ["GCNConv", "GCN"]


class GCNConv(nn.Module):
    features: int
    dtype: str | None = None  # "bfloat16" = mixed-precision compute

    def setup(self):
        # PyG GCNConv parameter shape: weight without bias + separate bias
        self.lin = nn.Dense(self.features, use_bias=False, dtype=self.dtype,
                            name="lin")
        self.bias = self.param("bias", nn.initializers.zeros,
                               (self.features,))

    def combine(self, agg):
        """W · (normalized aggregate) + b — exposed for layer-wise
        inference, which computes the normalized aggregate itself."""
        return self.lin(agg) + self.bias

    def __call__(self, x, edge_index, num_dst: int, fanout: int | None = None):
        N = x.shape[0]
        src, dst = edge_index[0], edge_index[1]
        valid = (src >= 0) & (dst >= 0)

        # in-block degrees of the self-loop-augmented graph: every dst gets
        # +1 (its loop), and a src that is also a dst carries that same loop
        # edge on its src side. src degrees have no regular layout (sources
        # land anywhere in the frontier), so they go through the
        # platform-resolved histogram either way.
        deg_src = occurrence_counts(src, valid, N, dtype=x.dtype)
        deg_src = deg_src.at[:num_dst].add(1.0)
        inv_s_src = jax.lax.rsqrt(jnp.maximum(deg_src, 1.0))

        h = x * inv_s_src[:, None]  # pre-scale once per node, not per edge
        if fanout is not None and src.shape[0] == num_dst * fanout:
            agg, cnt = fanout_gather_sum(
                h, jnp.where(valid, src, -1), num_dst, fanout)
            deg_dst = cnt.astype(x.dtype) + 1.0
        else:
            dst_safe = jnp.where(valid, dst, num_dst)
            deg_dst = jax.ops.segment_sum(
                valid.astype(x.dtype), dst_safe,
                num_segments=num_dst + 1)[:num_dst] + 1.0
            msgs = jnp.where(valid[:, None], h[jnp.clip(src, 0)], 0.0)
            agg = jax.ops.segment_sum(
                msgs, dst_safe, num_segments=num_dst + 1)[:num_dst]
        inv_s_dst = jax.lax.rsqrt(deg_dst)  # >= 1 by the self loop
        agg = agg + h[:num_dst]  # the self loop, already src-scaled
        agg = agg * inv_s_dst[:, None]
        return self.combine(agg)


class GCN(nn.Module):
    """Multi-layer GCN consuming sampler output (adjs deepest-first)."""

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
            x = GCNConv(feats, dtype=self.dtype, name=f"conv{i}")(
                x, adj.edge_index, num_dst, getattr(adj, "fanout", None)
            )
            if i != self.num_layers - 1:
                x = nn.relu(x)
                x = nn.Dropout(self.dropout, deterministic=not train)(x)
        # log-softmax in f32: bf16 has too little mantissa for stable NLL
        return nn.log_softmax(x.astype(jnp.float32), axis=-1)
