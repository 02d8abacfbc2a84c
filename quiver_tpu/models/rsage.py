"""Relational GraphSAGE over one CSR whose lanes carry their edge's relation.

OGB-LSC's MAG240M baseline with ``--model rgraphsage``
(snap-stanford/ogb, ``examples/lsc/mag240m/rgnn.py``): node types are
contiguous id ranges of one graph, every edge has a relation, and the
sampler draws ``min(deg, k)`` lanes from a node's whole row, whatever their
relation, as PyG's ``NeighborSampler`` does over the typed adjacency. Layer
``i`` over targets ``x_t = x[:num_dst]``::

    out = skip_i(x_t) + sum_r [r on a valid lane] (W_{i,r} mean_r(x) + b_{i,r})

``mean_r`` is the mean of a target's sources along relation ``r`` (0 where
it has none), one ``SAGEConv(root_weight=False)`` per relation; a relation
that no valid lane of the layer carries adds nothing, not even its bias
(the script's ``if subadj_t.nnz() > 0``). Then batch normalisation over the
valid targets (training mode, biased variance) and ReLU. The head is
``Linear -> BatchNorm -> ReLU -> Linear``. Rows may be stored in float16;
they are widened to float32 as they are summed, as the script widens its
batch after the gather.

It reads ``Adj.relation`` and ``Adj.dst_count``, which the sampler sets
over a topology with edge relations (``CSRTopo.set_edge_relation``).
Scopes under ``conv{i}``: ``rel_aggregate`` (the per-relation means),
``rel_transform`` (the per-relation products and the skip), ``norm``; the
head is ``mlp``, its batch norm ``mlp/norm``.
"""

from __future__ import annotations

from typing import Sequence

import jax.numpy as jnp
import flax.linen as nn

from ..utils.trace import trace_scope
from .layers import fanout_relation_sums, masked_batch_norm

__all__ = ["RelSAGEConv", "RGraphSAGE", "BatchNorm", "mlp_head",
           "valid_targets"]


def valid_targets(adj, num_dst: int):
    """Which of a block's ``num_dst`` target slots hold a node."""
    if adj.dst_count is None:
        raise ValueError(
            "a relational model needs Adj.dst_count: sample over a topology "
            "with edge relations (CSRTopo.set_edge_relation)")
    return jnp.arange(num_dst) < adj.dst_count


class BatchNorm(nn.Module):
    """Batch norm in training mode over the valid rows
    (``layers.masked_batch_norm``). Its running statistics, which only
    evaluation reads, are not kept. Its ops carry the module's name,
    ``norm``, as their scope."""

    @nn.compact
    def __call__(self, x, valid):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        bias = self.param("bias", nn.initializers.zeros, (x.shape[-1],))
        return masked_batch_norm(x, valid, scale, bias)


class RelSAGEConv(nn.Module):
    """One relational layer: per-relation means, their products, the skip,
    batch norm over the valid targets and ReLU."""

    features: int
    num_relations: int

    @nn.compact
    def __call__(self, x, adj):
        num_dst, fanout, R = adj.size[1], adj.fanout, self.num_relations
        if adj.relation is None or fanout is None:
            raise ValueError(
                "RelSAGEConv needs the sampler's regular layout and "
                "Adj.relation: sample over a topology with edge relations")
        d_in = x.shape[-1]
        kernel = self.param("rel_kernel", nn.initializers.lecun_normal(
            batch_axis=(0,)), (R, d_in, self.features))
        bias = self.param("rel_bias", nn.initializers.zeros,
                          (R, self.features))
        with trace_scope("rel_aggregate"):
            sums, counts = fanout_relation_sums(
                x, adj.edge_index[0], adj.relation, num_dst, fanout, R)
            means = [total / jnp.maximum(count, 1)[:, None]
                     for total, count in zip(sums, counts)]
            present = (counts > 0).any(axis=1)
        with trace_scope("rel_transform"):
            out = nn.Dense(self.features, name="skip")(
                x[:num_dst].astype(jnp.float32))
            for r in range(R):
                out = out + means[r] @ kernel[r] + jnp.where(
                    present[r], bias[r], 0)
        # what the batch norm is given (a bias shared by every target is
        # cancelled by it): ``apply(..., mutable="intermediates")`` reads it
        self.sow("intermediates", "combined", out)
        out = BatchNorm(name="norm")(out, valid_targets(adj, num_dst))
        return nn.relu(out)


class RGraphSAGE(nn.Module):
    """R-GraphSAGE over sampler output (adjs deepest-first) and its MLP
    head; log-probabilities of the seeds' rows."""

    hidden: int
    num_classes: int
    num_relations: int
    num_layers: int = 2
    dropout: float = 0.0

    @nn.compact
    def __call__(self, x, adjs: Sequence, *, train: bool = False):
        if len(adjs) != self.num_layers:
            raise ValueError(
                f"model has {self.num_layers} layers but got {len(adjs)} adjs; "
                "sampler sizes and num_layers must match"
            )
        for i, adj in enumerate(adjs):
            x = RelSAGEConv(self.hidden, self.num_relations,
                            name=f"conv{i}")(x, adj)
            x = nn.Dropout(self.dropout, deterministic=not train)(x)
        return mlp_head(x, valid_targets(adjs[-1], x.shape[0]), self.hidden,
                        self.num_classes, self.dropout, train)


def mlp_head(x, seeds, hidden: int, num_classes: int, dropout: float,
             train: bool):
    """The head of rgnn.py's models, called inside the model's compact
    ``__call__`` (its layers are the model's ``lin0``, ``norm``, ``lin1``):
    ``Linear -> BatchNorm over the seeds -> ReLU -> Linear``, then
    log-probabilities, under the scope ``mlp``."""
    with trace_scope("mlp"):
        x = nn.Dense(hidden, name="lin0")(x)
        x = nn.relu(BatchNorm(name="norm")(x, seeds))
        x = nn.Dropout(dropout, deterministic=not train)(x)
        x = nn.Dense(num_classes, name="lin1")(x)
    return nn.log_softmax(x, axis=-1)
