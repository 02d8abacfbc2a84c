"""Relational GAT over one CSR whose lanes carry their edge's relation.

OGB-LSC's MAG240M baseline with ``--model rgat`` (snap-stanford/ogb,
``examples/lsc/mag240m/rgnn.py``): the graph, the sampler and the head of
``models/rsage.py``, with one ``GATConv`` a relation in place of the
per-relation means. Layer ``i`` over targets ``x_t = x[:num_dst]``, ``H``
heads of width ``C``::

    out    = skip_i(x_t) + sum_r [r on a valid lane] GAT_r(x, x_t)
    z_j    = W_r x_j                  (W_r shared by sources and targets)
    e_tjh  = LeakyReLU_0.2(a_src[r,h] . z_jh + a_dst[r,h] . z_th)
    alpha  = softmax_j(e) per (t, r, h), j over t's lanes of relation r
    GAT_r(t) = concat_h sum_j alpha_tjh z_jh + b_r

There is no self term (``add_self_loops=False``): a target with no lane of
relation ``r`` gets ``b_r`` alone, and a relation that no valid lane of the
layer carries adds nothing, not even its bias (the script's ``if
subadj_t.nnz() > 0``). Then batch normalisation over the valid targets and
ELU. The head is ``models/rsage.py``'s.

**The order of the products.** Projected literally, every source row of the
block is multiplied by all ``R`` weights: 425,984 x 5 x 1,024 float32 values
at MAG240M's input layer, 8.7 GB beside a 5.46 GiB row table. The layer
aggregates first and transforms after, which is exact: a lane's logit term
``a_src[r,h] . (W_r,h x_j)`` is ``(W_r,h a_src[r,h]) . x_j``, so the logits
come from the raw rows, and ``sum_j alpha_tjh W_r,h x_j`` is ``W_r,h`` times
``sum_j alpha_tjh x_j``. So the layer gathers its source rows once,
fanout-major and in their stored dtype (``layers._fanout_index``, padded to
keep 256 rows in flight as ``fanout_relation_sums`` pads them), forms the
``(relations x heads)`` logit terms of every lane and target from
``(in)``-wide vectors, takes the softmax per target, relation and head
(``layers.fanout_relation_softmax``), sums each group's rows weighted by
their ``alpha`` (one ``(relations x heads, in)`` sum a target), and then
multiplies each ``(target, relation, head)`` sum by its ``(in, C)`` block of
``W_r``: one product that contracts relations and width together. That is a
third of the operations of projecting each lane by its relation's weight,
and no array of lanes by the output width. On a v5e the whole input layer,
forward and backward, took 29.0 ms, where the other order's grouped product
alone (forward and weight gradient) took 26.4 ms, before its own row
gather, its sort by relation and its passes over the projected lanes
(PERF.md section 6).

It reads ``Adj.relation`` and ``Adj.dst_count``, which the sampler sets
over a topology with edge relations (``CSRTopo.set_edge_relation``).
Scopes under ``conv{i}``: ``rgat_aggregate`` (the row gather and the
weighted sums), ``rgat_logits`` (the lane and target logit terms and the
LeakyReLU), ``rgat_softmax``, ``rgat_transform`` (the relation products,
the biases where present, the skip), ``norm``; the head is ``mlp``, its
batch norm ``mlp/norm``.
"""

from __future__ import annotations

from typing import Sequence

import jax.numpy as jnp
import flax.linen as nn
from jax import lax

from ..utils.trace import trace_scope
from .layers import _fanout_index, fanout_relation_softmax
from .rsage import BatchNorm, mlp_head, valid_targets

__all__ = ["RelGATConv", "RGAT"]

NEGATIVE_SLOPE = 0.2  # GATConv's, which rgnn.py leaves at its default


class RelGATConv(nn.Module):
    """One relational attention layer: a ``GATConv`` per relation with
    ``heads`` heads concatenated into ``features``, the skip, batch norm
    over the valid targets and ELU."""

    features: int
    heads: int
    num_relations: int

    @nn.compact
    def __call__(self, x, adj):
        num_dst, fanout = adj.size[1], adj.fanout
        R, H = self.num_relations, self.heads
        if adj.relation is None or fanout is None:
            raise ValueError(
                "RelGATConv needs the sampler's regular layout and "
                "Adj.relation: sample over a topology with edge relations")
        if self.features % H:
            raise ValueError(f"{self.features} features do not split into "
                             f"{H} heads")
        d_in, C = x.shape[-1], self.features // H
        kernel = self.param("rel_kernel", nn.initializers.lecun_normal(
            batch_axis=(0,)), (R, d_in, self.features))
        att_src = self.param("att_src", nn.initializers.glorot_uniform(),
                             (R, H, C))
        att_dst = self.param("att_dst", nn.initializers.glorot_uniform(),
                             (R, H, C))
        bias = self.param("rel_bias", nn.initializers.zeros,
                          (R, self.features))
        w = kernel.reshape(R, d_in, H, C)
        with trace_scope("rgat_aggregate"):
            # (fanout, targets + pad): the pad's lanes carry no relation
            idx = _fanout_index(adj.edge_index[0], num_dst, fanout, x.shape[0])
            rows = x[jnp.clip(idx, 0)].astype(jnp.float32)
            relation = lax.pad(adj.relation, jnp.asarray(-1, adj.relation.dtype),
                               ((0, 0, 0), (0, idx.shape[1] - num_dst, 0)))
            relation = jnp.where(idx >= 0, relation, -1)
            picked = relation[None] == jnp.arange(
                R, dtype=relation.dtype)[:, None, None]       # (R, K, T)
        with trace_scope("rgat_logits"):
            # a_src[r,h] . W_r,h x_j as (W_r,h a_src[r,h]) . x_j
            u_src = jnp.einsum("rfhc,rhc->rhf", w, att_src)
            u_dst = jnp.einsum("rfhc,rhc->rhf", w, att_dst)
            lane = jnp.einsum("rhf,ktf->rhkt", u_src, rows)
            target = jnp.einsum("rhf,tf->rht", u_dst,
                                x[:num_dst].astype(jnp.float32))
            target = jnp.pad(target, ((0, 0), (0, 0),
                                      (0, idx.shape[1] - num_dst)))
            logits = nn.leaky_relu(sum(
                jnp.where(picked[r], lane[r] + target[r][:, None], 0)
                for r in range(R)), NEGATIVE_SLOPE)            # (H, K, T)
        with trace_scope("rgat_softmax"):
            alpha = fanout_relation_softmax(logits, relation, R)
        with trace_scope("rgat_aggregate"):
            # each (target, relation, head)'s weighted sum of its rows
            sums = jnp.einsum("rhkt,ktf->trhf",
                              jnp.where(picked[:, None], alpha[None], 0), rows)
            present = picked.any(axis=(1, 2))
        with trace_scope("rgat_transform"):
            out = jnp.einsum("trhf,rfhc->thc", sums, w).reshape(
                -1, self.features)[:num_dst]
            out = out + jnp.where(present[:, None], bias, 0).sum(axis=0)
            out = out + nn.Dense(self.features, name="skip")(
                x[:num_dst].astype(jnp.float32))
        # what the batch norm is given: ``apply(..., mutable="intermediates")``
        # reads it
        self.sow("intermediates", "combined", out)
        out = BatchNorm(name="norm")(out, valid_targets(adj, num_dst))
        return nn.elu(out)


class RGAT(nn.Module):
    """R-GAT over sampler output (adjs deepest-first) and the MLP head of
    ``RGraphSAGE``; log-probabilities of the seeds' rows. Every layer has
    ``heads`` heads of ``hidden // heads`` per relation."""

    hidden: int
    heads: int
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
            x = RelGATConv(self.hidden, self.heads, self.num_relations,
                           name=f"conv{i}")(x, adj)
            x = nn.Dropout(self.dropout, deterministic=not train)(x)
        return mlp_head(x, valid_targets(adjs[-1], x.shape[0]), self.hidden,
                        self.num_classes, self.dropout, train)
