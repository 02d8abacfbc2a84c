"""Graph Attention Network over padded Adj blocks: ogbn-products GAT as
PyTorch Geometric's own example trains it.

The reference delegates GAT to PyG (its ogbn-products GAT config is plain
``torch_geometric.nn.GATConv`` fed by quiver's sampler/feature — BASELINE
config 4 "attention aggregation, exercises segment-softmax"). The recipe is
that of
https://github.com/pyg-team/pytorch_geometric/blob/master/examples/ogbn_products_gat.py:
``GATConv`` layers (v1, Velickovic et al.) with a linear skip added to every
layer's output, heads concatenated in the hidden layers and averaged in the
output layer. For a layer with source rows ``h_j`` (the frontier), targets
``i < num_dst`` (the first ``num_dst`` sources), ``H`` heads of width ``F``:

  z_j    = W h_j                                  (no bias)
  s_j    = <a_src, z_j>,  d_i = <a_dst, z_i>      per head
  L(i)   = {valid sampled lanes (j -> i) with j != i} + {i}
  e_ij   = LeakyReLU_0.2(s_j + d_i)
  alpha  = softmax over j in L(i), per head
  o_i    = sum_{j in L(i)} alpha_ij z_j
  hidden : y_i = concat_heads(o_i) + b
  output : y_i = mean_heads(o_i) + b              (H heads, not one)
  h'_i   = y_i + W_skip h_i + b_skip              then ELU (+ dropout) unless last

**The self lane.** ``GATConv``'s self loop (PyG's ``add_self_loops=True``:
existing self loops are removed, then one is added per node) is, in a
sampled bipartite block, a lane that the block does not hold: a sampled lane
whose source is its own target is dropped, and every target attends to
itself through one self term. On the dense fanout path that term is a
separate operand of the softmax's max and denominator and of the fanout sum
(``layers.fanout_softmax``), so nothing is scattered and no
``(num_dst, fanout + 1, H, F)`` copy is made to append it; the segment path
appends the self edges to the edge list and is the differential oracle.

**The logits on the dense fanout path** are built from operands that are
in lane order already. The layer gathers its ``z`` rows once, by lane, into
``(targets, fanout, H, F)`` (scope ``attn_aggregate``, entered a first time
for the gather); the source half ``s_j`` of a lane's logit is the sum over
``F`` of that row times ``a_src``, the target half ``d_i`` is formed for the
targets' rows alone and broadcast over the fanout axis (a lane's target is
``lane // fanout``); the same gathered rows are then weighted and summed
(``attn_aggregate`` again).

**The per-lane scalars on the dense fanout path are lanes-minor**, for
R-GAT's reason (``layers.fanout_relation_softmax``): the lanes' logits, the
softmax's weights and, in the backward, their cotangents are ``(heads,
fanout, targets)`` arrays and the targets' terms ``(heads, targets)``, since
a heads-minor ``(targets, fanout, 4)`` array is padded to 128 lanes on a TPU
(425 MiB a pass for 8.7 MB of values at ogbn-products' first layer, eighteen
such passes 12.3 ms of a 68 ms step on a v5e: PERF.md). The lanes' logit
sums over ``F`` are a product with ``a_src``'s block-diagonal at ``highest``
(the MXU writes them lanes-minor; as a multiply and a reduce, XLA writes
them heads-minor whatever their logical order), the targets' ``fanout``
times fewer stay a multiply and a reduce, and the weights go into the rows
as a broadcast of the rows' ``(H, K, T, F)`` transpose. So a layer has one
gather, of rows: no ``(lanes, H)`` array is gathered or scattered (on a v5e
the transposed gather of those 16-byte lanes cost 29 ms of a 158 ms step:
PERF.md), and the backward's broadcast over the fanout axis fuses
into its users. The segment path keeps the per-node halves ``project``
returns and gathers them by lane.

**The row gather's transpose on the dense fanout path** is a gather as well
(``layers.gather_lane_rows``; as one scatter-add of every lane's 2 KB
cotangent row it was 40 ms of a 107 ms step, padded lanes and all: PERF.md,
PR 35): two sorts give every source row one of its lanes and the list of
the lanes that repeat a row, one row gather takes each row's cotangent from
its lane, and only the repeats are scatter-added, in chunks, by a loop of as
many trips as they need; the masked lanes add nothing. Its ops keep the
path of the forward call (``transpose(jvp(GAT))/conv{i}/attn_aggregate``).
The function returns the targets' rows ``z_i`` beside the lanes', so that
their cotangent is added to its rows of the gathered one in place and the
whole projection's cotangent is that rule's result as it stands. The segment
path keeps ``h[src]`` and its plain transposed scatter-add.

Dense matmuls are batched over heads so the MXU sees (N, H*F)-shaped work.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import flax.linen as nn

from ..utils.trace import trace_scope
from .layers import (
    fanout_softmax, fanout_sum_aggregate, gather_lane_rows, segment_softmax)

__all__ = ["GATConv", "GAT"]


def _lane_logits(rows, att):
    """``<att[h], rows[t, k, h, :]>`` of ``(T, K, H, F)`` rows, lanes-minor:
    ``(H, K, T)``. A product with ``att``'s block-diagonal ``(H, H, F)`` at
    ``highest``, so that the MXU writes the sums lanes-minor and the passes
    that use them keep that layout; as a multiply and a reduce over ``F``
    XLA writes them heads-minor (module docstring). The same sums in
    another order."""
    H = att.shape[0]
    return jnp.einsum("tkhf,ghf->gkt", rows,
                      jnp.eye(H, dtype=att.dtype)[:, :, None] * att,
                      precision=jax.lax.Precision.HIGHEST)


def _weighted(rows, scalars):
    """``(T, K, H, F)`` rows times ``(H, K, T)`` scalars, ``(T, H, F)`` rows
    times ``(H, T)``: the scalars broadcast over ``F`` and transposed as 4-D
    (or 3-D) arrays, so that no heads-minor array of them is formed."""
    return rows * jnp.swapaxes(jnp.broadcast_to(
        scalars[..., None], scalars.shape + rows.shape[-1:]), 0, -2)


class GATConv(nn.Module):
    """Multi-head graph attention over a padded edge block.

    Every target also attends to itself, through the self lane of the
    module docstring, and a linear projection (with bias) of the targets'
    own input rows is added to the output, as the ogbn-products example's
    ``skips`` do.

    Args:
      features: per-head output width F.
      heads: number of attention heads H.
      concat: concatenate heads (output H*F) or average them (output F).
      negative_slope: LeakyReLU slope for attention logits.
    """

    features: int
    heads: int = 1
    concat: bool = True
    negative_slope: float = 0.2
    dtype: str | None = None  # "bfloat16" = mixed-precision compute

    def setup(self):
        # setup-style (attribute/param names keep the original compact
        # module's tree: lin/att_l/att_r/bias) so full-graph layer-wise
        # inference (models/inference.py) can reuse trained weights through
        # the project/finish/add_skip methods
        H, F = self.heads, self.features
        self.lin = nn.Dense(H * F, use_bias=False, dtype=self.dtype,
                            name="lin")
        self.att_l = self.param(
            "att_l", nn.initializers.glorot_uniform(), (H, F)
        )
        self.att_r = self.param(
            "att_r", nn.initializers.glorot_uniform(), (H, F)
        )
        self.bias = self.param(
            "bias", nn.initializers.zeros,
            (H * F,) if self.concat else (F,),
        )
        self.lin_skip = nn.Dense(
            H * F if self.concat else F, dtype=self.dtype, name="skip")

    def project(self, x):
        """Node-level halves of the attention: per-head projections plus the
        a_l·Wh / a_r·Wh summands (per-edge logits are their sum) — avoids
        forming the (E, H, 2F) concat the naive formulation would need."""
        H, F = self.heads, self.features
        h_all = self.lin(x).reshape(x.shape[0], H, F)
        alpha_src = (h_all * self.att_l).sum(-1)  # (N, H)
        alpha_dst = (h_all * self.att_r).sum(-1)  # (N, H)
        return h_all, alpha_src, alpha_dst

    def finish(self, out):
        """(num_dst, H, F) aggregated messages -> layer output (concat or
        head-mean, + bias)."""
        num_dst = out.shape[0]
        if self.concat:
            return out.reshape(num_dst, self.heads * self.features) + self.bias
        return out.mean(axis=1) + self.bias

    def add_skip(self, y, x_dst):
        """The layer output plus the skip projection of the targets' own
        input rows ``x_dst``."""
        return y + self.lin_skip(x_dst)

    def __call__(self, x, edge_index, num_dst: int, fanout: int | None = None):
        src, dst = edge_index[0], edge_index[1]
        # a sampled lane from a target to itself is the self loop that the
        # self lane replaces
        valid = (src >= 0) & (dst >= 0) & (src != dst)
        src_safe = jnp.clip(src, 0)
        dense = fanout is not None and src.shape[0] == num_dst * fanout
        if not dense:
            dst_safe = jnp.where(valid, dst, num_dst)  # overflow segment

        # the scopes are entered here and not in the methods, which flax
        # names for itself (``conv0.project``): paths read
        # ``conv{i}/<scope>/...`` (docs/Introduction.md)
        if dense:
            # the logits from operands in lane order (module docstring): no
            # per-row s / d, no (lanes, H) gather
            with trace_scope("attn_project"):
                h_all = self.lin(x).reshape(
                    x.shape[0], self.heads, self.features)
            with trace_scope("attn_aggregate"):
                # (T, K, H, F) and the targets' rows; the masked lanes read
                # row 0 and their cotangent is dropped, the targets'
                # cotangent joins the lanes' inside the transposed gather
                # (layers.gather_lane_rows)
                zg, h_dst = gather_lane_rows(
                    h_all, jnp.where(valid, src, -1).reshape(num_dst, fanout),
                    num_dst)
                # the targets' rows as an array of their own: every use of
                # z but the gather reads these alone, so the whole
                # projection is free once the lanes' rows are gathered and
                # does not stay live through the backward for its head
                h_dst = jax.lax.optimization_barrier(h_dst)
                # every per-lane and per-target scalar is lanes-minor from
                # here on: (H, K, T) and (H, T) (module docstring). The
                # lanes' product is taken in the gather's scope: its
                # transpose is the rows' second cotangent, and the sum of
                # the two is then this scope's op, as the transposed gather
                # it feeds is
                s_lane = _lane_logits(zg, self.att_l)
            with trace_scope("attn_project"):
                # (H, T): fanout times fewer than the lanes' terms, and laid
                # out targets-minor as they stand
                s_dst = (h_dst * self.att_l).sum(-1).T
                d_dst = (h_dst * self.att_r).sum(-1).T
            with trace_scope("attn_logits"):
                logits = nn.leaky_relu(s_lane + d_dst[:, None],
                                       self.negative_slope)
                self_logits = nn.leaky_relu(
                    s_dst + d_dst, self.negative_slope)
        else:
            with trace_scope("attn_project"):
                h_all, alpha_src, alpha_dst = self.project(x)
                h_dst, alpha_dst = h_all[:num_dst], alpha_dst[:num_dst]
            with trace_scope("attn_logits"):
                logits = nn.leaky_relu(
                    alpha_src[src_safe]
                    + alpha_dst[jnp.clip(dst, 0, num_dst - 1)],
                    self.negative_slope)                         # (E, H)
                self_logits = nn.leaky_relu(
                    alpha_src[:num_dst] + alpha_dst, self.negative_slope)
        # softmax over each destination's lanes and its self lane, all heads
        # at once (computed in f32 via the att-param promotion for
        # stability, then downcast so the big (E, H, F) message traffic runs
        # at the compute dtype rather than silently promoting back to f32)
        with trace_scope("attn_softmax"):
            if dense:
                alpha, alpha_self = fanout_softmax(
                    logits, self_logits, valid.reshape(num_dst, fanout).T)
            else:
                # the self edges appended to the edge list
                alpha_all = segment_softmax(
                    jnp.concatenate([logits, self_logits]),
                    jnp.concatenate(
                        [dst_safe, jnp.arange(num_dst, dtype=dst.dtype)]),
                    jnp.concatenate([valid, jnp.ones((num_dst,), bool)]),
                    num_dst)
                alpha, alpha_self = (alpha_all[:src.shape[0]],
                                     alpha_all[src.shape[0]:])
            alpha = alpha.astype(h_all.dtype)
            alpha_self = alpha_self.astype(h_all.dtype)

        with trace_scope("attn_aggregate"):
            if dense:
                msgs = _weighted(zg, alpha)
                out = fanout_sum_aggregate(
                    msgs.reshape((-1,) + msgs.shape[2:]), valid, num_dst,
                    fanout)
                out = out + _weighted(h_dst, alpha_self)
            else:
                msgs = h_all[src_safe] * alpha[:, :, None]       # (E, H, F)
                msgs = jnp.where(valid[:, None, None], msgs, 0.0)
                out = jnp.zeros((num_dst + 1,) + msgs.shape[1:], msgs.dtype)
                out = out.at[dst_safe].add(msgs)[:num_dst]
                out = out + h_dst * alpha_self[:, :, None]
            y = self.finish(out)
        with trace_scope("skip"):
            return self.add_skip(y, x[:num_dst])


class GAT(nn.Module):
    """Multi-layer GAT consuming sampler output (adjs deepest-first).

    The ogbn-products recipe of the module docstring: every layer has
    ``heads`` heads and a linear skip; hidden layers concatenate the heads
    (width ``heads * hidden``) and are followed by ELU and dropout; the
    output layer averages its ``heads`` heads into ``num_classes``."""

    hidden: int
    num_classes: int
    num_layers: int = 2
    heads: int = 4
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
            last = i == self.num_layers - 1
            x = GATConv(
                features=self.num_classes if last else self.hidden,
                heads=self.heads,
                concat=not last,
                dtype=self.dtype,
                name=f"conv{i}",
            )(x, adj.edge_index, num_dst, getattr(adj, "fanout", None))
            if not last:
                x = nn.elu(x)
                x = nn.Dropout(self.dropout, deterministic=not train)(x)
        # log-softmax in f32: bf16 has too little mantissa for stable NLL
        return nn.log_softmax(x.astype(jnp.float32), axis=-1)
