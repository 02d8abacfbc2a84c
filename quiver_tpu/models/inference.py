"""Full-neighbor layer-wise inference over the whole graph.

The reference's acceptance examples evaluate with a layer-wise full-neighbor
pass — ``model.inference`` walks one layer at a time over ALL nodes using
*all* edges (torch-quiver examples/pyg/reddit_quiver.py:68-92, fed by a
``sizes=[-1]`` NeighborSampler). That is the path behind the published
Reddit accuracy, and it is cheaper than sampled k-hop evaluation because
each layer's embeddings are computed once and reused.

TPU redesign: a ``sizes=[-1]`` sampler is ragged and hub-hostile under
static shapes (one padded row per max-degree node). But full-neighbor mean
aggregation over every node at once is just a sparse matmul — so the
layer-wise pass becomes **chunked whole-graph segment aggregation**: walk the
CSR edge array in fixed-size chunks, gather source features, scatter-add
into a (N, F) accumulator, divide by degree, then apply the trained layer
weights via ``SAGEConv.combine``. Every chunk is one compiled program; no
sampling, no padding waste, no per-hub blowup.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core.config import SampleMode
from ..core.memory import to_pinned_host
from ..ops.sample import staged_gather
from .gat import GATConv
from .gcn import GCNConv
from .sage import SAGEConv

__all__ = [
    "full_neighbor_mean",
    "sage_layerwise_inference",
    "gat_layerwise_inference",
    "gcn_layerwise_inference",
    "gin_layerwise_inference",
    "rgcn_layerwise_inference",
]


def _edge_chunk(indptr, indices, e0, chunk: int, n: int, host: bool):
    """(src, dst, in_range) for edges [e0, e0+chunk).

    Row (destination) ids are recovered on device from ``indptr`` by binary
    search — no E-sized host-materialized row array. Out-of-range tail
    lanes (last chunk) are masked to the bucket row ``n``. With ``host``
    the edge array lives in pinned host memory and each chunk's ids stage
    through host compute (the beyond-HBM placement).
    """
    E = indices.shape[0]
    epos = e0 + jnp.arange(chunk, dtype=indptr.dtype)
    in_range = epos < E
    src = staged_gather(indices, jnp.where(in_range, epos, 0), host)
    dst = jnp.searchsorted(indptr, epos, side="right").astype(jnp.int32) - 1
    dst = jnp.where(in_range, jnp.clip(dst, 0, n - 1), n)
    return src.astype(jnp.int32), dst, in_range


@functools.partial(
    jax.jit, donate_argnums=0, static_argnames=("chunk", "host")
)
def _accumulate_chunk(acc, x_all, indptr, indices, e0, chunk: int,
                      host: bool):
    """Scatter-add one edge chunk's source features into the accumulator.

    ``dst`` comes from a searchsorted over ascending edge positions, so it
    is non-decreasing (the mask bucket n sorts last) — the scatter gets the
    sorted-indices hint."""
    n = acc.shape[0] - 1  # last row is the mask bucket
    src, dst, _ = _edge_chunk(indptr, indices, e0, chunk, n, host)
    return acc.at[dst].add(x_all[src], indices_are_sorted=True)


@functools.partial(
    jax.jit, donate_argnums=0, static_argnames=("chunk", "span", "host")
)
def _accumulate_chunk_scan(acc, x_all, indptr, indices, e0, chunk: int,
                           span: int, host: bool):
    """Zero-scatter chunk aggregation (the TPU default; against the
    scatter sibling it has no chip number, ROADMAP D4).

    CSR edge order makes each chunk's destinations a sorted run over a
    CONTIGUOUS row window, so the segmented sum is exact dense algebra:
    cumsum the chunk's messages, difference the prefix at each window row's
    clipped [indptr[v], indptr[v+1]) span, and add the (span, F) result
    into the accumulator with one dynamic windowed update. ``span`` is a
    host-precomputed static bound on rows any aligned chunk intersects.
    """
    n = acc.shape[0] - 1
    f = x_all.shape[1]
    src, _, in_range = _edge_chunk(indptr, indices, e0, chunk, n, host)
    msgs = jnp.where(in_range[:, None], x_all[src], 0)
    # Precision: differencing a prefix sum loses ~eps*|prefix| absolutely,
    # and same-sign features (post-ReLU activations) grow the prefix to
    # ~chunk*mean — ~10% row-sum error at chunk=2^21. Mean-centering keeps
    # the prefix at random-walk magnitude (~sqrt(chunk)*sigma) and the
    # exact (hi-lo)*mean term restores the row sums losslessly. The prefix
    # is always carried in f32 so bf16 tables keep their low bits too.
    pdt = jnp.promote_types(msgs.dtype, jnp.float32)
    mean = msgs.astype(pdt).mean(axis=0)  # (f,)
    centered = jnp.where(in_range[:, None], msgs.astype(pdt) - mean, 0)
    prefix = jnp.concatenate(
        [jnp.zeros((1, f), pdt), jnp.cumsum(centered, axis=0)]
    )
    r0 = (jnp.searchsorted(indptr, e0, side="right") - 1).astype(jnp.int32)
    # any window covering [r0, last-row-in-chunk] works: rows whose spans
    # end before e0 (or start after the chunk) difference to zero
    r0 = jnp.clip(r0, 0, max(n + 1 - span, 0))
    rows = r0 + jnp.arange(span, dtype=jnp.int32)
    rows_c = jnp.clip(rows, 0, n - 1)
    lo = jnp.clip(indptr[rows_c] - e0, 0, chunk).astype(jnp.int32)
    hi = jnp.clip(indptr[rows_c + 1] - e0, 0, chunk).astype(jnp.int32)
    contrib = prefix[hi] - prefix[lo] + (hi - lo).astype(pdt)[:, None] * mean
    contrib = jnp.where((rows < n)[:, None], contrib.astype(acc.dtype), 0)
    window = jax.lax.dynamic_slice(acc, (r0, 0), (span, f))
    return jax.lax.dynamic_update_slice(acc, window + contrib, (r0, 0))


def _chunk_row_span(indptr_host, chunk: int) -> int:
    """Static bound on the rows any aligned edge chunk intersects —
    host-side numpy over the CSR offsets (zero-degree runs make this graph-
    dependent, so it cannot be derived from ``chunk`` alone)."""
    import numpy as np

    ip = np.asarray(indptr_host)
    E = int(ip[-1])
    n = ip.shape[0] - 1
    if E == 0 or n == 0:
        return 1
    starts = np.arange(0, E, chunk)
    r0 = np.searchsorted(ip, starts, side="right") - 1
    r1 = np.searchsorted(ip, np.minimum(starts + chunk - 1, E - 1),
                         side="right") - 1
    span = int((r1 - r0).max()) + 1
    return min(-(-span // 8) * 8, n + 1)  # pad to 8 rows, cap at all rows


def _use_scan_agg() -> bool:
    """Platform-resolved chunk-aggregation strategy with env override
    (``QUIVER_INFER_AGG=scan|scatter``)."""
    from ..core.config import resolve_platform_strategy

    return resolve_platform_strategy(
        "QUIVER_INFER_AGG", ("scan", "scatter"), tpu_default="scan",
        other_default="scatter",
    ) == "scan"


def _neighbor_mean_dev(indptr, indices, x_all, chunk: int,
                       host: bool = False, span: int | None = None):
    """full_neighbor_mean body on already-placed CSR arrays.

    Output row count comes from ``indptr`` (not ``x_all``), so rectangular
    relation CSRs — rows in a dst-type id space, columns in a src-type id
    space (hetero RelCSR) — aggregate correctly too. ``span`` (static,
    from _chunk_row_span) selects the zero-scatter scan path; None keeps
    the scatter path.
    """
    f = x_all.shape[1]
    n_out = indptr.shape[0] - 1
    E = indices.shape[0]
    acc = jnp.zeros((n_out + 1, f), x_all.dtype)  # +1 = masked-lane bucket
    for e0 in range(0, max(E, 1), chunk):
        if span is not None:
            acc = _accumulate_chunk_scan(
                acc, x_all, indptr, indices,
                jnp.asarray(e0, indptr.dtype), chunk, span, host,
            )
        else:
            acc = _accumulate_chunk(
                acc, x_all, indptr, indices,
                jnp.asarray(e0, indptr.dtype), chunk, host,
            )
    deg = jnp.maximum(jnp.diff(indptr).astype(x_all.dtype), 1.0)
    return acc[:n_out] / deg[:, None]


def _place(topo, mode):
    """(indptr_dev, indices, host_flag): HBM puts everything on device;
    HOST keeps the big edge array in pinned host memory (falls back to
    device where the platform has no pinned_host space)."""
    mode = SampleMode.parse(mode)
    indptr = jnp.asarray(topo.indptr)
    if mode == SampleMode.HOST:
        indices, host = to_pinned_host(topo.indices)
        return indptr, indices, host
    return indptr, jnp.asarray(topo.indices), False


def full_neighbor_mean(topo, x_all, chunk: int = 1 << 21,
                       mode: str | SampleMode = SampleMode.HBM):
    """Mean of ALL neighbors' features for every node: (N, F) -> (N, F).

    ``topo`` is a host CSRTopo. ``mode="HBM"`` places the edge array on
    device (needs HBM alongside two (N, F) buffers); ``mode="HOST"`` keeps
    it in pinned host memory and stages each chunk's ids through host
    compute — graphs beyond HBM stay evaluable. Equivalent to ``D^-1 A X``
    with mean over incoming CSR neighbors; zero-degree rows aggregate to
    zeros, matching segment_mean_aggregate's empty-segment convention.
    """
    indptr, indices, host = _place(topo, mode)
    span = _chunk_row_span(topo.indptr, chunk) if _use_scan_agg() else None
    return _neighbor_mean_dev(indptr, indices, jnp.asarray(x_all), chunk,
                              host, span=span)


def _gat_edge_chunk(indptr, indices, e0, chunk: int, n: int, host: bool):
    """(src, dst) of ``_edge_chunk`` with the graph's own self loops sent to
    the bucket row ``n`` with the tail lanes: GATConv's self lane replaces
    them (models/gat.py)."""
    src, dst, _ = _edge_chunk(indptr, indices, e0, chunk, n, host)
    return src, jnp.where(src == dst, n, dst)


def _edge_logits(alpha_src, alpha_dst, src, dst, negative_slope):
    logit = alpha_src[src] + alpha_dst[jnp.clip(dst, 0, alpha_dst.shape[0] - 1)]
    return jax.nn.leaky_relu(logit, negative_slope)


@functools.partial(
    jax.jit, donate_argnums=0, static_argnames=("chunk", "host", "slope")
)
def _gat_max_chunk(seg_max, a_s, a_d, indptr, indices, e0, chunk, host,
                   slope):
    n = seg_max.shape[0] - 1
    src, dst = _gat_edge_chunk(indptr, indices, e0, chunk, n, host)
    return seg_max.at[dst].max(_edge_logits(a_s, a_d, src, dst, slope))


@functools.partial(
    jax.jit, donate_argnums=(0, 1),
    static_argnames=("chunk", "host", "slope"),
)
def _gat_denom_accum_chunk(num, denom, h_all, seg_max, a_s, a_d, indptr,
                           indices, e0, chunk, host, slope):
    """One fused pass updating BOTH the softmax denominator and the
    weighted-message numerator — the per-edge work (staged gather,
    searchsorted, logits, exp) is identical, so splitting them would sweep
    the (possibly pinned-host multi-GB) edge array twice for nothing."""
    n = num.shape[0] - 1
    src, dst = _gat_edge_chunk(indptr, indices, e0, chunk, n, host)
    logit = _edge_logits(a_s, a_d, src, dst, slope)
    w = jnp.exp(logit - seg_max[dst])  # (chunk, H)
    return (
        num.at[dst].add(w[:, :, None] * h_all[src]),
        denom.at[dst].add(w),
    )


def gat_layerwise_inference(model, params, topo, x_all,
                            chunk: int = 1 << 20,
                            mode: str | SampleMode = SampleMode.HBM):
    """Layer-wise full-neighbor GAT inference — attention over ALL edges.

    Beyond-reference capability (the reference ships layer-wise inference
    only for SAGE): per layer, two chunked edge passes realize an exact
    whole-graph segment softmax — (1) per-destination logit max, (2) a
    fused pass accumulating both the shifted-exp denominator and the
    weighted-message numerator — then the trained head combine, bias and
    skip apply via GATConv.finish and add_skip. The recipe is
    ``models/gat.py``'s: every node attends to itself through the self lane,
    which starts each node's max, denominator and numerator, and the graph's
    own self loops are left out of the passes; every layer has
    ``model.heads`` heads (averaged in the output layer) and a skip. Matches
    the sampled model at full fanout (tested). Zero-in-degree nodes attend
    to themselves alone.
    """
    x = jnp.asarray(x_all)
    indptr, indices, host = _place(topo, mode)
    n = topo.node_count
    E = int(topo.edge_count)
    for i in range(model.num_layers):
        last = i == model.num_layers - 1
        conv = GATConv(
            features=model.num_classes if last else model.hidden,
            heads=model.heads,
            concat=not last,
        )
        slope = conv.negative_slope
        p_i = {"params": params[f"conv{i}"]}
        h_all, a_s, a_d = conv.apply(p_i, x, method=GATConv.project)
        H = h_all.shape[1]

        e0s = [jnp.asarray(e0, indptr.dtype)
               for e0 in range(0, max(E, 1), chunk)]
        self_logit = jax.nn.leaky_relu(a_s + a_d, slope)  # (n, H)
        # the bucket row's shift stays finite (what lands there is cut off)
        seg_max = jnp.concatenate(
            [self_logit, jnp.zeros((1, H), h_all.dtype)])
        for e0 in e0s:
            seg_max = _gat_max_chunk(seg_max, a_s, a_d, indptr, indices, e0,
                                     chunk, host, slope)
        w_self = jnp.exp(self_logit - seg_max[:n])
        pad = ((0, 1), (0, 0))
        denom = jnp.pad(w_self, pad)
        num = jnp.pad(w_self[:, :, None] * h_all, pad + ((0, 0),))
        for e0 in e0s:
            num, denom = _gat_denom_accum_chunk(
                num, denom, h_all, seg_max, a_s, a_d, indptr, indices, e0,
                chunk, host, slope,
            )
        out = num[:n] / denom[:n, :, None]
        y = conv.apply(p_i, out, method=GATConv.finish)
        x = conv.apply(p_i, y, x, method=GATConv.add_skip)
        if not last:
            x = jax.nn.elu(x)
    return jax.nn.log_softmax(x, axis=-1)


def gcn_layerwise_inference(model, params, topo, x_all,
                            chunk: int = 1 << 21,
                            mode: str | SampleMode = SampleMode.HBM):
    """Layer-wise full-neighbor GCN inference: symmetric-normalized
    aggregation over the self-loop-augmented FULL graph,
    ``D^-1/2 (A + I) D^-1/2 X`` per layer, with global degrees — exactly
    what GCNConv computes on a block that covers the whole graph.

    Reuses the chunked mean machinery: sum = mean · deg, with the feature
    matrix pre-scaled by rsqrt(deg+1) and the result post-scaled the same
    way (plus the self term). Assumes the usual undirected/symmetrized
    topology (CSR row degree = both sides' degree), like full-graph GCN
    itself; matches GCNConv exactly on such graphs.
    """
    x = jnp.asarray(x_all)
    indptr, indices, host = _place(topo, mode)
    span = _chunk_row_span(topo.indptr, chunk) if _use_scan_agg() else None
    deg = jnp.diff(indptr).astype(x.dtype)
    inv_s = jax.lax.rsqrt(deg + 1.0)  # self-loop-augmented degrees
    for i in range(model.num_layers):
        feats = (
            model.num_classes if i == model.num_layers - 1 else model.hidden
        )
        h = x * inv_s[:, None]
        agg = _neighbor_mean_dev(indptr, indices, h, chunk, host, span=span)
        agg = (agg * deg[:, None] + h) * inv_s[:, None]
        conv = GCNConv(feats)
        x = conv.apply(
            {"params": params[f"conv{i}"]}, agg, method=GCNConv.combine
        )
        if i != model.num_layers - 1:
            x = jax.nn.relu(x)
    return jax.nn.log_softmax(x, axis=-1)


def gin_layerwise_inference(model, params, topo, x_all,
                            chunk: int = 1 << 21,
                            mode: str | SampleMode = SampleMode.HBM):
    """Layer-wise full-neighbor GIN inference: SUM aggregation over the
    full graph, ``MLP((1+eps)·x + A·x)`` per layer — exactly what GINConv
    computes on a block covering every node (sum = mean · degree, reusing
    the chunked mean machinery)."""
    from .gin import GINConv

    x = jnp.asarray(x_all)
    indptr, indices, host = _place(topo, mode)
    span = _chunk_row_span(topo.indptr, chunk) if _use_scan_agg() else None
    deg = jnp.diff(indptr).astype(x.dtype)
    for i in range(model.num_layers):
        last = i == model.num_layers - 1
        conv = GINConv(
            features=model.num_classes if last else model.hidden,
            mlp_hidden=model.hidden,
            train_eps=model.train_eps,
        )
        agg = _neighbor_mean_dev(indptr, indices, x, chunk, host,
                                 span=span)
        agg = agg * deg[:, None]
        p_i = {"params": params[f"conv{i}"]}
        eps = p_i["params"]["eps"] if model.train_eps else conv.eps_init
        z = agg + (1.0 + eps) * x
        x = conv.apply(p_i, z, method=GINConv.combine)
        if not last:
            x = jax.nn.relu(x)
    return jax.nn.log_softmax(x, axis=-1)


def rgcn_layerwise_inference(model, params, topo, x_dict,
                             chunk: int = 1 << 20,
                             mode: str | SampleMode = SampleMode.HBM):
    """Layer-wise full-neighbor R-GCN inference over a typed graph.

    Beyond-reference capability (no hetero exists there at all): per layer,
    every node type's self-transform plus, per relation, a chunked
    whole-relation mean aggregation of the relation-projected source
    features — the rectangular analogue of the SAGE pass, walked over each
    relation's own CSR. Trained weights are read straight from the
    ``conv{i}`` param tree (``self_{type}``, ``rel_{s}__{r}__{d}`` or the
    basis-decomposition ``bases_{dim}``/``coef_*`` pair), matching
    RGCNLayer's math exactly (tested against the sampled model at full
    fanout).

    Args:
      model: trained RGCN module.
      params: its parameter tree.
      topo: HeteroCSRTopo.
      x_dict: {node_type: (N_t, F_t)} full feature tables.
      chunk / mode: as in sage_layerwise_inference.

    Returns (N_target, num_classes) log-probs for every target-type node.
    """
    x_dict = {t: jnp.asarray(v) for t, v in x_dict.items()}
    placed = {
        et: _place(rel, mode) for et, rel in topo.relations.items()
    }
    scan_agg = _use_scan_agg()
    spans = {
        et: _chunk_row_span(rel.indptr, chunk) if scan_agg else None
        for et, rel in topo.relations.items()
    }
    for i in range(model.num_layers):
        p = params[f"conv{i}"]
        # the sampled model creates weights only for types/relations active
        # at that hop (e.g. the final layer serves seed types alone) — the
        # param tree is the source of truth for what this layer computes
        out = {}
        for t, x in x_dict.items():
            if f"self_{t}" not in p:
                continue
            w = p[f"self_{t}"]
            out[t] = x @ w["kernel"] + w["bias"]
        for et in sorted(topo.relations, key=str):
            s_t, _, d_t = et
            name = f"{s_t}__{et[1]}__{d_t}"
            if d_t not in out or s_t not in x_dict:
                continue
            if model.num_bases > 0:
                if f"coef_{name}" not in p:
                    continue
                in_dim = x_dict[s_t].shape[-1]
                wmat = jnp.einsum(
                    "b,bif->if", p[f"coef_{name}"], p[f"bases_{in_dim}"]
                )
            else:
                if f"rel_{name}" not in p:
                    continue
                wmat = p[f"rel_{name}"]["kernel"]
            h = x_dict[s_t] @ wmat
            indptr, indices, host = placed[et]
            out[d_t] = out[d_t] + _neighbor_mean_dev(
                indptr, indices, h, chunk, host, span=spans[et]
            )
        if i != model.num_layers - 1:
            out = {t: jax.nn.relu(v) for t, v in out.items()}
        x_dict = out
    return jax.nn.log_softmax(x_dict[model.target_type], axis=-1)


def sage_layerwise_inference(model, params, topo, x_all,
                             chunk: int = 1 << 21,
                             mode: str | SampleMode = SampleMode.HBM):
    """Layer-wise full-neighbor GraphSAGE inference (reference
    reddit_quiver.py:68-92 parity): returns (N, num_classes) log-probs for
    EVERY node, using all edges at every layer.

    Args:
      model: the trained GraphSAGE module (its hidden/num_classes/num_layers
        fields drive the pass).
      params: the trained parameter tree (``conv{i}`` children).
      topo: host CSRTopo.
      x_all: (N, F) input features (will be placed on device).
      chunk: edges per aggregation program.
      mode: "HBM" or "HOST" (pinned-host edge array for beyond-HBM graphs).
    """
    x = jnp.asarray(x_all)
    # place the (possibly multi-GB) CSR arrays once, not once per layer
    indptr, indices, host = _place(topo, mode)
    span = _chunk_row_span(topo.indptr, chunk) if _use_scan_agg() else None
    for i in range(model.num_layers):
        feats = (
            model.num_classes if i == model.num_layers - 1 else model.hidden
        )
        agg = _neighbor_mean_dev(indptr, indices, x, chunk, host,
                                 span=span)
        conv = SAGEConv(feats)
        x = conv.apply(
            {"params": params[f"conv{i}"]}, agg, x, method=SAGEConv.combine
        )
        if i != model.num_layers - 1:
            x = jax.nn.relu(x)
    return jax.nn.log_softmax(x, axis=-1)
