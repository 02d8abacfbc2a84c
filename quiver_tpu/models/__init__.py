"""TPU-native GNN model layer (the reference delegates this to PyG/DGL).

Models consume the sampler's padded Adj contract directly; see
models/layers.py for the segment-op primitives and models/inference.py for
full-neighbor layer-wise inference (the reference's ``model.inference``
evaluation path, examples/pyg/reddit_quiver.py:68-92)."""

from .gat import GAT
from .gcn import GCN, GCNConv
from .gin import GIN, GINConv
from .inference import (
    full_neighbor_mean,
    gat_layerwise_inference,
    gcn_layerwise_inference,
    gin_layerwise_inference,
    rgcn_layerwise_inference,
    sage_layerwise_inference,
)
from .rgat import RGAT
from .rgcn import RGCN
from .sage import GraphSAGE, SAGEConv

__all__ = [
    "GAT",
    "GCN",
    "GCNConv",
    "GIN",
    "GINConv",
    "GraphSAGE",
    "RGAT",
    "RGCN",
    "SAGEConv",
    "full_neighbor_mean",
    "gat_layerwise_inference",
    "gcn_layerwise_inference",
    "gin_layerwise_inference",
    "rgcn_layerwise_inference",
    "sage_layerwise_inference",
]
