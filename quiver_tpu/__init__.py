"""quiver-tpu: TPU-native graph sampling + feature collection for GNN training.

A from-scratch JAX/XLA/Pallas framework with the capabilities of
ed-aisys/torch-quiver (see SURVEY.md): GPU-class k-hop neighbor sampling and
tiered feature caching for PyG-style mini-batch GNN training, redesigned for
TPU — static shapes, single-controller SPMD over a ``jax.Mesh``, ICI
collectives instead of NVLink peer access, and host-offload staging instead
of UVA zero-copy.

Top-level exports mirror the reference package surface
(torch-quiver srcs/python/quiver/__init__.py:1-10).
"""

from .control import AlphaTuner, CacheController, CostModel, FreqSketch, SplitTuner
from .core.config import CachePolicy, SampleMode, parse_size_bytes
from .datasets import GraphDataset, load_dataset, planted_partition
from .core.hetero import HeteroCSRTopo, RelCSR
from .core.hetero_sharded import HeteroShardedTopology
from .core.sharded_topology import ShardedTopology
from .core.topology import CSRTopo, DeviceTopology
from .feature.feature import Feature, HeteroFeature
from .feature.shard import ShardedFeature, ShardedTensor
from .parallel.mesh import MeshTopo, can_device_access_peer, init_p2p, make_mesh
from .parallel.pipeline import Batch, Prefetcher
from .parallel.trainer import DataParallelTrainer, DistributedTrainer
from .sampling.dist_hetero import DistHeteroSampler
from .sampling.hetero import HeteroGraphSampler, HeteroLayer, HeteroSampleOutput
from .sampling.saint import (
    SAINTEdgeSampler,
    SAINTNodeSampler,
    SAINTRandomWalkSampler,
    saint_subgraph,
)
from .obs import (
    FlightRecorder,
    MetricSnapshot,
    MetricsRegistry,
    StepTimeline,
    TelemetryEndpoint,
    Tracer,
)
from .ooc import (
    AsyncStager,
    CorruptRawDir,
    MmapFeatureStore,
    quarantine_raw_dir,
    verify_raw_dir,
)
from .resilience import (
    CircuitBreaker,
    CorruptCheckpoint,
    DegradedFeature,
    FaultPlan,
    Preemption,
    TransientFault,
)
from .sampling.dist import DistGraphSageSampler
from .sampling.sampler import Adj, GraphSageSampler, SampleOutput
from .serving import (
    AOTExecutableCache,
    DeadlineBatcher,
    EmbeddingRefresher,
    InferenceServer,
    ServeQueueFull,
    ServingFleet,
)
from .streaming import (
    CommitAborted,
    DeltaBatch,
    DeltaRejected,
    StreamingGraph,
    VersionMismatchError,
)
from .utils.debug import show_tensor_info, tensor_info
from .utils.reorder import reorder_by_degree
from .utils.trace import Timer, enable_trace, get_logger, trace_scope

# reference name parity: `quiver.p2pCliqueTopo` (utils.py:64-104) is the
# clique view of the device set — on TPU, the ICI-slice view
p2pCliqueTopo = MeshTopo

__all__ = [
    "CSRTopo",
    "DeviceTopology",
    "ShardedTopology",
    "HeteroShardedTopology",
    "DistGraphSageSampler",
    "DistHeteroSampler",
    "HeteroCSRTopo",
    "RelCSR",
    "GraphSageSampler",
    "HeteroGraphSampler",
    "HeteroLayer",
    "HeteroSampleOutput",
    "SAINTNodeSampler",
    "SAINTEdgeSampler",
    "SAINTRandomWalkSampler",
    "saint_subgraph",
    "Adj",
    "SampleOutput",
    "Feature",
    "HeteroFeature",
    "ShardedFeature",
    "ShardedTensor",
    "MeshTopo",
    "p2pCliqueTopo",
    "Batch",
    "Prefetcher",
    "DataParallelTrainer",
    "DistributedTrainer",
    "make_mesh",
    "init_p2p",
    "can_device_access_peer",
    "CachePolicy",
    "SampleMode",
    "parse_size_bytes",
    "GraphDataset",
    "load_dataset",
    "planted_partition",
    "reorder_by_degree",
    "show_tensor_info",
    "tensor_info",
    "Checkpointer",
    "Timer",
    "trace_scope",
    "enable_trace",
    "get_logger",
    "MetricsRegistry",
    "MetricSnapshot",
    "StepTimeline",
    "Tracer",
    "FlightRecorder",
    "TelemetryEndpoint",
    "MmapFeatureStore",
    "AsyncStager",
    "CorruptRawDir",
    "verify_raw_dir",
    "quarantine_raw_dir",
    "FaultPlan",
    "Preemption",
    "TransientFault",
    "CircuitBreaker",
    "CorruptCheckpoint",
    "DegradedFeature",
    "DeltaBatch",
    "DeltaRejected",
    "StreamingGraph",
    "CommitAborted",
    "VersionMismatchError",
    "InferenceServer",
    "DeadlineBatcher",
    "EmbeddingRefresher",
    "ServeQueueFull",
    "ServingFleet",
    "AOTExecutableCache",
    "AlphaTuner",
    "CacheController",
    "CostModel",
    "FreqSketch",
    "SplitTuner",
]

__version__ = "0.1.0"


def __getattr__(name):
    # Checkpointer stays a lazy resolve (historical import-shape parity:
    # the store was once orbax-backed and optional; it is self-contained
    # now, but call sites import it both ways)
    if name == "Checkpointer":
        from .utils.checkpoint import Checkpointer

        return Checkpointer
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
