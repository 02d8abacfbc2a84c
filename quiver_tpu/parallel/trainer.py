"""Fused SPMD training: sample + gather + forward/backward + update in one
jitted shard_map program over the device mesh.

This replaces the reference's entire multi-process runtime (mp.spawn + DDP +
NCCL allreduce + CUDA-IPC object sharing, dist_sampling_ogb_products_quiver.py:
82-163, reductions.py:5-32) with a single-controller SPMD program:

* ``data`` mesh axis = the reference's one-process-per-GPU data parallelism;
  per-device seed blocks mirror ``train_idx.split(world_size)[rank]``
  (dist_sampling_ogb_products_quiver.py:89).
* gradient ``pmean`` over the mesh = the DDP/NCCL allreduce (:100).
* ``feature`` mesh axis = the NVLink clique: the hot feature shard is
  gathered with a psum collective inside the same program (see
  feature/shard.py), so sampling, gathers, compute, and gradient sync all
  fuse into one XLA executable — there is no per-batch host round-trip at
  all, something the reference's CPU-driven loop cannot do.

Seed-block placement is selectable (``seed_sharding``): under ``"data"``
sampling runs redundantly across the ``feature`` axis (same seeds, same
fold-in key => identical results per replica) and the sharded gather is a
psum; under ``"all"`` every device is a full data worker over its own seed
block and the sharded gather routes requests to their owning shard with
all_to_all (ShardedTensor.routed_gather) — measured, the redundancy of
"data" costs ~linearly in the feature-axis width, so prefer "all" whenever
feature > 1 (docs/Introduction.md "Cost of redundant sampling").
"""

from __future__ import annotations

import operator
from functools import partial, reduce
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.config import parse_size_bytes
from ..feature.feature import Feature
from ..feature.shard import ShardedFeature
from ..control.freq import heat_num_bins, row_heat_histogram
from ..obs.compile_watch import CompileTotals, compile_watch
from ..obs.registry import (
    FEATURE_ROW_HEAT,
    GATHER_PAD_LANES,
    GUARD_NONFINITE,
    GUARD_SKIPPED,
    PIPELINE_REISSUES,
    ROUTED_OVERFLOW,
    SAMPLE_EDGES,
    SAMPLE_FRONTIER,
    SAMPLE_FRONTIER_OVERFLOW,
    SAMPLE_OVERFLOW,
    SAMPLE_RELATION_LANES,
    SAMPLE_RELATION_TARGETS,
    TIER_HITS,
    TRAIN_OVERLAP_EFFICIENCY,
    XLA_CACHE_HITS,
    XLA_COMPILE_SECONDS,
    XLA_COMPILES,
    MetricsRegistry,
)
from ..obs.timeline import StepTimeline
from ..obs.tracing import Tracer
from ..resilience.elastic import validate_resume_meta, worker_ordered_mean
from ..resilience.faults import Preemption
from ..resilience.guard import guard_verdict, guarded_update
from ..utils.trace import get_logger, host_span, info_once, trace_scope
from ..parallel.mesh import DATA_AXIS, FEATURE_AXIS, shard_map
from ..parallel.pipeline import PipelinedBatch, Prefetcher
from ..parallel.train import cross_entropy_on_seeds
from ..sampling.sampler import Adj, GraphSageSampler, multilayer_sample

__all__ = ["DistributedTrainer", "DataParallelTrainer"]


def _metrics_report(metrics: MetricsRegistry, timeline: StepTimeline,
                    empty_note: str = "") -> str:
    """Shared one-call telemetry summary: every recorded registry metric
    (totals + the most recent per-step value) plus the host StepTimeline's
    streaming percentiles."""
    lines = []
    snaps = metrics.snapshots()
    if snaps:
        lines.append("metrics:")
        for s in snaps:
            arr = s.numpy
            head = f"  {s.name} ({s.kind}"
            if s.steps is not None:
                head += f", {s.steps} steps"
            head += ")"
            if s.kind == "counter":
                head += f": total={int(arr.sum())}"
                if s.steps is not None:
                    head += f" last={np.asarray(s.last()).tolist()}"
            else:
                head += f": last={np.asarray(s.last()).tolist()}"
                if s.steps is not None:
                    head += f" total={arr.sum(axis=0).tolist()}"
            lines.append(head)
    else:
        lines.append(f"metrics: (none recorded{empty_note})")
    lines.append("timeline:")
    lines.extend("  " + ln for ln in timeline.report().splitlines())
    return "\n".join(lines)


class _Phase:
    """One host phase of ``DistributedTrainer.step``, told to every sink
    from ONE pair of clock readings: the slice ``quiver.<stage>`` on the
    profiler's timeline (``utils.trace.host_span``; ``attrs`` are its
    stats), an observation of stage ``<stage>`` of the trainer's
    ``StepTimeline``, and, with an enabled ``Tracer``, the span
    ``train.<stage>`` of ``trace`` under ``parent``'s span. A phase that
    raises is still told, the span tagged with the error's name."""

    __slots__ = ("_timeline", "_tracer", "_stage", "_trace", "_parent",
                 "_attrs", "_note", "_t0", "span")

    def __init__(self, trainer: "DistributedTrainer", stage: str,
                 trace: str, parent: "_Phase | None" = None, **attrs):
        self._timeline = trainer.timeline
        self._tracer = trainer.tracer
        self._stage = stage
        self._trace = trace
        self._parent = parent
        self._attrs = attrs

    def set(self, **attrs) -> None:
        """Attach ``attrs`` to the open phase: stats of its profiler
        slice, attributes of its tracer span."""
        self._note.set_metadata(**attrs)
        if self.span is not None:
            self.span.attrs.update(attrs)

    def __enter__(self) -> "_Phase":
        # the slice opens first and closes last: it holds the phase's own
        # bookkeeping, so the leaves of a step cover it
        self._note = host_span(self._stage, **self._attrs)
        self._note.__enter__()
        self.span = self._tracer.begin_span(
            "train." + self._stage, trace=self._trace,
            parent=None if self._parent is None else self._parent.span,
            subsystem="trainer", **self._attrs,
        )
        self._t0 = self._tracer.now()
        return self

    def __exit__(self, exc_type, exc, tb):
        dur = self._tracer.now() - self._t0
        self._timeline.observe(self._stage, dur)
        if self.span is not None:
            if exc_type is not None:
                self.span.attrs["error"] = exc_type.__name__
            self._tracer.finish_span(self.span, self._t0, dur)
        return self._note.__exit__(exc_type, exc, tb)


class DistributedTrainer:
    """Owns the fused train step for a (sampler, feature, model) triple.

    Args:
      mesh: (data, feature) mesh from parallel.mesh.make_mesh.
      sampler: GraphSageSampler (its topology is replicated to all devices)
        or a ``topo_sharding="mesh"`` DistGraphSageSampler (the CSR itself
        partitioned over the feature axis — requires
        ``seed_sharding="all"``; per-hop neighbor lookups and the sharded
        feature gather then share ONE ``routed_alpha`` budget and, with
        ``auto_alpha=True``, one tuner).
      feature: Feature (device_replicate) or ShardedFeature (mesh_shard).
        Cold tiers are fused too: pinned-host rows ride as mesh-replicated
        operands and their staged gathers compose into the step program.
      model: flax module with (x, adjs, train=...) signature.
      tx: optax optimizer.
      local_batch: per-device seed-block size (padded).
      nonfinite_guard: compile the non-finite step guard into the step —
        a NaN/Inf loss or gradient cond-skips the optimizer update
        (params/opt_state pass through bit-unchanged) on a mesh-agreed
        verdict; skip/non-finite counters ride the metrics registry.
      fault_plan: a resilience.FaultPlan for deterministic chaos drills
        (in-program NaN feature rows at planned steps, simulated
        preemption); None = no injection compiled in.
      checkpoint_dir / checkpoint_every / checkpoint_keep: enable async
        checkpointing (utils/checkpoint.py: atomic manifest-based saves
        with per-array checksums) — epoch_scan saves (params, opt_state,
        step, PRNG key) every ``checkpoint_every`` steps (between scan
        chunks), keeping ``checkpoint_keep`` checkpoints; see
        :meth:`resume`.
      logical_workers: pin the LOGICAL seed-block worker count
        independently of the mesh (elastic mode; requires
        ``seed_sharding="all"`` and a multiple of the device count). Each
        device then runs ``logical_workers / devices`` blocks per step
        with the per-block PRNG key folded on the logical worker index,
        and the gradient/loss mean reduces in fixed logical-worker order
        (``resilience.elastic.worker_ordered_mean``) — the trajectory
        becomes bitwise independent of the mesh shape, which is what lets
        ``resume(mesh=)`` continue a run checkpointed at F=8 on an F=4
        mesh bit-identically. None (default) = one block per device with
        the plain pmean reduction (the non-elastic fast path).
      pipeline_depth: 0 (default) = the serial epoch scan (sample ->
        gather -> fwd/bwd -> update strictly in order each step); 1 =
        the software-pipelined epoch schedule: the scan carry becomes
        (params, opt_state, next_batch) with a ONE-STEP skew — the body
        trains the carried batch while issuing step t+1's sample+gather,
        so XLA can overlap the all_to_all / cold-tier gather collectives
        with the forward/backward compute (a prologue issues batch 0, an
        epilogue trains the final carried batch). Only the schedule
        moves: per-step keys stay the pre-split matrix and the two
        halves compose to the exact serial op sequence, so losses,
        params, and per-step telemetry are BITWISE identical to depth 0
        (tests/test_pipelined_epoch.py), including across checkpoint
        chunks — each chunk re-issues its first batch from the seed
        matrix (deterministic replay; counted in
        ``train.pipeline_reissues``) so chunk state never needs to
        serialize the in-flight batch. Affects epoch_scan only; step()
        stays the fused serial program.
      controller: a :class:`~quiver_tpu.control.CacheController` that
        owns the store's placement/routing decisions. The trainer
        attaches it to a ShardedFeature (L0/L1 boundary moves + measured
        ``repin`` re-tiering), registers its in-program row-heat
        histogram feed (``feature.row_heat`` — rides the metrics pytree,
        zero-cost when ``collect_metrics=False``), delegates the shared
        ``routed_alpha`` tuning to it, and drives its epoch hooks from
        :meth:`epoch_scan`. ``auto_alpha=True`` with no controller is a
        compat shim: a default alpha-only controller is created (grow on
        overflow as before, PLUS shrink on sustained slack). A frozen
        controller observes without deciding — the step program and
        trajectory stay bitwise those of ``controller=None``.
    """

    def __init__(
        self,
        mesh: Mesh,
        sampler: GraphSageSampler,
        feature: Feature | ShardedFeature,
        model,
        tx: optax.GradientTransformation,
        local_batch: int = 128,
        seed_sharding: str = "data",
        routed_alpha: float | None = 2.0,
        replicate_budget: int | str | None = None,
        auto_alpha: bool = False,
        collect_metrics: bool = True,
        nonfinite_guard: bool = False,
        fault_plan=None,
        checkpoint_dir=None,
        checkpoint_every: int = 0,
        checkpoint_keep: int = 3,
        logical_workers: int | None = None,
        pipeline_depth: int = 0,
        controller=None,
        donate_epoch_state: bool = False,
        tracer: Tracer | None = None,
        recorder=None,
    ):
        # beyond-HBM configs fuse too: HOST-mode topology and cold-tier
        # feature rows ride as mesh-replicated pinned-host operands, and the
        # staged host gathers (ops/sample.staged_gather — memory-SPACE
        # transfers, shard_map-safe) compose into the same one-program step
        # (reference equivalent: UVA training is its main papers100M path,
        # dist_sampling_ogb_paper100M_quiver.py:120-165).
        # seed_sharding: which mesh axes carry seed blocks.
        #   "data" — the original design: every member of a feature-axis
        #     group runs the SAME seed block (sampling + model work is
        #     duplicated feature-size times; the sharded-table gather is a
        #     cheap psum). Right when feature == 1.
        #   "all"  — every device is a full data worker over its own seed
        #     block; the sharded-table gather routes requests to owners
        #     with all_to_all (ShardedTensor.routed_gather) — the true
        #     NVLink-clique analogue (each reference GPU runs its own batch
        #     and loads peer HBM). Measured on the 8-dev CPU mesh the
        #     redundancy of "data" costs ~linearly in feature size
        #     (docs/Introduction.md), so prefer "all" whenever feature > 1.
        # routed_alpha: capped-bucket factor for the seed_sharding="all"
        # sharded-table gather — destination buckets carry
        # ceil(alpha * L / F) lanes, so each all_to_all hop moves ~alpha*L
        # lanes instead of the exact-safe F*L (feature/shard.py comm
        # model). Overflowed lanes are fallback-served in-program (results
        # stay exact); their count lands in ``last_routed_overflow`` after
        # each step so callers can grow alpha between epochs. None = the
        # uncapped full-length buckets.
        self.seed_sharding = str(seed_sharding)
        if self.seed_sharding not in ("data", "all"):
            raise ValueError(
                f"seed_sharding must be 'data' or 'all', got {seed_sharding!r}"
            )
        if routed_alpha is not None and routed_alpha <= 0:
            raise ValueError(
                f"routed_alpha must be > 0 or None, got {routed_alpha}"
            )
        self.routed_alpha = None if routed_alpha is None else float(routed_alpha)
        # one routing budget for the whole step: the SAME routed_alpha caps
        # the sharded-feature gather buckets AND (for a topo_sharding="mesh"
        # sampler) the per-hop neighbor-routing buckets. auto_alpha=True
        # turns on the shared tuner (a default control.CacheController —
        # see _maybe_grow_routed_alpha): overflow from an eager batch
        # doubles alpha (capped at F), sustained slack shrinks it back
        # (floor-bounded, no oscillation), and either change retraces.
        self.auto_alpha = bool(auto_alpha)
        # graftscope (obs/): ONE registry serves every telemetry stream the
        # step program produces. The traced body feeds a MetricsTape, the
        # resulting metrics pytree rides the shard_map/scan outputs (psum'd
        # once per step at each metric's declared axes), and step()/
        # epoch_scan() land it as typed MetricSnapshots. The legacy
        # ``last_*`` attributes below are thin views of the registry:
        #   feature.routed_overflow — fallback-served lane count of the
        #     step (scalar; (steps,) after epoch_scan; 0 when the gather
        #     is psum-flavored or uncapped)
        #   feature.tier_hits — per-tier hits [replicated, sharded, cold],
        #     psum'd mesh-wide (int32 (3,); (steps, 3) after epoch_scan) —
        #     what the eager split tuner consumes between batches
        #   sample.hop_overflow — the topo-sharded sampler's per-hop
        #     fallback lanes (int32 (num_layers,), seeds-outward;
        #     (steps, num_layers) after epoch_scan; zeros for replicated
        #     topologies)
        # The sampler's own counts have no ``last_*`` view; read them with
        # ``trainer.metrics.value(name)``: sample.edges, sample.frontier
        # (int32 (num_layers,), seeds-outward) and
        # sample.frontier_overflow (scalar), on every topology;
        # sample.relation_lanes and sample.relation_targets (int32
        # (num_layers, relations)) over a topology with edge relations.
        # collect_metrics=False disables collection at the PROGRAM level:
        # the compiled step carries zero metric values/collectives and the
        # loss trajectory is bit-identical (tests/test_obs.py differential).
        self.collect_metrics = bool(collect_metrics)
        self.metrics = MetricsRegistry(enabled=self.collect_metrics)
        self.metrics.counter(
            ROUTED_OVERFLOW, unit="lanes",
            doc="capped-bucket fallback-served lanes of the step's sharded "
                "feature gather",
        )
        self.metrics.counter(
            GATHER_PAD_LANES, unit="lanes",
            doc="lanes added to the step's plain hot-tier row gather so "
                "that it keeps 256 rows in flight (host-side, set when the "
                "step is traced)",
        )
        self.metrics.gauge(
            TIER_HITS, shape=(3,), unit="hits",
            doc="mesh-total per-tier feature hits "
                "[replicated, sharded, cold]",
        )
        self.metrics.counter(
            SAMPLE_OVERFLOW, shape=(len(tuple(sampler.sizes)),),
            unit="lanes",
            doc="per-hop fallback-served lanes of the topo-sharded "
                "sampler (seeds-outward)",
        )
        hops = (len(tuple(sampler.sizes)),)
        self.metrics.counter(
            SAMPLE_EDGES, shape=hops, unit="edges",
            doc="mesh-total valid sampled edges per hop (seeds-outward: "
                "index 0 is the seeds' own hop)",
        )
        self.metrics.counter(
            SAMPLE_FRONTIER, shape=hops, unit="nodes",
            doc="mesh-total distinct nodes found per hop before the "
                "frontier cap (seeds-outward; each worker's count summed)",
        )
        self.metrics.counter(
            SAMPLE_FRONTIER_OVERFLOW, unit="nodes",
            doc="mesh-total uniques dropped for exceeding frontier_caps, "
                "all hops; > 0 means the step trained on truncated blocks",
        )
        # over a topology with edge relations, the lanes of each relation
        # per hop: registered (and compiled in) only there, so a program
        # over a plain topology is the one it was
        relations = getattr(sampler.topo, "num_relations", 0)
        if relations:
            self.metrics.counter(
                SAMPLE_RELATION_LANES, shape=hops + (relations,),
                unit="lanes",
                doc="mesh-total valid sampled lanes per hop (seeds-outward) "
                    "and edge relation",
            )
            self.metrics.counter(
                SAMPLE_RELATION_TARGETS, shape=hops + (relations,),
                unit="targets",
                doc="mesh-total valid targets per hop (seeds-outward) with "
                    "at least one valid lane of each edge relation",
            )
        # resilience (resilience/): nonfinite_guard=True compiles the
        # non-finite step guard into the step body — a NaN/Inf loss or
        # gradient cond-skips the optimizer update (params/opt_state pass
        # through bit-unchanged) with a mesh-psum'd verdict so every chip
        # takes the same branch. The guard's counters ride the registry
        # only when the guard is on: a guard-off program carries zero
        # extra values and its loss trajectory is the bit-identical
        # baseline (tests/test_resilience.py differential).
        self.nonfinite_guard = bool(nonfinite_guard)
        if self.nonfinite_guard:
            self.metrics.counter(
                GUARD_SKIPPED, unit="steps",
                doc="optimizer updates cond-skipped by the non-finite "
                    "step guard (mesh-agreed verdict)",
            )
            self.metrics.counter(
                GUARD_NONFINITE, unit="values",
                doc="non-finite loss/grad values detected before the "
                    "gradient pmean",
            )
        # software-pipelined epoch (pipeline_depth=1): epoch_scan runs the
        # one-step-skew schedule — train the carried batch while issuing
        # the next one — built from the same issue/train halves the serial
        # body composes, so the trajectory stays bitwise identical while
        # the sample/gather collectives overlap the fwd/bwd compute. The
        # pipeline telemetry registers only when the schedule exists: a
        # depth-0 registry is byte-for-byte the pre-pipeline one.
        self.pipeline_depth = int(pipeline_depth)
        if self.pipeline_depth not in (0, 1):
            raise ValueError(
                f"pipeline_depth must be 0 (serial) or 1 (one-step skew), "
                f"got {pipeline_depth}"
            )
        # donate_epoch_state=True marks the (params, opt_state) arguments
        # of the epoch program as donated: XLA reuses the incoming leaves
        # for the scan carry instead of double-buffering them, halving the
        # model-state HBM footprint of epoch_scan. CONSUME semantics — the
        # arrays the caller passes in are deleted after the call (on every
        # backend, including CPU), so it is opt-in: the differential tests
        # reuse their initial params across variants and must keep the
        # default. epoch_scan itself is donation-safe — its chunk loop
        # rebinds (params, opt_state) from each chunk's outputs. graftaudit
        # (tools/audit, donation-audit rule) verifies the claim on the
        # lowered IR: exactly the params+opt leaves carry donation attrs
        # and the trace emits no unused-donation warning.
        self.donate_epoch_state = bool(donate_epoch_state)
        self._pipeline_reissues = 0
        if self.pipeline_depth:
            self.metrics.counter(
                PIPELINE_REISSUES, unit="batches",
                doc="prologue batches re-issued from the seed matrix at "
                    "checkpoint-chunk/resume boundaries (the carried "
                    "batch is replayed, not serialized)",
            )
            self.metrics.gauge(
                TRAIN_OVERLAP_EFFICIENCY, dtype=jnp.float32, unit="x",
                doc="serial stage-sum over measured pipelined step time "
                    "(> 1.0 = sample/gather latency hidden under "
                    "compute; host-derived, see StepTimeline."
                    "overlap_efficiency)",
            )
        # fault_plan: deterministic chaos schedule (resilience/faults.py).
        # Step indices mean the epoch_scan row (or the eager step() call
        # count): planned steps get their gathered features NaN-poisoned
        # in-program, and the planned preemption raises Preemption once
        # the step has run but before its checkpoint lands.
        self.fault_plan = fault_plan
        self._fault_step = 0  # eager step() call counter the plan indexes
        self._preempt_fired = False
        # grafttrace: host-side span tracing (disabled tracer = zero work,
        # bitwise-identical trajectory — spans are taken OUTSIDE every
        # compiled program) + flight-recorder trigger on guard trips
        self.tracer = tracer if tracer is not None else Tracer(enabled=False)
        self.recorder = recorder
        self._guard_trips_seen = 0
        # checkpoint/auto-resume: checkpoint_dir= + checkpoint_every=
        # drive async atomic saves of (params, opt_state, step, PRNG key)
        # between scan chunks; resume() restores the latest and the
        # caller replays the packed seed stream from the saved step
        # (bit-identical trajectory — pack_epoch is deterministic per
        # seed, and the per-step keys are split from the saved key0).
        self.checkpoint_every = int(checkpoint_every)
        if checkpoint_dir is not None:
            if self.checkpoint_every < 1:
                raise ValueError(
                    "checkpoint_dir= requires checkpoint_every >= 1 "
                    f"(got {checkpoint_every})"
                )
            from ..utils.checkpoint import Checkpointer

            self.checkpointer = Checkpointer(
                checkpoint_dir, max_to_keep=checkpoint_keep,
                tracer=self.tracer,
            )
            latest = self.checkpointer.latest_step()
            # a pre-existing run directory: keep manager ids monotonic
            self._ckpt_seq = 0 if latest is None else latest + 1
        else:
            if self.checkpoint_every:
                raise ValueError(
                    "checkpoint_every= without checkpoint_dir= has "
                    "nothing to write to"
                )
            self.checkpointer = None
            self._ckpt_seq = 0
        # host-side stage timeline (streaming p50/p95/p99); step() and
        # epoch_scan() time their eager dispatch, callers can add their own
        # stages (or feed it via Timer(registry=trainer.timeline))
        self.timeline = StepTimeline()
        # what step()'s launches compiled (obs/compile_watch.py): a re-keyed
        # step program (eager resplit, refresh(), grown routed_alpha) is
        # put down to the step that paid for it, in the registry, the
        # timeline (stage step.compile), health() and the log
        self._xla = compile_watch()
        self._xla_paid = CompileTotals()
        self._last_compile_step: int | None = None
        self.metrics.counter(
            XLA_COMPILES, unit="programs",
            doc="backend compilations step()'s launches paid, those the "
                "persistent compilation cache served included (host-side)",
        )
        self.metrics.gauge(
            XLA_COMPILE_SECONDS, dtype=jnp.float32, unit="s",
            doc="wall seconds of those compilations (a cache hit: of its "
                "retrieval), total (host-side)",
        )
        self.metrics.counter(
            XLA_CACHE_HITS, unit="programs",
            doc="of xla.compiles, how many the persistent compilation "
                "cache served (host-side)",
        )
        # replicate_budget: L0 super-hot tier override. A value re-splits a
        # ShardedFeature's replicated/sharded boundary BEFORE the program
        # is built (needs the store's retained host region); on a plain
        # Feature the hot tier is already a per-device replica, so the
        # argument is accepted-and-INERT (one-shot log). None = keep the
        # store's own split.
        if replicate_budget is not None:
            if isinstance(feature, ShardedFeature):
                feature.resplit_budget(replicate_budget)
            elif parse_size_bytes(replicate_budget):
                info_once(
                    "trainer-replicate-budget-inert",
                    "DistributedTrainer(replicate_budget=%r) on a "
                    "device_replicate Feature is INERT: its hot tier is "
                    "already replicated per device (zero-comm); size it "
                    "with device_cache_size",
                    replicate_budget,
                )
        if self.seed_sharding == "data" and mesh.shape[FEATURE_AXIS] > 1:
            from ..utils.trace import get_logger

            get_logger().info(
                "seed_sharding='data' on a feature=%d mesh duplicates "
                "sampling/model work %dx across the feature group; "
                "seed_sharding='all' removes that cost (measured ~linear, "
                "docs/Introduction.md)",
                mesh.shape[FEATURE_AXIS], mesh.shape[FEATURE_AXIS],
            )
        self.mesh = mesh
        self.sampler = sampler
        self.feature = feature
        self.model = model
        self.tx = tx
        self.local_batch = int(local_batch)
        # topo_sharding="mesh" sampler: the graph is partitioned over the
        # feature axis — the step routes frontier vertices to their owning
        # shard per hop (sampling/dist.py), so it REQUIRES every device to
        # be a seed-block worker ("all"); under "data" the feature-group
        # members would route the same frontier redundantly
        self.topo_sharded = (
            getattr(sampler, "topo_sharding", "replicated") == "mesh"
        )
        if self.topo_sharded:
            if self.seed_sharding != "all":
                raise ValueError(
                    "a topo_sharding='mesh' sampler requires "
                    "seed_sharding='all' (every device a full sampling "
                    "worker over its own seed block)"
                )
            if sampler.mesh is not mesh:
                raise ValueError(
                    "the sampler's mesh must be the trainer's mesh "
                    "(the topology partition and the step program must "
                    "agree on the feature axis)"
                )
            if sampler.axis != FEATURE_AXIS:
                raise ValueError(
                    f"topo_sharding='mesh' sampler must shard over the "
                    f"'{FEATURE_AXIS}' axis, got {sampler.axis!r}"
                )
        # operands placed over the mesh, by slot: (source, placed) — see
        # _mesh_wide
        self._placed: dict = {}
        self.topo = self._bound_topo()
        self.data_size = mesh.shape[DATA_AXIS]
        self.feature_size = mesh.shape[FEATURE_AXIS]
        # seed-block workers: every device under "all", one per data group
        # under "data". Elastic mode (logical_workers=) decouples the
        # LOGICAL worker count from the mesh: seed packing, the per-block
        # PRNG fold-in, and the fixed-order gradient reduction all follow
        # the logical count, so the same run continues bit-identically on
        # a differently-shaped mesh (resume(mesh=)).
        self._device_workers = self.data_size * (
            self.feature_size if self.seed_sharding == "all" else 1
        )
        self.elastic = logical_workers is not None
        if self.elastic:
            lw = int(logical_workers)
            if self.seed_sharding != "all":
                raise ValueError(
                    "logical_workers= (elastic mode) requires "
                    "seed_sharding='all': every device must be a full "
                    "seed-block worker for blocks to re-map across mesh "
                    "shapes"
                )
            if lw < self._device_workers or lw % self._device_workers:
                raise ValueError(
                    f"logical_workers={lw} must be a multiple of the "
                    f"device worker count {self._device_workers} (each "
                    f"device runs logical_workers/devices seed blocks)"
                )
            self.workers = lw
        else:
            self.workers = self._device_workers
        self.blocks_per_device = self.workers // self._device_workers
        self.global_batch = self.local_batch * self.workers
        # quiver-ctl (control/): one controller owns the placement and
        # routing decisions the legacy flags delegate to. auto_alpha with
        # no controller builds an alpha-only default (heat_bins=0, NOT
        # attached to the store — it must not start moving a split the
        # user never opted into); an explicit controller is attached to a
        # ShardedFeature and gets the in-program heat feed when it asks
        # for one (registered HERE, before the program builds, so the
        # histogram rides the step's metrics pytree).
        if controller is None and self.auto_alpha:
            from ..control import CacheController

            controller = CacheController(heat_bins=0)
        elif controller is not None and isinstance(feature, ShardedFeature):
            controller.attach(feature)
        self.controller = controller
        if (
            controller is not None
            and controller.wants_heat
            and self.collect_metrics
            and isinstance(feature, ShardedFeature)
            and feature.shape
        ):
            self.metrics.gauge(
                FEATURE_ROW_HEAT,
                shape=(heat_num_bins(feature.shape[0],
                                     controller.heat_bins),),
                unit="hits",
                doc="in-program per-row access-heat histogram (positional "
                    "bins over the store's translated row order, "
                    "mesh-total; feeds the controller's FreqSketch)",
            )
        _, self.caps = sampler._compiled(self.local_batch)
        self._step = self._build()
        self._epoch_fn = self._build_epoch()
        # streaming-mutation versions this program is bound to: the step
        # captured device operands (the topology arrays above, the
        # mesh-wide cold copy) from the host state as of THESE versions;
        # a quiver_tpu.streaming commit bumps them, after which
        # dispatching the captured program would silently read the
        # pre-commit graph/rows — step()/epoch_scan() raise instead
        # (refresh() re-captures and re-binds)
        self._bound_versions = self._current_versions()

    # -- telemetry views (API compatibility over the metrics registry) ------

    @property
    def last_routed_overflow(self):
        """Thin view of registry metric ``feature.routed_overflow``."""
        return self.metrics.value(ROUTED_OVERFLOW)

    @last_routed_overflow.setter
    def last_routed_overflow(self, value):
        self.metrics.set(ROUTED_OVERFLOW, value)

    @property
    def last_tier_hits(self):
        """Thin view of registry metric ``feature.tier_hits``."""
        return self.metrics.value(TIER_HITS)

    @last_tier_hits.setter
    def last_tier_hits(self, value):
        self.metrics.set(TIER_HITS, value)

    @property
    def last_sample_overflow(self):
        """Thin view of registry metric ``sample.hop_overflow``."""
        return self.metrics.value(SAMPLE_OVERFLOW)

    @last_sample_overflow.setter
    def last_sample_overflow(self, value):
        self.metrics.set(SAMPLE_OVERFLOW, value)

    def metrics_report(self) -> str:
        """One-call text summary of the trainer's telemetry: every recorded
        registry metric (totals + the most recent per-step value) plus the
        host StepTimeline's streaming percentiles."""
        return _metrics_report(
            self.metrics, self.timeline,
            "" if self.collect_metrics else "; collect_metrics=False",
        )

    def health(self) -> dict:
        """The ``/healthz`` summary: worker geometry, bound streaming
        versions, checkpoint progress, guard-trip count, and the index of
        the last eager ``step()`` whose launch compiled (None: none has;
        past the first step it names a re-keyed program)."""
        topo_v, feat_v = self._current_versions()
        return {
            "workers": int(self.workers),
            "global_batch": int(self.global_batch),
            "topology_version": topo_v,
            "feature_version": feat_v,
            "checkpoint_seq": int(self._ckpt_seq),
            "guard_trips": int(self._guard_trips_seen),
            "last_compile_step": self._last_compile_step,
        }

    def serve_telemetry(self, host: str = "127.0.0.1",
                        port: int = 0):
        """Start (and return) a live telemetry endpoint over this
        trainer: ``/metrics`` from its registry, ``/traces`` from its
        tracer, ``/healthz`` from :meth:`health`. Off unless called —
        the endpoint reads host-side snapshots only, so serving it
        cannot perturb the compiled step."""
        from ..obs.endpoint import TelemetryEndpoint

        return TelemetryEndpoint(
            metrics=self.metrics, tracer=self.tracer, health=self.health,
            host=host, port=port,
        ).start()

    # -- streaming-mutation versioning --------------------------------------

    def _current_versions(self) -> tuple[int, int]:
        """(topology version, feature version) of the HOST state right
        now — what a streaming commit bumps."""
        return (
            int(getattr(self.sampler.csr_topo, "version", 0)),
            int(getattr(self.feature, "version", 0)),
        )

    def _check_versions(self) -> None:
        """Raise instead of dispatching a program whose captured operands
        predate a streaming commit (silent stale reads: the step would
        sample the pre-commit topology and gather the pre-commit cold
        rows)."""
        current = self._current_versions()
        if current != self._bound_versions:
            from ..core.topology import VersionMismatchError

            raise VersionMismatchError(
                f"trainer program is bound to (topology, feature) "
                f"versions {self._bound_versions} but the host state has "
                f"committed {current}; call trainer.refresh() to "
                f"re-capture the mutated state before training"
            )

    def refresh(self) -> "DistributedTrainer":
        """Re-capture the trainer's device operands from the (mutated)
        host state and rebuild the step/epoch programs — the consumer
        side of a ``quiver_tpu.streaming`` commit.

        Refreshes, in order: the sampler's device topology (via its own
        ``refresh_topology`` seam, when stale), the trainer's captured
        topology operands, the compiled step/epoch programs, and the
        bound versions (the feature tiers are read every step and placed
        anew whenever their source array changed). The mesh, the model,
        the optimizer state layout, the seed packing, and the PRNG
        discipline are untouched — only the graph/feature bytes the
        programs read are re-pulled."""
        if int(getattr(self.sampler.csr_topo, "version", 0)) != \
                self.sampler._topo_version:
            self.sampler.refresh_topology()
        self.topo = self._bound_topo()
        self._step = self._build()
        self._epoch_fn = self._build_epoch()
        self._bound_versions = self._current_versions()
        return self

    # -- program ------------------------------------------------------------

    def _mesh_wide(self, slot: str, arr, spec: P = P()):
        """``arr`` committed over the trainer's mesh under ``spec``, in the
        memory kind it already has; None passes through.

        Placed ONCE per source array and remembered by ``slot``: a plain
        ``jnp.asarray`` operand sits uncommitted on the first device, and
        handing that to the mesh-wide program would have jit broadcast it
        again on every step. An array that already spans the mesh this way
        comes back as it is; a new source in a slot (an eager resplit, a
        refresh) replaces the old placement."""
        if arr is None:
            self._placed.pop(slot, None)
            return None
        hit = self._placed.get(slot)
        if hit is not None and hit[0] is arr:
            return hit[1]
        kind = (
            arr.sharding.memory_kind if isinstance(arr, jax.Array) else None
        )
        placed = jax.device_put(
            arr, NamedSharding(self.mesh, spec, memory_kind=kind)
        )
        self._placed[slot] = (arr, placed)
        return placed

    def _bound_topo(self):
        """The topology operand of the step program. A mesh-sharded
        topology is already partitioned by its sampler; a replicated one
        (HBM or pinned host) is placed over the mesh here."""
        topo = self.sampler.topo
        if self.topo_sharded:
            return (topo.indptr, topo.indices)
        leaves, treedef = jax.tree_util.tree_flatten(topo)
        return treedef.unflatten([
            self._mesh_wide(f"topo.{i}", leaf)
            for i, leaf in enumerate(leaves)
        ])

    def _feature_parts(self):
        """The feature-store arrays handed to the shard_map program:
        (rep, hot, cold, feature_order, scale), each spanning the mesh.
        ``rep`` is the L0 replicated super-hot block (ShardedFeature only;
        None on a plain Feature, whose whole hot tier is already a
        per-device replica). Read fresh each step: an eager resplit between
        batches swaps the tier buffers, and the new shapes re-key the jit
        cache."""
        feature = self.feature
        if isinstance(feature, ShardedFeature):
            rep = feature.rep
            # the sharded tier is placed by its ShardedTensor
            hot = None if feature.hot is None else feature.hot.table
        else:
            rep = None
            hot = self._mesh_wide("hot", feature.hot)
        return (
            self._mesh_wide("rep", rep),
            hot,
            self._mesh_wide("cold", feature.cold),
            self._mesh_wide("order", feature.feature_order),
            self._mesh_wide("scale", feature.scale),
        )

    def _build(self):
        mesh = self.mesh
        sampler = self.sampler
        feature = self.feature
        model = self.model
        tx = self.tx
        caps = self.caps
        sizes = sampler.sizes
        sharded = isinstance(feature, ShardedFeature)
        cold_is_host = getattr(feature, "_cold_is_host", False)

        routed = self.seed_sharding == "all"
        routed_alpha = self.routed_alpha
        topo_sharded = self.topo_sharded
        metrics = self.metrics
        guard = self.nonfinite_guard
        # fault injection is compiled in ONLY when the plan schedules NaN
        # steps: a plan-free program is byte-for-byte the baseline
        inject_rows = (
            int(self.fault_plan.nan_rows)
            if self.fault_plan is not None and self.fault_plan.injects_nan()
            else 0
        )
        node_count = sampler.csr_topo.node_count
        relations = getattr(sampler.topo, "num_relations", 0)
        rows_per_shard = (
            sampler.topo.rows_per_shard if topo_sharded else 0
        )
        # in-program heat feed: compiled in ONLY when a controller
        # registered feature.row_heat (so a controller-off program is
        # byte-for-byte the baseline, like the guard counters)
        heat_on = metrics.enabled and FEATURE_ROW_HEAT in metrics.names()
        heat_bins = (
            metrics.spec(FEATURE_ROW_HEAT).shape[0] if heat_on else 0
        )

        def gather_features(parts, n_id):
            """Three-tier gather; returns (rows, routed_overflow_count,
            tier_hits) — the count is the feature-group total of
            capped-bucket fallback lanes (0 for psum/uncapped/unsharded
            gathers), tier_hits the local int32 (3,) per-tier hit vector
            (the step body psums it mesh-wide)."""
            from ..feature.feature import tiered_lookup, wrap_dequant_gathers
            from ..ops.gather import padded_row_gather, tile_pad
            from ..ops.sample import staged_gather

            rep_table, hot_table, cold_table, order, scale = parts
            # tier boundaries read at TRACE time, not capture time: an
            # eager resplit between batches moves them, and the changed
            # table shapes force this retrace
            rep_rows = feature.rep_rows if sharded else 0
            hot_rows = feature.hot_rows
            ov_box = [jnp.zeros((), jnp.int32)]
            pad_lanes = 0
            rep_g = (
                None if rep_table is None
                else lambda ids: rep_table[ids]
            )
            if hot_table is None:
                hot_g = None
            elif sharded and routed:
                # distinct ids per feature-group member: route to owners.
                # Bucket capacity is static per id-length (the tiered
                # lookup calls with the full n_id width). L0/cold lanes
                # arrive as -1 and occupy no bucket capacity.
                def hot_g(ids):
                    cap = (
                        None if routed_alpha is None
                        else feature.hot.routed_cap(
                            int(ids.shape[0]), routed_alpha
                        )
                    )
                    rows, ov = feature.hot.routed_gather(
                        hot_table, ids, cap=cap, with_overflow=True
                    )
                    ov_box[0] = ov_box[0] + ov
                    return rows
            elif sharded:
                hot_g = lambda ids: jax.lax.psum(
                    feature.hot.local_gather(hot_table, ids), feature.hot.axis
                )
            else:
                # padded so that the gather keeps 256 rows in flight
                # (PERF.md section 7 (3))
                pad_lanes = tile_pad(n_id.shape[0])
                hot_g = lambda ids: padded_row_gather(hot_table, ids)
            metrics.set(GATHER_PAD_LANES, np.int32(pad_lanes))
            cold_g = (
                None if cold_table is None
                else lambda ids: staged_gather(cold_table, ids, cold_is_host)
            )
            rep_g, hot_g, cold_g = wrap_dequant_gathers(
                scale, hot_rows, hot_g, cold_g, rep_g, rep_rows
            )
            x, hits = tiered_lookup(
                n_id, order, hot_rows, hot_g, cold_g,
                rep_rows=rep_rows, rep_gather=rep_g,
                hot_miss_id=-1 if sharded else 0, with_hits=True,
            )
            heat = None
            if heat_on:
                with trace_scope("step_metrics"):
                    heat = row_heat_histogram(
                        n_id, order, node_count, heat_bins)
            return x, ov_box[0], hits, heat

        elastic = self.elastic
        bpd = self.blocks_per_device
        workers = self.workers
        S = self.local_batch  # per-block seed length (static everywhere)

        def issue_block(topo, parts, seeds, key):
            # the SCHEDULE-MOVABLE half of one logical seed block: sample +
            # three-tier gather. ``key`` arrives already folded on the
            # block's LOGICAL worker index; the sampling stream is the
            # first split of it — exactly the stream the fused serial body
            # always drew — so an issued batch is bitwise the serial one
            # no matter where in the schedule it runs (the prologue, the
            # skewed scan body, or a checkpoint-chunk re-issue).
            with trace_scope("step_keys"):
                sample_key = jax.random.split(key)[0]
                num_seeds = jnp.sum((seeds >= 0).astype(jnp.int32))
            if topo_sharded:
                # sharded-topology sampling: per-hop owner routing over the
                # feature axis, SAME routing budget (routed_alpha) as the
                # sharded feature gather below
                from ..sampling.dist import dist_multilayer_sample

                indptr_blk, indices_blk = topo
                (n_id, _, adjs, frontier_ov, edges, frontier,
                 hop_ovs) = dist_multilayer_sample(
                    indptr_blk[0], indices_blk[0], rows_per_shard, seeds,
                    num_seeds, sample_key, sizes, caps,
                    axis=FEATURE_AXIS, num_shards=mesh.shape[FEATURE_AXIS],
                    routed_alpha=routed_alpha,
                )
                sample_ov = jnp.stack(hop_ovs)  # feature-group totals
            else:
                n_id, _, adjs, frontier_ov, edges, frontier = (
                    multilayer_sample(
                        topo, seeds, num_seeds, sample_key, sizes, caps,
                        weighted=sampler.weighted, kernel=sampler.kernel,
                    )
                )
                sample_ov = jnp.zeros((len(sizes),), jnp.int32)
            x, routed_ov, tier_hits, heat = gather_features(parts, n_id)
            # the block's local telemetry, one pytree: what feed_issue
            # hands to the tape (blocks of one device are summed leaf by
            # leaf first). The sampler's per-hop tuples come deepest hop
            # first; the metrics are seeds-outward like sample_ov.
            with trace_scope("step_metrics"):
                tele = dict(
                    routed_ov=routed_ov, tier_hits=tier_hits,
                    sample_ov=sample_ov, heat=heat,
                    edges=jnp.stack(edges[::-1]),
                    frontier=jnp.stack(frontier[::-1]),
                    frontier_ov=frontier_ov,
                )
                if relations:
                    # the lanes' relations as the model gets them (-1 on
                    # invalid lanes), counted per hop, seeds-outward
                    kinds = jnp.arange(relations, dtype=jnp.int8)
                    carried = [a.relation[..., None] == kinds
                               for a in adjs[::-1]]
                    tele["relation_lanes"] = jnp.stack([
                        c.sum(axis=(0, 1), dtype=jnp.int32) for c in carried])
                    # a padded target's lanes carry none
                    tele["relation_targets"] = jnp.stack([
                        c.any(axis=0).sum(axis=0, dtype=jnp.int32)
                        for c in carried])
            return n_id, x, adjs, num_seeds, tele

        def train_block(params, n_id, x, adjs, num_seeds, labels, key,
                        inject):
            # the COMPUTE half: fault injection, label/mask prep, loss +
            # grad. Draws the dropout stream — the second split of the
            # same block key issue_block split its sampling stream from.
            with trace_scope("step_keys"):
                dropout_key = jax.random.split(key)[1]
            if inject_rows:
                # FaultPlan NaN injection: poison the leading rows of the
                # gathered block on planned steps (inject is the per-step
                # plan flag) — a corrupt batch reaching the loss, which
                # the non-finite guard below must absorb. Lives in the
                # train half so a pipelined carried batch is poisoned at
                # the same point in the op sequence as the serial body.
                if not jnp.issubdtype(x.dtype, jnp.inexact):
                    raise ValueError(
                        f"FaultPlan NaN injection needs float features, "
                        f"got {x.dtype}"
                    )
                rows = min(inject_rows, int(x.shape[0]))
                poison = jnp.full((rows, x.shape[1]), jnp.nan, x.dtype)
                x = x.at[:rows].set(jnp.where(inject, poison, x[:rows]))
            with trace_scope("seed_loss"):
                lab = labels[jnp.clip(n_id[:S], 0)]
                mask = jnp.arange(S) < num_seeds

            def loss_fn(p):
                logits = model.apply(
                    {"params": p}, x, adjs, train=True,
                    rngs={"dropout": dropout_key}
                )
                # opened inside the differentiated function: its paths
                # read jvp(seed_loss) and transpose(jvp(seed_loss))
                with trace_scope("seed_loss"):
                    return cross_entropy_on_seeds(logits[:S], lab, mask)

            return jax.value_and_grad(loss_fn)(params)

        def one_block(params, topo, parts, seeds, labels, key, inject):
            # one logical seed block = the two halves composed in place
            # (the serial schedule; pipeline_depth=1 runs the same halves
            # as separate programs with a one-step skew between them)
            n_id, x, adjs, num_seeds, tele = issue_block(
                topo, parts, seeds, key)
            loss, grads = train_block(
                params, n_id, x, adjs, num_seeds, labels, key, inject
            )
            return loss, grads, tele

        # the step program's metric names, split by producing half: the
        # issue half owns the sample/gather telemetry, the train half the
        # guard counters. The serial body finalizes their union (exactly
        # the names the fused step always emitted — host-only metrics like
        # train.pipeline_reissues never enter the program), the pipelined
        # halves finalize their own subset so the merged per-step dict is
        # disjoint instead of zero-filled entries clobbering real values.
        issue_names = (
            ROUTED_OVERFLOW, TIER_HITS, SAMPLE_OVERFLOW, SAMPLE_EDGES,
            SAMPLE_FRONTIER, SAMPLE_FRONTIER_OVERFLOW,
        ) + ((FEATURE_ROW_HEAT,) if heat_on else ()) + (
            (SAMPLE_RELATION_LANES, SAMPLE_RELATION_TARGETS)
            if relations else ())
        train_names = (GUARD_SKIPPED, GUARD_NONFINITE) if guard else ()
        program_names = issue_names + train_names
        axes = (DATA_AXIS, FEATURE_AXIS)
        # lanes that are distinct per device under "all" (a mesh-wide psum
        # is the batch total) are processed redundantly by the feature-
        # group members under "data": summing those too would overcount
        # each lane F times
        lane_axes = axes if routed else DATA_AXIS

        def block_key(key, b):
            # distinct key per LOGICAL seed-block worker; under "data"
            # sharding the feature-axis members share the key (identical
            # redundant sampling). At bpd=1 the fold equals the non-elastic
            # one exactly.
            with trace_scope("step_keys"):
                widx = jax.lax.axis_index(DATA_AXIS)
                if routed:
                    widx = widx * mesh.shape[FEATURE_AXIS] + (
                        jax.lax.axis_index(FEATURE_AXIS))
                return jax.random.fold_in(key, widx * bpd + b)

        def sum_blocks(teles):
            # one device's blocks, summed leaf by leaf (heat may be None)
            return jax.tree_util.tree_map(
                lambda *v: reduce(operator.add, v), *teles)

        def feed_issue(tape, tele):
            # graftscope: the step's telemetry rides ONE metrics pytree.
            # Each metric declares its own mesh reduction (applied once by
            # tape.finalize): the routed overflow and per-hop sample
            # overflow are feature-psum'd inside the route already, so the
            # data-axis psum makes them mesh-wide totals; tier hits and the
            # sampler's counts reduce over ``lane_axes``. With
            # collect_metrics=False the tape feeds nothing and the program
            # carries zero metric collectives.
            tape.add(ROUTED_OVERFLOW, tele["routed_ov"], psum=DATA_AXIS)
            tape.set(TIER_HITS, tele["tier_hits"], psum=lane_axes)
            if heat_on:
                tape.set(FEATURE_ROW_HEAT, tele["heat"], psum=lane_axes)
            if topo_sharded:
                tape.add(SAMPLE_OVERFLOW, tele["sample_ov"], psum=DATA_AXIS)
            tape.add(SAMPLE_EDGES, tele["edges"], psum=lane_axes)
            tape.add(SAMPLE_FRONTIER, tele["frontier"], psum=lane_axes)
            tape.add(SAMPLE_FRONTIER_OVERFLOW, tele["frontier_ov"],
                     psum=lane_axes)
            if relations:
                tape.add(SAMPLE_RELATION_LANES, tele["relation_lanes"],
                         psum=lane_axes)
                tape.add(SAMPLE_RELATION_TARGETS, tele["relation_targets"],
                         psum=lane_axes)

        def allreduce_update(params, opt_state, blocks):
            """The shared tail of the serial body and the train half:
            verdict, cross-worker mean, optimizer update. ``blocks`` holds
            the ``(loss, grads)`` of each of this device's seed blocks (one
            outside elastic mode). Returns the guard's ``(ok, local_bad)``
            last (None without the guard)."""
            verdict = None
            with trace_scope("grad_allreduce"):
                if not elastic:
                    (losses, grads_blocks), = blocks
                else:
                    losses = jnp.stack([loss for loss, _ in blocks])
                    grads_blocks = jax.tree_util.tree_map(
                        lambda *g: jnp.stack(g), *[g for _, g in blocks]
                    )
                if guard:
                    # verdict BEFORE the mean (it spreads one worker's NaN
                    # mesh-wide); psum'd over both axes so every chip
                    # agrees. Stacked per-block values in elastic mode: one
                    # verdict for the whole step.
                    verdict = guard_verdict(losses, grads_blocks, axes)
                if not elastic:
                    grads = jax.lax.pmean(grads_blocks, axes)
                    loss = jax.lax.pmean(losses, axes)
                else:
                    # fixed logical-worker order (all_gather is device-
                    # major, blocks-minor = worker order): loss/grads are
                    # bitwise independent of how many devices the workers
                    # map onto — the seam resume(mesh=) relies on
                    grads = worker_ordered_mean(grads_blocks, axes, workers)
                    loss = worker_ordered_mean(losses, axes, workers)
            with trace_scope("optax_update"):
                if guard:
                    params, opt_state = guarded_update(
                        tx, grads, opt_state, params, verdict[0]
                    )
                else:
                    updates, opt_state = tx.update(grads, opt_state, params)
                    params = optax.apply_updates(params, updates)
            return params, opt_state, loss, verdict

        def feed_guard(tape, verdict):
            if guard:
                # local_bad counts this worker's non-finite values
                # (``lane_axes``: under "data" the feature-group members
                # recompute the SAME grads). The skip flag is already
                # mesh-agreed (psum'd verdict) — no further reduction.
                ok, local_bad = verdict
                tape.add(GUARD_NONFINITE, local_bad, psum=lane_axes)
                tape.add(GUARD_SKIPPED, (~ok).astype(jnp.int32))

        def body(params, opt_state, topo, parts, seeds, labels, key, inject):
            # elastic mode: this device runs ``bpd`` logical seed blocks
            # sequentially (every device runs the same per-block program,
            # so the per-block collectives stay uniform and deadlock-free),
            # each keyed on its LOGICAL worker index
            blocks = seeds.reshape(bpd, -1) if elastic else [seeds]
            outs = [
                one_block(
                    params, topo, parts, blocks[b], labels,
                    block_key(key, b), inject
                )
                for b in range(bpd)
            ]
            params, opt_state, loss, verdict = allreduce_update(
                params, opt_state, [o[:2] for o in outs])
            with trace_scope("step_metrics"):
                tape = metrics.tape()
                feed_issue(tape, sum_blocks([o[2] for o in outs]))
                feed_guard(tape, verdict)
                mtree = tape.finalize(names=program_names)
            return params, opt_state, loss, mtree

        hot_spec = P(FEATURE_AXIS, None) if sharded else P()
        parts_spec = (P(), hot_spec, P(), P(), P())
        topo_spec = (
            (P(FEATURE_AXIS, None), P(FEATURE_AXIS, None))
            if topo_sharded else P()
        )
        # metric values come out replicated (psum'd at their declared axes)
        metric_specs = (
            {name: P() for name in program_names}
            if metrics.enabled else {}
        )
        fn = shard_map(
            body,
            mesh=mesh,
            in_specs=(
                P(), P(), topo_spec, parts_spec, self._seed_spec(), P(),
                P(), P(),
            ),
            out_specs=(P(), P(), P(), metric_specs),
            check_vma=False,
        )
        step = jax.jit(fn)
        if not self.pipeline_depth:
            self._issue = self._train = None
            return step

        # -- pipeline_depth=1: the two halves as standalone programs -------
        # Same mesh, same specs, same per-block key folds as the serial
        # body — only the SCHEDULE differs. The issue program materializes
        # a PipelinedBatch (per-block arrays stacked on a leading
        # blocks-per-device axis) plus its finalized sample/gather
        # telemetry; the train program consumes a carried batch one step
        # later and emits the guard counters. Composed serially they
        # reproduce the fused body's op sequence exactly, which is what
        # makes the pipelined trajectory bitwise identical.

        def issue_body(topo, parts, seeds, key):
            blocks = seeds.reshape(bpd, -1)
            outs = [
                issue_block(topo, parts, blocks[b], block_key(key, b))
                for b in range(bpd)
            ]
            n_id = jnp.stack([o[0] for o in outs])
            x = jnp.stack([o[1] for o in outs])
            # Adj pytrees stack on their edge_index leaves; the static
            # size/fanout aux keeps describing the per-block shape (the
            # train half unstacks before the model consumes them)
            adjs = tuple(jax.tree_util.tree_map(
                lambda *leaves: jnp.stack(leaves), *[o[2] for o in outs]
            ))
            num_seeds = jnp.stack([o[3] for o in outs])
            # identical feeds (and psum axes) to the serial body — the
            # issue half owns the batch's telemetry so a carried batch's
            # metrics stay attributed to the step that SAMPLED it
            with trace_scope("step_metrics"):
                tape = metrics.tape()
                feed_issue(tape, sum_blocks([o[4] for o in outs]))
                mtree = tape.finalize(names=issue_names)
            return PipelinedBatch(n_id, x, adjs, num_seeds, mtree)

        def train_body(params, opt_state, batch, labels, key, inject):
            def block(b):
                adjs_b = jax.tree_util.tree_map(
                    lambda leaf: leaf[b], batch.adjs
                )
                return train_block(
                    params, batch.n_id[b], batch.x[b], adjs_b,
                    batch.num_seeds[b], labels, block_key(key, b), inject,
                )

            # the serial body's reduction exactly
            params, opt_state, loss, verdict = allreduce_update(
                params, opt_state, [block(b) for b in range(bpd)])
            with trace_scope("step_metrics"):
                tape = metrics.tape()
                feed_guard(tape, verdict)
                mtree = tape.finalize(names=train_names)
            return params, opt_state, loss, mtree

        # the batch rides device-resident: every array keeps its producing
        # worker's shard (the same placement the seed blocks arrive with),
        # only the finalized metrics are replicated
        bspec = (
            P((DATA_AXIS, FEATURE_AXIS)) if routed else P(DATA_AXIS)
        )
        batch_spec = PipelinedBatch(
            n_id=bspec, x=bspec, adjs=bspec, num_seeds=bspec,
            metrics=(
                {name: P() for name in issue_names}
                if metrics.enabled else {}
            ),
        )
        train_metric_specs = (
            {name: P() for name in train_names}
            if metrics.enabled else {}
        )
        self._issue = jax.jit(shard_map(
            issue_body,
            mesh=mesh,
            in_specs=(topo_spec, parts_spec, self._seed_spec(), P()),
            out_specs=batch_spec,
            check_vma=False,
        ))
        self._train = jax.jit(shard_map(
            train_body,
            mesh=mesh,
            in_specs=(P(), P(), batch_spec, P(), P(), P()),
            out_specs=(P(), P(), P(), train_metric_specs),
            check_vma=False,
        ))
        return step

    # -- API ----------------------------------------------------------------

    def init(self, rng):
        """Initialize params/opt_state from one locally-sampled batch."""
        n = self.sampler.csr_topo.node_count
        m = min(self.local_batch, n)
        if self.topo_sharded:
            # no single-device program exists over a sharded topology, and
            # model init only consumes Adj SHAPES/fanout — build empty
            # (all-invalid) per-layer blocks with the planned caps
            caps = self.caps
            adjs = []
            prev = self.local_batch
            for cap, k in zip(caps, self.sampler.sizes):
                ei = jnp.full((2, prev * k), -1, jnp.int32)
                adjs.append(Adj(ei, None, (cap, prev), fanout=k))
                prev = cap
            adjs = adjs[::-1]
        else:
            padded = np.full(self.local_batch, -1, np.int32)
            padded[:m] = np.arange(m)
            run, caps = self.sampler._compiled(self.local_batch)
            _, _, adjs, _, _, _ = run(
                self.sampler.topo, jnp.asarray(padded), jnp.int32(m),
                jax.random.PRNGKey(0)
            )
        # the model sees what the tiered gather returns: dequantized f32 for
        # int8 storage, else the stored dtype (bf16/f32)
        dtype = (
            jnp.float32 if self.feature.scale is not None else self.feature.dtype
        )
        x = jnp.zeros((caps[-1], self.feature.shape[1]), dtype)
        params = self.model.init({"params": rng}, x, adjs)["params"]
        # placed as step() returns them, so the first step's compile is
        # the only one
        return jax.device_put(
            (params, self.tx.init(params)), NamedSharding(self.mesh, P())
        )

    def _seed_spec(self) -> P:
        if self.seed_sharding == "all":
            return P((DATA_AXIS, FEATURE_AXIS))
        return P(DATA_AXIS)

    def shard_seeds(self, seeds: np.ndarray):
        """Pack a global seed array into per-worker valid-prefix blocks,
        padded to (workers * local_batch,) with -1 (workers = every device
        under seed_sharding="all", one per data group under "data")."""
        seeds = np.asarray(seeds)
        blocks = np.array_split(seeds, self.workers)
        out = np.full((self.workers, self.local_batch), -1, np.int32)
        for i, b in enumerate(blocks):
            if len(b) > self.local_batch:
                raise ValueError(
                    f"per-device block {len(b)} exceeds local_batch {self.local_batch}"
                )
            out[i, : len(b)] = b
        return out.reshape(-1)

    def _check_guard_trip(self) -> None:
        """Flight-recorder trigger: a nonfinite-guard trip (the guard
        skipped >= 1 step since last checked) dumps a postmortem bundle
        naming the train stage while the explaining spans/metrics are
        still in the rings."""
        if self.recorder is None or not self.nonfinite_guard:
            return
        snap = self.metrics.snapshot(GUARD_SKIPPED)
        total = int(snap.total()) if snap is not None else 0
        if total > self._guard_trips_seen:
            self._guard_trips_seen = total
            self.recorder.trigger(
                "nonfinite_guard", stage="train", skipped_total=total,
            )

    def _note_compile(self, step_idx: int, launch: _Phase,
                      before: CompileTotals) -> None:
        """Put what compiled during ``launch`` down to step ``step_idx``:
        ``compiles=<n>`` on the launch's span, the seconds as timeline
        stage ``step.compile``, the trainer's running totals in the
        registry, the index in :meth:`health`, one line in the log."""
        after = self._xla.totals
        n = after.compiles - before.compiles
        seconds = after.seconds - before.seconds
        hits = after.cache_hits - before.cache_hits
        launch.set(compiles=n)
        self.timeline.observe("step.compile", seconds)
        paid = self._xla_paid = CompileTotals(
            self._xla_paid.compiles + n,
            self._xla_paid.seconds + seconds,
            self._xla_paid.cache_hits + hits,
        )
        self.metrics.set(XLA_COMPILES, np.int32(paid.compiles))
        self.metrics.set(XLA_COMPILE_SECONDS, np.float32(paid.seconds))
        self.metrics.set(XLA_CACHE_HITS, np.int32(paid.cache_hits))
        self._last_compile_step = step_idx
        get_logger().info(
            "step %d compiled: %d program(s) in %.3f s, %d served by the "
            "persistent compilation cache",
            step_idx, n, seconds, hits,
        )

    def step(self, params, opt_state, seeds, labels, key):
        """One fused step. ``seeds``: global seed array (host). ``labels``:
        full (N,) label array (replicated).

        Batch metadata: after the call ``last_routed_overflow`` holds the
        step's capped-bucket fallback lane count (device scalar; 0 unless
        seed_sharding="all" with a sharded feature and a cap),
        ``last_tier_hits`` the mesh-total per-tier feature-hit vector
        (int32 (3,), [replicated, sharded, cold]), and
        ``last_sample_overflow`` the topo-sharded sampler's per-hop
        routed-fallback lane counts (int32 (num_layers,), seeds-outward;
        a replicated topology routes nothing, so zeros there). Persistent
        overflow means ``routed_alpha`` is too small for the id skew —
        pass ``auto_alpha=True`` (the shared tuner grows it between
        batches) or grow it yourself between epochs.

        What the sampler counted rides the same registry on every
        topology: ``metrics.value("sample.edges")`` and
        ``("sample.frontier")`` (int32 (num_layers,), seeds-outward: valid
        edges, and distinct nodes found before the cap) and
        ``("sample.frontier_overflow")`` (scalar). A non-zero overflow
        means ``frontier_caps`` are too small: the step dropped that many
        nodes, and their edges, from its blocks.

        A ShardedFeature built with ``auto_split=True`` consumes the hit
        vector here: the eager tuner moves its replicated/sharded boundary
        before the next step's dispatch (the changed tier shapes re-key
        the jit cache, so the program retraces on the new split).

        The host side of the call is five phases, each one ``with``:
        ``tune`` (version check, eager tuners), ``pack`` (seed blocks),
        ``place`` (the operands' placements), ``launch`` (the jitted call
        alone) and ``record`` (registry, guard check, the tuners' feeds).
        Each is stage ``step.<phase>`` of ``self.timeline`` inside stage
        ``step``, and with tracing enabled the slice
        ``quiver.step.<phase>`` inside ``quiver.step`` (stat ``step``: the
        count of eager calls) on the profiler's timeline; with an enabled
        tracer, span ``train.step.<phase>`` under ``train.step``. The
        names are an interface (``docs/Introduction.md``, "Reading the
        host side of a step"). A launch that compiled carries
        ``compiles=<n>`` and is put down to its step (:meth:`health`,
        ``xla.*`` in the registry, stage ``step.compile``).
        """
        feature = self.feature
        plan = self.fault_plan
        step_idx = self._fault_step
        trace = f"train.step.{step_idx}"
        with _Phase(self, "step", trace, step=step_idx) as whole:
            with _Phase(self, "step.tune", trace, whole):
                self._check_versions()
                # a step the version check refuses takes no index
                self._fault_step += 1
                if isinstance(feature, ShardedFeature) and (
                    feature.auto_split
                    or getattr(feature, "_controller", None) is not None
                ):
                    feature._maybe_auto_split()
                self._maybe_grow_routed_alpha()
            with _Phase(self, "step.pack", trace, whole):
                packed = self.shard_seeds(seeds)
                if self.controller is not None:
                    # seeds are the host-visible slice of the step's gather
                    # traffic — feed the controller's heavy-hitter set (the
                    # in-program histogram covers the full id stream, but
                    # only host-visible ids can NAME rows for a repin)
                    self.controller.observe_ids(packed)
            with _Phase(self, "step.place", trace, whole):
                # every operand reaches the mesh by an explicit placement:
                # nothing is left for jit to move between devices
                replicated = NamedSharding(self.mesh, P())
                packed = jax.device_put(
                    packed, NamedSharding(self.mesh, self._seed_spec())
                )
                key, inject = jax.device_put(
                    (key, np.asarray(
                        plan is not None and plan.nan_at(step_idx))),
                    replicated,
                )
                parts = self._feature_parts()
                labels = self._mesh_wide("labels", labels)
            with _Phase(self, "step.launch", trace, whole) as launch:
                # the jitted call alone: trace, lowering and compile (or
                # cache load) on the first call of a program, dispatch after
                compiled = self._xla.totals
                params, opt_state, loss, mtree = self._step(
                    params, opt_state, self.topo, parts, packed, labels,
                    key, inject
                )
                if self._xla.totals is not compiled:
                    self._note_compile(step_idx, launch, compiled)
            with _Phase(self, "step.record", trace, whole):
                self.metrics.record(mtree)
                self._check_guard_trip()
                if mtree and isinstance(feature, ShardedFeature):
                    # hand the batch totals to the store so its eager split
                    # tuner sees the fused path's traffic too
                    feature.last_tier_hits = mtree[TIER_HITS]
                if mtree and self.controller is not None:
                    # fold the step's heat histogram into the controller's
                    # sketch (no-op when the heat feed is off)
                    self.controller.observe_histogram(
                        mtree.get(FEATURE_ROW_HEAT))
        if (plan is not None and not self._preempt_fired
                and plan.preempts_in(step_idx, step_idx + 1)):
            # the step ran but its results are lost with the raise — the
            # caller resumes from the last checkpoint, like a real kill
            self._preempt_fired = True
            raise Preemption(f"simulated preemption at step {step_idx}")
        return params, opt_state, loss

    def pack_epoch(self, train_idx: np.ndarray, seed=None, key=None):
        """Shuffle ``train_idx`` and pack it into a (steps,
        workers*local_batch) seed matrix of per-worker valid-prefix blocks
        (-1 padded) — the xs of :meth:`epoch_scan`. Host-side preprocessing
        (the DataLoader shuffle of the reference's loop,
        dist_sampling_ogb_products:109)."""
        if seed is None:
            seed = key  # legacy name
        idx = np.asarray(train_idx)
        if seed is not None:
            # accept an int seed or a jax PRNGKey (typed or uint32 pair);
            # int() of a shape-(2,) key array would raise
            if hasattr(seed, "dtype") and jnp.issubdtype(
                    seed.dtype, jax.dtypes.prng_key):
                seed = jax.random.key_data(seed)
            if getattr(seed, "shape", ()) != ():
                seed = int(np.asarray(seed).ravel()[-1])
            idx = np.random.default_rng(int(seed)).permutation(idx)
        steps = -(-len(idx) // self.global_batch)
        return np.stack([
            self.shard_seeds(idx[s * self.global_batch: (s + 1) * self.global_batch])
            for s in range(steps)
        ])

    def _build_epoch(self):
        if self.pipeline_depth:
            return self._build_epoch_pipelined()
        step = self._step  # jitted shard_map; inlines under the outer jit

        # per-step keys arrive PRE-SPLIT (epoch_scan splits key0 eagerly —
        # a deterministic function of key0 and the FULL step count), so a
        # checkpoint-chunked epoch and a resumed one consume exactly the
        # slices an unchunked scan would have drawn: bit-identical keys
        # regardless of where the chunk/resume boundaries fall
        donate = (0, 1) if self.donate_epoch_state else ()

        @partial(jax.jit, donate_argnums=donate)
        def fn(params, opt_state, topo, parts, seed_mat, labels, keys,
               inject_vec):
            def body(carry, xs):
                p, o = carry
                seeds, k, inj = xs
                p, o, loss, mtree = step(
                    p, o, topo, parts, seeds, labels, k, inj
                )
                return (p, o), (loss, mtree)

            (p, o), (losses, mtrees) = jax.lax.scan(
                body, (params, opt_state), (seed_mat, keys, inject_vec)
            )
            # mtrees: each metric stacked to (steps,) + its per-step shape
            return p, o, losses, mtrees

        return fn  # jit's shape-keyed cache handles distinct step counts

    def _build_epoch_pipelined(self):
        """The software-pipelined epoch program (pipeline_depth=1).

        One-step skew: the scan carry is (params, opt_state, next_batch)
        where next_batch is step t's fully-materialized sample+gather
        result (a :class:`PipelinedBatch`). Iteration t trains the
        carried batch with step t's key/inject row, then issues step
        t+1's batch — two halves with NO data dependency between them,
        so XLA is free to overlap the issue half's all_to_all buckets
        and cold-tier host gathers with the train half's fwd/bwd
        compute. A prologue issues batch 0; an epilogue trains the final
        carried batch; scan's in-place carry aliasing keeps the double
        buffer allocation-free across iterations.

        Signature-compatible with the serial epoch fn, so epoch_scan's
        checkpoint chunking applies unchanged: each chunk's prologue
        re-issues its first batch from the seed matrix (per-step keys
        are pre-split from key0 over the FULL epoch — deterministic
        replay, bitwise the batch the previous chunk had in flight).
        """
        issue = self._issue
        train = self._train
        donate = (0, 1) if self.donate_epoch_state else ()

        @partial(jax.jit, donate_argnums=donate)
        def fn(params, opt_state, topo, parts, seed_mat, labels, keys,
               inject_vec):
            first = issue(topo, parts, seed_mat[0], keys[0])

            def body(carry, xs):
                p, o, batch = carry
                seeds_next, key_next, key_cur, inj_cur = xs
                p, o, loss, tmetrics = train(
                    p, o, batch, labels, key_cur, inj_cur
                )
                nxt = issue(topo, parts, seeds_next, key_next)
                # per-step telemetry = the TRAINED batch's issue metrics
                # (sampled possibly a chunk ago) + this step's guard
                # counters — disjoint dicts whose union is exactly the
                # serial step's metrics pytree
                return (p, o, nxt), (loss, {**batch.metrics, **tmetrics})

            # xs skewed by one: iteration t consumes step t's key/inject
            # for the train half and step t+1's seeds/key for the issue
            # half (length 0 for a single-step chunk — prologue+epilogue
            # alone cover it)
            xs = (seed_mat[1:], keys[1:], keys[:-1], inject_vec[:-1])
            (p, o, last), (losses, mtrees) = jax.lax.scan(
                body, (params, opt_state, first), xs
            )
            p, o, loss_last, tmetrics = train(
                p, o, last, labels, keys[-1], inject_vec[-1]
            )
            losses = jnp.concatenate([losses, loss_last[None]])
            last_m = {**last.metrics, **tmetrics}
            mtrees = {
                name: jnp.concatenate([mtrees[name], last_m[name][None]])
                for name in last_m
            }
            return p, o, losses, mtrees

        return fn

    def epoch_scan(self, params, opt_state, seed_mat, labels, key,
                   epoch: int = 0, start_step: int = 0):
        """A whole epoch as ONE compiled program: ``lax.scan`` over the
        packed per-step seed blocks with (params, opt_state) in the carry.

        This is the TPU-native epoch loop — the device never waits on the
        host between steps (the reference's per-batch Python loop pays a
        dispatch + sync round-trip per iteration). One program
        per distinct step count; one loss-vector readback per epoch.

        Returns (params, opt_state, losses[steps]); the per-step
        capped-bucket fallback counts land in ``last_routed_overflow``
        (an int32[steps] device array) and the per-step per-tier feature
        hits in ``last_tier_hits`` (int32[steps, 3],
        [replicated, sharded, cold] mesh totals) — batch metadata for the
        auto-tuners and scoreboard. The split is frozen for the scanned
        epoch (one compiled program); the eager tuner moves it between
        epochs.

        Resilience: with ``checkpoint_dir=``/``checkpoint_every=`` set the
        epoch runs as scan CHUNKS of ``checkpoint_every`` steps, with an
        async save of (params, opt_state, step, PRNG key) after each chunk
        — the device still never waits on the host inside a chunk.
        ``start_step``/``epoch`` replay a resumed epoch: pass the SAME
        packed ``seed_mat`` (``pack_epoch`` with the same seed) and the
        key returned by :meth:`resume`, and the remaining trajectory is
        bit-identical to the uninterrupted run (per-step keys are split
        from key0 over the FULL step count, then sliced). A ``fault_plan``
        with ``preempt_at_step`` raises
        :class:`~quiver_tpu.resilience.Preemption` once that step's chunk
        has run but before its checkpoint lands (the drill's "kill").

        With ``pipeline_depth=1`` the same call runs the software-
        pipelined schedule (one-step skew, see
        :meth:`_build_epoch_pipelined`): identical signature, identical
        chunking/resume semantics, bitwise-identical losses, params, and
        per-step telemetry — each chunk re-issues its first batch from
        the seed matrix (``train.pipeline_reissues`` counts these), so
        the carried batch never needs to cross a chunk boundary as
        state.
        """
        self._check_versions()
        steps = int(np.shape(seed_mat)[0])
        start = int(start_step)
        if not 0 <= start <= steps:
            raise ValueError(
                f"start_step {start} outside [0, {steps}] for a "
                f"{steps}-step epoch"
            )
        plan = self.fault_plan
        losses_parts: list = []
        mtrees_parts: list = []
        # the epoch trace id is DETERMINISTIC (train.epoch.<n>): a
        # preempted run's resume records its chunks under the same id,
        # so the stitched timeline reads as one epoch across the restart
        etrace = self.tracer.trace(f"train.epoch.{int(epoch)}")
        with self.timeline.stage("epoch_scan"):
            if isinstance(self.feature, ShardedFeature) and getattr(
                    self.feature, "_controller", None) is not None:
                # actuate any pending split decision between epochs (the
                # legacy auto_split flag only ever consumed hits via
                # step()/gather(); a controller tunes the scanned path too)
                self.feature._maybe_auto_split()
            self._maybe_grow_routed_alpha()
            if self.controller is not None:
                # the epoch's seed matrix is its host-visible id stream
                # (see step(): only host-visible ids can name repin rows)
                self.controller.observe_ids(np.asarray(seed_mat))
            packed = jax.device_put(
                np.asarray(seed_mat),
                NamedSharding(self.mesh, P(None, *self._seed_spec())),
            )
            if plan is not None and plan.injects_nan():
                inject_vec = np.asarray(plan.nan_mask(steps))
            else:
                inject_vec = np.zeros((steps,), bool)
            keys, inject_vec = jax.device_put(
                (jax.random.split(key, steps), inject_vec),
                NamedSharding(self.mesh, P()),
            )
            labels = self._mesh_wide("labels", labels)
            chunk = (
                self.checkpoint_every if self.checkpointer is not None
                else max(steps - start, 1)
            )
            lo = start
            while lo < steps:
                hi = min(lo + chunk, steps)
                t0 = self.tracer.now() if self.tracer.enabled else 0.0
                params, opt_state, losses, mtrees = self._epoch_fn(
                    params, opt_state, self.topo, self._feature_parts(),
                    packed[lo:hi], labels, keys[lo:hi], inject_vec[lo:hi]
                )
                # dispatch-timed (the device may still be running): under
                # pipelining the chunk span's issue half is this dispatch,
                # its train half drains inside the next blocking readback
                self.tracer.record(
                    "train.chunk", t0, self.tracer.now() - t0,
                    trace=etrace, subsystem="trainer", epoch=int(epoch),
                    start_step=lo, steps=hi - lo,
                    pipeline_depth=self.pipeline_depth,
                )
                if self.pipeline_depth and lo > start:
                    # pipelined chunks after the first re-issue their
                    # prologue batch (the previous chunk already had it in
                    # flight) — deterministic replay from the seed matrix
                    # instead of serializing the carried batch; count the
                    # overlap the boundary cost
                    self._pipeline_reissues += 1
                    self.metrics.set(
                        PIPELINE_REISSUES,
                        np.int32(self._pipeline_reissues),
                    )
                    self.tracer.event(
                        "train.reissue", trace=etrace,
                        subsystem="trainer", step=lo,
                    )
                losses_parts.append(losses)
                mtrees_parts.append(mtrees)
                if (plan is not None and not self._preempt_fired
                        and plan.preempts_in(lo, hi)):
                    # the chunk ran but dies un-checkpointed — resume()
                    # restores step `lo` and replays from there
                    self._preempt_fired = True
                    # land the partial epoch's telemetry before dying:
                    # the guard trips that explain the preempted run must
                    # reach the registry (and the flight recorder) even
                    # though the final record below never runs
                    if len(mtrees_parts) == 1:
                        self.metrics.record(mtrees_parts[0])
                    elif mtrees_parts:
                        self.metrics.record({
                            name: jnp.concatenate(
                                [m[name] for m in mtrees_parts]
                            )
                            for name in mtrees_parts[0]
                        })
                    self._check_guard_trip()
                    self.tracer.event(
                        "train.preempt", trace=etrace,
                        subsystem="trainer", step=plan.preempt_at_step,
                    )
                    if self.recorder is not None:
                        self.recorder.note(
                            "preemption", epoch=int(epoch),
                            step=int(plan.preempt_at_step),
                        )
                    raise Preemption(
                        f"simulated preemption at step "
                        f"{plan.preempt_at_step}: chunk [{lo}, {hi}) lost "
                        f"(last checkpoint at step {lo})"
                    )
                if self.checkpointer is not None:
                    self._save_checkpoint(
                        params, opt_state, key, epoch, hi,
                        steps_per_epoch=steps, trace=etrace,
                    )
                lo = hi
        if len(losses_parts) == 1:
            losses, mtrees = losses_parts[0], mtrees_parts[0]
        elif losses_parts:
            losses = jnp.concatenate(losses_parts)
            mtrees = {
                name: jnp.concatenate([m[name] for m in mtrees_parts])
                for name in mtrees_parts[0]
            }
        else:  # start == steps: a resumed, already-finished epoch
            losses, mtrees = jnp.zeros((0,), jnp.float32), {}
        self.metrics.record(mtrees)
        self._check_guard_trip()
        if self.controller is not None:
            # epoch-boundary controller hooks: fold the epoch's stacked
            # heat into the sketch, hand the epoch's tier-hit totals to
            # the store's split shim, then let the controller consider a
            # measured-hot repin and decay its sketch
            if mtrees:
                self.controller.observe_histogram(
                    mtrees.get(FEATURE_ROW_HEAT)
                )
                if isinstance(self.feature, ShardedFeature) and \
                        TIER_HITS in mtrees:
                    self.feature.last_tier_hits = np.asarray(
                        mtrees[TIER_HITS]
                    ).sum(axis=0)
            if isinstance(self.feature, ShardedFeature):
                self.controller.end_epoch(self.feature, self)
        return params, opt_state, losses

    # -- checkpoint / auto-resume -------------------------------------------

    def _save_checkpoint(self, params, opt_state, key, epoch, step,
                         steps_per_epoch: int | None = None,
                         trace: str | None = None) -> None:
        """Async atomic save between scan chunks. ``step`` counts completed
        rows of the CURRENT epoch's packed seed matrix; ``key`` is the
        epoch's key0 (stored as raw key data — restore re-splits it). The
        manifest metadata records the writer's mesh shape, logical worker
        count, and epoch geometry — what :meth:`resume` validates before
        trusting the state (and what makes the checkpoint
        topology-PORTABLE: an elastic resume onto a different mesh shape
        checks the logical facts, not the device layout)."""
        if hasattr(key, "dtype") and jnp.issubdtype(
                key.dtype, jax.dtypes.prng_key):
            key_data = jax.random.key_data(key)
        else:
            key_data = jnp.asarray(key)
        state = {
            "params": params,
            "opt_state": opt_state,
            "step": np.asarray(step, np.int32),
            "epoch": np.asarray(epoch, np.int32),
            "key": key_data,
        }
        meta = {
            "mesh": {DATA_AXIS: int(self.data_size),
                     FEATURE_AXIS: int(self.feature_size)},
            "workers": int(self.workers),
            "local_batch": int(self.local_batch),
            "seed_sharding": self.seed_sharding,
            "elastic": bool(self.elastic),
            "epoch": int(epoch),
            "step": int(step),
        }
        if steps_per_epoch is not None:
            meta["steps_per_epoch"] = int(steps_per_epoch)
        self.checkpointer.save(self._ckpt_seq, state, metadata=meta,
                               trace=trace)
        self._ckpt_seq += 1

    def resume(self, params, opt_state, mesh: Mesh | None = None,
               checkpoint_step: int | None = None):
        """Restore the newest VALID checkpoint, if any.

        ``checkpoint_step`` pins a specific checkpoint (the
        checkpointer's sequence id, see ``all_steps()``) instead of the
        newest valid one — e.g. rolling back past a bad data batch; a
        pinned checkpoint that fails verification raises
        ``CorruptCheckpoint`` instead of falling back.

        Returns ``(params, opt_state, key, step, epoch)`` — the restored
        train state, the saved epoch key0 (raw key data; feed it straight
        back to :meth:`epoch_scan`), and where training stopped. With no
        checkpoint on disk the inputs pass through with
        ``(key=None, step=0, epoch=0)``.

        Integrity: the checkpointer verifies per-array checksums and the
        COMMIT marker — a corrupt or half-written newest checkpoint is
        quarantined (one log line) and the newest VALID one restores
        instead; nothing resumes from garbage. The manifest metadata is
        then validated against this trainer: a logical-worker /
        local_batch mismatch, a restored step outside the saved epoch's
        ``steps_per_epoch``, or a mesh-shape change without the elastic
        opt-in below all raise instead of silently training a different
        run.

        **Elastic resume** (``mesh=``): restore onto a DIFFERENT mesh
        shape — preemption handed back a smaller slice. Requires the
        writing trainer to have pinned ``logical_workers=`` (the
        fixed-order reduction is what makes the trajectory mesh-shape
        independent). The trainer re-plans in place: the sharded topology
        and the three-tier feature store re-partition onto the new mesh
        via their ``replan`` seams, the step/epoch programs rebuild, and
        each device picks up ``logical_workers / devices`` seed blocks.
        A trainer freshly CONSTRUCTED on the new mesh (the real
        process-death flow) passes its own mesh explicitly —
        ``resume(mesh=trainer.mesh)`` — as the opt-in acknowledgment that
        the shape changed.

        To reproduce the uninterrupted run bit-identically, regenerate
        the SAME packed seed matrix (``pack_epoch`` with the same seed —
        the seed-stream replay) and call
        ``epoch_scan(..., key=key, epoch=epoch, start_step=step)``: the
        per-step keys are re-split from the saved key0 over the full
        epoch, so the remaining steps draw exactly the keys the
        preempted run would have.
        """
        if self.checkpointer is None:
            raise ValueError(
                "resume() needs checkpointing enabled "
                "(checkpoint_dir=/checkpoint_every= at construction)"
            )
        self.checkpointer.wait_until_finished()
        if checkpoint_step is None:
            latest = self.checkpointer.latest_valid_step()
            if latest is None:
                return params, opt_state, None, 0, 0
        else:
            latest = int(checkpoint_step)
        meta = self.checkpointer.metadata(latest)
        target = self.mesh if mesh is None else mesh
        target_shape = {DATA_AXIS: int(target.shape[DATA_AXIS]),
                        FEATURE_AXIS: int(target.shape[FEATURE_AXIS])}
        saved_mesh = meta.get("mesh")
        if (saved_mesh is not None and mesh is None
                and dict(saved_mesh) != target_shape):
            # satellite guard: the old path device_put a foreign-mesh
            # checkpoint blindly; a shape change must be an explicit
            # elastic opt-in
            raise ValueError(
                f"checkpoint was written on mesh {dict(saved_mesh)} but "
                f"this trainer's mesh is {target_shape}; pass "
                f"resume(mesh=) to opt into the elastic restore (requires "
                f"logical_workers= on the writing trainer)"
            )
        validate_resume_meta(
            meta, mesh_shape=target_shape, workers=self.workers,
            local_batch=self.local_batch,
        )
        if mesh is not None and mesh is not self.mesh:
            self._replan(mesh)
        template = {
            "params": params,
            "opt_state": opt_state,
            "step": np.zeros((), np.int32),
            "epoch": np.zeros((), np.int32),
            "key": np.zeros((2,), np.uint32),  # threefry2x32 key data
        }
        state = self.checkpointer.restore(latest, template=template)
        step = int(np.asarray(state["step"]))
        spe = meta.get("steps_per_epoch")
        if spe is not None and not 0 <= step <= int(spe):
            raise ValueError(
                f"restored step {step} is outside [0, {int(spe)}] for the "
                f"saved epoch — the checkpoint directory does not belong "
                f"to this run's seed packing"
            )
        # the restore hands back global host arrays; the step program
        # wants them mesh-replicated (in_spec P()) — anchor explicitly
        rep = NamedSharding(self.mesh, P())
        return (
            jax.device_put(state["params"], rep),
            jax.device_put(state["opt_state"], rep),
            jnp.asarray(np.asarray(state["key"])),
            step,
            int(np.asarray(state["epoch"])),
        )

    def _replan(self, mesh: Mesh) -> None:
        """Re-plan the trainer onto a new mesh shape (elastic resume).

        The logical worker count is FIXED (seed packing, per-block keys,
        and the fixed-order reduction all follow it); what changes is how
        many blocks each device runs. The sharded topology, the sharded
        feature store, and the sampler re-partition via their ``replan``
        seams — same bytes, new owners — and the compiled step/epoch
        programs rebuild against the new mesh.
        """
        if not self.elastic:
            raise ValueError(
                "resume(mesh=) needs an elastic trainer: construct with "
                "logical_workers=<the writing run's worker count> so the "
                "step reduction is mesh-shape independent"
            )
        dev_workers = int(mesh.shape[DATA_AXIS]) * int(
            mesh.shape[FEATURE_AXIS]
        )
        if dev_workers < 1 or self.workers % dev_workers:
            raise ValueError(
                f"cannot re-plan {self.workers} logical workers onto "
                f"{dev_workers} devices (must divide evenly)"
            )
        old = (int(self.data_size), int(self.feature_size))
        self.mesh = mesh
        self.data_size = mesh.shape[DATA_AXIS]
        self.feature_size = mesh.shape[FEATURE_AXIS]
        self._device_workers = dev_workers
        self.blocks_per_device = self.workers // dev_workers
        self._placed.clear()  # placements belong to the old mesh
        if self.topo_sharded:
            self.sampler.replan(mesh)
        self.topo = self._bound_topo()
        if isinstance(self.feature, ShardedFeature):
            self.feature.replan(mesh)
        info_once(
            "trainer-elastic-replan",
            "elastic replan: mesh (data=%d, feature=%d) -> (data=%d, "
            "feature=%d); %d logical workers now run %d block(s)/device "
            "(trajectory stays bit-identical — fixed-order reduction)",
            old[0], old[1], int(self.data_size), int(self.feature_size),
            self.workers, self.blocks_per_device,
        )
        self._step = self._build()
        self._epoch_fn = self._build_epoch()
        # the replanned programs captured the CURRENT host state
        self._bound_versions = self._current_versions()

    # graftlint: eager -- between-batch tuner on host numpy telemetry; the
    def _maybe_grow_routed_alpha(self) -> None:  # step program never calls it
        """Shared eager routing tuner (compat shim over the controller's
        :class:`~quiver_tpu.control.AlphaTuner`): the sampler's per-hop
        routing and the feature gather draw on ONE budget, so one tuner
        reads both overflow telemetries. Overflow from the PREVIOUS eager
        batch doubles ``routed_alpha`` (capped at F — full-length
        buckets) as it always did; sustained SLACK (consecutive clean
        batches) now also shrinks it, bounded by a floor the tuner raises
        whenever a shrink is immediately punished, so a transient skew
        burst no longer inflates comm for the rest of the run. Either
        change rebuilds the step program (one retrace); overflow lanes
        were served exactly either way. ``auto_alpha=True`` builds the
        default controller this delegates to; pass ``controller=`` to
        share one with the split/repin decisions."""
        if self.controller is None or self.routed_alpha is None:
            return
        total = 0
        for v in (self.last_routed_overflow, self.last_sample_overflow):
            if v is None:
                continue
            try:
                total += int(np.asarray(v).sum())
            except Exception:  # noqa: BLE001 — a deleted/donated buffer
                continue  # must not break the next step
        new = self.controller.decide_alpha(
            total, self.routed_alpha, float(self.feature_size)
        )
        if new is None:
            return
        old = self.routed_alpha
        self.routed_alpha = float(new)
        from ..utils.trace import get_logger

        get_logger().info(
            "shared routing budget: %d lanes fallback-served last batch "
            "(feature gather + sampler hops); alpha %.2f -> %.2f "
            "(one retrace)",
            total, old, self.routed_alpha,
        )
        self.last_routed_overflow = None
        self.last_sample_overflow = None
        self._step = self._build()
        self._epoch_fn = self._build_epoch()


class DataParallelTrainer:
    """Unfused multi-chip training — the reference-shaped papers100M loop.

    Since r4 the fused :class:`DistributedTrainer` handles beyond-HBM
    configs too (staged host gathers compose into its one-program step);
    this trainer remains as the *unfused* alternative — host-driven
    sample/gather with prefetch overlap — mirroring the reference's
    flagship scale architecture
    exactly (benchmarks/ogbn-papers100M/dist_sampling_ogb_paper100M_quiver.py:
    120-165): each data-parallel worker samples its own seed block and
    gathers its own features (here: the single-controller sample/gather
    paths, which already stage host-resident topology and cold-tier rows
    through host compute), and only the model step runs as one SPMD program —
    a shard_map over the ``data`` axis with a gradient ``pmean``, the
    reference's DDP/NCCL allreduce (:133). :class:`Prefetcher` overlap makes
    batch i+1's sample+gather run under batch i's step — the role UVA's
    "kernel reads host RAM while computing" plays in the reference.

    Accepts ANY sampler/feature configuration (mode="HOST", cold tiers,
    weighted, auto caps); the feature store must be a replicated
    :class:`Feature` (the reference's papers100M config is device_replicate
    too; mesh-sharded hot tiers belong to the fused trainer).
    """

    def __init__(
        self,
        mesh: Mesh,
        sampler: GraphSageSampler,
        feature: Feature,
        model,
        tx: optax.GradientTransformation,
        local_batch: int = 128,
        prefetch_retries: int = 0,
        prefetch_backoff: float = 0.05,
        prefetch_skip_policy: str = "raise",
    ):
        if isinstance(feature, ShardedFeature):
            raise ValueError(
                "DataParallelTrainer replicates the feature store; use the "
                "fused DistributedTrainer for mesh-sharded hot tiers"
            )
        if mesh.shape.get(FEATURE_AXIS, 1) != 1:
            raise ValueError(
                "DataParallelTrainer is pure data parallelism; build the "
                "mesh with feature=1"
            )
        self.mesh = mesh
        self.sampler = sampler
        self.feature = feature
        self.model = model
        self.tx = tx
        self.local_batch = int(local_batch)
        self.data_size = mesh.shape[DATA_AXIS]
        self.global_batch = self.local_batch * self.data_size
        self._step_cache = {}
        # graftscope: the epoch loop's Prefetcher lands its retry/skip
        # counters here, so pipeline health is readable next to the rest
        # of the telemetry (metrics_report)
        self.metrics = MetricsRegistry()
        self.timeline = StepTimeline()
        # resilience knobs forwarded to the epoch loop's Prefetcher
        # (bounded retry + skip-and-count for transient host faults —
        # see parallel/pipeline.py; defaults keep the fail-fast behavior)
        self.prefetch_retries = int(prefetch_retries)
        self.prefetch_backoff = float(prefetch_backoff)
        self.prefetch_skip_policy = str(prefetch_skip_policy)
        self._pin_auto_caps()

    def _pin_auto_caps(self):
        """Pin auto frontier caps at construction (VERDICT r5 weak #6).

        ``frontier_caps="auto"`` replans caps whenever a batch overflows the
        observed plan — mid-epoch that makes stacked per-worker blocks
        disagree on static shapes and ``_stack`` can only raise. Plan ONCE
        here from a probe batch, then freeze: later skewed batches get the
        fixed-caps behavior (clipped frontier + overflow report) instead of
        a mid-epoch shape change. The probe advances the sampler's PRNG
        call counter by one.
        """
        if not getattr(self.sampler, "_auto_caps", False):
            return
        n = self.sampler.csr_topo.node_count
        probe = np.arange(min(self.local_batch, n))
        self.sampler.sample(probe)
        self.sampler._auto_caps = False
        from ..utils.trace import get_logger

        get_logger().info(
            "auto frontier caps planned from a probe batch and PINNED at "
            "%s for the epoch loop (mid-epoch replanning would make "
            "stacked blocks disagree; overflowing batches are clipped and "
            "reported instead)",
            self.sampler._frontier_caps,
        )

    # -- program ------------------------------------------------------------

    def _adj_sizes(self, caps) -> list[tuple[int, int]]:
        """Static Adj sizes, deepest layer first (sampler output order)."""
        sizes = []
        prev = self.local_batch
        for cap in caps:
            sizes.append((cap, prev))
            prev = cap
        return sizes[::-1]

    def _compiled_step(self, caps: tuple, fanouts: tuple, feat_dim: int):
        key_ = (caps, fanouts, feat_dim)
        if key_ in self._step_cache:
            return self._step_cache[key_]

        model, tx = self.model, self.tx
        S = self.local_batch
        adj_sizes = self._adj_sizes(caps)
        # deepest-first fanouts arrive from the prefetched batches' own Adj
        # metadata (_stack), not re-derived from sampler.sizes — restores
        # the regular layout the stacked arrays lost, so the step uses the
        # dense zero-scatter aggregation path (ADVICE trainer.py:446: the
        # sampler-ordering re-derivation was an implicit contract; the Adjs
        # already carry fanout through tree_flatten aux)

        def body(params, opt_state, x, eis, n_id, bsz, labels, key):
            # blocks arrive with a leading length-1 shard dim; squeeze it
            x_b = x[0]
            adjs = [
                Adj(ei[0], None, sz, fanout=f)
                for ei, sz, f in zip(eis, adj_sizes, fanouts)
            ]
            seed_ids = n_id[0][:S]
            lab = labels[jnp.clip(seed_ids, 0)]
            # mask by the block's true batch size: for a short block, lanes
            # [bsz, S) of n_id hold FRONTIER nodes (masked_unique compacts
            # first-occurrence order), not -1 — they must not be trained on
            mask = (jnp.arange(S) < bsz[0]) & (seed_ids >= 0)
            key = jax.random.fold_in(key, jax.lax.axis_index(DATA_AXIS))

            def loss_fn(p):
                logits = model.apply(
                    {"params": p}, x_b, adjs, train=True, rngs={"dropout": key}
                )
                return cross_entropy_on_seeds(logits[:S], lab, mask)

            loss, grads = jax.value_and_grad(loss_fn)(params)
            grads = jax.lax.pmean(grads, DATA_AXIS)
            loss = jax.lax.pmean(loss, DATA_AXIS)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, loss

        n_layers = len(caps)
        fn = shard_map(
            body,
            mesh=self.mesh,
            in_specs=(
                P(),
                P(),
                P(DATA_AXIS),
                tuple([P(DATA_AXIS)] * n_layers),
                P(DATA_AXIS),
                P(DATA_AXIS),
                P(),
                P(),
            ),
            out_specs=(P(), P(), P()),
            check_vma=False,
        )
        step = jax.jit(fn)
        self._step_cache[key_] = step
        return step

    # -- API ----------------------------------------------------------------

    def metrics_report(self) -> str:
        """One-call telemetry summary (prefetch retry/skip counters from
        the epoch loop's Prefetcher + host stage timeline)."""
        return _metrics_report(self.metrics, self.timeline)

    def init(self, rng):
        """Initialize params/opt_state from one sampled block."""
        n = self.sampler.csr_topo.node_count
        m = min(self.local_batch, n)
        out = self.sampler.sample(np.arange(m))
        x = self.feature[out.n_id]
        params = self.model.init({"params": rng}, x, out.adjs)["params"]
        return params, self.tx.init(params)

    def seed_blocks(self, seeds: np.ndarray):
        """Split a global seed array into per-device blocks
        (``train_idx.split(world_size)[rank]`` parity)."""
        seeds = np.asarray(seeds)
        blocks = np.array_split(seeds, self.data_size)
        for b in blocks:
            if len(b) > self.local_batch:
                raise ValueError(
                    f"block {len(b)} exceeds local_batch {self.local_batch}"
                )
        return blocks

    def _stack(self, batches):
        """Stack D per-worker (out, x) into data-sharded step inputs.

        Returns (caps, fanouts, x, n_id, eis, bsz) — per-layer batch
        metadata read off the blocks' own Adjs: caps in sizes order (seeds
        outward, what _adj_sizes expects), fanouts deepest-first (what the
        step body zips against the deepest-first eis).
        """
        caps = fanouts = None
        for b in batches:
            c = tuple(a.size[0] for a in b.out.adjs[::-1])
            f = tuple(a.fanout for a in b.out.adjs)
            if caps is None:
                caps, fanouts = c, f
            elif c != caps or f != fanouts:
                # unreachable for trainer-owned samplers (_pin_auto_caps
                # froze the plan); guards externally mutated samplers
                raise ValueError(
                    "sampled blocks disagree on frontier caps/fanouts "
                    f"({caps}/{fanouts} vs {c}/{f}); pin frontier_caps on "
                    "the sampler (auto caps may replan between blocks)"
                )
        n_layers = len(caps)
        x = self._shard_stack([b.x for b in batches])
        n_id = self._shard_stack([b.out.n_id for b in batches])
        eis = tuple(
            self._shard_stack([b.out.adjs[l].edge_index for b in batches])
            for l in range(n_layers)
        )
        bsz = self._shard_stack(
            [jnp.int32(b.out.batch_size) for b in batches]
        )
        return caps, fanouts, x, n_id, eis, bsz

    def _shard_stack(self, blocks):
        """Stack D per-worker arrays directly onto their target devices.

        Equivalent to ``device_put(jnp.stack(blocks), P(DATA_AXIS))`` but
        never materializes the full stacked batch on one device — each
        block hops straight to its shard's device (one transfer per block,
        no device-0 peak)."""
        devs = self.mesh.devices.reshape(self.data_size, -1)[:, 0]
        shards = [
            jax.device_put(jnp.asarray(b)[None], d)
            for b, d in zip(blocks, devs)
        ]
        shape = (self.data_size,) + tuple(shards[0].shape[1:])
        sharding = NamedSharding(self.mesh, P(DATA_AXIS))
        return jax.make_array_from_single_device_arrays(
            shape, sharding, shards
        )

    def step(self, params, opt_state, batches, labels, key):
        """One DP step from D prefetched batches (``Prefetcher`` Batch or
        anything with ``.out``/``.x``). ``labels``: full (N,) array."""
        if len(batches) != self.data_size:
            raise ValueError(
                f"need {self.data_size} batches (one per data shard), "
                f"got {len(batches)}"
            )
        caps, fanouts, x, n_id, eis, bsz = self._stack(batches)
        step = self._compiled_step(caps, fanouts, x.shape[-1])
        return step(params, opt_state, x, eis, n_id, bsz, labels, key)

    def train_epoch(self, params, opt_state, train_idx, labels, key,
                    rng=None, depth: int = 2):
        """One epoch with prefetch overlap: sample+gather for the next
        step's blocks runs while the current step computes.

        Returns (params, opt_state, mean_loss, num_steps).
        """
        rng = rng or np.random.default_rng(0)
        train_idx = np.asarray(train_idx)
        if train_idx.size == 0:
            # a silent float("nan") mean loss poisons every downstream
            # consumer (schedulers, early stopping, logs) — fail loudly
            raise ValueError(
                "train_epoch got an empty seed set (train_idx) — nothing "
                "to train on; check the split/filter that produced it"
            )
        perm = rng.permutation(len(train_idx))
        steps = max(len(train_idx) // self.global_batch, 1)
        blocks = []
        for s in range(steps):
            chunk = train_idx[perm[s * self.global_batch:(s + 1) * self.global_batch]]
            blocks.extend(self.seed_blocks(chunk))

        losses = []
        group = []
        prefetcher = Prefetcher(
            self.sampler, self.feature, depth=depth,
            retries=self.prefetch_retries, backoff=self.prefetch_backoff,
            skip_policy=self.prefetch_skip_policy,
            timeline=self.timeline, metrics=self.metrics,
        )
        for batch in prefetcher.run(blocks):
            group.append(batch)
            if len(group) == self.data_size:
                key, sub = jax.random.split(key)
                params, opt_state, loss = self.step(
                    params, opt_state, group, labels, sub
                )
                losses.append(loss)
                group = []
        mean_loss = float(jnp.mean(jnp.stack(losses))) if losses else float("nan")
        return params, opt_state, mean_loss, len(losses)
