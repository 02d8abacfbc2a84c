"""graftaudit entry-point registry — the canonical programs the auditor
traces and walks.

Each :class:`Target` names one lowered program the repo stakes an
invariant on, a builder that AOT-traces it (``jax.jit(...).trace`` +
``.lower()`` — NO device execution; everything runs under
``JAX_PLATFORMS=cpu`` on a 2-device ``--xla_force_host_platform_device_count``
mesh), the source files whose edits make the target worth re-auditing
(``--changed`` scoping), and per-rule metadata/waivers.

Builders are memoized: a full ``run_audit()`` traces each program once and
every rule walks the shared artifact. Donation warnings are captured at
build time — jax reports an *unusable* donation only as a
``UserWarning`` at trace/lower time (the lowered text carries no attr for
it), so the warning stream is part of the audit artifact.
"""

from __future__ import annotations

import dataclasses
import warnings

import numpy as np

__all__ = ["Built", "Target", "REGISTRY", "build", "build_from",
           "clear_cache"]

MESH_DEVICES = 2  # the audit mesh: (data=1, feature=2)

_REGISTRY: dict = {}
_BUILT: dict = {}
_SHARED: dict = {}  # memoized heavyweight fixtures (trainers, ladders)


@dataclasses.dataclass(frozen=True)
class Built:
    """One audited program: the traced jaxpr, the lowered StableHLO text,
    and the donation warnings the build emitted."""

    name: str
    jaxpr: object  # ClosedJaxpr
    mlir: str
    donation_warnings: tuple
    meta: dict


@dataclasses.dataclass(frozen=True)
class Target:
    name: str
    doc: str
    builder: object  # () -> jax Traced (jit(...).trace result)
    sources: tuple  # repo-relative files this program is lowered from
    meta: dict = dataclasses.field(default_factory=dict)
    # rule -> reason: registry-side reasoned waivers (suppressed findings)
    waivers: dict = dataclasses.field(default_factory=dict)


def _register(name, doc, sources, meta=None, waivers=None):
    def deco(fn):
        _REGISTRY[name] = Target(
            name=name, doc=doc, builder=fn, sources=tuple(sources),
            meta=dict(meta or {}), waivers=dict(waivers or {}),
        )
        return fn

    return deco


REGISTRY = _REGISTRY


def build_from(t: Target) -> Built:
    """Trace + lower one target, capturing donation warnings (jax reports
    unusable donations ONLY as warnings — they lower to no attr). Also
    the entry point tests use to audit fixture programs that are not in
    the registry."""
    with warnings.catch_warnings(record=True) as wlist:
        warnings.simplefilter("always")
        traced = t.builder()
        mlir = traced.lower().as_text()
    donation = tuple(
        str(w.message) for w in wlist
        if "donat" in str(w.message).lower()
    )
    return Built(name=t.name, jaxpr=traced.jaxpr, mlir=mlir,
                 donation_warnings=donation, meta=t.meta)


def build(name: str) -> Built:
    """Memoized :func:`build_from` over the registry."""
    if name not in _BUILT:
        _BUILT[name] = build_from(_REGISTRY[name])
    return _BUILT[name]


def clear_cache() -> None:
    _BUILT.clear()
    _SHARED.clear()


# -- shared fixtures ----------------------------------------------------------


def _mesh():
    import jax

    from ...parallel.mesh import make_mesh

    if jax.device_count() < MESH_DEVICES:
        raise RuntimeError(
            f"graftaudit needs {MESH_DEVICES} CPU devices; run via the CLI "
            "(sets XLA_FLAGS before jax imports) or under tests/conftest.py"
        )
    if "mesh" not in _SHARED:
        _SHARED["mesh"] = make_mesh(MESH_DEVICES, data=1, feature=2)
    return _SHARED["mesh"]


def _tiny_trainer(**kw):
    """The test_obs.py acceptance-differential trainer, on the 2-device
    audit mesh: 96 nodes, 8-dim features, [3, 2] fanouts, local_batch=8,
    seed_sharding='all' — so the sharded-feature gather routes over
    all_to_all and the audited epoch body carries the full comm schedule.
    """
    key = tuple(sorted(kw.items()))
    if key in _SHARED:
        return _SHARED[key]
    import jax
    import jax.numpy as jnp
    import optax

    from ...core.topology import CSRTopo
    from ...feature.shard import ShardedFeature
    from ...models.sage import GraphSAGE
    from ...parallel.trainer import DistributedTrainer
    from ...sampling.sampler import GraphSageSampler

    mesh = _mesh()
    rng = np.random.default_rng(0)
    n = 96
    ei = rng.integers(0, n, size=(2, 800)).astype(np.int64)
    topo = CSRTopo(edge_index=ei)
    feat = rng.normal(size=(n, 8)).astype(np.float32)
    feature_kw = {}
    if kw.pop("int8", False):
        feature_kw["dtype"] = "int8"
    store = ShardedFeature(
        mesh, device_cache_size="1G", csr_topo=topo, **feature_kw
    ).from_cpu_tensor(feat)
    sampler = GraphSageSampler(topo, [3, 2], seed=0, seed_capacity=8)
    model = GraphSAGE(hidden=8, num_classes=4, num_layers=2)
    trainer = DistributedTrainer(
        mesh, sampler, store, model, optax.sgd(1e-2), local_batch=8,
        seed_sharding="all", **kw,
    )
    params, opt = trainer.init(jax.random.PRNGKey(0))
    labels = jnp.asarray(rng.integers(0, 4, n).astype(np.int32))
    out = (trainer, params, opt, labels)
    _SHARED[key] = out
    return out


def _trace_epoch(trainer, params, opt, labels, steps=1):
    import jax
    import jax.numpy as jnp

    seed_mat = trainer.pack_epoch(np.arange(steps * trainer.global_batch),
                                  seed=0)
    packed = jnp.asarray(seed_mat)
    keys = jax.random.split(jax.random.PRNGKey(1), steps)
    inject = jnp.zeros((steps,), bool)
    return trainer._epoch_fn.trace(
        params, opt, trainer.topo, trainer._feature_parts(), packed, labels,
        keys, inject,
    )


def _trace_step(trainer, params, opt, labels):
    import jax
    import jax.numpy as jnp

    seed_mat = trainer.pack_epoch(np.arange(trainer.global_batch), seed=0)
    packed = jnp.asarray(seed_mat)[0]
    key = jax.random.PRNGKey(1)
    inject = jnp.asarray(False)
    return trainer._step.trace(
        params, opt, trainer.topo, trainer._feature_parts(), packed, labels,
        key, inject,
    )


# comm model of the audited epoch body: W workers (seed_sharding="all"
# => every device), local_batch seeds each, prod(sizes) lanes per seed
_EPOCH_COMM = dict(feature_shards=2, local_len=2 * 8 * 3 * 2, feature_dim=8)

# the tiny step's metric reductions beyond the training math: the
# feature.routed_overflow scalar psum over "data", and over
# ("data", "feature") the feature.tier_hits (3,) psum and the three
# sampler counts (sample.edges, sample.frontier,
# sample.frontier_overflow) — update alongside obs/registry.py when a new
# per-step metric collective lands
_EXPECTED_METRIC_REDUCTIONS = 5


# -- targets ------------------------------------------------------------------


@_register(
    "routed_gather",
    "capped-bucket routed feature gather with the forced psum fallback "
    "cond (cap < per-shard demand)",
    sources=("quiver_tpu/feature/shard.py", "quiver_tpu/parallel/routing.py",
             "quiver_tpu/parallel/mesh.py"),
    meta={"hbm_budget": 2048},
)
def _routed_gather():
    import jax
    import jax.numpy as jnp

    from ...feature.shard import ShardedTensor
    from ...parallel.mesh import FEATURE_AXIS, shard_map
    from jax.sharding import PartitionSpec as P

    mesh = _mesh()
    tbl = np.random.default_rng(0).normal(size=(16, 4)).astype(np.float32)
    st = ShardedTensor(mesh).from_cpu_tensor(tbl)
    ids = jnp.arange(8, dtype=jnp.int32)

    def body(local, i):
        # cap=2 < the 8-lane demand: the overflow fallback cond is LIVE in
        # the lowered program (a statically exact cap folds it away)
        return st.routed_gather(local, i, cap=2, with_overflow=True)

    fn = jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(P(FEATURE_AXIS, None), P(FEATURE_AXIS)),
        out_specs=(P(FEATURE_AXIS, None), P()),
        # as the trainer builds it: under the varying-axes check the
        # overflow cond's two branches disagree
        check_vma=False,
    ))
    return fn.trace(st.table, ids)


@_register(
    "tiered_lookup_int8",
    "trainer step over an int8-quantized ShardedFeature: the three-tier "
    "lookup with int8 codes riding the routed all_to_all",
    sources=("quiver_tpu/feature/shard.py", "quiver_tpu/feature/feature.py",
             "quiver_tpu/parallel/trainer.py", "quiver_tpu/ops/gather.py"),
    meta={"int8_path": True, "hbm_budget": 40 * 1024},
)
def _tiered_lookup_int8():
    return _trace_step(*_tiny_trainer(int8=True, collect_metrics=False))


@_register(
    "sample_hop",
    "topo-sharded multilayer sample program (dist_sample_layer hops in "
    "shard_map, owner-routed frontiers)",
    sources=("quiver_tpu/sampling/dist.py", "quiver_tpu/sampling/sampler.py",
             "quiver_tpu/core/topology.py"),
    meta={"hbm_budget": 32 * 1024},
)
def _sample_hop():
    import jax

    from ...core.topology import CSRTopo
    from ...sampling.sampler import GraphSageSampler

    mesh = _mesh()
    rng = np.random.default_rng(7)
    ei = rng.integers(0, 120, size=(2, 900)).astype(np.int64)
    topo = CSRTopo(edge_index=ei)
    sampler = GraphSageSampler(
        topo, [3, 2], seed=7, seed_capacity=16,
        topo_sharding="mesh", mesh=mesh,
    )
    run, _caps = sampler._compiled(16)
    seeds = jax.ShapeDtypeStruct((sampler.workers * 16,), np.int32)
    key = jax.ShapeDtypeStruct(np.shape(sampler._key),
                               np.asarray(sampler._key).dtype)
    return run.trace(*sampler._topo_operands(), seeds, key)


@_register(
    "epoch_body_alpha1",
    "fused epoch body (scan over the one-program step) at routed_alpha=1 "
    "— the comm-budget anchor at the tight cap",
    sources=("quiver_tpu/parallel/trainer.py", "quiver_tpu/control/cost.py",
             "quiver_tpu/feature/shard.py"),
    meta={"comm": dict(_EPOCH_COMM, alpha=1.0), "hbm_budget": 64 * 1024},
)
def _epoch_alpha1():
    return _trace_epoch(*_tiny_trainer(routed_alpha=1.0))


@_register(
    "epoch_body_alpha2",
    "fused epoch body at routed_alpha=2 (the default budget) — comm "
    "lanes double against the same analytic model",
    sources=("quiver_tpu/parallel/trainer.py", "quiver_tpu/control/cost.py",
             "quiver_tpu/feature/shard.py"),
    meta={"comm": dict(_EPOCH_COMM, alpha=2.0), "hbm_budget": 64 * 1024},
)
def _epoch_alpha2():
    return _trace_epoch(*_tiny_trainer(routed_alpha=2.0))


@_register(
    "epoch_pipelined",
    "software-pipelined epoch body (pipeline_depth=1, one-step skew): "
    "same invariants as the serial scan",
    sources=("quiver_tpu/parallel/trainer.py",
             "quiver_tpu/parallel/pipeline.py"),
    meta={"hbm_budget": 128 * 1024},
)
def _epoch_pipelined():
    return _trace_epoch(*_tiny_trainer(pipeline_depth=1), steps=2)


@_register(
    "epoch_donating",
    "epoch body with donate_epoch_state=True: every params+opt leaf must "
    "actually be donated (aliased or buffer-donor) with zero "
    "unusable-donation warnings",
    sources=("quiver_tpu/parallel/trainer.py",),
    meta={"donation": "claimed", "hbm_budget": 64 * 1024},
)
def _epoch_donating():
    import jax

    trainer, params, opt, labels = _tiny_trainer(donate_epoch_state=True)
    leaves = len(jax.tree_util.tree_leaves((params, opt)))
    # record the exact claimed-leaf count for the donation-audit rule
    _REGISTRY["epoch_donating"].meta["donated_leaves"] = leaves
    return _trace_epoch(trainer, params, opt, labels)


@_register(
    "serve_forward",
    "serving-ladder forward program (largest bucket): AOT ladder rung the "
    "steady-state replay contract is staked on",
    sources=("quiver_tpu/serving/ladder.py", "quiver_tpu/models/sage.py",
             "quiver_tpu/models/layers.py", "quiver_tpu/ops/gather.py",
             "quiver_tpu/parallel/train.py"),
    meta={"donation": "none", "hbm_budget": 24 * 1024},
)
def _serve_forward():
    lad = _ladder()
    return lad.trace_forward(4)


@_register(
    "serve_sample",
    "serving-ladder per-bucket sample program (scan over lane samples)",
    sources=("quiver_tpu/serving/ladder.py", "quiver_tpu/ops/sample.py"),
    meta={"donation": "none", "hbm_budget": 24 * 1024},
)
def _serve_sample():
    lad = _ladder()
    return lad.trace_sample(4)


@_register(
    "metrics_on",
    "trainer step with collect_metrics=True — the telemetry-carrying "
    "half of the metrics-strip differential",
    sources=("quiver_tpu/parallel/trainer.py", "quiver_tpu/obs/registry.py"),
    meta={"hbm_budget": 64 * 1024},
)
def _metrics_on():
    return _trace_step(*_tiny_trainer(collect_metrics=True))


@_register(
    "metrics_off",
    "trainer step with collect_metrics=False — must equal metrics_on "
    "minus exactly the declared metric reductions",
    sources=("quiver_tpu/parallel/trainer.py", "quiver_tpu/obs/registry.py"),
    meta={"metrics_pair": "metrics_on",
          "expected_metric_reductions": _EXPECTED_METRIC_REDUCTIONS,
          "hbm_budget": 64 * 1024},
)
def _metrics_off():
    return _trace_step(*_tiny_trainer(collect_metrics=False))


@_register(
    "pallas_fused_interp",
    "fused sample megakernel family, interpret-mode lowering in ONE "
    "traced program: the uniform+eid hop over a host-numpy CSRTopo "
    "closure (regression: host indptr indexing broke this trace "
    "entirely) and the weighted inverse-CDF hop (kernel='pallas')",
    sources=("quiver_tpu/ops/pallas/fused.py",
             "quiver_tpu/ops/pallas/sample.py"),
    meta={"hbm_budget": 64 * 1024},
    # the CSR topology rides the closure as trace constants — bounded at
    # ~10KB here, and the production path passes topology as operands
    waivers={"constant-bloat": "fixture topology is closure-captured by "
                               "construction; production paths pass "
                               "topology operands"},
)
def _pallas_fused():
    import jax

    from ...core.topology import CSRTopo
    from ...ops.pallas.fused import fused_sample_layer

    rng = np.random.default_rng(0)
    ei = np.stack([rng.integers(0, 64, 900), rng.integers(0, 64, 900)])
    topo = CSRTopo(edge_index=ei)
    topo.set_edge_weight(rng.random(900).astype(np.float32) + 0.1)
    wtopo = topo.to_device(with_weights=True)
    seeds = jax.ShapeDtypeStruct((16,), np.int32)
    key = jax.ShapeDtypeStruct((2,), np.uint32)

    def program(s, k):
        uni = fused_sample_layer(topo, s, 16, 4, k, with_eid=True,
                                 window=128, interpret=True)
        wei = fused_sample_layer(wtopo, s, 16, 4, k, weighted=True,
                                 window=128, interpret=True)
        return uni, wei

    return jax.jit(program).trace(seeds, key)


def _ladder():
    if "ladder" in _SHARED:
        return _SHARED["ladder"]
    import jax
    import jax.numpy as jnp

    from ...core.topology import CSRTopo
    from ...models.sage import GraphSAGE
    from ...parallel.train import empty_adjs, init_model
    from ...sampling.sampler import GraphSageSampler
    from ...serving.ladder import ServeLadder

    rng = np.random.default_rng(0)
    n, e = 240, 1600
    ei = np.stack([rng.integers(0, n, e), rng.integers(0, n, e)])
    topo = CSRTopo(edge_index=ei)
    sampler = GraphSageSampler(topo, [4, 3], seed=1, seed_capacity=4)
    model = GraphSAGE(hidden=16, num_classes=5, num_layers=2)
    adjs = empty_adjs([4, 3], batch=4, node_count=n)
    params = init_model(
        model, jax.random.PRNGKey(0),
        jnp.zeros((adjs[0].size[0], 12), jnp.float32), adjs,
    )
    lad = ServeLadder(sampler, model, feature_dim=12)
    lad.bind_params(params)
    _SHARED["ladder"] = lad
    return lad


@_register(
    "serve_fleet_forward",
    "fleet replica serve-ladder forward, warm-from-AOT variant: the "
    "program a second replica REPLAYS after deserializing the first "
    "replica's published executables (PR 17's zero-compile join) — the "
    "traced forward must carry the same invariants whether it was "
    "compiled locally or loaded from the shared cache",
    sources=("quiver_tpu/serving/fleet.py", "quiver_tpu/serving/aot.py",
             "quiver_tpu/serving/server.py", "quiver_tpu/serving/ladder.py"),
    meta={"donation": "none", "hbm_budget": 24 * 1024},
)
def _serve_fleet_forward():
    fleet = _fleet()
    # the warm joiner, not the cache-populating first replica
    return fleet.servers[-1]._ladder.trace_forward(4)


@_register(
    "mmap_tiered_gather",
    "MmapFeatureStore device-side tier merge (quiver-ooc): the traced "
    "tiered_lookup + dequant wrapping one staged batch runs, with the "
    "host-assembled cold block as a program operand — the out-of-core "
    "path's only on-device program",
    sources=("quiver_tpu/ooc/store.py", "quiver_tpu/ooc/format.py",
             "quiver_tpu/ooc/stager.py", "quiver_tpu/feature/feature.py"),
    meta={"hbm_budget": 16 * 1024},
)
def _mmap_tiered_gather():
    return _mmap_store().trace_lookup(16)


def _fleet():
    """A two-replica ServingFleet over a throwaway disk AOT cache: the
    first replica compiles+publishes (bucket 4 only, to bound build
    cost), the second joins warm. Construction compiles — never
    executes — which keeps the registry's trace-only discipline."""
    if "fleet" in _SHARED:
        return _SHARED["fleet"]
    import tempfile

    import jax
    import jax.numpy as jnp

    from ...core.topology import CSRTopo
    from ...feature.feature import Feature
    from ...models.sage import GraphSAGE
    from ...parallel.train import empty_adjs, init_model
    from ...sampling.sampler import GraphSageSampler
    from ...serving.fleet import ServingFleet

    rng = np.random.default_rng(3)
    n, e = 160, 900
    ei = np.stack([rng.integers(0, n, e), rng.integers(0, n, e)])
    topo = CSRTopo(edge_index=ei)
    feat = Feature(device_cache_size="1G").from_cpu_tensor(
        rng.normal(size=(n, 12)).astype(np.float32))
    sampler = GraphSageSampler(topo, [4, 3], seed=1, seed_capacity=4)
    model = GraphSAGE(hidden=16, num_classes=5, num_layers=2)
    adjs = empty_adjs([4, 3], batch=4, node_count=n)
    params = init_model(
        model, jax.random.PRNGKey(0),
        jnp.zeros((adjs[0].size[0], 12), jnp.float32), adjs,
    )
    cache_dir = tempfile.mkdtemp(prefix="graftmem-aot-")
    fleet = ServingFleet(
        sampler, model, params, feat, replicas=1, aot_cache=cache_dir,
        seed=7, warm=True, max_batch=4, buckets=(4,),
    )
    fleet.add_replica(warm=True)
    # record the join ledger so tests can assert the audited program
    # really is the warm-from-AOT variant (zero compiles on join)
    _REGISTRY["serve_fleet_forward"].meta["warm_join"] = dict(
        loaded=int(fleet.cold_starts[-1]["loaded"]),
        compiled=int(fleet.cold_starts[-1]["compiled"]),
    )
    _SHARED["fleet"] = fleet
    return fleet


def _mmap_store():
    """A tiny on-disk raw feature dir + reopened MmapFeatureStore with
    live hot AND cold tiers (device_cache_size splits the 64 rows)."""
    if "mmap_store" in _SHARED:
        return _SHARED["mmap_store"]
    import tempfile

    from ...core.topology import CSRTopo
    from ...ooc.store import MmapFeatureStore

    rng = np.random.default_rng(5)
    n, f = 64, 8
    ei = np.stack([rng.integers(0, n, 400), rng.integers(0, n, 400)])
    topo = CSRTopo(edge_index=ei)
    tensor = rng.normal(size=(n, f)).astype(np.float32)
    path = tempfile.mkdtemp(prefix="graftmem-ooc-")
    MmapFeatureStore.write(path, tensor,
                           device_cache_size=16 * f * 4, csr_topo=topo)
    store = MmapFeatureStore(path, access="mmap", window_rows=16)
    _SHARED["mmap_store"] = store
    return store
