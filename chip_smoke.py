"""The quickest proof that the training path still starts on the chip.

Drives the products-GraphSAGE configuration (R1) once through the normal
entry points — ``GraphSageSampler`` -> ``Feature`` -> ``DistributedTrainer``
— at full width on one TPU process, checks what comes out against the host
copy of the graph and the features, and compiles the fused Pallas sampler at the
shapes the run used. Depth is cut (30 steps, a 4-step scanned epoch); no
width, fanout or batch is. With more than one device the trainer stage runs
again over a (data, feature=2) mesh with a sharded feature store.

    python chip_smoke.py

Exits non-zero at once unless ``jax.devices()[0].platform == "tpu"``, and on
the first failed check or raised stage. The last line of standard output is
``{"ok": true, "device": {...}}`` and nothing else ends a failed run.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import sys
import time

import numpy as np


@dataclasses.dataclass(frozen=True)
class Config:
    """R1 at full width. Tests shrink ``nodes`` and the depth, nothing else
    a chip run depends on."""

    nodes: int = 2_450_000
    avg_degree: float = 50.5
    feature_dim: int = 100
    fanout: tuple = (15, 10, 5)
    hidden: int = 256
    classes: int = 47
    batch: int = 1024
    cache_ratio: float = 0.2
    steps: int = 30
    scan_steps: int = 4
    check_rows: int = 300
    interpret: bool = False  # only the CPU test configuration interprets


class CheckFailed(AssertionError):
    """A stage's output is wrong."""


def check(ok, what: str) -> None:
    if not ok:
        raise CheckFailed(what)
    print(f"  ok: {what}")


class CompileMeter:
    """Backend compilations and their seconds, from JAX's own monitoring
    events (a persistent-cache hit still passes through the compile event,
    in the time the retrieval takes)."""

    def __init__(self):
        import jax.monitoring

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.compiles, self.compile_s, self.cache_hits


class Stage:
    """Wall, compile and run seconds of one stage, printed on exit."""

    def __init__(self, name: str, meter: CompileMeter):
        self.name, self.meter = name, meter
        self.setup_s = 0.0

    def __enter__(self):
        print(f"\n== {self.name} ==", flush=True)
        self.t0 = time.perf_counter()
        self.before = self.meter.snapshot()
        return self

    def setup_done(self):
        """Everything up to here was building objects and placing data."""
        self.setup_s = time.perf_counter() - self.t0

    def __exit__(self, exc_type, *_):
        if exc_type is not None:
            return
        wall = time.perf_counter() - self.t0
        compiles, compile_s, hits = (
            a - b for a, b in zip(self.meter.snapshot(), self.before)
        )
        print(
            f"[{self.name}] setup {self.setup_s:.1f}s  compile {compile_s:.1f}s "
            f"({compiles} compilations, {hits} cache hits)  run "
            f"{max(wall - self.setup_s - compile_s, 0.0):.1f}s  "
            f"wall {wall:.1f}s", flush=True,
        )


def require_tpu():
    """The first device, or exit non-zero before any stage runs."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: needs a TPU, found platform={dev.platform!r}; "
            "there is no CPU fallback", file=sys.stderr,
        )
        raise SystemExit(2)
    return dev


def build_inputs(cfg: Config):
    """Graph, features and learnable labels, all from seeds."""
    from quiver_tpu import CSRTopo
    from quiver_tpu.utils.graphgen import generate_pareto_graph

    topo = CSRTopo(
        edge_index=generate_pareto_graph(cfg.nodes, cfg.avg_degree, seed=0)
    )
    rng = np.random.default_rng(0)
    n = topo.node_count
    feat = rng.standard_normal((n, cfg.feature_dim), dtype=np.float32)
    # labels a GraphSAGE can learn from a node's own row: the argmax of a
    # fixed random projection
    proj = rng.standard_normal((cfg.feature_dim, cfg.classes), dtype=np.float32)
    labels = np.argmax(feat @ proj, axis=1).astype(np.int32)
    print(f"graph: {n} nodes, {topo.edge_count} edges, max degree "
          f"{topo.max_degree}; features {feat.shape} {feat.dtype}")
    return topo, feat, labels


def hot_budget(cfg: Config, n: int, shards: int = 1) -> int:
    """Per-device byte budget that caches ``cache_ratio`` of the rows."""
    return int(cfg.cache_ratio * n / shards) * cfg.feature_dim * 4


def median_ms(fn, reps: int = 5) -> float:
    import jax

    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2] * 1e3


def stage_per_call(cfg: Config, topo, feat, meter: CompileMeter):
    """``sampler.sample`` and ``feature[n_id]`` against the host copies."""
    from quiver_tpu import Feature, GraphSageSampler

    with Stage("per-call", meter) as stage:
        sampler = GraphSageSampler(topo, list(cfg.fanout), frontier_caps="auto")
        feature = Feature(
            device_cache_size=hot_budget(cfg, topo.node_count), csr_topo=topo
        ).from_cpu_tensor(feat)
        stage.setup_done()
        print(f"sampler: kernel={sampler.kernel}; "
              f"feature: hot_rows={feature.hot_rows}")

        rng = np.random.default_rng(1)
        seeds = rng.choice(topo.node_count, cfg.batch, replace=False)
        out = sampler.sample(seeds)  # plans the auto caps
        out = sampler.sample(seeds)  # the planned program
        n_id = np.asarray(out.n_id)
        print(f"frontier caps {sampler._frontier_caps}, "
              f"n_count {int(out.n_count)}")
        check(np.array_equal(n_id[:cfg.batch], seeds), "n_id[:B] == seeds")
        check(int(out.overflow) == 0, "overflow == 0")
        for hop, adj in enumerate(out.adjs[::-1]):
            check_neighbours(topo, n_id, adj, cfg.fanout[hop], cfg.check_rows,
                             f"hop {hop}")

        x = np.asarray(feature[out.n_id])
        valid = n_id >= 0
        check(x.shape == (n_id.shape[0], cfg.feature_dim)
              and np.isfinite(x).all(), "gathered block finite, expected shape")
        order = np.asarray(feature.feature_order)
        hot = valid & (order[np.clip(n_id, 0, None)] < feature.hot_rows)
        cold = valid & ~hot
        check(hot.any() and cold.any(), "ids from both the hot and cold tier "
              f"({int(hot.sum())} hot, {int(cold.sum())} cold)")
        check(np.array_equal(x[valid], feat[n_id[valid]]),
              "gathered rows == feat[n_id] exactly, both tiers")
        check(not x[~valid].any(), "invalid lanes gather zero rows")
        check(feature.cold.sharding.memory_kind == "pinned_host",
              "cold tier memory_kind == pinned_host")

        print(f"steady: sample {median_ms(lambda: sampler.sample(seeds).n_id):.1f} ms, "
              f"gather {median_ms(lambda: feature[out.n_id]):.1f} ms per call "
              f"({int(out.n_count)} rows)")
    return sampler, feature, out


def csr_row(topo, node: int):
    return topo.indices[topo.indptr[node]:topo.indptr[node + 1]]


def check_neighbours(topo, n_id, adj, k: int, rows: int, what: str) -> None:
    """Sampled neighbours of the first ``rows`` targets are CSR neighbours of
    their row, ``min(deg, k)`` of them."""
    src, dst = np.asarray(adj.edge_index)
    keep = (src >= 0) & (dst < rows)
    src, dst = src[keep], dst[keep]
    bad = 0
    checked = 0
    for r in range(min(rows, adj.size[1])):
        node = n_id[r]
        if node < 0:
            continue
        row = csr_row(topo, node)
        got = n_id[src[dst == r]]
        bad += int(len(got) != min(len(row), k) or not np.isin(got, row).all())
        checked += 1
    check(checked > 0 and bad == 0,
          f"{what}: sampled neighbours are CSR neighbours ({checked} rows)")


def stage_trainer(cfg: Config, name: str, mesh, sampler, feature, labels,
                  meter: CompileMeter, seed_sharding: str = "data",
                  before_first_step=None, steady_guard=None):
    """``DistributedTrainer`` init, ``cfg.steps`` steps and a short
    ``epoch_scan`` on learnable labels."""
    import contextlib

    import jax
    import optax
    from jax.sharding import NamedSharding, PartitionSpec

    from quiver_tpu.models.sage import GraphSAGE
    from quiver_tpu.parallel.trainer import DistributedTrainer

    with Stage(name, meter) as stage:
        model = GraphSAGE(hidden=cfg.hidden, num_classes=cfg.classes,
                          num_layers=len(cfg.fanout))
        trainer = DistributedTrainer(
            mesh, sampler, feature, model, optax.adam(1e-3),
            local_batch=cfg.batch, seed_sharding=seed_sharding,
        )
        params, opt_state = trainer.init(jax.random.PRNGKey(0))
        labels_dev = jax.device_put(
            labels, NamedSharding(mesh, PartitionSpec())
        )
        stage.setup_done()
        print(f"mesh {dict(mesh.shape)}, global batch {trainer.global_batch}, "
              f"caps {trainer.caps}")
        if before_first_step is not None:
            before_first_step(trainer)

        gb = trainer.global_batch
        train_idx = np.random.default_rng(2).permutation(
            sampler.csr_topo.node_count
        )
        need = (cfg.steps + cfg.scan_steps) * gb
        train_idx = np.resize(train_idx, need)  # tiny test graphs wrap
        keys = np.asarray(jax.random.split(jax.random.PRNGKey(1), cfg.steps + 1))
        losses, step_s = [], []
        compiles_after_first = None
        for i in range(cfg.steps):
            guard = (steady_guard() if steady_guard is not None and i >= 2
                     else contextlib.nullcontext())
            t0 = time.perf_counter()
            with guard:
                params, opt_state, loss = trainer.step(
                    params, opt_state, train_idx[i * gb:(i + 1) * gb],
                    labels_dev, keys[i],
                )
                losses.append(float(loss))
            step_s.append(time.perf_counter() - t0)
            if i == 0:
                compiles_after_first = meter.compiles
        check(meter.compiles == compiles_after_first,
              "step() compiled once: no compilation after the first step")
        check(np.isfinite(losses).all(), f"{cfg.steps} step losses finite")
        first, last = np.mean(losses[:5]), np.mean(losses[-5:])
        check(last < first, f"loss fell: mean first five {first:.4f} -> "
              f"mean last five {last:.4f}")
        print("losses: " + " ".join(f"{v:.4f}" for v in losses))
        print(f"first step {step_s[0]:.2f}s (compile included), steady step "
              f"median {np.median(step_s[2:]) * 1e3:.1f} ms")

        seed_mat = trainer.pack_epoch(train_idx[cfg.steps * gb:], seed=0)
        t0 = time.perf_counter()
        params, opt_state, scan_losses = trainer.epoch_scan(
            params, opt_state, seed_mat, labels_dev, keys[-1]
        )
        scan_losses = np.asarray(scan_losses)
        check(scan_losses.shape == (cfg.scan_steps,)
              and np.isfinite(scan_losses).all(),
              f"epoch_scan: {cfg.scan_steps} finite losses")
        print("epoch_scan losses: "
              + " ".join(f"{v:.4f}" for v in scan_losses)
              + f" ({time.perf_counter() - t0:.1f}s with compile)")
    return trainer


def stage_kernels(cfg: Config, sampler, out, meter: CompileMeter):
    """The fused Pallas sampler at the shapes the stages above used,
    against its XLA reference."""
    import jax
    import jax.numpy as jnp

    from quiver_tpu.ops.pallas.fused import DEFAULT_WINDOW, fused_sample_layer
    from quiver_tpu.ops.sample import sample_layer

    with Stage("kernels", meter) as stage:
        topo, dev = sampler.csr_topo, sampler.topo
        n_id = np.asarray(out.n_id)
        degree = topo.degree
        widths = (cfg.batch,) + tuple(sampler._frontier_caps[:-1])
        stage.setup_done()
        for hop, (width, k) in enumerate(zip(widths, cfg.fanout)):
            seeds_np = n_id[:width]
            num = int((seeds_np >= 0).sum())
            seeds = jnp.asarray(seeds_np)
            key = jax.random.PRNGKey(hop)
            want = sample_layer(dev, seeds, jnp.int32(num), k, key)
            got = fused_sample_layer(dev, seeds, jnp.int32(num), k, key,
                                     interpret=cfg.interpret)
            want_nbr, got_nbr = np.asarray(want[0]), np.asarray(got[0])
            fits = np.where(
                seeds_np >= 0, degree[np.clip(seeds_np, 0, None)], 0
            ) <= DEFAULT_WINDOW
            check(np.array_equal(got_nbr[fits], want_nbr[fits])
                  and np.array_equal(np.asarray(got[1]), np.asarray(want[1])),
                  f"fused_sample_layer S={width} k={k} window={DEFAULT_WINDOW}: "
                  f"bitwise == sample_layer on the {int(fits.sum())} rows "
                  "within the window")
            bad = 0
            for r in np.nonzero(~fits)[0]:
                picked = got_nbr[r][got_nbr[r] >= 0]
                bad += int(len(picked) != k or not np.isin(
                    picked, csr_row(topo, seeds_np[r])).all())
            check(bad == 0, f"  and its {int((~fits).sum())} rows over the "
                  "window sample CSR neighbours")


def stage_multichip(cfg: Config, topo, feat, labels, sampler,
                    meter: CompileMeter):
    """The trainer stage over a (data, feature=2) mesh with a sharded hot
    tier and every device a seed-block worker."""
    import jax

    from quiver_tpu import ShardedFeature
    from quiver_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(feature=2)
    feature = ShardedFeature(
        mesh, device_cache_size=hot_budget(cfg, topo.node_count, shards=2),
        csr_topo=topo,
    ).from_cpu_tensor(feat)

    def spans_the_mesh(trainer):
        leaves = jax.tree_util.tree_leaves(
            (trainer.topo, trainer._feature_parts())
        )
        check(leaves and all(
            len(a.sharding.device_set) == mesh.size for a in leaves
        ), f"all {len(leaves)} table and CSR operands span the "
           f"{mesh.size}-device mesh before the first step")

    trainer = stage_trainer(
        cfg, "trainer, sharded feature", mesh, sampler, feature, labels,
        meter, seed_sharding="all", before_first_step=spans_the_mesh,
        steady_guard=lambda: jax.transfer_guard_device_to_device("disallow"),
    )
    print("  ok: no implicit device-to-device transfer in steady-state steps")
    # `trainer` keeps its placements alive while this is read; the first
    # trainer's are garbage by now
    gc.collect()
    stats = [d.memory_stats() for d in mesh.devices.flat]
    if stats[0] is None:  # the CPU backend keeps no such statistics
        print("bytes_in_use: not reported by this backend, balance not checked")
        return
    in_use = [s["bytes_in_use"] for s in stats]
    print("bytes_in_use per device: "
          + " ".join(f"{b / 2**20:.0f}M" for b in in_use))
    # not 1x: the first device also holds the sampler's own copy of the
    # topology and every one-device executable of the earlier stages. With
    # the tables left on the first device the others would hold a tenth of it
    check(max(in_use) <= 3 * min(in_use),
          "device memory balanced: max bytes_in_use within 3x of min")


def one_device_stages(cfg: Config, topo, feat, labels, meter: CompileMeter):
    """Per-call, trainer and kernel stages; only the sampler outlives them,
    so the feature store and the batch they placed on the first device are
    gone before the multi-device stage measures memory."""
    from quiver_tpu.parallel.mesh import make_mesh

    sampler, feature, out = stage_per_call(cfg, topo, feat, meter)
    stage_trainer(cfg, "trainer", make_mesh(), sampler, feature, labels, meter)
    stage_kernels(cfg, sampler, out, meter)
    return sampler


def main() -> int:
    dev = require_tpu()

    import jax

    import quiver_tpu.native
    from quiver_tpu.utils.backend import enable_compile_cache

    t_all = time.perf_counter()
    cache_dir = enable_compile_cache()
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"jax {jax.__version__}  platform={dev.platform}  "
          f"device_kind={dev.device_kind!r}  devices={device['count']}")
    print(f"compile cache: {cache_dir}; native runtime available: "
          f"{quiver_tpu.native.available}")
    cfg = Config()
    print(f"config: {cfg}")
    meter = CompileMeter()

    with Stage("set-up", meter) as stage:
        topo, feat, labels = build_inputs(cfg)
        stage.setup_done()
    sampler = one_device_stages(cfg, topo, feat, labels, meter)
    if device["count"] > 1:
        stage_multichip(cfg, topo, feat, labels, sampler, meter)

    print(f"\nall stages passed in {time.perf_counter() - t_all:.1f}s "
          f"({meter.compiles} compilations, {meter.compile_s:.1f}s compiling, "
          f"{meter.cache_hits} cache hits)")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
