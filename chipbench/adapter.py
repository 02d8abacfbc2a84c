"""The system under test, built and driven as a user builds and drives it.

This module and the model files it finds by the configuration's ``model``
key (``models/<name>.py``: the flax module, the weights' tree, the scope of
its ops) are the only ones of the benchmark that import ``quiver_tpu``. It
calls the program's public constructors with the arguments the
configuration and the traffic mix give (explicit ``kernel``, ``dedup`` and
``frontier_caps``: nothing timed chooses the code, no election, no probe
program), hands it the harness's weights in the program's own tree, and
drives ``DistributedTrainer.step``. It computes nothing that is compared.
"""

from __future__ import annotations

import gc

import numpy as np

from . import spec
from .reference.graph import Block

__all__ = ["Program", "enable_compile_cache"]


def enable_compile_cache() -> str:
    """The persistent compilation cache where the program's entry points
    put it (``JAX_COMPILATION_CACHE_DIR`` if set, else a fixed directory
    under the checkout), keeping every program however small or quick to
    compile: the step is one large program among hundreds of small ones,
    and each of those costs every run its compile unless it is kept too.
    These are settings of the harness's process; no program default moves."""
    import jax

    from quiver_tpu.utils.backend import enable_compile_cache as enable

    cache_dir = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


class Program:
    """Topology, sampler, feature store, model and trainer of one cell."""

    def __init__(self, cfg: dict, traffic: dict, data, weights0: list,
                 devices: list):
        import jax
        import optax
        from jax.sharding import NamedSharding, PartitionSpec

        import quiver_tpu
        from quiver_tpu.parallel.mesh import make_mesh
        from quiver_tpu.parallel.trainer import DistributedTrainer

        if traffic["entry"] != "step":
            raise ValueError(f"no driver for entry {traffic['entry']!r}")
        # scope names are op metadata only: on in every run, so that the
        # traced and the untraced run load the same cached executables and
        # those carry the names the trace readers look for
        quiver_tpu.enable_trace()
        self.cfg, self.layers = cfg, int(cfg["layers"])
        mesh_shape = traffic["mesh"]
        self.mesh = make_mesh(
            data=int(mesh_shape["data"]), feature=int(mesh_shape["feature"]),
            devices=devices,
        )
        topo = quiver_tpu.CSRTopo(indptr=data.indptr, indices=data.indices)
        self.sampler = quiver_tpu.GraphSageSampler(
            topo, list(cfg["fanout"]),
            frontier_caps=list(cfg["frontier_caps"]),
            kernel=cfg["kernel"], dedup=cfg["dedup"],
        )
        nodes, width = data.features.shape
        placement = traffic["feature"]
        shards = int(mesh_shape["feature"]) if placement["store"] == "sharded" else 1
        rows = -(-int(round(float(placement["cache_ratio"]) * nodes)) // shards)
        budget = rows * width * data.features.dtype.itemsize
        if placement["store"] == "sharded":
            store = quiver_tpu.ShardedFeature(
                self.mesh, device_cache_size=budget, csr_topo=topo,
                kernel=cfg["kernel"],
            )
        elif placement["store"] == "plain":
            store = quiver_tpu.Feature(
                device_cache_size=budget, csr_topo=topo, kernel=cfg["kernel"],
            )
        else:
            raise ValueError(f"no feature store {placement['store']!r}")
        self.feature = store.from_cpu_tensor(data.features)
        self.model_file = spec.load_model(cfg["model"], "models")
        model = self.model_file.build(cfg)
        opt = cfg["optimizer"]
        if opt["name"] != "adam":
            raise ValueError(f"no optimizer {opt['name']!r}")
        tx = optax.adam(opt["lr"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"])
        self.trainer = DistributedTrainer(
            self.mesh, self.sampler, self.feature, model, tx,
            local_batch=int(cfg["batch"]),
            seed_sharding=traffic["seed_sharding"],
        )
        replicated = NamedSharding(self.mesh, PartitionSpec())
        params = self.model_file.to_program_tree(weights0)
        # placed as step() returns them, so that step compiles once
        self.params, self.opt_state = jax.device_put(
            (params, tx.init(params)), replicated)
        self.labels = jax.device_put(data.labels, replicated)
        self.global_batch = self.trainer.global_batch
        self.workers = self.trainer.workers

    def step(self, seeds: np.ndarray, key: np.ndarray):
        """One training step; returns the loss as a device scalar."""
        self.params, self.opt_state, loss = self.trainer.step(
            self.params, self.opt_state, seeds, self.labels, key)
        return loss

    def tier_hits(self):
        """The last step's rows gathered per tier (device array, no sync)."""
        return self.trainer.last_tier_hits

    def params_host(self) -> list:
        return self.model_file.from_program_tree(self.params, self.layers)

    def first_moment_host(self) -> list:
        """Adam's first moment in the reference's naming."""
        return self.model_file.from_program_tree(self.opt_state[0].mu, self.layers)

    def worker_seeds(self, seeds: np.ndarray, workers: int = None) -> list:
        """The seed block each worker gets of a global batch."""
        return np.array_split(np.asarray(seeds), workers or self.workers)

    def blocks(self, seeds: np.ndarray, key: np.ndarray,
               workers: int = None) -> list:
        """The blocks that ``step(seeds, key)`` trains on, one per worker,
        drawn again outside the step by the sampler's own jit-composable
        entry with the key the step derives for that worker
        (``split(fold_in(key, worker))[0]``). The fused step returns no
        block; that its loss equals the reference's on these is part of what
        the comparison shows. ``workers`` draws the blocks of a mesh of that
        many workers from one chip (``chipbench.readings``)."""
        import jax
        import jax.numpy as jnp

        out = []
        batch = int(self.cfg["batch"])
        for w, part in enumerate(self.worker_seeds(seeds, workers)):
            padded = np.full(batch, -1, np.int32)
            padded[:len(part)] = part
            sample_key = jax.random.split(
                jax.random.fold_in(jnp.asarray(key), w))[0]
            n_id, _, adjs, overflow, _, _ = self.sampler.sample_padded(
                self.sampler.topo, jnp.asarray(padded), jnp.int32(len(part)),
                sample_key)
            layers = []
            for adj in adjs:
                src, dst = np.asarray(adj.edge_index)
                layers.append((src, dst, int(adj.size[1])))
            block = Block(np.asarray(n_id), layers, len(part))
            block.overflow = int(overflow)
            out.append(block)
        return out

    def close(self) -> None:
        """Free the program's device state."""
        for name in ("trainer", "sampler", "feature", "params", "opt_state",
                     "labels"):
            setattr(self, name, None)
        gc.collect()
