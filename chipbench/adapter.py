"""The system under test, built and driven as a user builds and drives it.

This module, the assembly file it finds by the configuration's ``assembly``
key (``assemblies/<name>.py``: the topology, the sampler and the feature
store, by the program's public constructors) and the model files it finds by
the ``model`` key (``models/<name>.py``: the flax module, the weights' tree,
the scope of its ops) are the only ones of the benchmark that import
``quiver_tpu``. It builds the mesh, the model, the optimizer and
``DistributedTrainer`` over the assembly's parts, hands the trainer the
harness's weights in the program's own tree, and drives
``DistributedTrainer.step``. It computes nothing that is compared.
"""

from __future__ import annotations

import gc

import numpy as np

from . import spec

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
    """The assembly's parts, the model and the trainer of one cell."""

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
        # host annotations only (the `quiver.*` events of `step`'s phases):
        # the ops' scope names are in the program whether this is called or
        # not. On in every run, so that the traced and the untraced run do
        # the same host work
        quiver_tpu.enable_trace()
        self.cfg, self.layers = cfg, int(cfg["layers"])
        mesh_shape = traffic["mesh"]
        self.mesh = make_mesh(
            data=int(mesh_shape["data"]), feature=int(mesh_shape["feature"]),
            devices=devices,
        )
        self.assembly = spec.load_assembly(cfg["assembly"])
        self.parts = self.assembly.build(cfg, traffic, data, self.mesh)
        self.model_file = spec.load_model(cfg["model"], "models")
        model = self.model_file.build(cfg)
        opt = cfg["optimizer"]
        if opt["name"] != "adam":
            raise ValueError(f"no optimizer {opt['name']!r}")
        tx = optax.adam(opt["lr"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"])
        self.trainer = DistributedTrainer(
            self.mesh, self.parts.sampler, self.parts.feature, model, tx,
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

    def worker_seeds(self, seeds: np.ndarray) -> list:
        """The seed block each worker gets of a global batch."""
        return np.array_split(np.asarray(seeds), self.workers)

    def blocks(self, seeds: np.ndarray, key: np.ndarray,
               workers: int = None) -> list:
        """The blocks that ``step(seeds, key)`` trains on, one per worker,
        drawn again outside the step by the assembly. The fused step returns
        no block; that its loss equals the reference's on these is part of
        what the comparison shows. ``workers`` draws the blocks of a mesh of
        that many workers from one chip (``chipbench.readings``)."""
        return self.assembly.blocks(self.parts, self.cfg, seeds, key,
                                    workers or self.workers)

    def close(self) -> None:
        """Free the program's device state."""
        for name in ("trainer", "parts", "params", "opt_state", "labels"):
            setattr(self, name, None)
        gc.collect()
