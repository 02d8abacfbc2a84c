"""Compile a cell's step for a described ``v5e:2x2`` without a chip.

    JAX_PLATFORMS=cpu python3 -m chipbench.rehearse --workload products-sage.clique2x2

The TPU's compiler is installed where there is no TPU. This builds the cell's
program as ``adapter.Program`` does, over a mesh of *described* devices, with
every placement on that mesh turned into a shape (nothing can be put on a
device that is not attached), then lowers ``DistributedTrainer``'s step from
those shapes, compiles it and prints ``memory_analysis()``: the program and
its bytes per device are known before chip time is spent, and what the chip's
compiler would refuse, it refuses here. Nothing runs: this says nothing of
results or times and is never reported as a chip run. The graph's values are
zeros; only shapes reach the compiler.
"""

from __future__ import annotations

import argparse
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import numpy as np  # noqa: E402

from . import inputs, spec  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args()

    import jax
    from jax.experimental import topologies

    from .adapter import Program

    bench = spec.load_benchmark()
    cell = spec.cell(bench, args.workload)
    cfg = spec.load_config(cell["config"])
    traffic = spec.load_traffic(cell["traffic"])
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    devices = list(topo.devices)[:cell["chips"]]
    real_put = jax.device_put

    def put(x, device=None, **kw):
        """A placement on the described mesh becomes its shape."""
        devs = getattr(device, "device_set", ())
        if devs and all(d.platform == "tpu" for d in devs):
            return jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(
                    np.shape(a), np.result_type(a), sharding=device), x)
        return real_put(x, device, **kw)

    jax.device_put = put
    try:
        data = spec.load_graph(cfg["graph"]["generator"]).describe(cfg)
        program = Program(cfg, traffic, data, inputs.make_weights(cfg, 0),
                          devices)
        trainer = program.trainer
        feed = inputs.Feed.of(data, program.global_batch, 0)
        captured = {}
        real_step = trainer._step

        def capture(*step_args):
            captured["args"] = step_args
            raise _Captured

        trainer._step = capture
        try:
            program.step(feed.seeds(0), feed.key(0))
        except _Captured:
            pass
    finally:
        jax.device_put = real_put
    compiled = real_step.lower(*captured["args"]).compile()
    print(f"{args.workload}: compiled for {len(devices)} described "
          f"{devices[0].device_kind} device(s); per device:")
    print(compiled.memory_analysis())
    text = compiled.as_text()
    for kind in ("all-to-all", "all-reduce", "all-gather", "collective-permute"):
        print(f"  {kind}: {text.count(' ' + kind + '(') + text.count(kind + '-start(')} in the program")


class _Captured(Exception):
    """The step's operands were captured; nothing is dispatched."""


if __name__ == "__main__":
    main()
