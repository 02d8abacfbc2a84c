"""Multi-chip SPMD training — the reference's multi-GPU DDP example, TPU-way.

Parity with torch-quiver examples/multi_gpu/pyg/ogb-products/
dist_sampling_ogb_products_quiver.py, which spawns one process per GPU,
splits train_idx per rank, and allreduces gradients over NCCL. Here the
whole thing is ONE fused XLA program over a (data, feature) mesh
(quiver_tpu.parallel.trainer.DistributedTrainer): per-device seed blocks on
the data axis, the hot feature table sharded on the feature axis (the
NVLink-clique role, served by ICI collectives), gradients pmean'd in-program.

On a single-chip machine, simulate a mesh with virtual CPU devices:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m examples.train_multichip --data-axis 4 --feature-axis 2

On a real slice it uses the chips as-is (e.g. --data-axis 2 --feature-axis 2
on a v5e-4).
"""

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp
import optax

from quiver_tpu import CSRTopo, GraphSageSampler, ShardedFeature
from quiver_tpu.models.sage import GraphSAGE
from quiver_tpu.parallel.mesh import make_mesh
from quiver_tpu.parallel.trainer import DistributedTrainer
from quiver_tpu.utils.backend import enable_compile_cache
from quiver_tpu.utils.graphgen import generate_pareto_graph


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--nodes", type=int, default=100_000)
    p.add_argument("--avg-degree", type=float, default=25.0)
    p.add_argument("--feature-dim", type=int, default=128)
    p.add_argument("--classes", type=int, default=47)
    p.add_argument("--hidden", type=int, default=256)
    p.add_argument("--fanout", type=int, nargs="+", default=[15, 10, 5])
    p.add_argument("--local-batch", type=int, default=256)
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--data-axis", type=int, default=None)
    p.add_argument("--feature-axis", type=int, default=1)
    p.add_argument("--seed-sharding", default="data", choices=["data", "all"],
                   help="'all': every device a data worker; the sharded "
                   "gather owner-routes via all_to_all (recommended when "
                   "feature-axis > 1 — removes the redundant-sampling cost)")
    p.add_argument("--routed-alpha", type=float, default=2.0,
                   help="capped-bucket factor for the routed gather "
                   "(seed-sharding=all): each all_to_all hop moves "
                   "~alpha*L lanes instead of F*L; overflow is "
                   "fallback-served and reported. 0 = uncapped")
    p.add_argument("--replicate-budget", default="0", metavar="BYTES",
                   help="per-chip byte budget ('4M', '0.5G') for the L0 "
                   "replicated super-hot tier: the top-degree rows live "
                   "in every chip's HBM and are gathered with zero "
                   "interconnect lanes; per-tier hit counts are reported "
                   "after training. 0 = two-tier store")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()

    n_dev = len(jax.devices())
    mesh = make_mesh(data=args.data_axis, feature=args.feature_axis)
    print(f"mesh over {n_dev} devices: {dict(mesh.shape)}")

    rng = np.random.default_rng(args.seed)
    topo = CSRTopo(edge_index=generate_pareto_graph(args.nodes, args.avg_degree,
                                                    seed=args.seed))
    n = topo.node_count
    feat = rng.normal(size=(n, args.feature_dim)).astype(np.float32)
    # fused trainer needs the table fully device-resident: budget = all rows,
    # sharded over the feature axis (the clique-partitioned hot cache)
    feature = ShardedFeature(
        mesh, device_cache_size=n * args.feature_dim * 4, csr_topo=topo,
        replicate_budget=args.replicate_budget,
    ).from_cpu_tensor(feat)
    del feat
    labels = jnp.asarray(rng.integers(0, args.classes, n).astype(np.int32))

    sampler = GraphSageSampler(topo, args.fanout, seed=args.seed)
    model = GraphSAGE(hidden=args.hidden, num_classes=args.classes,
                      num_layers=len(args.fanout))
    trainer = DistributedTrainer(mesh, sampler, feature, model,
                                 optax.adam(1e-3), local_batch=args.local_batch,
                                 seed_sharding=args.seed_sharding,
                                 routed_alpha=args.routed_alpha or None)
    params, opt_state = trainer.init(jax.random.PRNGKey(args.seed))

    # global batch split over the data axis = train_idx.split(world)[rank]
    global_batch = trainer.global_batch
    t0 = time.time()
    for i in range(args.steps):
        seeds = rng.integers(0, n, global_batch)
        params, opt_state, loss = trainer.step(
            params, opt_state, seeds, labels, jax.random.PRNGKey(1000 + i))
        if i == 0:
            jax.block_until_ready(loss)
            print(f"step 0 (compile): {time.time()-t0:.1f}s")
            t0 = time.time()
        elif i % 10 == 0:
            print(f"step {i}: loss {float(loss):.4f}")
    jax.block_until_ready(loss)
    per_step = (time.time() - t0) / max(args.steps - 1, 1)
    print(
        f"done: {args.steps} steps, global batch {global_batch} "
        f"({per_step*1e3:.1f} ms/step, {global_batch/per_step:,.0f} seeds/s)"
    )
    if args.seed_sharding == "all" and trainer.last_routed_overflow is not None:
        print(f"routed overflow (last step): "
              f"{int(trainer.last_routed_overflow)} lanes fallback-served "
              f"(grow --routed-alpha if persistent)")
    if trainer.last_tier_hits is not None:
        h = np.asarray(trainer.last_tier_hits)
        tot = max(int(h.sum()), 1)
        print(f"feature tier hits (last step): L0 replicated {h[0]} "
              f"({100 * h[0] / tot:.1f}%, zero-comm), sharded {h[1]} "
              f"({100 * h[1] / tot:.1f}%), cold {h[2]} "
              f"({100 * h[2] / tot:.1f}%)")


if __name__ == "__main__":
    enable_compile_cache()
    main()
