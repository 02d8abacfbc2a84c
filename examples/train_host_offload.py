"""Beyond-HBM training: host-resident topology + cold-tier features.

The papers100M-scale configuration (reference benchmarks/ogbn-papers100M):
graphs and feature tables too large for device memory. The reference's answer
is UVA — GPU kernels dereference pinned host memory over PCIe. The TPU
answer here:

* ``mode="HOST"`` sampler — the big ``indices`` array stays in pinned host
  memory; sampling gathers stage through host compute (only index blocks and
  results cross the PCIe/DMA boundary).
* A small HBM hot tier + pinned-host cold tier for features
  (``device_cache_size`` budget), degree-ordered so the power-law head hits
  HBM.
* ``Prefetcher`` double-buffering so batch i+1's host-side staging overlaps
  batch i's device compute — the latency-hiding role UVA's in-kernel loads
  played.

    python -m examples.train_host_offload                    # ~1M-node demo
    python -m examples.train_host_offload --nodes 50000 --steps 20   # smoke
"""

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp
import optax

from quiver_tpu import Batch, CSRTopo, Feature, GraphSageSampler, Prefetcher
from quiver_tpu.models.sage import GraphSAGE
from quiver_tpu.parallel.train import make_train_step
from quiver_tpu.utils.backend import enable_compile_cache
from quiver_tpu.utils.graphgen import generate_pareto_graph


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--nodes", type=int, default=1_000_000)
    p.add_argument("--avg-degree", type=float, default=15.0)
    p.add_argument("--feature-dim", type=int, default=128)
    p.add_argument("--classes", type=int, default=172)  # papers100M: 172
    p.add_argument("--hidden", type=int, default=256)
    p.add_argument("--fanout", type=int, nargs="+", default=[12, 8])
    p.add_argument("--batch", type=int, default=1024)
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--cache-ratio", type=float, default=0.1)
    p.add_argument("--prefetch-depth", type=int, default=2)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()

    rng = np.random.default_rng(args.seed)
    print(f"building synthetic graph ({args.nodes} nodes)...")
    topo = CSRTopo(edge_index=generate_pareto_graph(args.nodes, args.avg_degree,
                                                    seed=args.seed))
    n = topo.node_count

    # HOST mode: topology beyond HBM (reference UVA, sage_sampler.py:25-27)
    sampler = GraphSageSampler(topo, args.fanout, mode="HOST",
                               seed_capacity=args.batch, seed=args.seed,
                               frontier_caps="auto")
    feat = rng.normal(size=(n, args.feature_dim)).astype(np.float32)
    budget = int(args.cache_ratio * n) * args.feature_dim * 4
    feature = Feature(device_cache_size=budget, csr_topo=topo).from_cpu_tensor(feat)
    del feat
    labels_all = jnp.asarray(rng.integers(0, args.classes, n).astype(np.int32))

    model = GraphSAGE(hidden=args.hidden, num_classes=args.classes,
                      num_layers=len(args.fanout))
    tx = optax.adam(1e-3)
    step = jax.jit(make_train_step(model, tx))

    out0 = sampler.sample(rng.integers(0, n, args.batch))
    x0 = feature[out0.n_id]
    params = model.init({"params": jax.random.PRNGKey(0)}, x0, out0.adjs)["params"]
    opt_state = tx.init(params)

    def with_labels(seeds, out, x):
        sid = out.n_id[: args.batch]
        return Batch(seeds, out, (x, labels_all[jnp.clip(sid, 0)], sid >= 0))

    stream = (rng.integers(0, n, args.batch) for _ in range(args.steps))
    prefetcher = Prefetcher(sampler, feature, depth=args.prefetch_depth,
                            transform=with_labels)

    t0 = time.time()
    loss = None
    for i, b in enumerate(prefetcher.run(stream)):
        x, labels, mask = b.x
        params, opt_state, loss = step(params, opt_state, x, b.out.adjs,
                                       labels, mask, jax.random.PRNGKey(i))
        if i == 0:
            jax.block_until_ready(loss)
            print(f"step 0 (compile): {time.time()-t0:.1f}s")
            t0 = time.time()
        elif i % 20 == 0:
            print(f"step {i}: loss {float(loss):.4f}")
    jax.block_until_ready(loss)
    per_step = (time.time() - t0) / max(args.steps - 1, 1)
    print(
        f"done: {args.steps} steps at {per_step*1e3:.1f} ms/step "
        f"(cache {feature.cache_ratio:.0%} hot, topology host-resident)"
    )


if __name__ == "__main__":
    enable_compile_cache()
    main()
