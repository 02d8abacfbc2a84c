"""Heterogeneous R-GCN training on a MAG-style schema.

Capability the reference only gestures at (its GraphSAINT/hetero tests are
rotted stubs, SURVEY §2.5): typed nodes and relations, per-relation neighbor
sampling, relational message passing. Schema mirrors OGB-MAG:
paper-cites-paper, author-writes-paper, inst-employs-author; the task is
paper venue classification.

    python -m examples.train_rgcn_hetero                 # small synthetic MAG
    python -m examples.train_rgcn_hetero --papers 2000   # smoke
"""

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp
import optax

from quiver_tpu import HeteroCSRTopo, HeteroFeature, HeteroGraphSampler
from quiver_tpu.models.rgcn import RGCN
from quiver_tpu.utils.backend import enable_compile_cache


def synthetic_mag(rng, n_paper, n_author, n_inst, deg=12):
    edges = {
        ("paper", "cites", "paper"): np.stack([
            rng.integers(0, n_paper, n_paper * deg),
            rng.integers(0, n_paper, n_paper * deg),
        ]),
        ("author", "writes", "paper"): np.stack([
            rng.integers(0, n_author, n_paper * 3),
            rng.integers(0, n_paper, n_paper * 3),
        ]),
        ("inst", "employs", "author"): np.stack([
            rng.integers(0, n_inst, n_author * 2),
            rng.integers(0, n_author, n_author * 2),
        ]),
    }
    num_nodes = {"paper": n_paper, "author": n_author, "inst": n_inst}
    return HeteroCSRTopo(num_nodes, edges), num_nodes


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--papers", type=int, default=20_000)
    p.add_argument("--feature-dim", type=int, default=128)
    p.add_argument("--classes", type=int, default=16)
    p.add_argument("--hidden", type=int, default=64)
    p.add_argument("--fanout", type=int, nargs="+", default=[8, 4])
    p.add_argument("--batch", type=int, default=512)
    p.add_argument("--steps", type=int, default=60)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()

    rng = np.random.default_rng(args.seed)
    topo, num_nodes = synthetic_mag(
        rng, args.papers, args.papers // 2, max(args.papers // 40, 4))
    feats = {
        t: rng.normal(size=(c, args.feature_dim)).astype(np.float32)
        for t, c in num_nodes.items()
    }
    feature = HeteroFeature.from_cpu_tensors(feats, device_cache_size="2G")
    labels_all = jnp.asarray(
        rng.integers(0, args.classes, num_nodes["paper"]).astype(np.int32))

    sampler = HeteroGraphSampler(topo, args.fanout, input_type="paper",
                                 seed_capacity=args.batch, seed=args.seed)
    model = RGCN(hidden=args.hidden, num_classes=args.classes,
                 target_type="paper", num_layers=len(args.fanout))

    out = sampler.sample(np.arange(args.batch) % num_nodes["paper"])
    params = model.init({"params": jax.random.PRNGKey(0)}, feature[out.n_id],
                        out.adjs)["params"]
    tx = optax.adam(5e-3)
    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state, x_dict, layers, labels, mask, key):
        def loss_fn(p):
            logp = model.apply({"params": p}, x_dict, layers, train=True,
                               rngs={"dropout": key})
            ll = jnp.take_along_axis(logp, jnp.clip(labels, 0)[:, None], axis=1)[:, 0]
            w = mask.astype(logp.dtype)
            return -(ll * w).sum() / jnp.maximum(w.sum(), 1.0)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    t0 = time.time()
    for i in range(args.steps):
        seeds = rng.integers(0, num_nodes["paper"], args.batch)
        out = sampler.sample(seeds)
        seed_ids = out.n_id["paper"][: args.batch]
        labels = labels_all[jnp.clip(seed_ids, 0)]
        mask = seed_ids >= 0
        params, opt_state, loss = step(
            params, opt_state, feature[out.n_id], out.adjs, labels, mask,
            jax.random.PRNGKey(i))
        if i == 0:
            jax.block_until_ready(loss)
            print(f"step 0 (compile): {time.time()-t0:.1f}s")
        elif i % 20 == 0:
            print(f"step {i}: loss {float(loss):.4f}")
    print(f"final loss {float(loss):.4f}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
