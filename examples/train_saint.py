"""GraphSAINT subgraph training — end-to-end.

The reference *planned* GraphSAINT (``qv.saint_subgraph`` survives only as a
commented-out test block, SURVEY §2.5); quiver-tpu ships it trainable: a
SAINT sampler draws one induced subgraph per step (ONE compiled program —
draw, dedup, induction all on device, sampling/saint.py), a GraphSAGE model
runs full message passing over the subgraph (the same padded-Adj layers the
neighbor-sampling path uses — a square (C, C) Adj applied at every layer),
and the GraphSAINT loss normalization (``estimate_saint_norm``) unbiases the
node-sampling distribution per Zeng et al. eq. 2.

Acceptance: on the planted-SBM dataset the SAINT-trained model must clear
feature-only Bayes, like the neighbor-sampling path (tests/test_datasets.py).

    python -m examples.train_saint --dataset planted:8000:6 --steps 300
    python -m examples.train_saint --sampler rw --roots 256 --walk-length 3
"""

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp
import optax

from quiver_tpu import (
    Adj,
    SAINTEdgeSampler,
    SAINTNodeSampler,
    SAINTRandomWalkSampler,
)
from quiver_tpu.datasets import load_dataset
from quiver_tpu.models.sage import GraphSAGE
from quiver_tpu.sampling.saint import estimate_saint_norm
from quiver_tpu.utils.backend import enable_compile_cache


def subgraph_adjs(sub, num_layers: int):
    """Full subgraph message passing: the same square (C, C) Adj at every
    layer (every layer sees all induced edges — GraphSAINT's GCN-style
    regime, vs the neighbor sampler's shrinking bipartite frontiers)."""
    C = sub.node_id.shape[0]
    # edge_index rows are (src_local, dst_local); layers' models expect
    # [source, target] with -1 invalid lanes — already the case
    adj = Adj(sub.edge_index, None, (C, C))
    return [adj] * num_layers


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--dataset", default="planted:8000:6")
    p.add_argument("--root", default=None)
    p.add_argument("--sampler", default="node", choices=["node", "edge", "rw"])
    p.add_argument("--budget", type=int, default=1024)
    p.add_argument("--roots", type=int, default=256)
    p.add_argument("--walk-length", type=int, default=3)
    p.add_argument("--hidden", type=int, default=64)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--steps", type=int, default=300)
    p.add_argument("--lr", type=float, default=5e-3)
    p.add_argument("--norm-iters", type=int, default=30,
                   help="pre-sampling draws for the loss-normalization "
                   "estimate (0 disables normalization)")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    ds = load_dataset(args.dataset, root=args.root)
    topo, n = ds.topo, ds.node_count
    print(f"{ds.name}: {n} nodes, {topo.edge_count} edges, "
          f"{ds.num_classes} classes")

    if args.sampler == "node":
        sampler = SAINTNodeSampler(topo, budget=args.budget, seed=args.seed)
    elif args.sampler == "edge":
        sampler = SAINTEdgeSampler(topo, budget=args.budget, seed=args.seed)
    else:
        sampler = SAINTRandomWalkSampler(
            topo, roots=args.roots, walk_length=args.walk_length,
            seed=args.seed,
        )

    # GraphSAINT loss normalization: node_norm[v] ~ 1 / P(v in subgraph)
    if args.norm_iters > 0:
        norm, _ = estimate_saint_norm(sampler, num_iters=args.norm_iters)
        # nodes unseen in the pre-sampling draws report norm 0 — default
        # them to 1 so they still train when they DO appear in a subgraph
        norm = np.where(norm > 0, norm, 1.0).astype(np.float32)
        node_norm = jnp.asarray(norm)
    else:
        node_norm = jnp.ones(n, jnp.float32)

    feats_all = jnp.asarray(ds.features)
    labels_all = jnp.asarray(ds.labels)
    train_mask_all = jnp.zeros(n, bool).at[jnp.asarray(ds.train_idx)].set(True)

    model = GraphSAGE(hidden=args.hidden, num_classes=ds.num_classes,
                      num_layers=args.layers)
    tx = optax.adam(args.lr)

    sub0 = sampler.sample()
    adjs0 = subgraph_adjs(sub0, args.layers)
    x0 = feats_all[jnp.clip(sub0.node_id, 0)]
    params = model.init({"params": jax.random.PRNGKey(args.seed)}, x0, adjs0)[
        "params"]
    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state, node_id, edge_index, key):
        ids = jnp.clip(node_id, 0)
        x = feats_all[ids]
        labels = labels_all[ids]
        C = node_id.shape[0]
        adjs = [Adj(edge_index, None, (C, C))] * args.layers
        # loss over TRAIN subgraph nodes, weighted by the SAINT node norm
        w = (
            (node_id >= 0)
            & train_mask_all[ids]
        ).astype(jnp.float32) * node_norm[ids]

        def loss_fn(p):
            logp = model.apply({"params": p}, x, adjs, train=True,
                               rngs={"dropout": key})
            ll = jnp.take_along_axis(
                logp, labels[:, None].astype(jnp.int32), axis=1
            )[:, 0]
            return -(ll * w).sum() / jnp.maximum(w.sum(), 1.0)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    t0 = time.time()
    for i in range(args.steps):
        sub = sampler.sample()
        params, opt_state, loss = step(
            params, opt_state, sub.node_id, sub.edge_index,
            jax.random.PRNGKey(1000 + i),
        )
        if (i + 1) % 50 == 0:
            print(f"Step {i + 1:4d}, Loss: {float(loss):.4f} "
                  f"({time.time() - t0:.1f}s)")

    # test accuracy via full-neighbor layer-wise inference over all nodes
    from quiver_tpu.models.inference import sage_layerwise_inference

    logp = sage_layerwise_inference(model, params, topo, ds.features)
    test_idx = jnp.asarray(ds.test_idx)
    pred = jnp.argmax(logp[test_idx], axis=-1)
    acc = float((pred == labels_all[test_idx]).mean())
    line = f"Test Acc: {acc:.4f}"
    if "feature_bayes_acc" in ds.meta:
        line += f" (feature-only Bayes: {ds.meta['feature_bayes_acc']:.4f})"
    print(line)
    return acc, ds


if __name__ == "__main__":
    enable_compile_cache()
    main()
