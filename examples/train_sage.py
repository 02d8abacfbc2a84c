"""Single-chip GraphSAGE training — the framework's acceptance example.

Parity with the reference's canonical example (torch-quiver
examples/pyg/reddit_quiver.py): build topology, a [25,10] neighbor sampler,
a 20%-cached feature store, a 2-layer SAGE model, train with the
"Epoch xx, Loss ..., Approx. Train Acc ..." progress line (README.md:76-78
success criterion), then report held-out test accuracy.

Datasets (quiver_tpu.datasets):
    --dataset synthetic            random power-law graph, random labels
                                   (throughput exercise; accuracy ~1/C)
    --dataset planted[:n[:C]]      stochastic-block-model acceptance graph —
                                   test accuracy must clear feature-only
                                   Bayes by a wide margin
    --dataset reddit --root DIR    PyG Reddit npz layout (reference's
                                   reddit_quiver.py workload; expect ~0.93+)
    --dataset ogbn-products --root DIR   OGB raw CSV layout

    python -m examples.train_sage --dataset planted:20000 --epochs 4
    python -m examples.train_sage --dataset reddit --root /data/Reddit/raw
"""

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp
import optax

from quiver_tpu import CSRTopo, Feature, GraphSageSampler
from quiver_tpu.datasets import GraphDataset, load_dataset
from quiver_tpu.models.sage import GraphSAGE
from quiver_tpu.parallel.train import make_eval_step, make_train_step
from quiver_tpu.utils.backend import enable_compile_cache
from quiver_tpu.utils.graphgen import generate_pareto_graph


def synthetic_dataset(args) -> GraphDataset:
    rng = np.random.default_rng(args.seed)
    topo = CSRTopo(
        edge_index=generate_pareto_graph(args.nodes, args.avg_degree, seed=args.seed)
    )
    n = topo.node_count
    labels = rng.integers(0, args.classes, n).astype(np.int32)
    feat = rng.normal(size=(n, args.feature_dim)).astype(np.float32)
    perm = rng.permutation(n)
    return GraphDataset(
        name="synthetic", topo=topo, features=feat, labels=labels,
        train_idx=perm[: n // 10], val_idx=perm[n // 10 : n // 5],
        test_idx=perm[n // 5 : n // 2], num_classes=args.classes,
    )


def evaluate_layerwise(model, params, topo, feature, labels_all, idx):
    """Full-neighbor layer-wise inference over the whole graph — the
    reference's ``model.inference`` evaluation path (reddit_quiver.py:68-92),
    rebuilt as chunked segment aggregation (models/inference.py). Features
    are streamed back out of the tiered store in blocks, so the cold tier is
    exercised too."""
    from quiver_tpu.models.inference import sage_layerwise_inference

    n, _ = feature.shape
    block = 65536
    # one concatenate = one full copy at a transient 2x footprint; eager
    # .at[].set would copy the whole array once per block (O(N^2) traffic)
    x_all = jnp.concatenate([
        feature[jnp.arange(lo, min(lo + block, n))]
        for lo in range(0, n, block)
    ])
    logp = sage_layerwise_inference(model, params, topo, x_all)
    idx = jnp.asarray(idx)
    pred = jnp.argmax(logp[idx], axis=-1)
    return float((pred == labels_all[idx]).mean())


def evaluate(sampler, feature, eval_step, params, labels_all, idx, batch):
    """Batched accuracy over a node-id split (reference test() loop parity)."""
    correct = total = 0
    for lo in range(0, len(idx), batch):
        seeds = idx[lo : lo + batch]
        out = sampler.sample(seeds)
        x = feature[out.n_id]
        # logits span the padded seed capacity; lanes past batch_size hold
        # frontier nodes (not -1), so mask by the true batch size
        cap = out.adjs[-1].size[1]
        seed_ids = out.n_id[:cap]
        labels = labels_all[jnp.clip(seed_ids, 0)]
        mask = (jnp.arange(cap) < out.batch_size) & (seed_ids >= 0)
        c, t = eval_step(params, x, out.adjs, labels, mask)
        correct += int(c)
        total += int(t)
    return correct / max(total, 1)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--dataset", default="synthetic",
                   help="synthetic | planted[:n[:C]] | reddit | ogbn-* ")
    p.add_argument("--root", default=None, help="on-disk dataset directory")
    p.add_argument("--nodes", type=int, default=232_965)  # Reddit scale
    p.add_argument("--avg-degree", type=float, default=100.0)
    p.add_argument("--feature-dim", type=int, default=602)  # Reddit: 602
    p.add_argument("--classes", type=int, default=41)  # Reddit: 41
    p.add_argument("--hidden", type=int, default=256)
    p.add_argument("--fanout", type=int, nargs="+", default=[25, 10])
    p.add_argument("--batch", type=int, default=1024)
    p.add_argument("--epochs", type=int, default=5)
    p.add_argument("--cache-ratio", type=float, default=0.2)
    p.add_argument("--lr", type=float, default=0.01)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument(
        "--bf16", action="store_true",
        help="bfloat16 feature storage + mixed-precision model compute",
    )
    p.add_argument(
        "--save-dir", default=None,
        help="checkpoint directory (atomic manifest Checkpointer): "
        "training resumes "
        "from the latest checkpoint there and saves each epoch — the "
        "checkpoint/resume capability the reference has none of",
    )
    p.add_argument(
        "--eval", default="sampled", choices=["sampled", "layerwise"],
        help="test-time evaluation: batched sampled fanout (fast) or "
        "full-neighbor layer-wise inference over all edges (the "
        "reference's model.inference path)",
    )
    args = p.parse_args(argv)

    if args.dataset == "synthetic":
        ds = synthetic_dataset(args)
    else:
        ds = load_dataset(args.dataset, root=args.root)
    topo, n = ds.topo, ds.node_count
    print(f"{ds.name}: {n} nodes, {topo.edge_count} edges, "
          f"{ds.feature_dim} features, {ds.num_classes} classes, "
          f"{len(ds.train_idx)} train / {len(ds.test_idx)} test")

    # quiver.Feature equivalent: degree-ordered 20% HBM cache, cold rows on host
    budget = int(args.cache_ratio * n) * ds.feature_dim * 4
    feature = Feature(
        device_cache_size=budget, csr_topo=topo,
        dtype="bfloat16" if args.bf16 else None,
    ).from_cpu_tensor(ds.features)
    # drop the source array: the tiered store holds the only copy now
    # (for Reddit/products scale this halves peak host memory)
    ds = ds._replace(features=None)
    labels_all = jnp.asarray(ds.labels)
    train_idx = np.asarray(ds.train_idx)

    sampler = GraphSageSampler(topo, args.fanout, seed_capacity=args.batch,
                               seed=args.seed, frontier_caps="auto")
    model = GraphSAGE(hidden=args.hidden, num_classes=ds.num_classes,
                      num_layers=len(args.fanout),
                      dtype="bfloat16" if args.bf16 else None)
    tx = optax.adam(args.lr)
    train_step = jax.jit(make_train_step(model, tx))
    eval_step = jax.jit(make_eval_step(model))

    out = sampler.sample(train_idx[: args.batch])
    x = feature[out.n_id]
    params = model.init({"params": jax.random.PRNGKey(args.seed)}, x, out.adjs)[
        "params"]
    opt_state = tx.init(params)

    ckpt = start_epoch = None
    if args.save_dir:
        from quiver_tpu.utils.checkpoint import Checkpointer

        ckpt = Checkpointer(args.save_dir)
        start_epoch = ckpt.latest_step()
        if start_epoch is not None:
            state = ckpt.restore(template={
                "params": params, "opt_state": opt_state,
            })
            params, opt_state = state["params"], state["opt_state"]
            print(f"resumed from {args.save_dir} at epoch {start_epoch}")

    step_i = 0
    for epoch in range(1, args.epochs + 1):
        if start_epoch is not None and epoch <= start_epoch:
            continue  # already trained in a previous run
        t0 = time.time()
        order = np.random.default_rng(epoch).permutation(train_idx)
        losses, correct, total = [], 0, 0
        for lo in range(0, len(order) - args.batch + 1, args.batch):
            seeds = order[lo : lo + args.batch]
            out = sampler.sample(seeds)
            x = feature[out.n_id]
            seed_ids = out.n_id[: args.batch]
            labels = labels_all[jnp.clip(seed_ids, 0)]
            mask = seed_ids >= 0
            params, opt_state, loss = train_step(
                params, opt_state, x, out.adjs, labels, mask,
                jax.random.PRNGKey(step_i))
            losses.append(float(loss))
            c, t = eval_step(params, x, out.adjs, labels, mask)
            correct += int(c)
            total += int(t)
            step_i += 1
        print(
            f"Epoch {epoch:02d}, Loss: {np.mean(losses):.4f}, "
            f"Approx. Train Acc: {correct / max(total, 1):.4f} "
            f"({time.time() - t0:.1f}s)"
        )
        if ckpt is not None:
            ckpt.save(epoch, {"params": params, "opt_state": opt_state})

    if ckpt is not None:
        ckpt.wait_until_finished()

    if args.eval == "layerwise":
        test_acc = evaluate_layerwise(
            model, params, topo, feature, labels_all, np.asarray(ds.test_idx)
        )
    else:
        test_acc = evaluate(
            sampler, feature, eval_step, params, labels_all,
            np.asarray(ds.test_idx), args.batch,
        )
    line = f"Test Acc: {test_acc:.4f}"
    if "feature_bayes_acc" in ds.meta:
        line += f" (feature-only Bayes: {ds.meta['feature_bayes_acc']:.4f})"
    print(line)
    return test_acc, ds


if __name__ == "__main__":
    enable_compile_cache()
    main()
