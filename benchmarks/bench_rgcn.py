"""Heterogeneous R-GCN end-to-end epoch benchmark.

No reference baseline exists (torch-quiver's hetero/SAINT support is rotted
stubs, SURVEY §2.5) — this tracks the framework's own hetero capability:
MAG-style schema (paper-cites-paper, author-writes-paper,
inst-employs-author), per-relation sampling with auto frontier caps
(VERDICT r1 item 7: worst-case caps overshoot ~3x on power-law graphs and
R-GCN pays it in every gather/aggregate), relational message passing.
Methodology matches bench_epoch: trimmed-mean iteration time x
iterations-per-epoch.
"""

import time

import numpy as np

from benchmarks.common import (
    apply_smoke,
    base_parser,
    emit,
    init_backend,
    log,
    run_guarded,
    trimmed_mean,
)


def main():
    p = base_parser(__doc__)
    p.add_argument("--feature-dim", type=int, default=128)
    p.add_argument("--classes", type=int, default=16)
    p.add_argument("--hidden", type=int, default=64)
    p.add_argument("--fanout", type=int, nargs="+", default=[8, 4])
    p.add_argument("--caps", default="auto", choices=["auto", "worst"])
    p.add_argument(
        "--topo-sharding",
        default="replicated",
        choices=["replicated", "mesh"],
        dest="topo_sharding",
        help="relation placement: 'replicated' (every chip holds every "
        "relation's full CSR) or 'mesh' — each relation partitioned "
        "across the mesh's feature axis (~1/F topology bytes per chip), "
        "sampled by DistHeteroSampler through ONE shared BucketRoute "
        "plan per (hop, destination type); the record carries the exact "
        "per-edge-type lanes-per-hop comm model + the measured "
        "per-(hop, edge type) fallback overflow",
    )
    p.add_argument(
        "--routed-alpha",
        type=float,
        default=2.0,
        metavar="A",
        dest="routed_alpha",
        help="--topo-sharding mesh: capped-bucket factor — per-destination "
        "bucket capacity ceil(A*S_t/F) per (hop, dst type); 0 = uncapped "
        "full-length buckets. Overflow lanes are fallback-served (exact) "
        "and counted per (hop, edge type)",
    )
    p.add_argument(
        "--weighted",
        action="store_true",
        help="attach per-edge weights to every relation and draw "
        "inverse-CDF weighted samples (mesh lane: the owner searches its "
        "routed prefix-weight segment; +F*cap f32 lanes per relation "
        "per hop in the comm model)",
    )
    p.add_argument(
        "--stream", type=int, default=0, metavar="N",
        help="also measure N training steps as ONE compiled program "
        "(lax.scan: hetero sample -> tiered gather -> R-GCN fwd/bwd -> "
        "update, params in carry, one loss readback) — the fused-epoch "
        "dispatch that sidesteps per-call host round-trips",
    )
    p.set_defaults(nodes=200_000, batch=512, iters=30, warmup=3)
    args = p.parse_args()
    run_guarded(lambda: _body(args), args)


def _body(args):
    init_backend(smoke=args.smoke)
    apply_smoke(args)

    import jax
    import jax.numpy as jnp
    import optax

    from quiver_tpu import HeteroCSRTopo, HeteroFeature, HeteroGraphSampler
    from quiver_tpu.models.rgcn import RGCN
    from quiver_tpu.utils.graphgen import generate_pareto_graph

    n_paper = args.nodes
    n_author = n_paper // 2
    n_inst = max(n_paper // 40, 4)
    rng = np.random.default_rng(args.seed)
    t0 = time.time()
    topo = HeteroCSRTopo(
        {"paper": n_paper, "author": n_author, "inst": n_inst},
        {
            ("paper", "cites", "paper"): generate_pareto_graph(
                n_paper, 10.0, seed=args.seed
            ),
            ("author", "writes", "paper"): np.stack([
                rng.integers(0, n_author, n_paper * 3),
                rng.integers(0, n_paper, n_paper * 3),
            ]),
            ("inst", "employs", "author"): np.stack([
                rng.integers(0, n_inst, n_author * 2),
                rng.integers(0, n_author, n_author * 2),
            ]),
        },
    )
    log(f"hetero graph: {n_paper}+{n_author}+{n_inst} nodes "
        f"({time.time() - t0:.1f}s build)")
    if args.weighted:
        wrng = np.random.default_rng(args.seed + 5)
        for et in topo.relations:
            topo.set_edge_weight(
                et, np.exp(wrng.normal(size=topo.relations[et].edge_count))
            )

    feats = {
        t: rng.normal(size=(c, args.feature_dim)).astype(np.float32)
        for t, c in
        {"paper": n_paper, "author": n_author, "inst": n_inst}.items()
    }
    feature = HeteroFeature.from_cpu_tensors(feats, device_cache_size="4G")
    del feats
    labels_all = jnp.asarray(
        rng.integers(0, args.classes, n_paper).astype(np.int32)
    )

    model = RGCN(hidden=args.hidden, num_classes=args.classes,
                 target_type="paper", num_layers=len(args.fanout))
    tx = optax.adam(5e-3)

    if args.topo_sharding == "mesh":
        return _body_mesh(args, topo, feature, labels_all, model, tx, rng,
                          n_paper)

    sampler = HeteroGraphSampler(
        topo, args.fanout, input_type="paper", seed_capacity=args.batch,
        frontier_caps="auto" if args.caps == "auto" else None,
        weighted=args.weighted, seed=args.seed,
    )

    out = sampler.sample(rng.integers(0, n_paper, args.batch))
    params = model.init(
        {"params": jax.random.PRNGKey(0)}, feature[out.n_id], out.adjs
    )["params"]
    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state, x_dict, layers, labels, mask, key):
        def loss_fn(p):
            logp = model.apply({"params": p}, x_dict, layers, train=True,
                               rngs={"dropout": key})
            ll = jnp.take_along_axis(
                logp, jnp.clip(labels, 0)[:, None], axis=1
            )[:, 0]
            w = mask.astype(logp.dtype)
            return -(ll * w).sum() / jnp.maximum(w.sum(), 1.0)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    def iteration(params, opt_state, i):
        seeds = rng.integers(0, n_paper, args.batch)
        out = sampler.sample(seeds)
        seed_ids = out.n_id["paper"][: args.batch]
        labels = labels_all[jnp.clip(seed_ids, 0)]
        mask = seed_ids >= 0
        return step(params, opt_state, feature[out.n_id], out.adjs, labels,
                    mask, jax.random.PRNGKey(i))

    t0 = time.time()
    for i in range(args.warmup):
        params, opt_state, loss = iteration(params, opt_state, i)
    jax.block_until_ready(loss)
    log(f"warmup+compile: {time.time() - t0:.1f}s")

    times = []
    for i in range(args.iters):
        t0 = time.time()
        params, opt_state, loss = iteration(params, opt_state, 100 + i)
        jax.block_until_ready(loss)
        times.append(time.time() - t0)

    iter_s = trimmed_mean(times)
    train_nodes = n_paper // 10
    iters_per_epoch = -(-train_nodes // args.batch)

    emit(
        "rgcn-epoch-time",
        iter_s * iters_per_epoch,
        "s",
        None,
        iter_ms=round(iter_s * 1e3, 2),
        iters_per_epoch=iters_per_epoch,
        caps=args.caps,
        batch=args.batch,
        fanout=args.fanout,
        dispatch="percall",
        topo_sharding="replicated",
        weighted=args.weighted,
        final_loss=round(float(loss), 4),
    )

    # AFTER the per-call record is safely flushed: a stream-side hang or
    # timeout must not cost the measurement already in hand
    if args.stream:
        try:
            _stream_epoch(args, sampler, feature, labels_all, step, params,
                          opt_state, rng, n_paper, iters_per_epoch)
        except Exception as e:  # noqa: BLE001 — per-call record stands
            log(f"stream measure failed (per-call record stands): "
                f"{type(e).__name__}: {str(e)[:200]}")


def _stream_epoch(args, sampler, feature, labels_all, step, params,
                  opt_state, rng, n_paper, iters_per_epoch, reps: int = 3):
    """N hetero training steps as ONE compiled program (lax.scan)."""
    import time as _time

    import jax
    import jax.numpy as jnp
    from jax import lax

    run = sampler._compiled(args.batch)

    @jax.jit
    def scan_train(params, opt_state, dev_topos, seed_mat, key0):
        keys = jax.random.split(key0, seed_mat.shape[0])

        def body(carry, xs):
            p, o, oflo = carry
            seeds, k = xs
            ks, kd = jax.random.split(k)
            frontier, counts, layers, overflow, _ = run(
                dev_topos, seeds, jnp.int32(args.batch), ks
            )
            seed_ids = frontier["paper"][: args.batch]
            labels = labels_all[jnp.clip(seed_ids, 0)]
            mask = seed_ids >= 0
            p, o, loss = step(p, o, feature[frontier], layers, labels,
                              mask, kd)
            return (p, o, oflo + overflow), loss

        (p, o, oflo), losses = lax.scan(
            body, (params, opt_state, jnp.zeros((), jnp.int32)),
            (seed_mat, keys),
        )
        return p, o, losses, oflo

    def one_rep():
        seed_mat = jnp.asarray(
            rng.integers(0, n_paper, (args.stream, args.batch)).astype(
                np.int32
            )
        )
        key = jax.random.PRNGKey(int(rng.integers(1 << 30)))
        t0 = _time.time()
        p, o, losses, oflo = scan_train(params, opt_state,
                                        sampler.dev_topos, seed_mat, key)
        final = float(losses[-1])
        return (_time.time() - t0) / args.stream, final, int(oflo)

    t0 = _time.time()
    one_rep()  # compile
    log(f"stream compile: {_time.time()-t0:.1f}s "
        f"({args.stream} steps/scan)")
    results = [one_rep() for _ in range(reps)]
    iter_s = float(np.median([r[0] for r in results]))
    emit(
        "rgcn-epoch-time",
        iter_s * iters_per_epoch,
        "s",
        None,
        iter_ms=round(iter_s * 1e3, 2),
        iters_per_epoch=iters_per_epoch,
        caps=args.caps,
        batch=args.batch,
        fanout=args.fanout,
        dispatch="stream",
        stream_batches=args.stream,
        overflow=results[-1][2],
        final_loss=round(results[-1][1], 4),
    )

def _hetero_comm_model(sampler, seed_cap: int) -> dict:
    """Exact per-device lanes-per-hop model of the mesh-sharded hetero
    sampler.

    The shared route plan moves each (hop, destination type) frontier's
    ids ONCE — ``F * cap_t`` lanes, ``cap_t = ceil(alpha * S_t / F)`` —
    and every relation into that type reuses the cached routed ids. Each
    uniform relation then adds ``F * cap_t`` (degrees back) +
    ``2 * F * cap_t * k`` (offsets out, neighbor blocks back); a weighted
    relation adds one more ``F * cap_t`` f32 exchange (row weight totals
    back). Bucket shapes are static, so the model is exact; the measured
    per-(hop, edge type) fallback overflow rides alongside it.
    """
    from quiver_tpu.sampling.dist import routed_sample_cap

    F = sampler.workers
    alpha = sampler.routed_alpha
    lanes, lanes_unc, hop_caps = [], [], []
    for active, caps_prev, _ in sampler._plan(seed_cap,
                                              sampler._cap_overrides):
        hop, hop_unc, caps_t = 0, 0, {}
        for t in sorted({et[2] for et in active}):
            S_t = caps_prev[t]
            cap_t = routed_sample_cap(S_t, F, alpha) or S_t
            caps_t[t] = cap_t
            hop += F * cap_t  # shared plan: ids out once per dst type
            hop_unc += F * S_t
        for et, k in sorted(active.items(), key=lambda kv: str(kv[0])):
            cap_t, S_t = caps_t[et[2]], caps_prev[et[2]]
            extra = 1 if et in sampler.weighted_rels else 0
            hop += F * cap_t * (1 + extra + 2 * k)
            hop_unc += F * S_t * (1 + extra + 2 * k)
        hop_caps.append(caps_t)
        lanes.append(hop)
        lanes_unc.append(hop_unc)
    plan = sampler.dev_topos.plan
    return {
        "topo_sharding": "mesh",
        "routed_alpha": alpha,
        "hop_caps": hop_caps,
        "lanes_per_hop": lanes,
        "lanes_per_hop_uncapped": lanes_unc,
        "comm_reduction": round(sum(lanes_unc) / max(sum(lanes), 1), 2),
        "topo_bytes_per_chip": plan["per_chip_bytes"],
        "topo_bytes_replicated": plan["replicated_bytes"],
        "topo_shrink": round(plan["shrink_factor"], 2),
    }


def _body_mesh(args, topo, feature, labels_all, model, tx, rng, n_paper):
    """--topo-sharding mesh lane: DistHeteroSampler over per-relation
    mesh partitions. Methodology matches the replicated lane (trimmed-mean
    iteration time x iterations-per-epoch); each iteration samples every
    worker's block, runs the R-GCN fwd/bwd per block, and applies the
    worker-averaged update — the record adds the exact per-edge-type
    lanes-per-hop comm model and the measured per-(hop, edge type)
    fallback overflow."""
    import jax
    import jax.numpy as jnp
    import optax

    from quiver_tpu import DistHeteroSampler
    from quiver_tpu.parallel.mesh import make_mesh

    if args.stream:
        log("WARNING: --stream is not supported with --topo-sharding mesh; "
            "measuring the per-call dispatch loop only")
    F = len(jax.devices())
    mesh = make_mesh(data=1, feature=F)
    sampler = DistHeteroSampler(
        topo, args.fanout, input_type="paper", mesh=mesh,
        seed_capacity=-(-args.batch // F),
        frontier_caps="auto" if args.caps == "auto" else None,
        weighted=args.weighted, routed_alpha=args.routed_alpha or None,
        seed=args.seed,
    )
    W = sampler.workers
    cap = -(-args.batch // F)

    def sample_blocks(i):
        seeds = rng.integers(0, n_paper, args.batch)
        outs = sampler.sample_per_worker(seeds, key=jax.random.PRNGKey(i))
        return outs, np.array_split(seeds, W)

    outs, _ = sample_blocks(0)
    params = model.init(
        {"params": jax.random.PRNGKey(0)}, feature[outs[0].n_id],
        outs[0].adjs,
    )["params"]
    opt_state = tx.init(params)

    @jax.jit
    def grad_step(params, x_dict, layers, labels, mask, key):
        def loss_fn(p):
            logp = model.apply({"params": p}, x_dict, layers, train=True,
                               rngs={"dropout": key})
            ll = jnp.take_along_axis(
                logp, jnp.clip(labels, 0)[:, None], axis=1
            )[:, 0]
            w = mask.astype(logp.dtype)
            return -(ll * w).sum() / jnp.maximum(w.sum(), 1.0)

        return jax.value_and_grad(loss_fn)(params)

    @jax.jit
    def apply_update(params, opt_state, grads):
        updates, opt_state = tx.update(
            jax.tree_util.tree_map(lambda g: g / W, grads), opt_state,
            params
        )
        return optax.apply_updates(params, updates), opt_state

    def iteration(params, opt_state, i):
        outs, _ = sample_blocks(i)
        grads_acc, loss = None, None
        for o in outs:
            seed_ids = o.n_id["paper"][:cap]
            labels = labels_all[jnp.clip(seed_ids, 0)]
            loss, grads = grad_step(params, feature[o.n_id], o.adjs,
                                    labels, seed_ids >= 0,
                                    jax.random.PRNGKey(i))
            grads_acc = grads if grads_acc is None else \
                jax.tree_util.tree_map(jnp.add, grads_acc, grads)
        params, opt_state = apply_update(params, opt_state, grads_acc)
        return params, opt_state, loss

    t0 = time.time()
    for i in range(args.warmup):
        params, opt_state, loss = iteration(params, opt_state, i)
    jax.block_until_ready(loss)
    log(f"warmup+compile: {time.time() - t0:.1f}s ({W} workers)")

    times = []
    for i in range(args.iters):
        t0 = time.time()
        params, opt_state, loss = iteration(params, opt_state, 100 + i)
        jax.block_until_ready(loss)
        times.append(time.time() - t0)

    iter_s = trimmed_mean(times)
    train_nodes = n_paper // 10
    iters_per_epoch = -(-train_nodes // args.batch)
    model_rec = _hetero_comm_model(sampler, cap)
    ov = sampler.last_sample_overflow_by_rel or {}
    emit(
        "rgcn-epoch-time",
        iter_s * iters_per_epoch,
        "s",
        None,
        iter_ms=round(iter_s * 1e3, 2),
        iters_per_epoch=iters_per_epoch,
        caps=args.caps,
        batch=args.batch,
        fanout=args.fanout,
        dispatch="percall",
        mesh_devices=W,
        weighted=args.weighted,
        sample_overflow={
            f"hop{li}:{'-'.join(et)}": int(v) for (li, et), v in ov.items()
        },
        final_loss=round(float(loss), 4),
        **model_rec,
    )


if __name__ == "__main__":
    main()
