"""Primitive micro-benchmarks: the building blocks of the sampler hot path.

Measures, via fused scans (one compiled program per primitive, distinct
inputs per step, full-output checksums (a sliced element would let XLA
dead-code the op) and one scalar readback, so dispatch and the host sync
stay out of the number), the per-element cost of exactly the operations
the three dedup strategies are built from:

* ``sort``        — jnp.sort of int32 (the scan/sort strategies' engine)
* ``argsort-pair``— stable argsort + payload gather (what the ``sort``
  strategy's view was built from until PR 29; ``masked_unique`` now sorts
  the pair itself, ``lax.sort((vals, pos))``: on a v5e at 852,480 lanes
  1.2 ms against 1.2 + 6.4 for the argsort and one gather, PERF.md PR 29)
* ``gather``      — random int32 gather (every strategy)
* ``scatter-set`` — .at[].set into a same-sized buffer (sort-path compaction)
* ``scatter-min`` — .at[].min into a node_count-sized map (map strategy)
* ``cummax``      — lax.cummax (scan strategy's run-representative)

The r3 link data showed TPU sort at ~1.8 ms/M while reindex ran tens of ms
— these rows decide whether XLA scatters are the serialization point and
therefore which dedup strategy should win (ops/reindex.py). ~2 minutes of
chip time; scheduled early in the scoreboard so even a brief window lands
the diagnosis.

Reference counterpart: none (the reference's primitives are thrust/cub
calls benchmarked nowhere; this is chip triage tooling).
"""

import time

import numpy as np

from benchmarks.common import base_parser, emit, log, run_guarded


def _measure(name, make_inputs, op, n_elems: int, reps: int, key):
    """Median Melem/s of ``op`` over a fused scan of ``reps`` distinct
    inputs. ``make_inputs(key, reps)`` returns the stacked xs."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    xs = make_inputs(key, reps)

    @jax.jit
    def run(xs_all):
        def step(carry, xs_one):
            return carry + op(xs_one), None
        total, _ = lax.scan(step, jnp.float32(0), xs_all)
        return total

    t0 = time.time()
    jax.block_until_ready(run(xs))
    log(f"{name}: compile {time.time() - t0:.1f}s")
    times = []
    for _ in range(3):
        t0 = time.time()
        jax.block_until_ready(run(xs))
        times.append(time.time() - t0)
    dt = sorted(times)[1]
    melems = reps * n_elems / dt / 1e6
    emit("primitive-Melem/s", melems, "Melem/s", None, op=name,
         elems=n_elems, reps=reps, ms_per_call=round(dt / reps * 1e3, 3))


def _body(args):
    import jax
    import jax.numpy as jnp

    from benchmarks.common import init_backend, set_record_context

    init_backend(smoke=args.smoke)
    n = 200_000 if args.smoke else 1_000_000
    bound = 500_000 if args.smoke else 2_450_000  # the dense-map size
    reps = 4 if args.smoke else 8
    set_record_context(nodes=bound, smoke=True if args.smoke else None)
    key = jax.random.PRNGKey(args.seed)

    def rand_ids(key, reps, hi=n):
        return jax.random.randint(key, (reps, n), 0, hi, dtype=jnp.int32)

    _measure("sort", rand_ids, lambda x: jnp.sum(jnp.sort(x).astype(jnp.float32)),
             n, reps, key)
    _measure(
        "argsort-pair", rand_ids,
        lambda x: jnp.sum(x[jnp.argsort(x, stable=True)].astype(jnp.float32)),
        n, reps, key)
    table = jnp.arange(bound, dtype=jnp.float32)
    _measure("gather", lambda k, r: rand_ids(k, r, bound),
             lambda i: jnp.sum(table[i]), n, reps, key)
    vals = jnp.arange(n, dtype=jnp.int32)
    _measure(
        "scatter-set", rand_ids,
        lambda i: jnp.sum(jnp.zeros(n, jnp.int32).at[i].set(
            vals, mode="drop").astype(jnp.float32)),
        n, reps, key)
    _measure(
        "scatter-min", lambda k, r: rand_ids(k, r, bound),
        lambda i: jnp.sum(jnp.full(bound, n, jnp.int32).at[i].min(
            vals, mode="drop").astype(jnp.float32)),
        n, reps, key)
    _measure("cummax", rand_ids,
             lambda x: jnp.sum(jax.lax.cummax(x).astype(jnp.float32)),
             n, reps, key)


def main():
    p = base_parser(__doc__)
    args = p.parse_args()
    run_guarded(lambda: _body(args), args)


if __name__ == "__main__":
    main()
