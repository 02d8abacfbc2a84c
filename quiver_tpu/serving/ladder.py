"""Persistent compiled micro-batch programs for online point queries.

PyGraph (arxiv 2503.19779) quantifies what every serving stack relearns:
at small batch sizes the per-request cost is dominated by dispatch and
(re)compilation, not math — the fix is to compile once and *replay*. The
:class:`ServeLadder` applies that to sampled k-hop GNN inference: for
each power-of-two bucket size ``B`` it AOT-compiles (``jit(...).lower(
...).compile()``) exactly two fixed-shape programs and replays the
executables directly — no jit cache lookup, no retrace, no Python per
request beyond array packing:

* **sample**: a ``lax.scan`` over the ``B`` lanes; each lane runs its own
  single-seed ``multilayer_sample`` under a per-request PRNG key
  ``fold_in(base_key, seq)`` with per-lane frontier caps planned for ONE
  seed. Lanes never share frontier state, so a request's neighborhood is
  a function of ``(node, seq)`` alone — independent of bucket size,
  padding, and co-batched requests. That independence is the bit-parity
  contract: ladder output == the direct single-query oracle, bitwise, at
  every bucket size.
* **forward**: a ``lax.scan`` applying the model per lane over the
  gathered feature block (donated — the (B, cap, F) buffer is the big
  per-batch allocation and is dead after the forward).

The host-side feature gather sits *between* the two programs on purpose:
that is where the three-tier store, the mesh-sharded store, and the
circuit-breaker's :class:`~quiver_tpu.resilience.elastic.DegradedFeature`
wrapper all live, so resilience wiring costs the serving path nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..sampling.sampler import Adj, GraphSageSampler, multilayer_sample

__all__ = ["ServeLadder"]


class ServeLadder:
    """Per-bucket AOT-compiled (sample, forward) executable pairs.

    Args:
      sampler: a *replicated* :class:`GraphSageSampler` — the ladder
        replays its device topology, fanouts and kernel choice.
        The mesh-sharded sampler is rejected: its per-hop collectives
        assume trainer-scale frontiers, not single-seed lanes (serve
        against a replicated topology; a mesh-sharded *feature* store is
        fully supported via the host gather stage).
      model: the trained module; ``model.apply`` must accept
        ``(x, adjs, train=False)`` and return per-seed log-probs.
      feature_dim: row width of the feature store (static forward shape).
      row_dtype: dtype the gather stage produces (the store's served row
        dtype — float32 for dequantized int8, bf16 for bf16 stores).
      lane_caps: per-layer frontier caps for ONE seed; defaults to the
        sampler's worst-case single-seed plan (tight for modest fanouts).
      on_compile: callback invoked once per program build — the server
        feeds ``serve.recompiles`` from it.
      aot_cache: optional :class:`~quiver_tpu.serving.aot
        .AOTExecutableCache`. When set, every program build consults the
        cache first (a hit deserializes the backend executable — ZERO
        compiles, ``on_compile`` not invoked) and every compile publishes
        its executable for the next replica. Keyed by
        :meth:`fingerprint`; any mismatch (new CSR commit, different
        toolchain, different geometry) falls back to compile-and-publish.
      on_cache_load: callback invoked once per cache-served program — the
        server feeds ``serve.aot_loads`` from it.
    """

    def __init__(self, sampler: GraphSageSampler, model, feature_dim: int,
                 row_dtype=jnp.float32, lane_caps=None, on_compile=None,
                 aot_cache=None, on_cache_load=None):
        if getattr(sampler, "topo_sharding", "replicated") != "replicated":
            raise NotImplementedError(
                "ServeLadder requires a replicated-topology sampler; the "
                "mesh-sharded DistGraphSageSampler's collective hops are "
                "planned for trainer-scale frontiers, not single-seed "
                "serving lanes (shard the FEATURE store instead — the "
                "host gather stage serves ShardedFeature unchanged)"
            )
        self.sampler = sampler
        self.model = model
        self.feature_dim = int(feature_dim)
        self.row_dtype = jnp.dtype(row_dtype)
        caps = tuple(lane_caps) if lane_caps is not None else (
            sampler._worst_caps(1)
        )
        if len(caps) != len(sampler.sizes):
            raise ValueError(
                f"lane_caps needs one entry per layer ({len(sampler.sizes)}), "
                f"got {caps}"
            )
        self.lane_caps = tuple(int(c) for c in caps)
        self.sizes = tuple(sampler.sizes)
        self._on_compile = on_compile
        # static Adj metadata per layer, sample order: layer l maps a
        # frontier of width src_w[l] onto dst_w[l] targets (dst_w[0] = 1,
        # the seed lane)
        widths = (1,) + self.lane_caps[:-1]
        self._adj_meta = tuple(
            (self.lane_caps[l], widths[l], self.sizes[l])
            for l in range(len(self.sizes))
        )
        self.aot_cache = aot_cache
        self._on_cache_load = on_cache_load
        self.compiles = 0
        self.cache_loads = 0
        self._sample_exec: dict[int, object] = {}
        self._forward_exec: dict[int, object] = {}
        self._params_struct = None

    # -- per-lane bodies (shared by every bucket AND the parity oracle) ------

    def _lane_sample(self, topo, seed, nvalid, seq, base_key):
        """One request's k-hop sample: seed (), nvalid (), seq () ->
        (n_id (cap_last,), edge_index per layer deepest-first, overflow)."""
        key = jax.random.fold_in(base_key, seq)
        s = self.sampler
        n_id, _n_count, adjs, overflow, _ec, _fc = multilayer_sample(
            topo, seed[None] if seed.ndim == 0 else seed, nvalid, key,
            self.sizes, self.lane_caps, weighted=s.weighted, kernel=s.kernel,
            with_eid=False,
        )
        return n_id, tuple(a.edge_index for a in adjs), overflow

    def _lane_forward(self, x, edge_indices, params):
        """One request's model forward: x (cap_last, F) + deepest-first
        edge_index arrays -> (num_classes,) log-probs for the seed lane."""
        adjs = [
            Adj(ei, None, (cap, dst), fanout=k)
            for ei, (cap, dst, k) in zip(
                edge_indices, reversed(self._adj_meta)
            )
        ]
        logits = self.model.apply({"params": params}, x, adjs, train=False)
        return logits[0]

    # -- bucket programs -----------------------------------------------------

    def trace_sample(self, bucket: int):
        """AOT-trace one bucket's sample program (no compile, no device
        work) — the shared front half of :meth:`_build_sample`, also the
        artifact graftaudit (``tools/audit``) walks."""
        def run(topo, seeds, nvalid, seqs, base_key):
            def lane(_, xs):
                seed, nv, seq = xs
                return _, self._lane_sample(topo, seed, nv, seq, base_key)

            _, out = jax.lax.scan(lane, 0, (seeds, nvalid, seqs))
            return out

        i32 = jnp.int32
        shp = jax.ShapeDtypeStruct((bucket,), i32)
        key = jax.ShapeDtypeStruct(
            jnp.shape(self.sampler._key), jnp.asarray(self.sampler._key).dtype
        )
        return jax.jit(run).trace(self.sampler.topo, shp, shp, shp, key)

    def trace_forward(self, bucket: int):
        """AOT-trace one bucket's forward program against the bound
        parameter structure. The gathered feature block is deliberately
        NOT donated: ``(bucket, lane_cap, F)`` rows can never alias the
        ``(bucket, classes)`` logits, so a ``donate_argnums=0`` here is an
        unusable donation — pure warning noise at every bucket compile and
        a standing invitation to believe memory is being saved when none
        is (graftaudit's donation-audit rule flags exactly this)."""
        def run(x, edge_indices, params):
            def lane(_, xs):
                xb, eis = xs
                return _, self._lane_forward(xb, eis, params)

            _, out = jax.lax.scan(lane, 0, (x, edge_indices))
            return out

        x = jax.ShapeDtypeStruct(
            (bucket, self.lane_caps[-1], self.feature_dim), self.row_dtype
        )
        eis = tuple(
            jax.ShapeDtypeStruct((bucket, 2, dst * k), jnp.int32)
            for (_cap, dst, k) in reversed(self._adj_meta)
        )
        params = self._params_struct
        if params is None:
            raise RuntimeError("call bind_params() before compiling forward")
        return jax.jit(run).trace(x, eis, params)

    # -- persisted-executable fingerprint ------------------------------------

    @staticmethod
    def _avals(tree) -> list:
        out = []
        for x in jax.tree_util.tree_leaves(tree):
            # leaves are arrays OR ShapeDtypeStructs (the bound params
            # struct) — both carry .shape/.dtype
            a = x if hasattr(x, "dtype") else jnp.asarray(x)
            out.append([list(map(int, a.shape)), str(a.dtype)])
        return out

    def fingerprint_components(self, kind: str, bucket: int) -> dict:
        """Everything the ``(kind, bucket)`` program's compiled artifact
        closed over, as a JSON-able dict (see :func:`~quiver_tpu.serving
        .aot.program_fingerprint`). The CSR committed ``version`` AND the
        topology leaf avals are both in the key: a streaming commit
        always forks the fingerprint (refresh re-checks the cache instead
        of trusting a pre-commit executable), and shape-changing commits
        are caught even if versions were ever reused."""
        s = self.sampler
        dev = jax.devices()[0]
        comp = {
            "target": f"serve.{kind}",  # graftaudit-style target id
            "bucket": int(bucket),
            "sizes": list(self.sizes),
            "lane_caps": list(self.lane_caps),
            "kernel": s.kernel,
            "weighted": bool(s.weighted),
            "csr_version": int(getattr(s.csr_topo, "version", 0)),
            "topo_avals": self._avals(s.topo),
            "key_aval": self._avals(s._key),
            "jax": jax.__version__,
            "platform": dev.platform,
            "device_kind": str(dev.device_kind),
            "n_devices": int(jax.device_count()),
        }
        if kind == "forward":
            if self._params_struct is None:
                raise RuntimeError(
                    "call bind_params() before fingerprinting forward"
                )
            comp["model"] = f"{type(self.model).__name__}:{self.model!r}"
            comp["params_treedef"] = str(
                jax.tree_util.tree_structure(self._params_struct)
            )
            comp["params_avals"] = self._avals(self._params_struct)
            comp["feature_dim"] = self.feature_dim
            comp["row_dtype"] = str(self.row_dtype)
        return comp

    def fingerprint(self, kind: str, bucket: int) -> str:
        from .aot import program_fingerprint

        return program_fingerprint(self.fingerprint_components(kind, bucket))

    # -- program builds (cache-first when an AOT cache is attached) ----------

    def _build(self, kind: str, bucket: int, trace_fn):
        fp = None
        if self.aot_cache is not None:
            fp = self.fingerprint(kind, bucket)
            ex = self.aot_cache.load(fp)
            if ex is not None:
                self.cache_loads += 1
                if self._on_cache_load is not None:
                    self._on_cache_load()
                return ex
        compiled = trace_fn(bucket).lower().compile()
        self._note_compile()
        if self.aot_cache is not None:
            self.aot_cache.store(
                fp, compiled, self.fingerprint_components(kind, bucket)
            )
        return compiled

    def _build_sample(self, bucket: int):
        return self._build("sample", bucket, self.trace_sample)

    def _build_forward(self, bucket: int):
        return self._build("forward", bucket, self.trace_forward)

    def _note_compile(self):
        self.compiles += 1
        if self._on_compile is not None:
            self._on_compile()

    def bind_params(self, params) -> None:
        """Record the parameter tree's structure/shapes (forward programs
        lower against it; the concrete tree is passed per call)."""
        self._params_struct = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.asarray(a).dtype),
            params,
        )

    # -- replay --------------------------------------------------------------

    def sample_exec(self, bucket: int):
        ex = self._sample_exec.get(bucket)
        if ex is None:
            ex = self._sample_exec[bucket] = self._build_sample(bucket)
        return ex

    def forward_exec(self, bucket: int):
        ex = self._forward_exec.get(bucket)
        if ex is None:
            ex = self._forward_exec[bucket] = self._build_forward(bucket)
        return ex

    def warmup(self, buckets) -> int:
        """Compile every bucket's program pair up front; returns the
        number of compilations performed. After this, steady-state serving
        replays executables only (``serve.recompiles`` stays flat)."""
        before = self.compiles
        for b in buckets:
            self.sample_exec(int(b))
            self.forward_exec(int(b))
        return self.compiles - before

    def warm_from_cache(self, buckets) -> dict:
        """Warm every bucket's program pair, deserializing from the
        attached :class:`~quiver_tpu.serving.aot.AOTExecutableCache`
        wherever the fingerprint matches and compiling (then publishing)
        only the rest. Returns ``{"loaded": n, "compiled": m}`` — a
        replica warming from a populated cache reports ``compiled == 0``
        and its replayed executables answer bitwise-identically to a
        compile-from-scratch replica (same program, same backend
        artifact)."""
        before_c, before_l = self.compiles, self.cache_loads
        for b in buckets:
            self.sample_exec(int(b))
            self.forward_exec(int(b))
        return {"loaded": self.cache_loads - before_l,
                "compiled": self.compiles - before_c}

    # -- parity oracle -------------------------------------------------------

    @functools.cached_property
    def _oracle_sample_jit(self):
        return jax.jit(
            lambda topo, seed, nvalid, seq, base_key: self._lane_sample(
                topo, seed, nvalid, seq, base_key
            )
        )

    @functools.cached_property
    def _oracle_forward_jit(self):
        return jax.jit(
            lambda x, eis, params: self._lane_forward(x, eis, params)
        )

    def oracle_sample(self, topo, node: int, seq: int, base_key):
        """Direct (ladder-free) single-query sample at the same key —
        the reference half of the bit-parity differential."""
        return self._oracle_sample_jit(
            topo, jnp.int32(node), jnp.int32(1), jnp.int32(seq), base_key
        )

    def oracle_forward(self, x, edge_indices, params):
        return self._oracle_forward_jit(x, edge_indices, params)
