"""Multi-layer heterogeneous neighbor sampler.

Extends the homogeneous padded-shape design (sampling/sampler.py) to typed
graphs: each hop samples every active relation ``(src_t, rel, dst_t)`` whose
destination type currently has frontier nodes, then deduplicates per *node
type* (seeds-first, first-occurrence order — the same masked_unique core the
homogeneous reindex uses). All per-hop/per-type capacities are planned
statically from the fanouts, so the whole multi-layer program jits once.

Output contract mirrors the homogeneous sampler (and thus PyG's hetero
NeighborSampler): ``adjs`` deepest-layer first; each layer is a
``HeteroLayer`` holding one padded Adj per relation plus the per-type
src/dst capacities a model needs for slicing and segment sizes;
``n_id[input_type][:batch_size] == seeds``.
"""

from __future__ import annotations

from typing import NamedTuple, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from ..core.config import SampleMode
from ..core.hetero import HeteroCSRTopo
from ..ops.reindex import masked_unique
from ..ops.sample import sample_layer
from .sampler import Adj, _round_up

__all__ = ["HeteroLayer", "HeteroSampleOutput", "HeteroGraphSampler"]


@jax.tree_util.register_pytree_node_class
class HeteroLayer:
    """One hop's relation-wise adjacency: ``adjs`` maps each edge type to a
    padded Adj; ``src_caps``/``dst_caps`` are the per-type frontier
    capacities on the source/target side — static metadata (pytree aux), so
    models can use them as slice bounds and segment counts under jit."""

    def __init__(self, adjs: dict, src_caps: dict, dst_caps: dict):
        self.adjs = adjs
        self.src_caps = src_caps
        self.dst_caps = dst_caps

    def __repr__(self):
        return (
            f"HeteroLayer(rels={[f'{s}-{r}->{d}' for s, r, d in self.adjs]}, "
            f"src_caps={self.src_caps}, dst_caps={self.dst_caps})"
        )

    def tree_flatten(self):
        keys = tuple(sorted(self.adjs, key=str))
        children = tuple(self.adjs[k] for k in keys)
        aux = (
            keys,
            tuple(sorted(self.src_caps.items())),
            tuple(sorted(self.dst_caps.items())),
        )
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        keys, src_caps, dst_caps = aux
        return cls(dict(zip(keys, children)), dict(src_caps), dict(dst_caps))


class HeteroSampleOutput(NamedTuple):
    n_id: dict  # node_type -> (cap,) global ids, -1 padded
    n_count: dict  # node_type -> scalar valid count
    batch_size: int
    adjs: list  # HeteroLayer records, deepest first
    overflow: jax.Array  # total uniques dropped by caps (0 = exact)
    # per-hop UNCLIPPED unique counts {type: scalar}, seeds-outward order —
    # what the auto-cap planner reads (homogeneous frontier_counts analogue)
    frontier_counts: tuple = ()


def _normalize_sizes(sizes, topo: HeteroCSRTopo):
    """Per-layer fanout spec: int (all relations) or {edge_type: k}.

    -1 means full neighborhood for that relation (its max in-degree),
    matching GraphSageSampler's convention; 0 (dict form) disables the
    relation for that hop; other non-positive fanouts are rejected.
    """
    edge_types = topo.edge_types

    def resolve(et, k):
        k = int(k)
        if k == -1:
            return max(topo.relations[et].max_degree, 1)
        if k < 1:
            raise ValueError(
                f"fanout for {et} must be >= 1, -1 (full), or 0 (disable, "
                f"dict form only); got {k}"
            )
        return k

    out = []
    for layer in sizes:
        if isinstance(layer, int):
            out.append({et: resolve(et, layer) for et in edge_types})
        else:
            unknown = set(layer) - set(edge_types)
            if unknown:
                raise ValueError(f"unknown edge types in sizes: {unknown}")
            out.append({
                et: resolve(et, k) for et, k in layer.items() if int(k) != 0
            })
    return out


def hetero_multilayer_sample(dev_topos, seeds, num_seeds, key, input_type,
                             layer_plans, weighted_rels=frozenset(),
                             with_eid: bool = False):
    """The jit-composable hetero sampling loop.

    ``layer_plans`` is a static tuple of per-hop plans, each
    ``(rel_fanouts, caps_prev, caps_next)`` where rel_fanouts maps active
    edge types to fanouts and caps_* map node types to static capacities.
    ``weighted_rels`` (static) names edge types whose draws are
    weight-proportional (their DeviceTopology must carry cum_weights);
    ``with_eid`` threads per-edge global edge ids into every Adj — the
    homogeneous contract (multilayer_sample, sampler.py) extended to typed
    relations: ids are COO positions within each relation's own edge list.
    Returns (frontier dict, counts dict, layers deepest-first, overflow).
    """
    frontier = {input_type: seeds}
    counts = {input_type: num_seeds}
    layers = []
    frontier_counts = []
    overflow = jnp.zeros((), jnp.int32)

    for rel_fanouts, caps_prev, caps_next in layer_plans:
        # 1) sample every active relation
        samples = {}  # edge_type -> (S, K) src-type global ids
        eids = {}  # edge_type -> (S, K) relation-local edge ids
        for et, k in rel_fanouts.items():
            _, _, d = et
            key, sub = jax.random.split(key)
            res = sample_layer(
                dev_topos[et], frontier[d], counts[d], k, sub,
                weighted=et in weighted_rels, with_eid=with_eid,
            )
            samples[et] = res[0]
            if with_eid:
                eids[et] = res[2]

        # 2) per-type dedup: previous frontier first (forced), then each
        #    relation's samples targeting this src type, concatenated in a
        #    deterministic relation order
        new_frontier, new_counts, locals_per_rel = {}, {}, {}
        layer_uniques = {}
        for t, cap in caps_next.items():
            blocks, valids, spans = [], [], {}
            prev = frontier.get(t)
            n_prev = 0
            if prev is not None:
                n_prev = prev.shape[0]
                blocks.append(prev)
                valids.append(
                    (jnp.arange(n_prev) < counts[t]) & (prev >= 0)
                )
            for et in sorted(samples, key=str):
                if et[0] != t:
                    continue
                flat = samples[et].reshape(-1)
                spans[et] = (sum(b.shape[0] for b in blocks),
                             flat.shape[0])
                blocks.append(flat)
                valids.append(flat >= 0)
            ids = jnp.concatenate(blocks)
            valid = jnp.concatenate(valids)
            uniq, num_u, local = masked_unique(
                ids, valid, cap, num_forced=n_prev
            )
            new_frontier[t] = uniq
            new_counts[t] = jnp.minimum(num_u, cap)
            layer_uniques[t] = num_u
            overflow = overflow + jnp.maximum(num_u - cap, 0)
            for et, (off, ln) in spans.items():
                locals_per_rel[et] = local[off:off + ln]

        # 3) build one padded Adj per relation: src = frontier-local id in
        #    the NEW src-type frontier, dst = row position in the PREVIOUS
        #    dst-type frontier (identical to its local id next layer, since
        #    previous nodes are forced first)
        adjs = {}
        for et, k in rel_fanouts.items():
            s_t, _, d_t = et
            S = frontier[d_t].shape[0]
            col = locals_per_rel[et].reshape(S, k)
            row = jnp.broadcast_to(
                jnp.arange(S, dtype=jnp.int32)[:, None], (S, k)
            )
            row = jnp.where(col >= 0, row, -1)
            edge_index = jnp.stack([col.reshape(-1), row.reshape(-1)])
            e_id = None
            if with_eid:
                # re-mask with col: neighbors dropped by frontier-cap
                # overflow must not leak their edge ids (same rule as the
                # homogeneous loop)
                e_id = jnp.where(col >= 0, eids[et], -1).reshape(-1)
            adjs[et] = Adj(edge_index, e_id, (caps_next[s_t], S), fanout=k)
        layers.append(HeteroLayer(adjs, dict(caps_next), dict(caps_prev)))
        frontier_counts.append(layer_uniques)

        frontier, counts = new_frontier, new_counts

    return frontier, counts, layers[::-1], overflow, tuple(frontier_counts)


class HeteroGraphSampler:
    """K-hop typed neighbor sampler over a HeteroCSRTopo.

    Args:
      topo: HeteroCSRTopo (relations stored as incoming adjacency).
      sizes: per-layer fanouts — each entry an int (applied to every
        relation) or a dict {edge_type: fanout} (omitted/0 disables the
        relation that hop).
      input_type: node type of the seeds.
      mode: topology placement, "GPU"/HBM or "UVA"/host.
      seed_capacity: padded seed batch; defaults to first batch rounded up.
      frontier_caps: ``"auto"`` right-sizes every per-hop/per-type capacity
        from the first batch's observed unique counts (x ``auto_margin``) —
        the homogeneous auto planner (sampler.py) ported to typed frontiers.
        Worst-case caps overshoot ~3x on power-law graphs (SURVEY §7.4.2),
        and R-GCN pays that in every gather/aggregate. Default: worst case.
      seed: PRNG seed.
      auto_margin: headroom factor for "auto" caps (>= 1).
      weighted: weight-proportional neighbor draws — ``True`` uses every
        relation that has weights attached (``set_edge_weight``; at least one
        required), or pass an iterable of edge types to weight exactly those
        (each must have weights). Unlisted relations sample uniformly.
      with_eid: populate every ``Adj.e_id`` with relation-local global edge
        ids (COO positions) — the homogeneous sampler's contract
        (sage_sampler.py:100-109 parity) extended to typed graphs.
    """

    def __init__(self, topo: HeteroCSRTopo, sizes: Sequence,
                 input_type: str, mode: str | SampleMode = SampleMode.HBM,
                 seed_capacity: int | None = None,
                 frontier_caps: str | None = None, seed: int = 0,
                 auto_margin: float = 1.25, weighted=False,
                 with_eid: bool = False):
        if input_type not in topo.num_nodes:
            raise ValueError(f"unknown input_type {input_type!r}")
        self.topo = topo
        self.input_type = input_type
        self.sizes = _normalize_sizes(sizes, topo)
        self.mode = SampleMode.parse(mode)
        self.with_eid = bool(with_eid)
        if weighted is True:
            weighted_rels = topo.weighted_edge_types
            if not weighted_rels:
                raise ValueError(
                    "weighted=True requires at least one relation with edge "
                    "weights; call topo.set_edge_weight() first"
                )
        elif weighted:
            # str-normalize components like HeteroCSRTopo does its keys
            weighted_rels = [tuple(str(t) for t in et) for et in weighted]
            missing = [
                et for et in weighted_rels
                if et not in topo.relations
                or topo.relations[et].cum_weights is None
            ]
            if missing:
                raise ValueError(
                    f"weighted relations need edge weights attached: {missing}"
                )
        else:
            weighted_rels = []
        self.weighted_rels = frozenset(weighted_rels)
        self.dev_topos = self._init_topo()
        self._seed_capacity = seed_capacity
        if frontier_caps not in (None, "auto"):
            raise ValueError(
                f"frontier_caps must be None or 'auto', got {frontier_caps!r}"
            )
        self._auto_caps = frontier_caps == "auto"
        self._auto_margin = float(auto_margin)
        if self._auto_margin < 1.0:
            raise ValueError(f"auto_margin must be >= 1.0, got {auto_margin}")
        # per-layer {type: cap} overrides planned from observed counts
        self._cap_overrides: tuple | None = None
        self._key = jax.random.PRNGKey(seed)
        self._call = 0
        self._compiled_cache = {}

    def _init_topo(self):
        """Place every relation's CSR on device. The mesh-sharded sampler
        (``sampling.dist_hetero.DistHeteroSampler``) overrides this to
        partition each relation across the mesh instead of replicating."""
        return self.topo.to_device(
            self.mode, with_eid=self.with_eid,
            weighted_rels=self.weighted_rels,
        )

    # -- static planning ----------------------------------------------------

    def _plan(self, seed_cap: int, overrides: tuple | None = None):
        """Per-hop (active relations, caps before, caps after).

        ``overrides`` (auto mode): per-layer {type: planned cap}; each is
        clamped into [previous hop's cap, worst case] so the seeds-first
        invariant and correctness bounds hold no matter what was observed.
        """
        caps = {self.input_type: seed_cap}
        plans = []
        for li, layer in enumerate(self.sizes):
            active = {
                et: k for et, k in layer.items()
                if caps.get(et[2], 0) > 0 and k > 0
            }
            caps_next = dict(caps)
            for et, k in active.items():
                s_t, _, d_t = et
                grow = caps[d_t] * k
                caps_next[s_t] = caps_next.get(s_t, 0) + grow
            for t in caps_next:
                # clamp growth at the type's node count, but never below the
                # previous hop's capacity: forced (seeds-first) lanes keep
                # duplicates as distinct slots, so the frontier must always
                # be able to hold the full previous frontier
                worst = _round_up(
                    max(min(caps_next[t], self.topo.num_nodes[t]),
                        caps.get(t, 0)),
                    8,
                )
                cap = worst
                if overrides is not None and t in overrides[li]:
                    cap = _round_up(int(overrides[li][t]), 128)
                    cap = max(cap, caps.get(t, 0), 128)
                    cap = min(cap, worst)
                caps_next[t] = cap
            plans.append((active, dict(caps), caps_next))
            caps = caps_next
        return tuple(plans)

    def _plan_auto(self, observed: Sequence[dict]) -> None:
        """Fold a run's per-layer unclipped unique counts into the cap
        overrides (margin headroom; never shrinking below a previous plan)."""
        old = self._cap_overrides or tuple({} for _ in observed)
        new = []
        for obs, prev in zip(observed, old):
            layer = dict(prev)
            for t, n in obs.items():
                want = int(self._auto_margin * int(n))
                layer[t] = max(want, prev.get(t, 0))
            new.append(layer)
        self._cap_overrides = tuple(new)

    def _compiled(self, seed_cap: int):
        ov = self._cap_overrides
        cache_key = (
            seed_cap,
            None if ov is None
            else tuple(tuple(sorted(layer.items())) for layer in ov),
        )
        if cache_key in self._compiled_cache:
            return self._compiled_cache[cache_key]
        plans = self._plan(
            seed_cap, self._cap_overrides if self._auto_caps else None
        )
        input_type = self.input_type
        weighted_rels = self.weighted_rels
        with_eid = self.with_eid

        @jax.jit
        def run(dev_topos, seeds, num_seeds, key):
            return hetero_multilayer_sample(
                dev_topos, seeds, num_seeds, key, input_type, plans,
                weighted_rels=weighted_rels, with_eid=with_eid,
            )

        self._compiled_cache[cache_key] = run
        return run

    # -- public API ----------------------------------------------------------

    def sample(self, input_nodes) -> HeteroSampleOutput:
        seeds = np.asarray(input_nodes)
        batch = int(seeds.shape[0])
        n = self.topo.num_nodes[self.input_type]
        if batch and (seeds.min() < 0 or seeds.max() >= n):
            raise ValueError(
                f"seed ids must be in [0, {n}); got "
                f"[{seeds.min()}, {seeds.max()}]"
            )
        cap = self._seed_capacity or max(_round_up(batch, 128), 128)
        if batch > cap:
            raise ValueError(f"batch {batch} exceeds seed_capacity {cap}")
        padded = np.full(cap, -1, dtype=np.int32)
        padded[:batch] = seeds
        run = self._compiled(cap)
        self._call += 1
        key = jax.random.fold_in(self._key, self._call)
        dev_seeds = jnp.asarray(padded)
        frontier, counts, layers, overflow, fcounts = run(
            self.dev_topos, dev_seeds, jnp.int32(batch), key
        )
        if self._auto_caps:
            # same discipline as the homogeneous sampler: one scalar sync per
            # call to watch for overflow; regrow is bounded and saturates at
            # worst-case caps (then the clipped result + report stand)
            first_plan = self._cap_overrides is None
            for _ in range(len(self.sizes) + 2):
                if not first_plan and int(overflow) == 0:
                    break
                observed = [
                    {t: int(v) for t, v in layer.items()} for layer in fcounts
                ]
                before = self._cap_overrides
                self._plan_auto(observed)
                if not first_plan and self._cap_overrides == before:
                    break  # saturated: rerunning the same program can't help
                if first_plan and int(overflow) == 0:
                    first_plan = False
                    break  # worst-case first run was exact; keep its result
                run = self._compiled(cap)
                frontier, counts, layers, overflow, fcounts = run(
                    self.dev_topos, dev_seeds, jnp.int32(batch), key
                )
                first_plan = False
        return HeteroSampleOutput(
            frontier, counts, batch, layers, overflow, fcounts
        )
