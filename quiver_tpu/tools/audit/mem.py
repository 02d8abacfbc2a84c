"""graftmem — static per-device memory & layout accounting over lowered
programs.

The comm rule families prove what a program *moves*; this module proves
what it *holds*. Everything operates on the same traced artifacts the
rest of graftaudit walks (a ``ClosedJaxpr`` + the lowered StableHLO
text) — nothing executes, and the only compile anywhere is the optional
XLA cross-check (:func:`xla_memory_stats`), which the CI memory-audit
job and the slow-lane tolerance test run, not the rules.

The accounting model, calibrated against XLA ``memory_analysis()`` on
the 2-device CPU audit mesh:

* **per-device bytes** — a top-level operand counts its global aval
  bytes divided by the product of the mesh-axis sizes its consuming
  ``shard_map`` partitions it over (:func:`arg_divisors` propagates the
  divisor through ``pjit``/``scan``/``while``/``cond`` wrappers; inside a
  shard_map body shapes are already per-device local). Argument and
  output byte totals reproduce XLA's ``argument_size_in_bytes`` /
  ``output_size_in_bytes`` exactly on the simple registry targets (the
  exact-match list lives in tests/test_memaudit.py); multi-output
  programs carry an 8-byte tuple-table entry per output
  (:data:`OUT_TUPLE_ENTRY_BYTES`).
* **peak** — a liveness walk over the eqns: a buffer is born at its
  defining eqn (or entry, for args/consts) and dies after its last use;
  the peak is the largest live set at any program point. A sub-program
  eqn contributes ``max(0, inner_peak - inner_operand_bytes)`` on top of
  the outer live set (XLA reuses the operand buffers across the call
  boundary). ``pallas_call`` is special-cased: its kernel works out of
  VMEM/SMEM blocks (counted by :func:`vmem_usages`), so its HBM
  contribution is its operands/results, not the interpret-mode body.
* **donation** — args the lowering aliased to outputs
  (``tf.aliasing_output`` / ``jax.buffer_donor``, via
  :func:`~quiver_tpu.tools.audit.ir.main_arg_attrs`) are discounted from
  the peak: XLA writes the output into the donated buffer.

The estimate is a fusion-blind upper-shape of the true footprint (XLA
fuses intermediates away, and pads/aligns small buffers up), so it
tracks — not equals — the compiled number; the stated agreement band
lives with the slow-lane test. Budgets (``meta["hbm_budget"]``) gate the
*estimate*, which keeps the rule trace-only and regression-sensitive:
a program that doubles its lowered footprint doubles its estimate.
"""

from __future__ import annotations

import dataclasses
import math
import re

import numpy as np

from . import ir

__all__ = [
    "DEFAULT_VMEM_BUDGET",
    "OUT_TUPLE_ENTRY_BYTES",
    "PADDING_WASTE_LIMIT",
    "REPLICATION_BYTES_LIMIT",
    "MemoryEstimate",
    "VmemUsage",
    "arg_divisors",
    "aval_bytes",
    "estimate_peak",
    "feature_replications",
    "out_divisors",
    "padding_waste",
    "peak_table",
    "vmem_usages",
    "xla_memory_stats",
]

# TPU VMEM is ~16 MB/core; a Pallas kernel whose resident blocks+scratch
# exceed it cannot schedule. Targets override via meta["vmem_budget"].
DEFAULT_VMEM_BUDGET = 16 * 1024 * 1024

# XLA's tuple result table: one pointer entry per output buffer when a
# program returns more than one (single-output programs return the
# buffer bare) — measured against memory_analysis() on the audit mesh.
OUT_TUPLE_ENTRY_BYTES = 8

# a feature-axis-replicated intermediate below this is noise (scalars,
# overflow flags); above it, replication is a real F-times memory cliff.
# Targets override via meta["replication_bytes_limit"].
REPLICATION_BYTES_LIMIT = 1 << 10

# padded all_to_all lanes above this fraction of the shipped buckets are
# a finding: alpha=2 (the default routed budget) sits at 0.5 waste by
# construction, so the default threshold clears it with margin while
# catching runaway caps. Targets override via meta["padding_waste_limit"].
PADDING_WASTE_LIMIT = 0.6


def _itemsize(dt) -> int:
    try:
        return np.dtype(dt).itemsize
    except TypeError:
        # extended dtypes (PRNG key arrays): jax exposes no numpy dtype;
        # a threefry key is 2 x uint32
        return int(getattr(dt, "itemsize", 8))


def _unwrap(obj):
    """ClosedJaxpr/Jaxpr/param-wrapped program -> the raw Jaxpr."""
    j = ir._jaxpr_of(obj)
    if j is not None and not hasattr(j, "invars"):
        j = j.jaxpr
    return j


def aval_bytes(aval, divisor: int = 1) -> int:
    """Per-device bytes of one abstract value under a sharding divisor
    (ceil division: an uneven shard still allocates the padded block)."""
    shape = getattr(aval, "shape", None)
    dt = getattr(aval, "dtype", None)
    if dt is None or shape is None:
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    return int(math.ceil(n * _itemsize(dt) / max(int(divisor), 1)))


def _shard_div(spec, mesh) -> int:
    """shard_map in/out_specs entry (a PartitionSpec) -> the product of
    partitioned mesh-axis sizes, i.e. the per-device byte divisor."""
    div = 1
    for axes in spec:
        if axes is None:
            continue
        for ax in (axes,) if isinstance(axes, str) else axes:
            div *= int(mesh.shape[ax])
    return div


def _operand_pairs(eqn):
    """``[(inner_jaxpr, [(outer_var, inner_var), ...])]`` for sub-program
    eqns whose operand positions correspond shape-for-shape: pjit/cond
    map every operand, scan maps consts+carry (xs are sliced inside),
    while maps the body's consts+carry."""
    prim = eqn.primitive.name
    out = []
    if prim in ("pjit", "closed_call", "core_call") or \
            prim.startswith("custom_"):
        sub = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
        sj = _unwrap(sub)
        if sj is not None and len(sj.invars) == len(eqn.invars):
            out.append((sj, list(zip(eqn.invars, sj.invars))))
    elif prim == "cond":
        ops = eqn.invars[1:]
        for br in eqn.params.get("branches", ()):
            sj = _unwrap(br)
            if sj is not None and len(sj.invars) == len(ops):
                out.append((sj, list(zip(ops, sj.invars))))
    elif prim == "scan":
        sj = _unwrap(eqn.params.get("jaxpr"))
        if sj is not None:
            n = int(eqn.params.get("num_consts", 0)) + int(
                eqn.params.get("num_carry", 0))
            out.append((sj, list(zip(eqn.invars[:n], sj.invars[:n]))))
    elif prim == "while":
        sj = _unwrap(eqn.params.get("body_jaxpr"))
        cn = int(eqn.params.get("cond_nconsts", 0))
        if sj is not None:
            ops = eqn.invars[cn:]
            if len(sj.invars) == len(ops):
                out.append((sj, list(zip(ops, sj.invars))))
    return out


def _names_divisors(jaxpr, select):
    """Shared engine of :func:`arg_divisors` / :func:`out_divisors`:
    chase the given top-level vars through operand-pairing wrappers to
    the shard_map that names their sharding. ``select(eqn)`` returns the
    ``(vars, names, mesh)`` triple to read at a shard_map eqn."""
    j = _unwrap(jaxpr)
    divs: dict = {}
    if j is None:
        return divs

    def _scan(jx, lift):
        for eqn in jx.eqns:
            if eqn.primitive.name == "shard_map":
                evars, names, mesh = select(eqn)
                for v, nm in zip(evars, names):
                    if hasattr(v, "val"):
                        continue
                    key = lift.get(id(v))
                    if key is not None:
                        divs.setdefault(key, _shard_div(nm, mesh))
            else:
                for sj, opairs in _operand_pairs(eqn):
                    inner = {}
                    for ov, iv in opairs:
                        if not hasattr(ov, "val") and id(ov) in lift:
                            inner[id(iv)] = lift[id(ov)]
                    if inner:
                        _scan(sj, inner)

    _scan(j, {id(v): id(v) for v in j.invars})
    return divs


def arg_divisors(jaxpr) -> dict:
    """``{id(top_level_invar): divisor}`` — the per-device byte divisor
    each argument's consuming shard_map declares for it, propagated
    through pjit/scan/while/cond wrappers. Args no shard_map consumes
    (replicated operands) are absent — divisor 1."""
    return _names_divisors(
        jaxpr,
        lambda eqn: (eqn.invars, eqn.params["in_specs"],
                     eqn.params["mesh"]),
    )


def out_divisors(jaxpr) -> dict:
    """``{id(top_level_outvar): divisor}`` via shard_map ``out_specs``,
    propagated through pjit outvar positions."""
    j = _unwrap(jaxpr)
    divs: dict = {}
    if j is None:
        return divs

    def _scan(jx, lift):
        for eqn in jx.eqns:
            if eqn.primitive.name == "shard_map":
                mesh = eqn.params["mesh"]
                for v, nm in zip(eqn.outvars, eqn.params["out_specs"]):
                    key = lift.get(id(v))
                    if key is not None:
                        divs.setdefault(key, _shard_div(nm, mesh))
            elif eqn.primitive.name == "pjit":
                sj = _unwrap(eqn.params["jaxpr"])
                if sj is not None and \
                        len(sj.outvars) == len(eqn.outvars):
                    inner = {}
                    for ov, iv in zip(eqn.outvars, sj.outvars):
                        if id(ov) in lift and not hasattr(iv, "val"):
                            inner[id(iv)] = lift[id(ov)]
                    if inner:
                        _scan(sj, inner)

    _scan(j, {id(v): id(v) for v in j.outvars if not hasattr(v, "val")})
    return divs


_TENSOR_RE = re.compile(r"tensor<([0-9x]*?)x?([a-z]+[0-9]*)>")
_DEVICES_RE = re.compile(r"devices=\[([0-9,]+)\]")

_MLIR_ITEMSIZE = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8": 1,
    "i64": 8, "ui64": 8, "i32": 4, "ui32": 4, "i16": 2, "ui16": 2,
    "i8": 1, "ui8": 1, "i1": 1,
}


def _mlir_arg_bytes(arg_text: str) -> int:
    """Per-device bytes of one lowered ``@main`` argument, from its
    MLIR text: the ``tensor<...>`` type (global shape) divided by the
    device product of any ``mhlo.sharding`` attr on the arg."""
    m = _TENSOR_RE.search(arg_text)
    if m is None:
        return 0
    dims, dt = m.group(1), m.group(2)
    n = 1
    for d in dims.split("x"):
        if d:
            n *= int(d)
    nbytes = n * _MLIR_ITEMSIZE.get(dt, 8)
    dm = _DEVICES_RE.search(arg_text)
    if dm is not None:
        div = 1
        for d in dm.group(1).split(","):
            div *= int(d)
        nbytes = int(math.ceil(nbytes / max(div, 1)))
    return nbytes


def _donated_bytes(mlir_text: str) -> int:
    """Per-device bytes of every ``@main`` argument the lowering donated
    (``tf.aliasing_output`` / ``jax.buffer_donor``), read straight off
    the MLIR arg text — the jaxpr's invars can NOT be zipped against the
    lowered args (``keep_unused=False`` prunes dead operands), and the
    arg text carries both the type and the sharding in one place.
    Matches XLA's ``alias_size_in_bytes`` on the donating targets."""
    m = ir._MAIN_RE.search(mlir_text)
    if m is None:
        return 0
    total = 0
    for arg in ir._split_top_level(m.group(1)):
        if "tf.aliasing_output" in arg or "jax.buffer_donor" in arg:
            total += _mlir_arg_bytes(arg)
    return total


@dataclasses.dataclass(frozen=True)
class MemoryEstimate:
    """Per-device static memory model of one lowered program."""

    peak_bytes: int  # liveness-walk peak, donation-discounted
    arg_bytes: int  # argument footprint (matches XLA on exact targets)
    out_bytes: int  # output footprint incl. the tuple-table entries
    aliased_bytes: int  # donated-arg bytes discounted from the peak
    n_args: int
    n_outputs: int


def _kernel_block_bytes(eqn) -> int:
    """HBM-side stand-in for a pallas_call body: the VMEM/SMEM-resident
    blocks (the kernel's working set — everything else it touches stays
    in place as the call's operands/results)."""
    kj = _unwrap(eqn.params.get("jaxpr"))
    total = 0
    if kj is None:
        return 0
    for kv in kj.invars:
        ms = str(getattr(kv.aval, "memory_space", ""))
        if "vmem" in ms or "smem" in ms:
            total += aval_bytes(kv.aval)
    return total


def _walk_peak(jaxpr, div_in=None) -> int:
    """The liveness walk: peak live bytes over one jaxpr's program
    points, recursing into sub-programs (see module docstring)."""
    j = _unwrap(jaxpr)
    if j is None:
        return 0
    divs: dict = {}
    if div_in is None:
        div_in = [1] * len(j.invars)
    for v, d in zip(j.invars, div_in):
        divs[id(v)] = d

    def b(v):
        if hasattr(v, "val"):  # literal
            return 0
        return aval_bytes(v.aval, divs.get(id(v), 1))

    last_use: dict = {}
    for i, eqn in enumerate(j.eqns):
        for v in eqn.invars:
            if not hasattr(v, "val"):
                last_use[id(v)] = i
    for v in j.outvars:
        if not hasattr(v, "val"):
            last_use[id(v)] = len(j.eqns)

    live = {id(v): b(v) for v in list(j.invars) + list(j.constvars)}
    peak = sum(live.values())

    for i, eqn in enumerate(j.eqns):
        prim = eqn.primitive.name
        inner_extra = 0
        out_div = [1] * len(eqn.outvars)
        if prim == "pallas_call":
            inner_extra = _kernel_block_bytes(eqn)
        elif prim == "shard_map":
            mesh = eqn.params["mesh"]
            inner = eqn.params["jaxpr"]
            ij = _unwrap(inner)
            # body shapes are already per-device local -> divisor 1
            inner_peak = _walk_peak(inner, [1] * len(ij.invars))
            in_b = sum(
                aval_bytes(v.aval, _shard_div(nm, mesh))
                for v, nm in zip(eqn.invars, eqn.params["in_specs"])
                if not hasattr(v, "val"))
            inner_extra = max(0, inner_peak - in_b)
            out_div = [_shard_div(nm, mesh)
                       for nm in eqn.params["out_specs"]]
        else:
            pair_divs: dict = {}
            for sj_, opairs in _operand_pairs(eqn):
                for ov, iv in opairs:
                    if not hasattr(ov, "val"):
                        pair_divs[id(iv)] = divs.get(id(ov), 1)
            subpeaks = []
            for _k, _i, sub in ir._sub_jaxprs(eqn):
                sj = _unwrap(sub)
                din = [pair_divs.get(id(v), 1) for v in sj.invars]
                subpeaks.append(_walk_peak(sub, din))
            if subpeaks:
                in_b = sum(b(v) for v in eqn.invars)
                inner_extra = max(0, max(subpeaks) - in_b)
        for v, d in zip(eqn.outvars, out_div):
            divs[id(v)] = d
        out_b = sum(b(v) for v in eqn.outvars)
        peak = max(peak, sum(live.values()) + out_b + inner_extra)
        for v in eqn.outvars:
            live[id(v)] = b(v)
        for v in eqn.invars:
            if not hasattr(v, "val") and last_use.get(id(v)) == i:
                live.pop(id(v), None)
        peak = max(peak, sum(live.values()))
    return peak


def estimate_peak(closed_jaxpr, mlir: str | None = None) -> MemoryEstimate:
    """Static per-device memory model of a traced program: argument and
    output footprints under the audit mesh's shardings, plus the
    liveness-walk peak (donation-discounted when the lowered text is
    provided — an aliased arg's buffer is reused for its output)."""
    top = _unwrap(closed_jaxpr)
    if top is None:
        return MemoryEstimate(0, 0, 0, 0, 0, 0)
    adiv = arg_divisors(closed_jaxpr)
    odiv = out_divisors(closed_jaxpr)
    din = [adiv.get(id(v), 1) for v in top.invars]
    arg_bytes = sum(
        aval_bytes(v.aval, adiv.get(id(v), 1)) for v in top.invars
    )
    outs = [v for v in top.outvars if not hasattr(v, "val")]
    out_bytes = sum(aval_bytes(v.aval, odiv.get(id(v), 1)) for v in outs)
    if len(outs) > 1:
        out_bytes += OUT_TUPLE_ENTRY_BYTES * len(outs)
    peak = _walk_peak(closed_jaxpr, din)
    aliased = _donated_bytes(mlir) if mlir else 0
    return MemoryEstimate(
        peak_bytes=max(0, peak - aliased),
        arg_bytes=arg_bytes,
        out_bytes=out_bytes,
        aliased_bytes=aliased,
        n_args=len(top.invars),
        n_outputs=len(outs),
    )


# -- VMEM accounting ----------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class VmemUsage:
    """One Pallas kernel's static on-core footprint: every VMEM/SMEM
    memory-ref the kernel body binds (grid blocks + scratch buffers)."""

    name: str
    path: tuple
    vmem_bytes: int
    smem_bytes: int
    buffers: tuple  # ("vmem int32[8,128]", ...) for the finding message

    def __str__(self):
        loc = "/".join(self.path) or "top"
        return (f"{self.name} @ {loc}: vmem={self.vmem_bytes}B "
                f"smem={self.smem_bytes}B [{', '.join(self.buffers)}]")


def vmem_usages(closed_jaxpr) -> list:
    """Static VMEM/scratch accounting per ``pallas_call`` in a program.

    The kernel jaxpr's invars are memory-refs carrying their space
    (``vmem`` grid blocks and scratch, ``smem`` scalar prefetch, ``any``
    un-staged HBM tables, ``semaphore_mem`` DMA semaphores); the VMEM
    total is what must fit on-core simultaneously — window lanes, gather
    tiles and scratch all at once."""
    out = []
    for eqn, path in ir.iter_eqns(closed_jaxpr):
        if eqn.primitive.name != "pallas_call":
            continue
        kj = _unwrap(eqn.params.get("jaxpr"))
        if kj is None:
            continue
        vmem = smem = 0
        bufs = []
        for kv in kj.invars:
            ms = str(getattr(kv.aval, "memory_space", ""))
            nb = aval_bytes(kv.aval)
            shape = tuple(getattr(kv.aval, "shape", ()))
            dt = getattr(kv.aval, "dtype", "?")
            if "vmem" in ms:
                vmem += nb
                bufs.append(f"vmem {dt}{list(shape)}")
            elif "smem" in ms:
                smem += nb
                bufs.append(f"smem {dt}{list(shape)}")
        name = getattr(eqn.params.get("name_and_src_info"), "name",
                       None) or "pallas_call"
        out.append(VmemUsage(name=str(name), path=path, vmem_bytes=vmem,
                             smem_bytes=smem, buffers=tuple(bufs)))
    return out


# -- replication detection ----------------------------------------------------

_GATHER_PRIMS = frozenset({"all_gather", "all_gather_invariant"})


def feature_replications(closed_jaxpr, axis: str = "feature",
                         limit: int = REPLICATION_BYTES_LIMIT) -> list:
    """Intermediates whose sharding degenerates to full replication
    along ``axis``: every gather-family collective over the axis whose
    result is at least ``limit`` bytes — the exact op that turns a
    "sharded" operand into an F-times-per-device buffer. Each entry
    carries a backward-slice attribution naming the producer of the
    gathered operand."""
    out = []

    def _walk(jx, path):
        j = _unwrap(jx)
        if j is None:
            return
        defmap = {}
        for eqn in j.eqns:
            for ov in eqn.outvars:
                defmap[id(ov)] = eqn
        for eqn in j.eqns:
            if eqn.primitive.name in _GATHER_PRIMS and \
                    axis in ir._axes_of(eqn):
                res = eqn.outvars[0].aval
                nbytes = aval_bytes(res)
                if nbytes >= int(limit):
                    op = eqn.invars[0]
                    src = defmap.get(id(op))
                    producer = (src.primitive.name if src is not None
                                else "a program input")
                    out.append({
                        "prim": eqn.primitive.name,
                        "path": path,
                        "axis": axis,
                        "shape": tuple(getattr(res, "shape", ())),
                        "dtype": str(getattr(res, "dtype", "?")),
                        "bytes": nbytes,
                        "producer": producer,
                    })
            for _k, i, sub in ir._sub_jaxprs(eqn):
                hop = (f"{eqn.primitive.name}[{i}]"
                       if eqn.primitive.name == "cond"
                       else eqn.primitive.name)
                _walk(sub, path + (hop,))

    _walk(closed_jaxpr, ())
    return out


# -- padding waste ------------------------------------------------------------


def padding_waste(built) -> list:
    """Lanes-vs-payload accounting per routed all_to_all of a target
    declaring a comm model (``meta["comm"]``): the shipped buckets are
    ``F * cap`` lanes, the real payload is ``local_len * (1 - h0)``
    requests, and the difference is bought with real HBM and wire bytes.
    Returns one entry per all_to_all with its waste fraction."""
    comm = built.meta.get("comm")
    if comm is None:
        return []
    F = int(comm["feature_shards"])
    L = int(comm["local_len"])
    h0 = float(comm.get("h0", 0.0))
    payload = L * (1.0 - h0)
    out = []
    for c in ir.collectives_of(built.jaxpr):
        if c.prim != "all_to_all" or len(c.shape) < 2:
            continue
        lanes = int(c.shape[0]) * int(c.shape[1])
        waste = 1.0 - min(payload / lanes, 1.0) if lanes else 0.0
        out.append({
            "collective": str(c),
            "cap": int(c.shape[1]),
            "lanes": lanes,
            "payload_lanes": payload,
            "waste": waste,
        })
    return out


# -- XLA cross-check + table --------------------------------------------------

_XLA_STATS: dict = {}


def xla_memory_stats(target) -> dict | None:
    """Compile one registry target on the audit mesh and return XLA's
    buffer-assignment totals (``memory_analysis()``), or None when the
    backend exposes none. This is the ONLY compiling entry point in the
    auditor — the rules never call it; the memory-audit CI job and the
    slow-lane tolerance test do."""
    name = getattr(target, "name", str(target))
    if name in _XLA_STATS:
        return _XLA_STATS[name]
    stats = None
    try:
        compiled = target.builder().lower().compile()
        ma = compiled.memory_analysis()
        if ma is not None:
            arg = int(ma.argument_size_in_bytes)
            outb = int(ma.output_size_in_bytes)
            temp = int(ma.temp_size_in_bytes)
            alias = int(ma.alias_size_in_bytes)
            stats = {
                "argument_bytes": arg,
                "output_bytes": outb,
                "temp_bytes": temp,
                "alias_bytes": alias,
                "peak_bytes": arg + outb + temp - alias,
            }
    except Exception:  # noqa: BLE001 — cross-check is best-effort by contract
        stats = None
    _XLA_STATS[name] = stats
    return stats


def clear_xla_cache() -> None:
    _XLA_STATS.clear()


def peak_table(names=None, with_xla: bool = False) -> list:
    """Per-target memory rows for the CLI table, the memory-audit
    scoreboard job and ``CostModel.calibrate_hbm``: the static estimate,
    the declared budget and its headroom, optionally joined with the
    compiled XLA stats (``with_xla=True`` compiles every row)."""
    from .audit_targets import REGISTRY, build

    rows = []
    for name in (names or list(REGISTRY)):
        t = REGISTRY[name]
        built = build(name)
        est = estimate_peak(built.jaxpr, built.mlir)
        budget = built.meta.get("hbm_budget")
        row = {
            "target": name,
            "peak_bytes": est.peak_bytes,
            "arg_bytes": est.arg_bytes,
            "out_bytes": est.out_bytes,
            "aliased_bytes": est.aliased_bytes,
            "hbm_budget": None if budget is None else int(budget),
            "headroom_bytes": (None if budget is None
                               else int(budget) - est.peak_bytes),
        }
        if with_xla:
            stats = xla_memory_stats(t)
            row["xla_peak_bytes"] = (None if stats is None
                                     else stats["peak_bytes"])
            row["xla_ratio"] = (
                None if not stats or not stats["peak_bytes"]
                else round(est.peak_bytes / stats["peak_bytes"], 3))
        rows.append(row)
    return rows


def format_peak_table(rows) -> str:
    """Render :func:`peak_table` rows as the fixed-width budget table the
    memory-audit CI job prints into its log."""
    with_xla = any("xla_peak_bytes" in r for r in rows)
    head = (f"{'target':26s} {'est-peak':>10s} {'args':>8s} {'out':>7s} "
            f"{'budget':>8s} {'headroom':>9s}")
    if with_xla:
        head += f" {'xla-peak':>9s} {'ratio':>6s}"
    lines = [head]
    for r in rows:
        budget = r["hbm_budget"]
        line = (f"{r['target']:26s} {r['peak_bytes']:10d} "
                f"{r['arg_bytes']:8d} {r['out_bytes']:7d} "
                f"{'-' if budget is None else budget:>8} "
                f"{'-' if r['headroom_bytes'] is None else r['headroom_bytes']:>9}")
        if with_xla:
            xp = r.get("xla_peak_bytes")
            ratio = r.get("xla_ratio")
            line += (f" {'-' if xp is None else xp:>9}"
                     f" {'-' if ratio is None else ratio:>6}")
        lines.append(line)
    return "\n".join(lines)
