"""From a profiler trace (``.xplane.pb``) to device time by scope.

``load`` turns the file into plain records with nothing but JAX's own
``ProfileData``; everything after it is arithmetic on intervals, which the
tests check on a small recorded trace:

* an op's **self time** is its duration less the ops nested inside it on
  the same line (a ``while`` spans its body's ops), so times by scope add
  up to the busy time and nothing is counted twice;
* **busy** is the union of the op intervals of a device inside the traced
  window, averaged over the devices; idle is the rest of the window;
* an **idle gap** is attributed to the benchmark's host span that covers
  it (``chipbench.step``, ``chipbench.loss_read``, ...);
* a collective's **exposed** time is its self time less what ops on other
  lines of the same device compute meanwhile.
"""

from __future__ import annotations

import dataclasses
import gzip
import json
import re

__all__ = ["Op", "Span", "Trace", "load", "load_json", "dump_json",
           "describe"]

OP_LINES = re.compile(r"^XLA Ops")
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+")
SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"
# an instruction is named after its opcode (all-reduce.3) or after the jax
# primitive that made it (all_to_all.8)
COLLECTIVE = re.compile(
    r"^(ragged[-_])?(all[-_]reduce|all[-_]to[-_]all|all[-_]gather"
    r"|reduce[-_]scatter|collective[-_]permute|collective[-_]broadcast)")


@dataclasses.dataclass
class Op:
    device: str
    line: str
    name: str     # the HLO op's name, e.g. fusion.407
    path: str     # the op's scope path (op_name metadata), "" if none
    start: int    # ns
    end: int      # ns
    self_ns: int = 0


@dataclasses.dataclass
class Span:
    name: str
    start: int
    end: int


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        elif e > s:
            out.append([s, e])
    return out


def _overlap(s: int, e: int, union: list) -> int:
    return sum(max(0, min(e, b) - max(s, a)) for a, b in union
               if a < e and b > s)


def _clip(ops: list, lo: int, hi: int) -> list:
    return [(max(o.start, lo), min(o.end, hi)) for o in ops
            if o.end > lo and o.start < hi]


@dataclasses.dataclass
class Trace:
    ops: list     # every device op; self time is filled in
    spans: list   # the benchmark's host spans
    # derived: ``devices``, the window ``lo``..``hi`` and the ops ``inside`` it

    def __post_init__(self):
        by_line: dict = {}
        for op in self.ops:
            by_line.setdefault((op.device, op.line), []).append(op)
        for ops in by_line.values():
            ops.sort(key=lambda o: (o.start, -o.end))
            stack: list = []
            for op in ops:
                op.self_ns = op.end - op.start
                while stack and stack[-1].end <= op.start:
                    stack.pop()
                if stack:
                    stack[-1].self_ns -= min(op.end, stack[-1].end) - op.start
                stack.append(op)
        self.devices = sorted({op.device for op in self.ops})
        # the traced window: the ``chipbench.window`` span where the run
        # recorded one, else first op start to last op end
        self.lo, self.hi = next(
            ((s.start, s.end) for s in self.spans if s.name == WINDOW_SPAN),
            (min((op.start for op in self.ops), default=0),
             max((op.end for op in self.ops), default=0)))
        self.inside = [op for op in self.ops
                       if op.start >= self.lo and op.end <= self.hi]

    def _busy(self, device: str) -> list:
        return _union(_clip([o for o in self.ops if o.device == device],
                            self.lo, self.hi))

    def busy_s(self) -> float:
        """Seconds with an op running, averaged over the devices."""
        total = sum(e - s for dev in self.devices for s, e in self._busy(dev))
        return total / max(len(self.devices), 1) / 1e9

    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def time_by(self, keep) -> float:
        """Self seconds of the window's ops that ``keep(op)`` accepts,
        averaged over the devices."""
        total = sum(op.self_ns for op in self.inside if keep(op))
        return total / max(len(self.devices), 1) / 1e9

    def scope_s(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return self.time_by(lambda op: bool(rx.search(op.path)))

    def unclaimed_s(self, patterns: list) -> float:
        rxs = [re.compile(p) for p in patterns]
        return self.time_by(
            lambda op: not any(rx.search(op.path) for rx in rxs))

    def collective_exposed_s(self) -> float:
        """Self seconds of collective ops during which no other line of
        the same device computes, averaged over the devices."""
        total = 0
        for dev in self.devices:
            mine = [o for o in self.inside if o.device == dev]
            compute: dict = {}  # line -> union of the other lines' compute
            for op in mine:
                if not COLLECTIVE.match(op.name):
                    continue
                if op.line not in compute:
                    compute[op.line] = _union([
                        (o.start, o.end) for o in mine
                        if o.line != op.line
                        and not COLLECTIVE.match(o.name)])
                total += max(0, op.self_ns - _overlap(
                    op.start, op.end, compute[op.line]))
        return total / max(len(self.devices), 1) / 1e9

    def top_ops(self, n: int = 10) -> list:
        """[name, seconds] of the ops with most self time, one device's
        worth (the mean over the devices)."""
        acc: dict = {}
        for op in self.inside:
            key = f"{op.name} {op.path}".strip()[:120]
            acc[key] = acc.get(key, 0) + op.self_ns
        ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / max(len(self.devices), 1) / 1e9] for k, v in ranked]

    def idle_gaps(self, n: int = 10) -> list:
        """[span name, seconds] of the first device's idle time inside the
        window, by the host span that covers it."""
        if not self.devices:
            return []
        gaps, at = [], self.lo
        for s, e in self._busy(self.devices[0]) + [[self.hi, self.hi]]:
            if s > at:
                gaps.append((at, s))
            at = max(at, e)
        acc: dict = {}
        # the loop's spans follow one another on one thread: sweep both
        # sorted lists once
        spans = sorted((s for s in self.spans if s.name != WINDOW_SPAN),
                       key=lambda s: s.start)
        first = 0
        for s, e in gaps:
            left = e - s
            while first < len(spans) and spans[first].end <= s:
                first += 1
            i = first
            while i < len(spans) and spans[i].start < e:
                part = max(0, min(e, spans[i].end) - max(s, spans[i].start))
                acc[spans[i].name] = acc.get(spans[i].name, 0) + part
                left -= part
                i += 1
            if left > 0:
                acc["outside any span"] = acc.get("outside any span", 0) + left
        ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e9] for k, v in ranked]


def _varint(buf, i: int):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if not byte & 0x80:
            return value, i


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint,
    bytes for everything else. The trace's own format, read with no schema
    library: only field numbers of xplane.proto and hlo.proto are used."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} in a trace")
        yield key >> 3, value


def hlo_scope_paths(raw: bytes) -> dict:
    """HLO instruction name -> its ``op_name`` metadata (the scope path
    that ``jax.named_scope`` and the flax module names write), from the
    HLO protos that the profiler keeps in the ``/host:metadata`` plane.

    XSpace.planes=1; XPlane.name=2, .event_metadata=4 (map value=2);
    XEventMetadata.stats=5; XStat.bytes_value=6; HloProto.hlo_module=1;
    HloModuleProto.computations=3; HloComputationProto.instructions=2;
    HloInstructionProto.name=1, .metadata=7; OpMetadata.op_name=2."""
    protos = []
    for f, plane in _fields(raw):
        if f != 1:
            continue
        parts = list(_fields(plane))
        if not any(pf == 2 and pv == b"/host:metadata" for pf, pv in parts):
            continue
        for pf, entry in parts:
            if pf != 4:
                continue
            for ef, meta in _fields(entry):
                if ef != 2:
                    continue
                for mf, stat in _fields(meta):
                    if mf == 5:
                        protos += [v for sf, v in _fields(stat) if sf == 6]
    paths: dict = {}
    for proto in sorted(protos, key=len):  # the largest program wins a name
        for f, module in _fields(proto):
            if f != 1:
                continue
            for mf, comp in _fields(module):
                if mf != 3:
                    continue
                for cf, inst in _fields(comp):
                    if cf != 2:
                        continue
                    name = path = None
                    for nf, value in _fields(inst):
                        if nf == 1:
                            name = value.decode()
                        elif nf == 7:
                            path = next((v.decode() for of, v in
                                         _fields(value) if of == 2), None)
                    if name and path:
                        paths[name] = path
    return paths


_OP_NAME = re.compile(r"^%?([^\s=]+)")


def load(path: str) -> Trace:
    """Read an ``.xplane.pb``: the ops of every device plane's op lines
    with their scope paths, and the benchmark's own spans from the host
    planes."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    paths = hlo_scope_paths(raw)
    data = ProfileData.from_serialized_xspace(raw)
    ops, spans = [], []
    for plane in data.planes:
        device = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if device and OP_LINES.match(line.name):
                for ev in line.events:
                    # the event is named by the instruction's whole text:
                    # "%fusion.373 = s32[...] fusion(...)"
                    name = _OP_NAME.match(ev.name).group(1)
                    start = int(ev.start_ns)
                    ops.append(Op(plane.name, line.name, name,
                                  paths.get(name, ""), start,
                                  start + int(ev.duration_ns)))
            elif not device:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        start = int(ev.start_ns)
                        spans.append(Span(ev.name, start,
                                          start + int(ev.duration_ns)))
    return Trace(ops, spans)


def dump_json(trace: Trace, path: str) -> None:
    """A trace as gzipped JSON: what the tests keep of a recorded trace."""
    with gzip.open(path, "wt") as f:
        json.dump({
            "ops": [[o.device, o.line, o.name, o.path, o.start, o.end]
                    for o in trace.ops],
            "spans": [[s.name, s.start, s.end] for s in trace.spans],
        }, f)


def load_json(path: str) -> Trace:
    with gzip.open(path, "rt") as f:
        raw = json.load(f)
    return Trace([Op(*o) for o in raw["ops"]],
                 [Span(*s) for s in raw["spans"]])


def describe(path: str, limit: int = 12) -> str:
    """Planes, lines and a few events with their stats: what to read by
    hand before trusting a reader on a new device or JAX version."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  LINE {line.name!r}: {len(events)} events")
            for ev in events[:limit]:
                out.append(f"    {ev.name} start={ev.start_ns} "
                           f"dur={ev.duration_ns} {dict(ev.stats)}")
    return "\n".join(out)
