"""The program's own stage names in a traced window.

The program names its stages in two ways, both recorded by the
``--trace 1`` run's profiler session on one clock:

* device scopes: ``jax.named_scope`` segments of each operation's
  ``op_name`` path (``round1``, ``round2``, ``seed``, ``update``,
  ``sensitivity``): an ``XLA Ops`` event's ``tf_op`` metadata stat
  where the trace has one, else the op_name of its instruction in the
  HLO of its program (found through the ``XLA Modules`` line), which the
  profiler stores with the trace;
* host spans: ``jax.profiler.TraceAnnotation`` events (``round1``,
  ``allocate``, ``round2``, ``final_solve``) whose arguments give the
  rows each stage sweeps, known from shapes.

:func:`of` re-reads the run's trace and caches the result on the context.
Time in a scope is the union of the intervals of the operations in it, so
a ``while`` operation and the body operations nested inside it count
once. A program without the scopes or spans yields None from every
reader, never an error.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
import struct
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from chipbench.trace import _union as union_ns

STAGES = ("round1", "allocate", "round2", "final_solve")
_WRAPPED = re.compile(r"^[\w.-]+\((.*)\)$")


@dataclasses.dataclass
class ScopedOp:
    start: float               # ns
    dur: float                 # ns
    path: Tuple[str, ...]      # op_name segments, transforms unwrapped


@dataclasses.dataclass
class HostSpan:
    name: str
    start: float               # ns
    dur: float                 # ns
    args: Dict[str, float]

    @property
    def end(self) -> float:
        return self.start + self.dur


def segments(op_name: str) -> Tuple[str, ...]:
    """The scope names of an ``op_name`` path. A transform wraps the
    first name pushed under it (``vmap(sensitivity)``,
    ``vmap(jit(_lloyd))``); it is unwrapped to the name itself, so
    ``jit(round1_local_solves)`` reads ``round1_local_solves`` and never
    matches ``round1``."""
    out = []
    for seg in op_name.split("/"):
        m = _WRAPPED.match(seg)
        while m:
            seg = m.group(1)
            m = _WRAPPED.match(seg)
        out.append(seg)
    return tuple(out)


def overlap_ns(a: Sequence[Tuple[int, int]],
               b: Sequence[Tuple[int, int]]) -> int:
    """Length of the intersection of two sorted, disjoint interval lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


class Scopes:
    """Device operations with their scope paths, per device, and the
    program's host spans with their arguments."""

    def __init__(self, ops: Dict[int, List[ScopedOp]],
                 spans: List[HostSpan]):
        self.ops, self.spans = ops, spans

    def scope_ns(self, t0: int, t1: int, all_of: Sequence[str] = (),
                 none_of: Sequence[str] = ()) -> Optional[float]:
        """Nanoseconds per device, averaged over devices, in the union of
        the operations whose path holds every scope of ``all_of`` and none
        of ``none_of``, clipped to [t0, t1]; None where no operation in
        the window matches."""
        per, found = [], False
        for dev_ops in self.ops.values():
            iv = []
            for o in dev_ops:
                if (all(s in o.path for s in all_of)
                        and not any(s in o.path for s in none_of)):
                    s, e = max(o.start, t0), min(o.start + o.dur, t1)
                    if e > s:
                        iv.append((s, e))
            found |= bool(iv)
            per.append(sum(e - s for s, e in union_ns(iv)))
        if not found:
            return None
        return sum(per) / len(per)

    def spans_in(self, names: Sequence[str], t0: int,
                 t1: int) -> List[HostSpan]:
        """Host spans of these names that start inside [t0, t1]."""
        return [s for s in self.spans
                if s.name in names and t0 <= s.start < t1]


# The trace is read from the ``.xplane.pb`` file itself: JAX's
# ``ProfileData`` shows an event's own stats (on a TPU op, only its
# times), neither its metadata's stats (``tf_op``) nor the HLO that the
# profiler stores in the ``/host:metadata`` plane. Field numbers are those
# of ``tsl/profiler/protobuf/xplane.proto`` and ``xla/service/hlo.proto``.


def _varint(buf, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """(field number, value) of a protobuf message: an int for a varint,
    the raw bytes for every other wire type."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")
        yield key >> 3, v


def _str(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _stat(buf, names: Dict[int, str]) -> Tuple[str, object]:
    """An ``XStat`` as (name, value); a ref value is a stat name."""
    key, val = "", None
    for f, v in _fields(buf):
        if f == 1:
            key = names.get(v, "")
        elif f == 2:
            val = struct.unpack("<d", v)[0]
        elif f == 3:
            val = v
        elif f == 4:
            val = v - (1 << 64) if v >= 1 << 63 else v
        elif f in (5, 6):
            val = _str(v) if f == 5 else bytes(v)
        elif f == 7:
            val = names.get(v, "")
    return key, val


def _entry(buf) -> Tuple[int, object]:
    """A map entry: (key, value bytes)."""
    key, val = 0, b""
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


class _Plane:
    """An ``XPlane``: its name, lines, event names and stat names, with
    each event's metadata stats decoded on demand."""

    def __init__(self, buf):
        self.name, self.lines = "", []
        self._meta: Dict[int, object] = {}
        self.stat_names: Dict[int, str] = {}
        for f, v in _fields(buf):
            if f == 2:
                self.name = _str(v)
            elif f == 3:
                self.lines.append(v)
            elif f == 4:
                k, meta = _entry(v)
                self._meta[k] = meta
            elif f == 5:
                k, meta = _entry(v)
                self.stat_names[k] = next(
                    (_str(x) for g, x in _fields(meta) if g == 2), "")
        self.event_names = {k: next((_str(x) for g, x in _fields(m)
                                     if g == 2), "")
                            for k, m in self._meta.items()}

    def event_stats(self, meta_id: int) -> Dict[str, object]:
        return dict(_stat(x, self.stat_names)
                    for g, x in _fields(self._meta.get(meta_id, b""))
                    if g == 5)

    def events(self, line_names: Optional[Sequence[str]] = None):
        """(line name, name, start ns, duration ns, stats bytes, metadata
        id) of every event, on the lines named (all when None)."""
        for line in self.lines:
            name, t0, evs = "", 0, []
            for f, v in _fields(line):
                if f == 2:
                    name = _str(v)
                elif f == 3:
                    t0 = v
                elif f == 4:
                    evs.append(v)
            if line_names is not None and name not in line_names:
                continue
            for ev in evs:
                mid = off = dur = 0
                stats = []
                for f, v in _fields(ev):
                    if f == 1:
                        mid = v
                    elif f == 2:
                        off = v
                    elif f == 3:
                        dur = v
                    elif f == 4:
                        stats.append(v)
                yield (name, self.event_names.get(mid, ""),
                       t0 + off / 1000.0, dur / 1000.0, stats, mid)


def hlo_op_names(plane: "_Plane") -> Dict[str, Dict[str, str]]:
    """The op_name of every instruction of every program the profiler
    stored in the ``/host:metadata`` plane (``Hlo Proto`` stats), keyed
    by program (``jit_f(5)`` and ``jit_f``), then by instruction name."""
    out: Dict[str, Dict[str, str]] = {}
    for mid, program in plane.event_names.items():
        proto = plane.event_stats(mid).get("Hlo Proto")
        if not isinstance(proto, bytes):
            continue
        table = {}
        module = next((v for f, v in _fields(proto) if f == 1), b"")
        for f, comp in _fields(module):
            if f != 3:
                continue
            for g, ins in _fields(comp):
                if g != 2:
                    continue
                name = op_name = ""
                for h, v in _fields(ins):
                    if h == 1:
                        name = _str(v)
                    elif h == 7:
                        op_name = next((_str(x) for k, x in _fields(v)
                                        if k == 2), "")
                if op_name:
                    table[name] = op_name
        out[program] = table
        out.setdefault(_program(program), table)
    return out


def _program(name: str) -> str:
    """``jit_f(5)`` -> ``jit_f``."""
    return re.sub(r"\(\d+\)$", "", name)


def _instruction(name: str) -> str:
    """``%fusion.16 = f32[...] fusion(...)`` -> ``fusion.16``."""
    m = re.match(r"%?([^\s=]+)", name)
    return m.group(1) if m else name


def from_xspace(buf) -> Scopes:
    """Scopes and spans of a serialized ``XSpace``. An op's path is its
    metadata's ``tf_op`` stat (``<op_name>:<op_type>``) where the trace
    has one, else the op_name that its program's stored HLO gives the
    instruction; the two agree wherever both exist."""
    planes = [_Plane(v) for f, v in _fields(memoryview(buf)) if f == 1]
    meta = next((p for p in planes if p.name == "/host:metadata"), None)
    tables = None
    ops: Dict[int, List[ScopedOp]] = {}
    spans: List[HostSpan] = []
    for plane in planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        if m:
            out = ops.setdefault(int(m.group(1)), [])
            mods = sorted((s, name) for _, name, s, _, _, _ in
                          plane.events(("XLA Modules",)))
            starts = [s for s, _ in mods]
            tf_op: Dict[int, str] = {}
            for _, name, start, dur, _, mid in plane.events(("XLA Ops",)):
                if mid not in tf_op:
                    tf_op[mid] = str(plane.event_stats(mid).get("tf_op")
                                     or "").rsplit(":", 1)[0]
                path = tf_op[mid]
                if not path:
                    if tables is None:
                        tables = hlo_op_names(meta) if meta else {}
                    i = bisect.bisect_right(starts, start) - 1
                    module = mods[i][1] if i >= 0 else ""
                    path = (tables.get(module)
                            or tables.get(_program(module))
                            or {}).get(_instruction(name))
                if path:
                    out.append(ScopedOp(start, dur, segments(path)))
        elif plane.name.startswith("/host:"):
            for _, name, start, dur, stats, _ in plane.events():
                if name in STAGES:
                    args = dict(_stat(x, plane.stat_names) for x in stats)
                    spans.append(HostSpan(name, start, dur, {
                        k: v for k, v in args.items()
                        if isinstance(v, (int, float))}))
    spans.sort(key=lambda s: s.start)
    return Scopes(ops, spans)


def trace_dir(ctx) -> str:
    from chipbench.harness import OUT
    return os.path.join(ctx.cell.root, OUT, "trace", ctx.cell.name)


def of(ctx) -> Optional[Scopes]:
    """The run's scopes and spans, read once per run; None without a
    traced window, or where its trace cannot be read (every metric of
    this module is then left out of the result line)."""
    if ctx.reduced is None:
        return None
    if not hasattr(ctx, "scopes"):
        ctx.scopes = None
        files = sorted(glob.glob(os.path.join(trace_dir(ctx), "**",
                                              "*.xplane.pb"),
                                 recursive=True))
        try:
            with open(files[-1], "rb") as fh:
                ctx.scopes = from_xspace(fh.read())
        except Exception as e:      # a trace this reader cannot parse
            print(f"chipbench.scopes: no scopes read: {e!r}",
                  file=sys.stderr)
    return ctx.scopes


def scope_ms_per_job(ctx, all_of: Sequence[str],
                     none_of: Sequence[str] = ()) -> Optional[float]:
    """Device milliseconds per job in a scope, over the traced window."""
    sc, jobs = of(ctx), ctx.stats.get("jobs")
    if sc is None or not jobs:
        return None
    ns = sc.scope_ns(ctx.reduced.t0, ctx.reduced.t1, all_of, none_of)
    return None if ns is None else 1e-6 * ns / jobs


def span_arg(ctx, name: str, arg: str) -> Optional[float]:
    """The mean of one argument over the window's spans of that name."""
    sc = of(ctx)
    if sc is None:
        return None
    vals = [s.args[arg] for s in sc.spans_in((name,), ctx.reduced.t0,
                                             ctx.reduced.t1)
            if arg in s.args]
    return sum(vals) / len(vals) if vals else None


def stage_gap_ms_per_job(ctx) -> Optional[float]:
    """Device-idle milliseconds per job while the host is inside a stage
    span, averaged over devices."""
    sc, r, jobs = of(ctx), ctx.reduced, ctx.stats.get("jobs")
    if sc is None or not jobs or not r.ops:
        return None
    spans = sc.spans_in(STAGES, r.t0, r.t1)
    if not spans:
        return None
    inside = union_ns((max(s.start, r.t0), min(s.end, r.t1)) for s in spans)
    per = [overlap_ns(r.gaps(dev), inside) for dev in r.ops]
    return 1e-6 * sum(per) / len(per) / jobs
