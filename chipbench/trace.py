"""The device trace of a window, reduced to what the per-layer metrics
read: device busy time, time per program and per kernel, and the idle
gaps named by the benchmark's own host spans.

``jax.profiler`` writes the window's trace as an ``.xplane.pb`` file;
:func:`reduce_dir` reads it with ``jax.profiler.ProfileData``. Device
planes are ``/device:TPU:<i>``; their ``XLA Ops`` line holds one event per
operation run, their ``XLA Modules`` line one per program run. Host
planes hold the ``TraceAnnotation`` spans of the benchmark (``window``,
``job``, ``serve.step``, ``serve.generate``).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
from typing import Dict, List, Optional, Tuple

# the benchmark's own host spans, outermost first
SPANS = ("window", "job", "serve.generate", "serve.step")
KERNELS = ("distance_argmin_batched", "distance_argmin", "lloyd_stats",
           "weiszfeld_stats")


@dataclasses.dataclass
class Op:
    name: str          # the operation (a kernel's name for a Mosaic call)
    start: int         # ns
    dur: int           # ns
    module: str        # the program it ran in
    kernel: Optional[str] = None
    shapes: Tuple[Tuple[int, ...], ...] = ()   # operand shapes, if known


@dataclasses.dataclass
class Span:
    name: str
    start: int
    dur: int

    @property
    def end(self) -> int:
        return self.start + self.dur


def _union(intervals) -> List[Tuple[int, int]]:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


class Reduced:
    """Ops and programs per device, host spans, and the window."""

    def __init__(self, ops: Dict[int, List[Op]],
                 modules: Dict[int, List[Span]], host: List[Span],
                 host_events: Optional[List[Span]] = None):
        self.ops, self.modules, self.host = ops, modules, host
        self.host_events = host_events or []
        win = [s for s in host if s.name == "window"]
        if win:
            self.t0, self.t1 = win[0].start, win[0].end
        else:
            starts = [o.start for v in ops.values() for o in v]
            ends = [o.start + o.dur for v in ops.values() for o in v]
            self.t0, self.t1 = (min(starts), max(ends)) if starts else (0, 0)
        self.window_s = (self.t1 - self.t0) * 1e-9
        self._busy = {d: self._busy_intervals(d) for d in ops}
        self.busy_s = (sum(sum(e - s for s, e in b)
                           for b in self._busy.values())
                       / max(len(self._busy), 1) * 1e-9)

    def _busy_intervals(self, dev):
        iv = (_clip(o.start, o.start + o.dur, self.t0, self.t1)
              for o in self.ops[dev])
        return _union((s, e) for s, e in iv if e > s)

    def _mean(self, per_dev: Dict[int, float]) -> float:
        return sum(per_dev.values()) / max(len(per_dev), 1)

    def program_s(self, names) -> float:
        """Seconds per device in runs of programs whose name contains one
        of ``names``, inside the window, averaged over devices."""
        per = {}
        for d, spans in self.modules.items():
            per[d] = sum(max(0, min(s.end, self.t1) - max(s.start, self.t0))
                         for s in spans
                         if any(n in s.name for n in names)) * 1e-9
        return self._mean(per)

    def kernel_ops(self, kernel: str) -> List[Op]:
        """Every run of a kernel inside the window, all devices."""
        return [o for v in self.ops.values() for o in v
                if o.kernel == kernel and self.t0 <= o.start < self.t1]

    def gaps(self, dev=None) -> List[Tuple[int, int]]:
        if not self._busy:
            return []
        dev = min(self._busy) if dev is None else dev
        busy = self._busy.get(dev, [])
        out, prev = [], self.t0
        for s, e in busy:
            if s > prev:
                out.append((prev, s))
            prev = max(prev, e)
        if self.t1 > prev:
            out.append((prev, self.t1))
        return out

    def _labels(self, gaps, longest: int = 500) -> List[str]:
        """What the host was doing in each gap: the innermost benchmark
        span at its middle and, for the ``longest`` gaps, the host event
        that overlaps it most."""
        import numpy as np
        if not gaps:
            return []
        g = np.asarray(gaps, np.int64)
        mid = (g[:, 0] + g[:, 1]) // 2
        labels = ["window"] * len(g)
        for name in SPANS[1:]:
            sp = np.asarray([(x.start, x.end) for x in self.host
                             if x.name == name], np.int64).reshape(-1, 2)
            if not len(sp):
                continue
            sp = sp[np.argsort(sp[:, 0])]
            i = np.searchsorted(sp[:, 0], mid, side="right") - 1
            inside = (i >= 0) & (mid < sp[np.maximum(i, 0), 1])
            for j in np.flatnonzero(inside):
                labels[j] = name
        if self.host_events:
            ev = np.asarray([(x.start, x.end) for x in self.host_events],
                            np.int64)
            order = np.argsort(g[:, 0] - g[:, 1])[:longest]
            for j in order:
                ov = (np.minimum(ev[:, 1], g[j, 1])
                      - np.maximum(ev[:, 0], g[j, 0]))
                best = int(np.argmax(ov))
                if ov[best] > 0:
                    labels[j] = (f"{labels[j]}: "
                                 f"{self.host_events[best].name[:80]}")
        return labels

    def breakdown(self, top: int = 10) -> dict:
        ops: Dict[str, float] = {}
        for v in self.ops.values():
            for o in v:
                if self.t0 <= o.start < self.t1:
                    label = o.kernel or (f"{re.sub(r'[(].*', '', o.module)}:"
                                         f"{_base(o.name)}")
                    ops[label] = ops.get(label, 0.0) + o.dur * 1e-9
        n_dev = max(len(self.ops), 1)
        gaps: Dict[str, float] = {}
        found = self.gaps()
        for (s, e), label in zip(found, self._labels(found)):
            gaps[label] = gaps.get(label, 0.0) + (e - s) * 1e-9
        return dict(
            device_ops=[[k, v / n_dev] for k, v in
                        sorted(ops.items(), key=lambda x: -x[1])[:top]],
            idle_gaps=[[k, v] for k, v in
                       sorted(gaps.items(), key=lambda x: -x[1])[:top]])


def _base(name: str) -> str:
    return re.sub(r"[.\d]+$", "", name) or name


def kernel_of(*texts) -> Optional[str]:
    """The Mosaic kernel an operation runs, found by its name."""
    for k in KERNELS:
        pat = re.compile(rf"(?<![A-Za-z_]){k}(?![A-Za-z_])")
        if any(t and pat.search(t) for t in texts):
            return k
    return None


def clear(trace_dir: str) -> None:
    shutil.rmtree(trace_dir, ignore_errors=True)


def reduce_dir(trace_dir: str) -> Reduced:
    import jax
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    return from_profile(jax.profiler.ProfileData.from_file(files[-1]))


def _stats(ev) -> Dict[str, str]:
    try:
        return {str(k): str(v) for k, v in ev.stats}
    except Exception:
        return {}


def from_profile(pd) -> Reduced:
    ops: Dict[int, List[Op]] = {}
    modules: Dict[int, List[Span]] = {}
    host: List[Span] = []
    host_events: List[Span] = []
    for plane in pd.planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        if m:
            dev = int(m.group(1))
            mods = []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods = [Span(e.name, e.start_ns, e.duration_ns)
                            for e in line.events]
            modules[dev] = sorted(mods, key=lambda s: s.start)
            starts = [s.start for s in modules[dev]]
            out = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    st = _stats(e)
                    i = _bisect(starts, e.start_ns)
                    mod = modules[dev][i].name if i >= 0 else ""
                    out.append(Op(e.name, e.start_ns, e.duration_ns, mod,
                                  kernel_of(e.name, st.get("long_name"),
                                            st.get("tf_op"),
                                            st.get("hlo_op")),
                                  _shapes(st.get("long_name", ""))))
            ops[dev] = out
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in SPANS:
                        host.append(Span(e.name, e.start_ns, e.duration_ns))
                    elif e.duration_ns > 0:
                        host_events.append(Span(e.name, e.start_ns,
                                                e.duration_ns))
    return Reduced(ops, modules, host, host_events)


def _bisect(starts, x) -> int:
    import bisect
    return bisect.bisect_right(starts, x) - 1


def _shapes(text: str) -> Tuple[Tuple[int, ...], ...]:
    """Operand shapes of an HLO instruction, ``f32[100,21504,128]`` ->
    (100, 21504, 128), in the order they appear after its ``(``."""
    m = re.search(r"=\s*(?:\([^()]*\)|\S+)\s+[\w.-]+\((.*)", text)
    args = m.group(1) if m else ""
    return tuple(tuple(int(x) for x in m.split(",") if x)
                 for m in re.findall(r"[a-z]\d*\[([\d,]*)\]", args))
