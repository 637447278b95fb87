"""Loop-aware collective analysis of compiled HLO text.

XLA's ``compiled.cost_analysis()`` counts while-loop bodies ONCE (a scan of
length 10 reports the same numbers as length 1), and the SPMD clustering
path issues its ring/torus relays inside ``fori_loop`` bodies. This module
therefore parses the post-partitioning HLO text into a computation graph,
extracts while-loop trip counts from their condition computations, and
accumulates, per ``jax.named_scope`` phase:

  * per-collective issue counts and link bytes with the standard
    algorithmic factors
      all-reduce        2 (N-1)/N x bytes
      all-gather        (N-1)/N x result bytes
      reduce-scatter    (N-1) x result bytes   (= (N-1)/N x operand)
      all-to-all        (N-1)/N x bytes
      collective-permute  bytes
  * DCN vs ICI classification: a replica group whose device ids span
    multiple pod blocks crosses the data-center network.

All quantities are per device: the post-SPMD module is the per-device
program and operand shapes are shard shapes.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "ragged-all-to-all")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE_RE = re.compile(r"^(.*?)\s([\w\-]+)\((.*)$")
_COMP_RE = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->.*\{\s*$")


def _shape_bytes(type_str: str) -> int:
    """Total bytes of a (possibly tuple) HLO type string."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


@dataclasses.dataclass
class Op:
    name: str
    opcode: str
    result_type: str
    rest: str            # operands + attrs (text after "opcode(")
    raw: str             # the full line


@dataclasses.dataclass
class Computation:
    name: str
    ops: List[Op]


def parse_computations(hlo: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    for line in hlo.splitlines():
        if _COMP_RE.match(line):
            cur = Computation(_COMP_RE.match(line).group(1), [])
            comps[cur.name] = cur
            continue
        if cur is None:
            continue
        stripped = line.strip()
        if stripped.startswith("}"):
            cur = None
            continue
        m = _OP_RE.match(line)
        if not m:
            continue
        name, tail = m.groups()
        # split "<result type> <opcode>(<rest>"; the type may itself be a
        # tuple "(...)", but only the opcode is a word directly followed by
        # "(" -- earliest such match after a space wins
        m2 = _OPCODE_RE.match(tail)
        if not m2:
            continue
        rtype, opcode, rest = m2.groups()
        cur.ops.append(Op(name, opcode, rtype.strip(), rest, line))
    return comps


_CALL_RE = re.compile(
    r"(?:to_apply|body|condition|calls|branch_computations)=\{?%?([\w.\-]+)")
_CONST_RE = re.compile(r"constant\((\d+)\)")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _trip_count(cond: Computation,
                comps: Optional[Dict[str, "Computation"]] = None,
                depth: int = 0) -> int:
    """Heuristic: the largest integer constant in the loop condition
    computation (jax's scan lowers to `lt(iv, constant(T))`), following
    fused/called sub-computations."""
    best = 1
    for op in cond.ops:
        for c in _CONST_RE.findall(op.raw):
            best = max(best, int(c))
        if comps is not None and depth < 3:
            for called in _CALL_RE.findall(op.raw):
                if called in comps:
                    best = max(best, _trip_count(comps[called], comps,
                                                 depth + 1))
    return best


def _group_size_and_span(op: Op, pod_block: Optional[int]
                         ) -> Tuple[int, bool]:
    """(replica group size, crosses_pod). ``pod_block`` = devices per pod."""
    m = _GROUPS_BRACE_RE.search(op.rest)
    if m:
        ids = [int(x) for x in m.group(1).split(",")]
        crosses = (pod_block is not None and
                   len({i // pod_block for i in ids}) > 1)
        return max(len(ids), 1), crosses
    m = _GROUPS_IOTA_RE.search(op.rest)
    if m:
        n_groups, group_size = int(m.group(1)), int(m.group(2))
        # iota order: contiguous ids in a group unless a transpose follows;
        # conservative: crosses pod iff the group span exceeds the pod block
        crosses = (pod_block is not None and group_size > pod_block)
        return max(group_size, 1), crosses
    return 1, False


@dataclasses.dataclass
class Analysis:
    ici_collective_bytes: float = 0.0
    dcn_collective_bytes: float = 0.0
    collective_counts: Dict[str, float] = dataclasses.field(
        default_factory=dict)
    collective_bytes_by_kind: Dict[str, float] = dataclasses.field(
        default_factory=dict)

    def add(self, other: "Analysis", mult: float = 1.0):
        self.ici_collective_bytes += other.ici_collective_bytes * mult
        self.dcn_collective_bytes += other.dcn_collective_bytes * mult
        for k, v in other.collective_counts.items():
            self.collective_counts[k] = (
                self.collective_counts.get(k, 0.0) + v * mult)
        for k, v in other.collective_bytes_by_kind.items():
            self.collective_bytes_by_kind[k] = (
                self.collective_bytes_by_kind.get(k, 0.0) + v * mult)


_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')


def _collective_link(op: Op, pod_block: Optional[int]
                     ) -> Optional[Tuple[str, float, bool]]:
    """(kind, link_bytes, crosses_pod) for a collective op
    (including async ``*-start`` halves), else ``None``. The link factors
    are the standard algorithmic ones from the module docstring."""
    if not (op.opcode in COLLECTIVES
            or (op.opcode.endswith("-start")
                and op.opcode[:-6] in COLLECTIVES)):
        return None
    kind = op.opcode[:-6] if op.opcode.endswith("-start") else op.opcode
    rb = _shape_bytes(op.result_type)
    if op.opcode.endswith("-start"):
        # async result tuples carry (operand, result[, ...]): use the
        # result buffer only
        shapes = _SHAPE_RE.findall(op.result_type)
        if len(shapes) >= 2:
            dtype, dims = shapes[1]
            rb = _DTYPE_BYTES.get(dtype, 4)
            for d in dims.split(","):
                if d:
                    rb *= int(d)
    n, crosses = _group_size_and_span(op, pod_block)
    if kind == "all-reduce":
        link = 2.0 * (n - 1) / max(n, 1) * rb
    elif kind == "all-gather":
        link = (n - 1) / max(n, 1) * rb
    elif kind == "reduce-scatter":
        link = (n - 1) * rb
    elif kind in ("all-to-all", "ragged-all-to-all"):
        link = (n - 1) / max(n, 1) * rb
    else:  # collective-permute
        link = rb
    return kind, link, crosses


def _op_phase(op: Op, phases: Tuple[str, ...]) -> Optional[str]:
    """The phase scope segment of an op's ``metadata={op_name="..."}``.

    ``jax.named_scope("round1")`` survives jit+compile as a ``/round1/``
    path segment in the op_name of every op traced under it -- including
    the ``ppermute``s inside a ``fori_loop`` while-body -- which is what
    makes per-phase collective attribution possible on compiled HLO."""
    m = _OP_NAME_RE.search(op.raw)
    if not m:
        return None
    segs = m.group(1).split("/")
    for p in phases:
        if p in segs:
            return p
    return None


def collective_phase_analysis(
    hlo: str,
    phases: Tuple[str, ...] = ("round1", "round2"),
    pod_block: Optional[int] = None,
    entry: Optional[str] = None,
) -> Dict[str, Analysis]:
    """Per-phase collective ledger: loop-aware collective op counts and
    link bytes, attributed to the ``jax.named_scope`` phase each collective
    was traced under (``_op_phase``). Collectives outside every named phase
    land in ``"other"``.

    Counts are *sequential issue* counts: a ``ppermute`` inside a
    ``fori_loop`` while-body counts once per trip, so
    ``collective_counts["collective-permute"]`` of a phase is exactly the
    hop depth of its ring/torus schedule -- the measured counterpart of
    :func:`repro.core.message_passing.collective_hops`, which
    ``bench_collectives`` cross-checks per mode.
    """
    comps = parse_computations(hlo)
    out = {p: Analysis() for p in (*phases, "other")}
    if not comps:
        return out
    entry_name = entry
    if entry_name is None:
        m = re.search(r"ENTRY\s+%?([\w.\-]+)", hlo)
        entry_name = m.group(1) if m else next(iter(comps))
    cache: Dict[str, Dict[str, Analysis]] = {}

    def merge(dst: Dict[str, Analysis], src: Dict[str, Analysis],
              mult: float = 1.0) -> None:
        for p, a in src.items():
            dst.setdefault(p, Analysis()).add(a, mult)

    def visit(name: str, depth: int = 0) -> Dict[str, Analysis]:
        if name in cache:
            return cache[name]
        acc: Dict[str, Analysis] = {}
        comp = comps.get(name)
        if comp is None or depth > 60:
            return acc
        cache[name] = acc  # provisional (cycles cannot occur in HLO)
        for op in comp.ops:
            if op.opcode == "while":
                mb = re.search(r"body=%?([\w.\-]+)", op.rest)
                mc = re.search(r"condition=%?([\w.\-]+)", op.rest)
                called = _CALL_RE.findall(op.rest)
                body = mb.group(1) if mb else (called[0] if called else None)
                cond = mc.group(1) if mc else None
                trips = (_trip_count(comps[cond], comps)
                         if cond in comps else 1)
                if body:
                    merge(acc, visit(body, depth + 1), mult=trips)
                continue
            link = _collective_link(op, pod_block)
            if link is not None:
                kind, bytes_, crosses = link
                phase = _op_phase(op, phases) or "other"
                a = acc.setdefault(phase, Analysis())
                a.collective_counts[kind] = (
                    a.collective_counts.get(kind, 0.0) + 1)
                a.collective_bytes_by_kind[kind] = (
                    a.collective_bytes_by_kind.get(kind, 0.0) + bytes_)
                if crosses:
                    a.dcn_collective_bytes += bytes_
                else:
                    a.ici_collective_bytes += bytes_
                continue
            for called in _CALL_RE.findall(op.rest):
                if called in comps:
                    merge(acc, visit(called, depth + 1))
        return acc

    merge(out, visit(entry_name))
    cache.pop(entry_name, None)
    return out
