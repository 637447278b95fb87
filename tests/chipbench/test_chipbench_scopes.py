"""The readers of the program's own scopes and spans, on synthetic events
with hand-counted answers and on host spans recorded here on the CPU."""
import importlib.util
import os
import types

import jax
import jax.numpy as jnp
import pytest

from chipbench import scopes
from chipbench.scopes import HostSpan, ScopedOp, Scopes
from chipbench.trace import Op, Reduced, Span

from conftest import ROOT

MS = 1_000_000   # ns
R1 = "jit(round1_local_solves)/round1"
SEED = R1 + "/vmap(jit(_kmeans_pp_init))/seed"
UPD = R1 + "/vmap(jit(_lloyd))/update"


def _op(start_ms, dur_ms, name):
    return ScopedOp(start_ms * MS, dur_ms * MS, scopes.segments(name))


def test_segments_unwrap_transforms_and_match_whole_names():
    assert scopes.segments(SEED + "/while") == (
        "round1_local_solves", "round1", "_kmeans_pp_init", "seed", "while")
    assert scopes.segments(R1 + "/vmap(sensitivity)/mul")[2] == \
        "sensitivity"
    # the program's name is not its scope
    ops = {0: [_op(0, 10, "jit(round1_local_solves)/while")]}
    assert Scopes(ops, []).scope_ns(0, 100 * MS, ("round1",)) is None


def _scopes():
    # device 0: a seeding while 10-40 ms with its body ops 12-20 and 30-45
    # nested in it and past it; an update 50-60; Round 2 60-70; the final
    # solve's seeding 80-110 (clipped at the window's end, 100)
    d0 = [_op(10, 30, SEED + "/while"),
          _op(12, 8, SEED + "/while/body/fusion"),
          _op(30, 15, SEED + "/while/body/custom-call"),
          _op(50, 10, UPD + "/while"),
          _op(60, 10, "jit(round2_local_samples)/round2/vmap(x)/gather"),
          _op(80, 30, "jit(_kmeans_pp_init)/seed/while")]
    # device 1: one seeding op 0-20 ms in Round 1
    d1 = [_op(0, 20, SEED + "/fusion")]
    spans = [HostSpan("round1", 5 * MS, 3 * MS, {"rows": 1000, "sites": 4}),
             HostSpan("allocate", 8 * MS, 1 * MS, {}),
             HostSpan("round2", 46 * MS, 6 * MS, {"rows": 1000}),
             HostSpan("final_solve", 75 * MS, 10 * MS, {"rows": 400}),
             HostSpan("round1", 200 * MS, 1 * MS, {"rows": 9})]
    return Scopes({0: d0, 1: d1}, spans)


def test_nested_ops_count_once_and_are_clipped():
    sc = _scopes()
    # device 0: 10-45 = 35 ms (the while and its body, once); device 1: 20
    assert sc.scope_ns(0, 100 * MS, ("round1", "seed")) == \
        pytest.approx(27.5 * MS)
    # clipped to 15-100: device 0 15-45 = 30 ms, device 1 15-20 = 5 ms
    assert sc.scope_ns(15 * MS, 100 * MS, ("round1", "seed")) == \
        pytest.approx(17.5 * MS)
    assert sc.scope_ns(0, 100 * MS, ("round1", "update")) == \
        pytest.approx(5 * MS)
    assert sc.scope_ns(0, 100 * MS, ("round2",)) == pytest.approx(5 * MS)
    # the final solve's seeding: 80-100 on device 0, nothing on device 1
    assert sc.scope_ns(0, 100 * MS, ("seed",), ("round1", "round2")) == \
        pytest.approx(10 * MS)
    assert sc.scope_ns(0, 100 * MS, ("sensitivity",)) is None


def _ctx(sc, jobs=2):
    # the device trace of the same window: device 0 busy as above,
    # device 1 busy 0-20 ms
    r = Reduced({0: [Op("w", 10 * MS, 35 * MS, "m"),
                     Op("u", 50 * MS, 20 * MS, "m"),
                     Op("s", 80 * MS, 30 * MS, "m")],
                 1: [Op("f", 0, 20 * MS, "m")]},
                {0: [], 1: []}, [Span("window", 0, 100 * MS)])
    return types.SimpleNamespace(
        reduced=r, scopes=sc, stats={"jobs": jobs},
        config=dict(n=515_345, t=15_000, sites=100, k=50))


def _reader(name):
    path = os.path.join(ROOT, "chipbench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("m_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_readers_per_job():
    ctx = _ctx(_scopes())
    assert _reader("round1_seed_ms")(ctx) == pytest.approx(13.75)
    assert _reader("round1_update_ms")(ctx) == pytest.approx(2.5)
    assert _reader("round2_ms")(ctx) == pytest.approx(2.5)
    assert _reader("final_seed_ms")(ctx) == pytest.approx(5.0)
    # idle inside stage spans: device 0 idle 0-10, 45-50, 70-80, 100;
    # spans cover 5-9, 46-52, 75-85 -> 4 + 4 + 5 = 13 ms; device 1 idle
    # 20-100 -> 46-52 and 75-85 -> 16 ms; mean 14.5 ms over 2 jobs
    assert _reader("stage_gap_ms")(ctx) == pytest.approx(7.25)
    # the span at 200 ms lies outside the window and is not read
    assert _reader("round1_live_share")(ctx) == pytest.approx(
        100 * 515_345 / 1000)
    assert _reader("final_live_share")(ctx) == pytest.approx(
        100 * 20_000 / 400)


def test_a_program_without_scopes_or_spans_reads_none():
    bare = Scopes({0: [_op(0, 50, "jit(round1_local_solves)/while")]}, [])
    ctx = _ctx(bare)
    for name in ("round1_seed_ms", "round1_update_ms", "round2_ms",
                 "final_seed_ms", "stage_gap_ms", "round1_live_share",
                 "final_live_share"):
        assert _reader(name)(ctx) is None, name
    untraced = types.SimpleNamespace(reduced=None, stats={"jobs": 3},
                                     config={})
    assert _reader("round2_ms")(untraced) is None


def test_a_trace_that_cannot_be_read_reads_none(tmp_path, capsys):
    d = tmp_path / ".chipbench_out" / "trace" / "c" / "plugins"
    d.mkdir(parents=True)
    (d / "h.xplane.pb").write_bytes(b"\x0b\x01")    # wire type 3
    ctx = _ctx(None)
    del ctx.scopes
    ctx.cell = types.SimpleNamespace(root=str(tmp_path), name="c")
    assert _reader("round1_live_share")(ctx) is None
    assert ctx.scopes is None and "no scopes read" in capsys.readouterr().err
    # no trace file at all
    ctx = _ctx(None)
    del ctx.scopes
    ctx.cell = types.SimpleNamespace(root=str(tmp_path), name="none")
    assert _reader("round2_ms")(ctx) is None


def _varint(x):
    out = bytearray()
    while True:
        b, x = x & 0x7F, x >> 7
        out.append(b | (0x80 if x else 0))
        if not x:
            return bytes(out)


def _msg(*fields):
    """A protobuf message from (field number, int | str | bytes) pairs."""
    out = b""
    for num, val in fields:
        if isinstance(val, int):
            out += _varint(num << 3) + _varint(val)
        else:
            val = val.encode() if isinstance(val, str) else val
            out += _varint(num << 3 | 2) + _varint(len(val)) + val
    return out


def _plane(name, lines, events, stat_names=()):
    """An XPlane: ``lines`` are (name, [(metadata id, start ns, dur ns,
    stats)]), ``events`` map a metadata id to (name, stats); a stat is
    (stat id, field, value)."""
    def stats(st):
        return [(4, _msg((1, sid), (field, v))) for sid, field, v in st]
    return _msg(
        (2, name),
        *[(3, _msg((2, ln), (3, 0), *[
            (4, _msg((1, mid), (2, start * 1000), (3, dur * 1000),
                     *stats(st))) for mid, start, dur, st in evs]))
          for ln, evs in lines],
        *[(4, _msg((1, mid), (2, _msg((1, mid), (2, nm), *[
            (5, v) for _, v in stats(st)])))) for mid, (nm, st)
          in events.items()],
        *[(5, _msg((1, sid), (2, _msg((1, sid), (2, nm)))))
          for sid, nm in stat_names])


def _hlo(module, instructions):
    return _msg((1, _msg((1, module), (3, _msg((1, "main"), *[
        (2, _msg((1, name), (7, _msg((2, op_name)))))
        for name, op_name in instructions.items()])))))


def test_from_xspace_reads_tf_op_paths_and_span_args():
    tpu = _plane("/device:TPU:0",
                 [("XLA Modules", [(1, 0, 9, [])]),
                  ("XLA Ops", [(2, 0, 5, []), (3, 5, 1, [])])],
                 {1: ("jit_x(1)", []),
                  2: ("%while.3 = (s32[]) while(...)",
                      [(1, 5, SEED + "/while:")]),
                  3: ("copy.1", [])},
                 [(1, "tf_op")])
    host = _plane("/host:CPU",
                  [("python", [(1, 0, 4, [(1, 4, 1815200), (2, 4, 100)]),
                               (2, 0, 9, [])])],
                  {1: ("round1", []), 2: ("job", [])},
                  [(1, "rows"), (2, "sites")])
    sc = scopes.from_xspace(_msg((1, tpu), (1, host)))
    assert [(o.start, o.dur, o.path[-2:]) for o in sc.ops[0]] == [
        (0, 5, ("seed", "while"))]
    assert [(s.name, s.start, s.dur, s.args) for s in sc.spans] == [
        ("round1", 0, 4, {"rows": 1815200, "sites": 100})]


def test_ops_without_tf_op_are_looked_up_in_the_stored_hlo():
    tpu = _plane("/device:TPU:0",
                 [("XLA Modules", [(1, 0, 9, []), (2, 10, 9, [])]),
                  ("XLA Ops", [(3, 0, 5, []), (4, 12, 2, []),
                               (5, 15, 1, []), (6, 16, 1, [])])],
                 {1: ("jit_a(7)", []), 2: ("jit_b(8)", []),
                  3: ("%while.3 = (s32[]) while(...)", []),
                  4: ("%fusion.1 = f32[2] fusion(...)", []),
                  5: ("%copy.2 = f32[2] copy(...)", []),
                  6: ("%add.4 = f32[2] add(...)",
                      [(2, 5, "jit(b)/update/add:")])},
                 [(2, "tf_op")])
    meta = _plane("/host:metadata", [],
                  {1: ("jit_a(7)", [(1, 6, _hlo("jit_a", {
                      "while.3": SEED + "/while"}))]),
                   2: ("jit_b(3)", [(1, 6, _hlo("jit_b", {
                       "fusion.1": "jit(b)/round2/x"}))])},
                  [(1, "Hlo Proto")])
    sc = scopes.from_xspace(_msg((1, tpu), (1, meta)))
    # jit_b(8) is found by its program name, as jit_b(3) was stored; an
    # op with a tf_op takes it; copy.2 is in neither and has no path
    assert [(o.start, o.path[-2:]) for o in sc.ops[0]] == [
        (0, ("seed", "while")), (12, ("round2", "x")),
        (16, ("update", "add"))]


def test_recorded_cpu_trace_spans_and_stored_hlo(tmp_path):
    @jax.jit
    def f(x):
        with jax.named_scope("seed"):
            return jnp.sin(x) * 2.0

    x = jnp.ones((8,))
    f(x).block_until_ready()
    d = str(tmp_path / "trace")
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("final_solve", rows=1505000, k=50):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    import glob
    path = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    buf = open(path[0], "rb").read()
    sc = scopes.from_xspace(buf)
    assert [(s.name, s.args) for s in sc.spans] == [
        ("final_solve", {"rows": 1505000, "k": 50})]
    assert sc.ops == {}           # no TPU plane on the CPU
    # the same times as jax.profiler.ProfileData reads
    pd = jax.profiler.ProfileData.from_file(path[0])
    starts = [e.start_ns for p in pd.planes for ln in p.lines
              for e in ln.events if e.name == "final_solve"]
    assert [s.start for s in sc.spans] == starts
    meta = next(p for p in (scopes._Plane(v) for n, v in
                            scopes._fields(memoryview(buf)) if n == 1)
                if p.name == "/host:metadata")
    tables = scopes.hlo_op_names(meta)
    assert any(scopes.segments(n)[1:2] == ("seed",)
               for n in tables["jit_f"].values())
