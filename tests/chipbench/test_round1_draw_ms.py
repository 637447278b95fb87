"""The reader of Round 1's seeding draws, on synthetic events with a
hand-counted answer: operations under ``round1``, ``seed`` and ``draw``."""
import importlib.util
import os
import types

import pytest

from chipbench import scopes
from chipbench.scopes import ScopedOp, Scopes
from chipbench.trace import Op, Reduced, Span

from conftest import ROOT

MS = 1_000_000   # ns
SEED = "jit(round1_local_solves)/round1/vmap(jit(_kmeans_pp_init))/seed"


def _op(start_ms, dur_ms, name):
    return ScopedOp(start_ms * MS, dur_ms * MS, scopes.segments(name))


def _read(sc, jobs):
    path = os.path.join(ROOT, "chipbench", "metrics", "round1_draw_ms.py")
    spec = importlib.util.spec_from_file_location("m_round1_draw_ms", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    r = Reduced({d: [Op("x", 0, 100 * MS, "m")] for d in sc.ops},
                {d: [] for d in sc.ops}, [Span("window", 0, 100 * MS)])
    return mod.read(types.SimpleNamespace(reduced=r, scopes=sc,
                                          stats={"jobs": jobs}))


def test_draw_ms_counts_draws_under_round1_seeding_only():
    # device 0: the seeding while 10-60 ms is not a draw and is not
    # counted; draws 12-20 and 18-25 (overlapping: 12-25 once) and 40-44;
    # the final solve's draw 80-90 lies outside round1. Device 1: a draw
    # 0-6 ms and a sweep fusion 6-30 ms. Mean (13 + 4 + 6) / 2 = 11.5 ms
    # over 2 jobs is 5.75 ms a job
    d0 = [_op(10, 50, SEED + "/while"),
          _op(12, 8, SEED + "/while/body/draw/reduce"),
          _op(18, 7, SEED + "/while/body/draw/argmax"),
          _op(30, 5, SEED + "/while/body/sub"),
          _op(40, 4, SEED + "/draw/log"),
          _op(80, 10, "jit(_kmeans_pp_init)/seed/while/body/draw/reduce")]
    d1 = [_op(0, 6, SEED + "/draw/threefry"),
          _op(6, 24, SEED + "/while/body/fusion")]
    assert _read(Scopes({0: d0, 1: d1}, []), jobs=2) == \
        pytest.approx(5.75)


def test_seeding_without_draw_scopes_reads_none():
    # the parent's programs: seeding ops carry no draw scope
    ops = {0: [_op(10, 50, SEED + "/while"),
               _op(12, 8, SEED + "/while/body/reduce")]}
    assert _read(Scopes(ops, []), jobs=2) is None
