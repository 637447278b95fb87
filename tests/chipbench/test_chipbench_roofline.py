"""Each roofline's operation and byte count against a hand count, and the
share against a hand-computed trace."""
import importlib.util
import os
import types

import pytest

from chipbench import peaks, rooflines
from chipbench.trace import Op, Reduced, Span

from conftest import ROOT

MS = 1_000_000


def _metric(name):
    path = os.path.join(ROOT, "chipbench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("m_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_distance_argmin_count():
    # n=10, k=2, d=3: cross term 2*10*2*3=120, norms and min 3*10*2=60,
    # point norms 10*3=30; bytes 4*(30+6) + 8*10
    assert _metric("distance_argmin_roofline").flops_bytes(10, 2, 3) == (
        210, 224)


def test_lloyd_stats_count():
    # distances 210, weighted adds 2*10*3=60, counts and cost 4*10=40;
    # bytes: points 30, weights 10, centers 6 read, sums 6, counts 2 and
    # the cost 1 written, 4 bytes each
    assert _metric("lloyd_stats_roofline").flops_bytes(10, 2, 3) == (
        310, 4 * (30 + 10 + 6 + 6 + 2 + 1))


def test_weiszfeld_stats_count():
    # distances 210, exact distance 3*10*3=90, weighted adds 2*10*3=60,
    # inverse, denominator and cost 8*10=80
    assert _metric("weiszfeld_stats_roofline").flops_bytes(10, 2, 3) == (
        440, 220)


def test_batched_count():
    # 10 rows in 2 tenant-chunks, k=2, d=3: distances as above; the centers
    # are read once per chunk
    assert _metric("distance_argmin_batched_roofline").flops_bytes(
        10, 2, 2, 3) == (210, 4 * (30 + 12) + 80)


def _ctx(ops, **stats):
    r = Reduced({0: ops}, {0: [Span("jit_round1_local_solves(1)", 0,
                                    100 * MS)]},
                [Span("window", 0, 100 * MS)])
    return types.SimpleNamespace(
        reduced=r, device_kind="TPU v5 lite", stats=stats,
        config=dict(n=1000, d=90, k=50, t=300, sites=10))


def test_share_by_hand():
    # one lloyd_stats call of 1 ms over n=1000, k=50, d=90: bytes bound
    m = _metric("lloyd_stats_roofline")
    ctx = _ctx([Op("lloyd_stats", 0, MS, "jit_round1_local_solves(1)",
                   "lloyd_stats", ((1024, 128), (56, 128), (1024, 1)))])
    f, b = m.flops_bytes(1000, 50, 90)
    want = 100 * max(f / 197e12, b / 819e9) / 1e-3
    assert m.read(ctx) == pytest.approx(want)
    assert b / 819e9 > f / 197e12


def test_seeding_sweep_counts_one_center():
    ctx = _ctx([])
    op = Op("distance_argmin", 0, MS, "jit_round1_local_solves(1)",
            "distance_argmin", ((1024, 128), (8, 128)))
    assert rooflines.job_call(ctx, op) == (1000, 1)
    op.shapes = ((1024, 128), (64, 128))
    assert rooflines.job_call(ctx, op) == (1000, 50)
    op.module = "jit__lloyd(3)"
    assert rooflines.job_call(ctx, op) == (300 + 10 * 50, 50)


def test_nothing_to_read_is_none_and_unknown_device_raises():
    m = _metric("weiszfeld_stats_roofline")
    assert m.read(_ctx([])) is None
    with pytest.raises(ValueError):
        peaks.device_peaks("cpu")
