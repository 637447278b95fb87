"""The reduction from a device trace to the per-layer metrics, on
synthetic events with hand-counted answers and on a trace recorded here
on the CPU."""
import jax
import jax.numpy as jnp
import pytest

from chipbench import trace
from chipbench.trace import Op, Reduced, Span

MS = 1_000_000   # ns


def _reduced():
    # window 0-100 ms; device 0 busy 10-30 and 25-40 (overlap) and 90-110
    # (clipped at 100); device 1 busy 0-50
    ops = {
        0: [Op("lloyd_stats", 10 * MS, 20 * MS, "jit_round1", "lloyd_stats"),
            Op("fusion.12", 25 * MS, 15 * MS, "jit_round1"),
            Op("distance_argmin", 90 * MS, 20 * MS, "jit__lloyd",
               "distance_argmin")],
        1: [Op("fusion.3", 0, 50 * MS, "jit_round1")],
    }
    modules = {0: [Span("jit_round1(1)", 5 * MS, 40 * MS),
                   Span("jit__lloyd(2)", 85 * MS, 30 * MS)],
               1: [Span("jit_round1(1)", 0, 50 * MS)]}
    host = [Span("window", 0, 100 * MS), Span("job", 0, 60 * MS),
            Span("job", 60 * MS, 40 * MS)]
    host_events = [Span("np.asarray", 40 * MS, 45 * MS)]
    return Reduced(ops, modules, host, host_events)


def test_busy_union_and_window():
    r = _reduced()
    assert r.window_s == pytest.approx(0.1)
    # device 0: 10-40 and 90-100 -> 40 ms; device 1: 0-50 -> 50 ms
    assert r.busy_s == pytest.approx(0.045)


def test_program_and_kernel_sums():
    r = _reduced()
    # round1: 40 ms on device 0, 50 ms on device 1 -> mean 45 ms
    assert r.program_s(["round1"]) == pytest.approx(0.045)
    # _lloyd from 85 ms, clipped at the window's end: 15 ms / 2 devices
    assert r.program_s(["_lloyd"]) == pytest.approx(0.0075)
    assert [o.dur for o in r.kernel_ops("lloyd_stats")] == [20 * MS]
    assert r.kernel_ops("weiszfeld_stats") == []


def test_gaps_are_named_by_host_spans():
    r = _reduced()
    assert r.gaps() == [(0, 10 * MS), (40 * MS, 90 * MS)]
    b = r.breakdown()
    gaps = dict(b["idle_gaps"])
    # 40-90: midpoint 65 ms in the second job; np.asarray overlaps most
    assert gaps["job: np.asarray"] == pytest.approx(0.05)
    assert gaps["job"] == pytest.approx(0.01)
    ops = dict(b["device_ops"])
    assert ops["lloyd_stats"] == pytest.approx(0.01)   # 20 ms / 2 devices
    assert ops["jit_round1:fusion"] == pytest.approx(0.0325)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_kernel_names_and_shapes():
    assert trace.kernel_of("distance_argmin_batched.1") == \
        "distance_argmin_batched"
    assert trace.kernel_of("custom-call.4", "distance_argmin") == \
        "distance_argmin"
    assert trace.kernel_of("fusion.2") is None
    text = ("%custom-call.4 = (f32[100,21504,1]{2,1,0}) custom-call("
            "f32[100,21504,128]{2,1,0} %a, f32[100,64,128]{2,1,0} %b)")
    assert trace._shapes(text) == ((100, 21504, 128), (100, 64, 128))


def test_recorded_cpu_trace(tmp_path):
    d = str(tmp_path / "trace")
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("job"):
            jnp.sum(jnp.ones((256, 256)) @ jnp.ones((256, 256))
                    ).block_until_ready()
    jax.profiler.stop_trace()
    r = trace.reduce_dir(d)
    assert [s.name for s in r.host if s.name in ("window", "job")] == [
        "window", "job"] or {"window", "job"} <= {s.name for s in r.host}
    assert r.window_s > 0
    assert r.busy_s == 0.0       # no TPU plane on the CPU
