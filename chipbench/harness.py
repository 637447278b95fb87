"""The harness: finds a cell's configuration, traffic and metrics by the
names in ``BENCHMARK.json``, runs set-up, the measured window and the
checks, and prints the result line.

A cell is one ``workloads`` entry. Its configuration is the JSON file the
``configs`` entry names; its traffic is ``traffic/<traffic>.json``, whose
``kind`` names the driver module ``kinds/<kind>.py``; each per-layer metric
is ``metrics/<name>.py`` with a ``read(ctx)`` function. Adding a cell
takes new files and a new ``workloads`` entry, never an edit here.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys
import time
from typing import Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(ROOT, ".chipbench_cache")
OUT = ".chipbench_out"      # a run's files, inside its checkout


class Refused(Exception):
    """The run cannot be made here (no TPU, too few chips, unknown cell)."""


def load_spec(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        return json.load(fh)


def _by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise Refused(f"no {what} named {name!r}")


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise Refused(f"no module at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _mod_name(prefix: str, name: str) -> str:
    return prefix + "".join(c if c.isalnum() else "_" for c in name)


class Cell:
    """Everything a run of one workload needs, found by name."""

    def __init__(self, name: str, spec: Optional[dict] = None,
                 root: str = ROOT, bench_dir: str = BENCH_DIR):
        self.spec = load_spec(root) if spec is None else spec
        self.root, self.bench_dir = root, bench_dir
        self.workload = _by_name(self.spec["workloads"], name, "workload")
        self.name = name
        self.chips = int(self.workload["chips"])
        entry = _by_name(self.spec["configs"], self.workload["config"],
                         "configuration")
        with open(os.path.join(root, entry["file"])) as fh:
            self.config = json.load(fh)
        with open(os.path.join(bench_dir, "traffic",
                               self.workload["traffic"] + ".json")) as fh:
            self.traffic = json.load(fh)
        self.kind = _load_module(
            os.path.join(bench_dir, "kinds", self.traffic["kind"] + ".py"),
            _mod_name("chipbench_kind_", self.traffic["kind"]))

    def end_to_end(self):
        return [m for m in self.spec["end_to_end"]
                if "workloads" not in m or self.name in m["workloads"]]

    def per_layer(self):
        mine = {m["name"] for m in self.end_to_end()}
        return [m for m in self.spec["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in mine)]

    def reader(self, metric: str):
        return _load_module(os.path.join(self.bench_dir, "metrics",
                                         metric + ".py"),
                            _mod_name("chipbench_metric_", metric))

    def limits(self) -> dict:
        return self.config["limits"][self.traffic["kind"]]


class CompileClock:
    """Backend compiles, their seconds, and persistent-cache hits."""

    def __init__(self):
        import jax
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return dict(compile_s=self.seconds, compiles=self.compiles,
                    cache_hits=self.cache_hits)


class Context:
    """What a traffic driver and a metric reader see of a run."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool):
        self.cell, self.seed, self.seconds, self.trace = (cell, seed,
                                                          seconds, trace)
        self.config, self.traffic = cell.config, cell.traffic
        self.phases = {}
        self.stats = {}          # host counters and spans of the window
        self.reduced = None      # the reduced device trace (trace runs)
        self.device_kind = None

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        yield
        self.phases[name] = self.phases.get(name, 0.0) + (
            time.perf_counter() - t0)


def enable_cache() -> str:
    """The persistent compilation cache at its fixed path. Called before
    anything compiles: JAX settles whether a process uses the cache at its
    first compile, so it is reset here in case something compiled first."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compilation_cache.reset_cache()
    return CACHE_DIR


def tpu_devices(chips: int):
    """The TPU devices of this machine; refuses anything else."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise Refused(f"JAX found no devices: {e}") from None
    if devices[0].platform != "tpu":
        raise Refused(f"no TPU: JAX found {devices[0].platform!r} devices")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} TPU chips, JAX found "
                      f"{len(devices)}")
    return devices


def memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def memory_in_use(devices) -> Optional[int]:
    used = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
    used = [u for u in used if u is not None]
    return max(used) if used else None


def judge(numbers: dict, limits: dict):
    """Each compared number beside its limit; correct when none is over
    (a missing or non-finite number is over)."""
    checks, ok = {}, True
    for name, limit in limits.items():
        v = numbers.get(name)
        good = v is not None and v == v and v <= limit
        ok &= good
        checks[name] = {"value": v, "limit": limit}
    return ok, checks


def write_setup_file(ctx: Context, setup: dict) -> None:
    """Set-up by phase, one JSON line per run, for a later PR to see what
    to shorten."""
    out_dir = os.path.join(ctx.cell.root, OUT)
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "setup_phases.jsonl"), "a") as fh:
        fh.write(json.dumps(dict(workload=ctx.cell.name, seed=ctx.seed,
                                 **setup)) + "\n")


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float, devices=None) -> dict:
    """One run of a cell. ``devices`` None means any JAX devices (the CPU
    tests); the command passes the TPUs it found. Returns the result
    line as a dict."""
    import jax
    clock = CompileClock()
    ctx = Context(cell, seed, seconds, trace)
    devices = list(jax.devices()) if devices is None else devices
    ctx.device_kind = devices[0].device_kind
    ctx.devices = devices[:cell.chips]
    state = cell.kind.setup(ctx)
    setup_s = time.perf_counter() - t_start
    setup_clock = clock.snapshot()
    say("setup", setup_s=setup_s, phases=ctx.phases, **setup_clock)
    write_setup_file(ctx, dict(setup_s=setup_s, phases=ctx.phases,
                               **setup_clock))

    trace_dir = os.path.join(cell.root, OUT, "trace", cell.name)
    if trace:
        from chipbench import trace as trace_mod
        trace_mod.clear(trace_dir)
        jax.profiler.start_trace(trace_dir)
    with jax.profiler.TraceAnnotation("window"):
        window = cell.kind.window(ctx, state)
    if trace:
        jax.profiler.stop_trace()
    after = clock.snapshot()
    say("window", compiles=after["compiles"] - setup_clock["compiles"],
        compile_s=after["compile_s"] - setup_clock["compile_s"],
        cache_hits=after["cache_hits"] - setup_clock["cache_hits"],
        **window.get("host", {}))
    # a kind whose set-up holds more than its window (serving: one
    # clustering job makes the tenants' centers) reports what the window
    # holds, the bytes in use as it closes
    peak = (memory_in_use(ctx.devices)
            if getattr(cell.kind, "MEMORY", "peak") == "in_use"
            else memory_peak(ctx.devices))

    out = cell.kind.finish(ctx, state, window)
    # every number the checks read, compared or not: each run is also a
    # reading for the limits
    say("numbers", **out["numbers"])
    ok, checks = judge(out["numbers"], cell.limits())
    ok &= out["failed"] == 0
    device = dict(platform=devices[0].platform, kind=ctx.device_kind,
                  count=len(devices), memory_peak_bytes=peak)
    result = dict(correct=bool(ok), attempted=out["attempted"],
                  failed=out["failed"])
    if trace:
        from chipbench import trace as trace_mod
        ctx.reduced = trace_mod.reduce_dir(trace_dir)
        device.update(busy_s=ctx.reduced.busy_s,
                      window_s=ctx.reduced.window_s)
        metrics = {}
        for m in cell.per_layer():
            v = cell.reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = ctx.reduced.breakdown()
    else:
        values = dict(out["metrics"], setup_s=setup_s)
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end()}
        result["device"] = device
    result["checks"] = checks
    return result


def say(what: str, **fields) -> None:
    print(f"{what} {json.dumps(fields)}", flush=True)


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(
        description="Run one benchmark cell once and print its result.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        enable_cache()
        cell = Cell(args.workload)
        devices = tpu_devices(cell.chips)
        result = run(cell, args.seed, args.seconds, bool(args.trace),
                     t_start, devices)
    except Refused as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} <= {c['limit']!r}",
              file=sys.stderr)
    print(f"correct {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
