"""Find the serving knee once: the highest offered rate at which the
backlog does not grow over a window. The benchmark's own runs never run
this; the rate it finds is written into the traffic file by hand.

    python3 chipbench/sweep.py --workload msd_kmeans.serve --seed 5 \
        --seconds 8 --rates 1000 2000 4000 8000

For each rate it prints one ``rate {json}`` line: requests, the median
latency of the first and the last quarter of the window's requests, p99,
and the ratio of the last quarter's median to the first's (a backlog that
grows makes it rise well above 1). Rates run in rising order; the last
line, ``knee {json}``, is the highest sustained rate (below) under the
first that is not.
"""
from __future__ import annotations

import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import harness  # noqa: E402

# a rate is sustained while the last quarter's median latency stays under
# GROWTH times the first's, every request finishes, and the loop finishes
# what was due within DRAIN_S of the window's close; the sweep stops after
# STOP_AFTER rates in a row that are not
GROWTH, DRAIN_S, STOP_AFTER = 1.5, 0.25, 2


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", default="msd_kmeans.serve")
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    harness.enable_cache()
    cell = harness.Cell(args.workload)
    clock = harness.CompileClock()
    ctx = harness.Context(cell, args.seed, args.seconds, trace=False)
    ctx.devices = jax.devices()[:cell.chips]
    st = cell.kind.setup(ctx)
    over, over_seen, knee = 0, False, None
    for rate in sorted(args.rates):
        tr = dict(cell.traffic, rate_per_s=rate)
        st["req"] = cell.kind.requests(args.seed, args.seconds, tr,
                                       len(st["pts"]))
        before = clock.snapshot()["compiles"]
        win = cell.kind.window(ctx, st)
        lat, arr = win["latency"], st["req"]["arrival"]
        q = args.seconds / 4
        first = np.median(lat[arr < q]) * 1e3
        last = np.median(lat[arr >= 3 * q]) * 1e3
        done = np.isfinite(lat)
        print("rate " + json.dumps(dict(
            rate=rate, requests=len(lat), failed=int((~done).sum()),
            first_median_ms=first, last_median_ms=last,
            growth=last / first, drain_s=win["elapsed"] - args.seconds,
            p99_ms=float(np.percentile(lat[done], 99) * 1e3),
            steps=win["steps"], window_s=win["elapsed"],
            compiles=clock.snapshot()["compiles"] - before,
            late_p99_ms=win["host"]["late_p99_ms"])), flush=True)
        drain = win["elapsed"] - args.seconds
        if last / first > GROWTH or drain > DRAIN_S or not done.all():
            over += 1
            if over >= STOP_AFTER:
                break
        else:
            if not over_seen:
                knee = rate
            over = 0
        over_seen = over_seen or over > 0
    print("knee " + json.dumps(dict(knee=knee)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
