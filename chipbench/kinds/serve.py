"""Open-loop nearest-center queries through ``ClusterServeEngine``.

Set-up makes the tenants' centers: tenant 0 serves the centers of one
clustering job of the configuration, the others seeded D^2-sampled rows
of the data set. Requests arrive as a Poisson process at the traffic's
fixed rate; each goes to a tenant drawn Zipf(s) and carries a log-uniform
number of rows of the data set. Every seed gets the same multiset of
sizes, gaps and tenants (stratified quantiles), in its own order, so the
seed changes which rows and when, not how much work.

One loop enqueues what is due and calls ``step()``. A request's latency
runs from its due time to the step after which its ticket is done (its
results are on the host then). Requests due in the window count; one not
done ``grace_s`` after the window closes has failed.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from chipbench import data as data_mod
from chipbench import reference
from chipbench.kinds import job as job_kind
from repro.serve import ClusterServeEngine, StaticCenters

# the device memory a run reports: set-up's clustering job sets the
# process's peak, so the bytes in use as the window closes stand for it
MEMORY = "in_use"


def schedule(seed: int, n: int, tr: dict) -> dict:
    """``n`` requests: gaps between arrivals (s), tenants and row counts.
    Every seed gets the same multiset of each, in its own order."""
    u = (np.arange(n) + 0.5) / n
    rng = data_mod.host_seed(seed, 3)
    gaps = rng.permutation(-np.log1p(-u) / tr["rate_per_s"])
    lo, hi = tr["rows_min"], tr["rows_max"]
    sizes = np.floor(np.exp(np.log(lo) + u * (np.log(hi + 1) - np.log(lo))))
    sizes = rng.permutation(np.clip(sizes, lo, hi).astype(np.int64))
    p = 1.0 / np.arange(1, tr["tenants"] + 1) ** tr["zipf_s"]
    cdf = np.cumsum(p / p.sum())
    tenant = rng.permutation(np.minimum(np.searchsorted(cdf, u),
                                        tr["tenants"] - 1))
    return dict(gap=gaps, size=sizes, tenant=tenant, rng=rng)


def requests(seed: int, seconds: float, tr: dict, n_points: int) -> dict:
    """Arrival times (s), tenants, row counts and row offsets of every
    request due in ``[0, seconds)``."""
    s = schedule(seed, int(np.ceil(tr["rate_per_s"] * seconds)) + 1, tr)
    arrival = np.cumsum(s["gap"])
    due = arrival < seconds
    offset = s["rng"].integers(0, n_points - s["size"] + 1)
    return dict(arrival=arrival[due], tenant=s["tenant"][due],
                size=s["size"][due], offset=offset[due])


def setup(ctx):
    cfg, tr = ctx.config, ctx.traffic
    with ctx.phase("data"):
        pts, site, sp, sm = data_mod.make_sites(
            ctx.seed, cfg["n"], cfg["d"], cfg["components"], cfg["noise"],
            cfg["sites"])
        pts_h = np.asarray(pts)
    key = data_mod.base_key(ctx.seed)
    with ctx.phase("tenant_centers"):
        from repro.core.topology import Graph
        graph = Graph(cfg["sites"], data_mod.erdos_renyi_edges(
            cfg["sites"], cfg["graph_p"], ctx.seed))
        st = dict(sp=sp, sm=sm, graph=graph, t=cfg["t"])
        first = job_kind.run_job(ctx, st, jax.random.fold_in(key, 1 << 31))
        z = 2 if cfg["objective"] == "kmeans" else 1
        centers = [np.asarray(first.centers)] + [
            np.asarray(reference.seed_centers(jax.random.fold_in(key, i),
                                              pts, cfg["k"], z))
            for i in range(1, tr["tenants"])]
        del first, st, sp, sm
    engine = ClusterServeEngine(max_bucket=tr["rows_max"])
    tids = [engine.add_tenant(StaticCenters(c), cfg["k"], cfg["d"],
                              objective=cfg["objective"]) for c in centers]
    with ctx.phase("warm_up"):
        # every (requests per dispatch, bucket) shape the traffic can make:
        # a dispatch stacks up to ``max_group`` request chunks of one
        # bucket, padded to a power of two
        b = engine.min_bucket
        while b <= tr["rows_max"]:
            g = 1
            while g <= engine.max_group:
                for i in range(g):
                    engine.enqueue(tids[i % len(tids)], pts_h[:b])
                engine.step()
                g *= 2
            b *= 2
    with ctx.phase("traffic"):
        req = requests(ctx.seed, ctx.seconds, tr, len(pts_h))
    return dict(engine=engine, tids=tids, centers=centers, pts=pts_h,
                req=req)


def window(ctx, st):
    engine, req, pts = st["engine"], st["req"], st["pts"]
    arrival, tenant = req["arrival"], req["tenant"]
    size, offset = req["size"], req["offset"]
    n = len(arrival)
    tids = st["tids"]
    tickets = [None] * n
    latency = np.full(n, np.inf)
    late = np.zeros(n)
    s0 = dict(engine.stats.as_dict())
    outstanding = []
    step_s, steps = 0.0, 0
    limit = ctx.seconds + ctx.traffic["grace_s"]
    clock = time.perf_counter
    t0 = clock()
    i = 0
    while True:
        now = clock() - t0
        if i < n and arrival[i] <= now:
            with jax.profiler.TraceAnnotation("serve.generate"):
                while i < n and arrival[i] <= now:
                    a = offset[i]
                    tickets[i] = engine.enqueue(tids[tenant[i]],
                                                pts[a:a + size[i]])
                    late[i] = now - arrival[i]
                    outstanding.append(i)
                    i += 1
        if outstanding:
            s = clock()
            with jax.profiler.TraceAnnotation("serve.step"):
                engine.step()
            e = clock()
            step_s += e - s
            steps += 1
            done_at = e - t0
            still = []
            for j in outstanding:
                if tickets[j].done:
                    latency[j] = done_at - arrival[j]
                else:
                    still.append(j)
            outstanding = still
        elif i < n:
            wait = arrival[i] - (clock() - t0)
            if wait > 2e-4:
                time.sleep(wait - 1e-4)
        else:
            break
        if now > limit:
            break
    elapsed = clock() - t0
    s1 = engine.stats.as_dict()
    delta = {k: s1[k] - s0[k] for k in ("n_queries", "n_padded",
                                        "n_dispatches",
                                        "n_tenant_dispatches")}
    return dict(tickets=tickets, latency=latency, late=late, steps=steps,
                step_s=step_s, delta=delta, elapsed=elapsed,
                host=dict(requests=n, steps=steps, window_s=elapsed,
                          late_p99_ms=float(np.percentile(late, 99) * 1e3)
                          if n else 0.0,
                          late_max_ms=float(late.max() * 1e3) if n else 0.0,
                          **delta))


def finish(ctx, st, win):
    req, tickets = st["req"], win["tickets"]
    n = len(tickets)
    done = np.isfinite(win["latency"])
    failed = int(n - done.sum())
    rng = data_mod.host_seed(ctx.seed, 4)
    pick = np.flatnonzero(done)
    pick = rng.choice(pick, min(len(pick), ctx.traffic["checked_requests"]),
                      replace=False)
    checked = []
    for j in pick:
        a = req["offset"][j]
        checked.append((st["pts"][a:a + req["size"][j]],
                        st["centers"][req["tenant"][j]],
                        tickets[j].assign, tickets[j].dist))
    numbers = reference.serve_numbers(checked)
    # a request that never finished waited at least until the loop gave up
    lat = np.where(done, win["latency"],
                   ctx.seconds + ctx.traffic["grace_s"] - req["arrival"])
    ctx.stats.update(steps=win["steps"], step_s=win["step_s"],
                     **win["delta"])
    return dict(
        metrics=dict(query_p99_ms=float(np.percentile(lat, 99) * 1e3)),
        numbers=numbers, attempted=n, failed=failed)
