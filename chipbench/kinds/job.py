"""Back-to-back clustering jobs through ``graph_distributed_kmeans``.

A job starts with the sites on the device and ends when its centers are
ready. Job ``j`` of a run uses the key ``fold_in(key(seed), j)``; jobs run
one after another until the window closes, and the job that is running
then finishes and counts. ``job_s`` is the window's time over its jobs.

After the window: ``cost_ratio`` is the mean over the window's jobs of
cost(job centers) / cost(the reference's centralized solve), both on all
points at HIGHEST precision; ``correct`` compares a seeded sample of the
jobs (plus the last one) with the float64 reference.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import data as data_mod
from chipbench import reference
from repro.core import clustering, graph_distributed_kmeans
from repro.core.topology import Graph

# jobs compared with the reference: two of the first three, drawn from
# the seed, and the window's last job
CHECKED_EARLY = 2


def setup(ctx):
    cfg = ctx.config
    with ctx.phase("data"):
        pts, site, sp, sm = data_mod.make_sites(
            ctx.seed, cfg["n"], cfg["d"], cfg["components"], cfg["noise"],
            cfg["sites"])
        jax.block_until_ready((sp, sm))
        graph = Graph(cfg["sites"], data_mod.erdos_renyi_edges(
            cfg["sites"], cfg["graph_p"], ctx.seed))
    z = 2 if cfg["objective"] == "kmeans" else 1
    key = data_mod.base_key(ctx.seed)
    st = dict(pts=pts, site=site, sp=sp, sm=sm, graph=graph, z=z, key=key,
              t=cfg["t"])
    with ctx.phase("warm_up"):
        # one job with a key no window job uses compiles (or loads) every
        # program of the timed path
        run_job(ctx, st, jax.random.fold_in(key, 1 << 31))
    rng = data_mod.host_seed(ctx.seed, 2)
    st["checked"] = set(rng.choice(3, CHECKED_EARLY, replace=False).tolist())
    return st


def run_job(ctx, st, key):
    cfg = ctx.config
    res = graph_distributed_kmeans(
        key, st["sp"], st["sm"], cfg["k"], st["t"], st["graph"],
        objective=cfg["objective"], lloyd_iters=cfg["lloyd_iters"],
        engine=cfg["engine"], strategy=cfg["strategy"])
    jax.block_until_ready(res.centers)
    return res


def window(ctx, st):
    centers, kept = [], {}
    t0 = time.perf_counter()
    j = 0
    last = None
    while time.perf_counter() - t0 < ctx.seconds:
        with jax.profiler.TraceAnnotation("job"):
            res = run_job(ctx, st, jax.random.fold_in(st["key"], j))
        centers.append(res.centers)
        if j in st["checked"]:
            kept[j] = res
        last = (j, res)
        j += 1
    elapsed = time.perf_counter() - t0
    kept[last[0]] = last[1]
    return dict(jobs=j, elapsed=elapsed, centers=centers, kept=kept,
                host=dict(jobs=j, window_s=elapsed))


def job_outputs(res, cfg) -> dict:
    """The parts of a job's result that the checks compare, on the host:
    each site's Round-1 centers and sampled rows travel in the coreset as
    ``n_sites`` portions of ``t_buffer`` sample slots then ``k`` centers.

    The final solve is read through its own program: the job's coreset
    and centers go back into the ``lloyd`` call the final solve made
    (same shapes, same compiled program), whose first cost is the
    program's coreset cost of the job's centers."""
    n_sites, k, t = cfg["sites"], cfg["k"], cfg["t"]
    pts = res.coreset.points.reshape(n_sites, -1, cfg["d"])
    w = res.coreset.weights.reshape(n_sites, -1)
    t_buf = pts.shape[1] - k
    sw = w[:, :t_buf]
    idx = jnp.nonzero(sw.reshape(-1) != 0.0, size=t + n_sites,
                      fill_value=-1)[0]
    idx_h = np.asarray(idx)
    idx_h = idx_h[idx_h >= 0]
    flat = pts[:, :t_buf].reshape(-1, cfg["d"])
    _, hist = clustering.lloyd(res.coreset.points, res.centers,
                               weights=res.coreset.weights,
                               iters=cfg["lloyd_iters"],
                               objective=cfg["objective"])
    cw = res.coreset.weights
    cidx = jnp.nonzero(cw != 0.0, size=t + n_sites * (k + 1),
                       fill_value=-1)[0]
    cidx_h = np.asarray(cidx)
    cidx_h = cidx_h[cidx_h >= 0]
    return dict(
        centers=np.asarray(res.centers),
        local_costs=np.asarray(res.local_costs),
        site_centers=np.asarray(pts[:, t_buf:]),
        samples=np.asarray(flat[idx_h]),
        sample_weights=np.asarray(sw.reshape(-1)[idx_h]),
        sample_sites=idx_h // t_buf,
        coreset_points=np.asarray(res.coreset.points[cidx_h]),
        coreset_weights=np.asarray(cw[cidx_h]),
        final_cost=float(hist[0]),
    )


def finish(ctx, st, win):
    cfg = ctx.config
    # the reference's centralized solve, the cost ratio's denominator
    central = reference.central_solve(
        jax.random.fold_in(st["key"], 1 << 30), st["pts"], cfg["k"],
        st["z"])
    base_cost = float(reference.full_cost(st["pts"], central, st["z"]))
    costs = np.asarray([reference.full_cost(st["pts"], c, st["z"])
                        for c in win["centers"]], np.float64)
    ratios = costs / base_cost
    finite = bool(all(np.isfinite(np.asarray(c)).all()
                      for c in win["centers"]))
    outs = {j: job_outputs(res, cfg) for j, res in win["kept"].items()}
    del win["kept"]
    site_data = reference.SiteData(np.asarray(st["pts"]),
                                   np.asarray(st["site"]), cfg["sites"])
    per_job = {j: reference.job_numbers(site_data, o, st["t"], st["z"])
               for j, o in sorted(outs.items())}
    numbers = {name: max(n[name] for n in per_job.values())
               for name in next(iter(per_job.values()))}
    ctx.stats.update(jobs=win["jobs"], window_s=win["elapsed"])
    return dict(
        metrics=dict(job_s=win["elapsed"] / win["jobs"],
                     cost_ratio=float(np.mean(ratios))),
        numbers=numbers, attempted=win["jobs"],
        failed=0 if finite else win["jobs"],
        per_job=per_job)
