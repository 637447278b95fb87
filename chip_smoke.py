"""Run the paper's full-scale clustering job once on a TPU and check what
comes out.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the SPMD mesh path on four chips, only

The default run takes the largest setting of the paper's Sec. 6 --
YearPredictionMSD (515,345 x 90, k=50) over the 100 sites of an
ER(100, 0.3) graph, weighted partition, budget t = 3 k n -- through
``graph_distributed_kmeans`` with the default backend and engine, for
k-means and k-median. It checks that

* the default backend resolves to ``pallas``, and one site's
  ``lloyd_stats`` / ``weiszfeld_stats`` lower to their Mosaic kernels (not
  interpret mode, not the XLA one-hot fallback);
* each kernel at the job's widths agrees with a float64 NumPy computation
  to the f32 tolerances of ``tests/test_kernels.py``;
* the cost ratio against the centralized ``clustering.solve`` is at most
  1.3, for the job and for the same job on the dense ``jnp`` backend at
  highest matmul precision, and the two agree within 2%, as do their
  Round-1 cost totals;
* ``ClusterServeEngine`` serving both center sets agrees with a float64
  argmin on at least 99.9% of the queries.

``--chips 4`` runs only ``spmd_distributed_kmeans`` on a 4-device mesh (the
100 sites placed 25 per chip) with its three collective lowerings, for both
objectives, and the centralized solve it is compared with.

Every phase prints one ``<phase> {json}`` line. The last line of stdout is
``{"ok": true, "device": {...}}``. With no TPU, or on any failed check, the
script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(_ROOT, "src"), _ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from benchmarks.common import Setting, load_setting_host  # noqa: E402
from repro.cache import enable_compilation_cache  # noqa: E402
from repro.core import backend as backend_mod  # noqa: E402
from repro.core import (clustering, graph_distributed_kmeans,  # noqa: E402
                        spmd_distributed_kmeans)
from repro.core.coreset import proportional_allocation  # noqa: E402
from repro.core.message_passing import torus_mesh_shape  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.kernels.ref import WEISZFELD_ETA2  # noqa: E402
from repro.serve import ClusterServeEngine, StaticCenters  # noqa: E402

# the paper's largest Sec. 6 setting (benchmarks/bench_fig2_graphs.py)
SETTING = Setting("yearpredictionmsd", "random", "weighted", 100)
OBJECTIVES = ("kmeans", "kmedian")
LOWERINGS = ("all_gather", "neighbor_rounds", "torus_2d")
MAX_RATIO = 1.3        # the SPMD test's bound (tests/test_core_distributed.py)
REF_AGREEMENT = 0.02   # the dense reference vs the default backend
SERVE_AGREEMENT = 0.999


class SmokeFailure(AssertionError):
    pass


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def report(phase: str, **fields) -> None:
    print(f"{phase} {json.dumps(fields)}", flush=True)


class CompileClock:
    """Seconds spent in XLA backend compiles, and persistent-cache hits."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def timed(fn, clock: CompileClock):
    """Call ``fn`` twice: the first call compiles, the second is warm.
    Returns the warm result and {cold_s, compile_s, cache_hits, warm_s}."""
    s0, h0 = clock.seconds, clock.cache_hits
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(getattr(out, "centers", out))
    cold = time.perf_counter() - t0
    compile_s, hits = clock.seconds - s0, clock.cache_hits - h0
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(getattr(out, "centers", out))
    return out, dict(cold_s=cold, compile_s=compile_s, cache_hits=hits,
                     warm_s=time.perf_counter() - t0)


def full_cost(pts, centers, objective: str) -> float:
    """Cost of ``centers`` on all points: dense, at highest precision."""
    with jax.default_matmul_precision("highest"):
        return float(clustering.cost(pts, centers, objective=objective,
                                     chunk=65536, backend="jnp"))


def central_centers(key, pts, k: int, objective: str, backend=None):
    """The centralized baseline: ``clustering.solve`` on all points."""
    centers, _ = clustering.solve(jax.random.fold_in(key, 7), pts, k,
                                  objective=objective, lloyd_iters=12,
                                  restarts=3, backend=backend)
    return centers


def peak_bytes(device):
    return (device.memory_stats() or {}).get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def kernels_lowered(site, centers, w) -> None:
    """The default backend's statistics ops must lower to their Mosaic
    kernels: interpret mode lowers to plain HLO (no ``tpu_custom_call``),
    and the large-k fallback to ``distance_argmin`` plus XLA one-hots."""
    b = backend_mod.get_backend(None)
    for op in ("lloyd_stats", "weiszfeld_stats"):
        text = jax.jit(getattr(b, op)).lower(site, centers, w).as_text()
        check("tpu_custom_call" in text and f'kernel_name = "{op}"' in text,
              f"{op} did not lower to its Mosaic kernel")


def _worst(got, want, rtol: float, atol: float) -> float:
    """Largest error over its tolerance (<= 1 passes)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want) / (atol + rtol * np.abs(want))))


def kernel_numerics(x, c, w) -> dict:
    """Every kernel on ``x`` (n, d), ``c`` (k, d), ``w`` (n,) against float64
    NumPy. Tolerances are the f32 ones of ``tests/test_kernels.py``; the
    distance tolerance is taken relative to |p|^2 + |c|^2, which is the
    size of a squared distance at those tests' zero-mean data. A single
    bf16 pass over the distance matmul misses it by two orders."""
    x64, c64, w64 = (np.asarray(a, np.float64) for a in (x, c, w))
    p2, c2 = (x64 ** 2).sum(1), (c64 ** 2).sum(1)
    d2 = np.maximum(p2[:, None] + c2[None, :] - 2.0 * x64 @ c64.T, 0.0)
    am = d2.argmin(1)
    md = d2[np.arange(len(x64)), am]
    scale = p2 + c2[am]

    md_k, am_k = ops.min_dist_argmin(jnp.asarray(x), jnp.asarray(c))
    md_k, am_k = np.asarray(md_k, np.float64), np.asarray(am_k)
    out = dict(distance_err=float(np.max(np.abs(md_k - md) / scale)),
               argmin_mismatch=int((am_k != am).sum()))
    # a different argmin is allowed only at a tie (the tests' rule)
    flip = am_k != am
    out["argmin_tie_worst"] = (_worst(d2[flip, am_k[flip]], md[flip],
                                      1e-3, 1e-3) if flip.any() else 0.0)

    oh = np.zeros_like(d2)
    oh[np.arange(len(x64)), am] = w64
    s_k, n_k, cost_k = ops.lloyd_stats(jnp.asarray(x), jnp.asarray(c),
                                       jnp.asarray(w))
    out.update(lloyd_sums=_worst(s_k, oh.T @ x64, 1e-4, 1e-3),
               lloyd_counts=_worst(n_k, oh.sum(0), 1e-4, 1e-2),
               lloyd_cost=_worst(cost_k, (w64 * md).sum(), 5e-3, 0.0))

    inv = np.maximum(w64, 0.0) / np.sqrt(md + WEISZFELD_ETA2)
    oh = np.zeros_like(d2)
    oh[np.arange(len(x64)), am] = inv
    nu_k, de_k, wc_k = ops.weiszfeld_stats(jnp.asarray(x), jnp.asarray(c),
                                           jnp.asarray(w))
    out.update(weiszfeld_nums=_worst(nu_k, oh.T @ x64, 1e-4, 1e-3),
               weiszfeld_denoms=_worst(de_k, oh.sum(0), 1e-4, 1e-2),
               weiszfeld_cost=_worst(wc_k, (w64 * np.sqrt(md)).sum(), 5e-3,
                                     0.0))

    # the serving kernel: two tenants split the rows, each with its own
    # centers
    tenants = 2
    m = len(x64) // tenants
    cs = np.stack([c64 + 0.1 * i for i in range(tenants)])
    qs = x64[:tenants * m].reshape(tenants, m, -1)
    md_b, _ = ops.min_dist_argmin_batched(jnp.asarray(qs, jnp.float32),
                                          jnp.asarray(cs, jnp.float32))
    qp2, cp2 = (qs ** 2).sum(-1), (cs ** 2).sum(-1)
    d2b = np.maximum(qp2[..., None] + cp2[:, None, :]
                     - 2.0 * np.einsum("tmd,tkd->tmk", qs, cs), 0.0)
    amb = d2b.argmin(-1)
    scale_b = qp2 + np.take_along_axis(cp2, amb, 1)
    out["batched_distance_err"] = float(np.max(
        np.abs(np.asarray(md_b, np.float64) - d2b.min(-1)) / scale_b))

    check(out["distance_err"] <= 1e-5 and out["batched_distance_err"] <= 1e-5,
          f"distance kernels miss f32 precision: {out}")
    check(out["argmin_tie_worst"] <= 1.0, f"argmin differs off a tie: {out}")
    check(all(out[f] <= 1.0 for f in (
        "lloyd_sums", "lloyd_counts", "lloyd_cost", "weiszfeld_nums",
        "weiszfeld_denoms", "weiszfeld_cost")),
        f"statistics kernels miss their tolerances: {out}")
    return out


def objective_phase(key, pts, sp, sm, k: int, graph, t: int, objective: str,
                    clock: CompileClock, backend=None) -> np.ndarray:
    """The distributed job on the default (or given) backend, timed, beside
    the centralized solve and the same job on the dense ``jnp`` backend at
    highest matmul precision. Both cost ratios are held to ``MAX_RATIO``
    and to each other, and so is the Round-1 cost total, which every site
    computes deterministically from its own data."""
    res, times = timed(lambda: graph_distributed_kmeans(
        key, sp, sm, k, t, graph, objective=objective, backend=backend),
        clock)
    centers = np.asarray(res.centers)
    check(centers.shape == (k, pts.shape[1]) and np.isfinite(centers).all(),
          f"{objective}: centers not finite of shape {(k, pts.shape[1])}")
    with jax.default_matmul_precision("highest"):
        ref = graph_distributed_kmeans(key, sp, sm, k, t, graph,
                                       objective=objective, backend="jnp")
    base = full_cost(pts, central_centers(key, pts, k, objective, backend),
                     objective)
    ratio = full_cost(pts, res.centers, objective) / base
    ref_ratio = full_cost(pts, ref.centers, objective) / base
    round1 = float(jnp.sum(res.local_costs))
    round1_vs_ref = float(jnp.sum(ref.local_costs)) / round1
    report(objective, jax=jax.__version__,
           backend=backend_mod.resolve_name(backend), t=t, **times,
           cost_ratio=ratio, ref_cost_ratio=ref_ratio,
           round1_cost=round1, ref_round1_vs_round1=round1_vs_ref,
           ledger_bytes=res.ledger.bytes,
           peak_bytes_in_use=peak_bytes(jax.devices()[0]))
    check(ratio <= MAX_RATIO and ref_ratio <= MAX_RATIO,
          f"{objective}: cost ratio {ratio} / reference {ref_ratio} "
          f"above {MAX_RATIO}")
    check(abs(ratio / ref_ratio - 1.0) <= REF_AGREEMENT
          and abs(round1_vs_ref - 1.0) <= REF_AGREEMENT,
          f"{objective}: cost ratio {ratio} and Round-1 total {round1} "
          f"differ from the reference's by more than {REF_AGREEMENT:.0%} "
          f"({ref_ratio}, x{round1_vs_ref})")
    return centers


def serving_phase(centers: dict, pts: np.ndarray, seed: int,
                  rows_per_tenant: int = 3000) -> dict:
    """Both center sets as tenants of one engine; uneven batches of dataset
    rows; assignments against a float64 argmin."""
    engine = ClusterServeEngine()
    rng = np.random.default_rng(seed)
    sent = []
    for objective, c in centers.items():
        tid = engine.add_tenant(StaticCenters(c), c.shape[0], c.shape[1],
                                objective=objective)
        n = 0
        while n < rows_per_tenant:
            rows = pts[rng.integers(0, len(pts), int(rng.integers(1, 700)))]
            sent.append((c, rows, engine.enqueue(tid, rows)))
            n += len(rows)
    engine.run()
    agree = total = 0
    for c, rows, ticket in sent:
        check(ticket.done and np.isfinite(ticket.dist).all(),
              "a serving ticket is unfinished or has non-finite distances")
        q, c64 = rows.astype(np.float64), c.astype(np.float64)
        want = ((q[:, None, :] - c64[None]) ** 2).sum(-1).argmin(1)
        agree += int((np.asarray(ticket.assign) == want).sum())
        total += len(rows)
    out = dict(tenants=len(centers), batches=len(sent), queries=total,
               dispatches=engine.stats.n_dispatches, agreement=agree / total)
    report("serving", **out)
    check(agree / total >= SERVE_AGREEMENT,
          f"serving agrees with the float64 argmin on {agree}/{total}")
    return out


def one_chip(pts, k: int, graph, sp, sm, key, t: int, seed: int,
             clock: CompileClock) -> None:
    backend = backend_mod.resolve_name(None)
    check(backend == "pallas", f"default backend is {backend!r}, not pallas")
    pts_d = jax.device_put(pts)
    sp, sm = jax.device_put(sp), jax.device_put(sm)
    kernels_lowered(sp[0], jnp.asarray(pts[:k]), sm[0].astype(jnp.float32))
    report("kernels", backend=backend, lowered="tpu_custom_call")

    # one site's width, centers near data points, positive weights
    rng = np.random.default_rng(seed)
    x = pts[:sp.shape[1]]
    c = (x[rng.choice(len(x), k, replace=False)]
         + 0.5 * rng.standard_normal((k, x.shape[1]))).astype(np.float32)
    w = rng.uniform(0.0, 2.0, len(x)).astype(np.float32)
    report("numerics", n=len(x), d=x.shape[1], k=k,
           **kernel_numerics(x, c, w))

    centers = {obj: objective_phase(key, pts_d, sp, sm, k, graph, t, obj,
                                    clock) for obj in OBJECTIVES}
    serving_phase(centers, pts, seed)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def mesh_phase(mesh, key, pts: np.ndarray, k: int, sp: np.ndarray,
               sm: np.ndarray, t: int, clock: CompileClock,
               objectives=OBJECTIVES) -> dict:
    """``spmd_distributed_kmeans`` over ``mesh``'s one axis with every
    collective lowering; sites go from the host straight to their chips."""
    axis = mesh.axis_names[0]
    n_dev = mesh.devices.size
    per_dev = sp.shape[0] // n_dev
    shard = NamedSharding(mesh, P(axis))
    sp_d, sm_d = jax.device_put(sp, shard), jax.device_put(sm, shard)
    devices = set(mesh.devices.flat)
    for arr in (sp_d, sm_d):
        shards = arr.addressable_shards
        check(len(shards) == n_dev and {s.device for s in shards} == devices,
              "site shards are not spread over the mesh's devices")
        check(all(s.data.shape[0] == per_dev and s.data.devices() == {s.device}
                  for s in shards),
              f"a device does not hold its own {per_dev} sites")
    pts_d = jnp.asarray(pts)
    out = {}
    for objective in objectives:
        base = full_cost(pts_d, central_centers(key, pts_d, k, objective),
                         objective)
        runs = {}
        for low in LOWERINGS:
            (c, lc, t_i), times = timed(
                lambda low=low: spmd_distributed_kmeans(
                    mesh, axis, key, sp_d, sm_d, k, t, objective=objective,
                    collectives=low), clock)
            runs[low] = (np.asarray(c), np.asarray(lc), np.asarray(t_i))
            report(f"mesh_{objective}_{low}", devices=n_dev,
                   sites_per_device=per_dev,
                   mesh_shape=(list(torus_mesh_shape(n_dev))
                               if low == "torus_2d" else None), **times)
        c, lc, t_i = runs["all_gather"]
        check(all(np.array_equal(r[0], c) for r in runs.values()),
              f"{objective}: centers differ across collective lowerings")
        alloc = np.asarray(proportional_allocation(jnp.asarray(lc), t))
        check(np.array_equal(t_i, alloc) and int(t_i.sum()) == t,
              f"{objective}: t_i {t_i} is not the allocation {alloc} of t={t}")
        ratio = full_cost(pts_d, c, objective) / base
        out[objective] = dict(cost_ratio=ratio, t_i=t_i.tolist(),
                              centers_bit_equal=True)
        report(f"mesh_{objective}", **out[objective])
        check(ratio <= MAX_RATIO,
              f"{objective}: mesh cost ratio {ratio} above {MAX_RATIO}")
    out["peak_bytes_in_use"] = [peak_bytes(d) for d in mesh.devices.flat]
    report("mesh_memory", peak_bytes_in_use=out["peak_bytes_in_use"])
    return out


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Run the paper's full-scale clustering job on a TPU.")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the SPMD mesh path across four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU: JAX found {devices[0].platform!r} "
              f"devices only", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 1
    cache_dir = enable_compilation_cache()
    clock = CompileClock()
    dev = devices[0]
    report("device", jax=jax.__version__, platform=dev.platform,
           kind=dev.device_kind, count=len(devices), cache_dir=cache_dir)

    st = SETTING
    pts, k, graph, sp, sm = load_setting_host(Setting(
        st.dataset, st.topology, st.partition, st.n_sites, seed=args.seed))
    t = 3 * k * graph.n               # the fig2 budget
    report("data", n=len(pts), d=pts.shape[1], k=k, sites=graph.n,
           edges=graph.m, site_shape=list(sp.shape), site_bytes=sp.nbytes,
           t=t)
    key = jax.random.PRNGKey(args.seed)
    if args.chips == 4:
        mesh = jax.make_mesh((4,), ("sites",), devices=devices[:4])
        mesh_phase(mesh, key, pts, k, sp, sm, t, clock)
    else:
        one_chip(pts, k, graph, sp, sm, key, t, args.seed, clock)
    report("compile", seconds=clock.seconds, cache_hits=clock.cache_hits)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
