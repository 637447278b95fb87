"""Kernel + backend-dispatch benchmarks.

Two sections:

1. **Backend A/B through the dispatch layer** -- the two primitive ops and
   an end-to-end weighted Lloyd solve routed through every registered
   backend (``jnp`` / ``jnp_chunked`` / ``pallas``). On this CPU container
   the pallas rows run in interpret mode (wall times are NOT TPU times);
   the same sweep on a TPU host measures the fused kernels for real. One
   JSON row per (op, backend, shape) so the perf trajectory can track
   backend speedups across PRs.

2. **Analytic roofline** for each kernel configuration, against the peaks
   of the device it runs on (:data:`PEAKS`, keyed by ``device_kind``; an
   unknown device raises, so this section runs on a TPU only):
       flops  = 2 n k d (distance matmul) [+ 2 n k d accumulate for lloyd]
       bytes  = 4(nd + kd + n(out))   HBM, fused (distance matrix never stored)
       naive  = + 4 n k               HBM for the materialized matrix
   The fused kernel's arithmetic intensity flops/bytes rises by ~k/2 vs
   naive.
"""
from __future__ import annotations

import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import json_row
from repro.core import backend as backend_mod
from repro.core import clustering, objective
from repro.kernels import ops, ref

# (peak FLOP/s, peak HBM bytes/s) per ``jax.Device.device_kind``. TPU v5e:
# 197 TFLOP/s bf16 and 819 GB/s HBM (Google Cloud documentation, "TPU v5e").
PEAKS = {
    "TPU v5 lite": (197e12, 819e9),
}


def device_peaks(device_kind: str):
    """(peak FLOP/s, peak bytes/s) of a device kind; raises for a device
    the table does not know rather than assuming another chip's peaks."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no roofline peaks for device_kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None

# the chunked entrant uses a chunk *below* the sweep sizes so the lax.map
# path actually runs (the registry default of 65536 would fall through to
# the dense code at benchmark n)
BENCH_CHUNK = 1024


def dispatch_entrants():
    chunked = backend_mod.register_backend(
        backend_mod.JnpChunkedBackend(BENCH_CHUNK, name="jnp_chunked_bench"))
    return (("jnp", backend_mod.get_backend("jnp")),
            ("jnp_chunked", chunked),
            ("pallas", backend_mod.get_backend("pallas")))


def _time(fn, *args, reps=3):
    out = fn(*args)
    jax.tree.leaves(out)[0].block_until_ready()
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
        jax.tree.leaves(out)[0].block_until_ready()
    return (time.time() - t0) / reps * 1e6


def _data(n, k, d, seed=0):
    rng = np.random.default_rng(seed)
    pts = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
    ctr = jnp.asarray(rng.standard_normal((k, d)).astype(np.float32))
    w = jnp.asarray(np.abs(rng.standard_normal(n)).astype(np.float32))
    return pts, ctr, w


def run_dispatch(out_rows: List[str] | None = None,
                 shapes=((4096, 64, 32), (16384, 50, 16))) -> List[str]:
    """A/B the registered backends on the primitive ops and an end-to-end
    weighted Lloyd solve, all through the dispatch layer. One row per
    (objective, backend, shape): the k-means rows time ``lloyd_stats``, the
    k-median rows time the fused ``weiszfeld_stats`` primitive, and the
    trimmed rows time the two-pass robust update (``min_dist_argmin`` for
    the residual trim mask, then ``lloyd_stats`` on the masked weights) --
    all objectives are peers of the dispatch layer."""
    rows = out_rows if out_rows is not None else []
    interpreted = jax.default_backend() != "tpu"
    for n, k, d in shapes:
        pts, ctr, w = _data(n, k, d)
        for name, b in dispatch_entrants():
            t_mda = _time(jax.jit(lambda p, c: b.min_dist_argmin(p, c)),
                          pts, ctr)
            t_ls = _time(jax.jit(lambda p, c, ww: b.lloyd_stats(p, c, ww)),
                         pts, ctr, w)
            t_e2e = _time(
                lambda p, c, ww: clustering.lloyd(p, c, weights=ww, iters=2,
                                                  backend=b),
                pts, ctr, w, reps=1)

            json_row(
                rows, f"backend_dispatch/{name}/n={n}/k={k}/d={d}", t_ls,
                backend=name,
                objective="kmeans",
                interpret=bool(interpreted and name == "pallas"),
                chunk=getattr(b, "chunk", None),
                n=n, k=k, d=d,
                min_dist_argmin_us=round(t_mda, 1),
                lloyd_stats_us=round(t_ls, 1),
                lloyd2_e2e_us=round(t_e2e, 1),
            )

            t_ws = _time(
                jax.jit(lambda p, c, ww: b.weiszfeld_stats(p, c, ww)),
                pts, ctr, w)
            t_e2e_med = _time(
                lambda p, c, ww: clustering.lloyd(p, c, weights=ww, iters=2,
                                                  objective="kmedian",
                                                  backend=b),
                pts, ctr, w, reps=1)
            json_row(
                rows,
                f"backend_dispatch_kmedian/{name}/n={n}/k={k}/d={d}", t_ws,
                backend=name,
                objective="kmedian",
                interpret=bool(interpreted and name == "pallas"),
                chunk=getattr(b, "chunk", None),
                n=n, k=k, d=d,
                weiszfeld_stats_us=round(t_ws, 1),
                lloyd2_e2e_us=round(t_e2e_med, 1),
            )

            # trimmed robust update: pass 1 residuals (min_dist_argmin),
            # pass 2 lloyd_stats with the top-t residual weights zeroed --
            # never an (n, k) materialization
            trimmed = objective.kmeans_trimmed(max(n // 20, 1))
            t_trim = _time(
                jax.jit(lambda p, c, ww: trimmed.update(b, p, ww, c)),
                pts, ctr, w)
            t_e2e_trim = _time(
                lambda p, c, ww: clustering.lloyd(p, c, weights=ww, iters=2,
                                                  objective=trimmed.name,
                                                  backend=b),
                pts, ctr, w, reps=1)
            json_row(
                rows,
                f"backend_dispatch_trimmed/{name}/n={n}/k={k}/d={d}",
                t_trim,
                backend=name,
                objective=trimmed.name,
                interpret=bool(interpreted and name == "pallas"),
                chunk=getattr(b, "chunk", None),
                n=n, k=k, d=d,
                trimmed_update_us=round(t_trim, 1),
                lloyd2_e2e_us=round(t_e2e_trim, 1),
                overhead_vs_lloyd_stats=round(t_trim / t_ls, 2),
            )
    return rows


def run_roofline(out_rows: List[str] | None = None) -> List[str]:
    rows = out_rows if out_rows is not None else []
    PEAK, BW = device_peaks(jax.devices()[0].device_kind)
    shapes = [(4096, 64, 128), (16384, 256, 128), (65536, 50, 128)]
    for n, k, d in shapes:
        pts, ctr, w = _data(n, k, d)

        t_ref = _time(jax.jit(ref.min_dist_argmin_ref), pts, ctr)
        t_pal = _time(lambda p, c: ops.min_dist_argmin(p, c), pts, ctr)

        flops = 2.0 * n * k * d
        fused_bytes = 4.0 * (n * d + k * d + 2 * n)
        naive_bytes = fused_bytes + 4.0 * n * k
        t_compute = flops / PEAK
        t_fused = max(t_compute, fused_bytes / BW)
        t_naive = max(t_compute, naive_bytes / BW)
        rows.append(
            f"kernel_distance_argmin/n={n}/k={k}/d={d},{t_pal:.0f},"
            f"ref_us={t_ref:.0f};pallas_us={t_pal:.0f};"
            f"bound_fused_us={t_fused*1e6:.1f};"
            f"bound_naive_us={t_naive*1e6:.1f};"
            f"bound_speedup={t_naive/t_fused:.2f}")
        print(rows[-1], flush=True)

        t_ref2 = _time(jax.jit(ref.lloyd_stats_ref), pts, ctr, w)
        t_pal2 = _time(lambda p, c, ww: ops.lloyd_stats(p, c, ww), pts, ctr,
                       w)
        flops2 = 4.0 * n * k * d
        fused2 = 4.0 * (n * d + 2 * k * d + k + n)
        naive2 = fused2 + 8.0 * n * k
        tf = max(flops2 / PEAK, fused2 / BW)
        tn = max(flops2 / PEAK, naive2 / BW)
        rows.append(
            f"kernel_lloyd_stats/n={n}/k={k}/d={d},{t_pal2:.0f},"
            f"ref_us={t_ref2:.0f};pallas_us={t_pal2:.0f};"
            f"bound_fused_us={tf*1e6:.1f};bound_naive_us={tn*1e6:.1f};"
            f"bound_speedup={tn/tf:.2f}")
        print(rows[-1], flush=True)
    return rows


def run(out_rows: List[str] | None = None) -> List[str]:
    rows = out_rows if out_rows is not None else []
    run_dispatch(out_rows=rows)
    if jax.default_backend() == "tpu":
        run_roofline(out_rows=rows)
    else:
        print("# kernel roofline: not measured (needs a TPU)", flush=True)
    return rows


if __name__ == "__main__":
    run()
