"""Shared arithmetic of the ``*_roofline`` metrics: a kernel's share of
its roofline over the traced window,

    sum over its calls of max(flops / peak FLOP/s, bytes / peak bytes/s)
    ------------------------------------------------------------------
                sum over its calls of device time

where a call's flops and bytes are those of its logical problem (real
rows, real k, the configuration's d), computed by the metric's own
``flops_bytes``. Padding is not counted, so removing it changes the time
and not the count.
"""
from __future__ import annotations

from chipbench.peaks import device_peaks

# programs whose kernels work on the real points of every site at once
# (the vmapped Round 1), and those of the final solve on the coreset
ROUND1 = ("round1_local_solves",)
FINAL = ("jit__kmeans_pp_init", "jit__lloyd")


def job_call(ctx, op):
    """(rows, k) of one kernel call in a job: Round 1 covers all n points;
    the final solve covers the coreset's t sampled rows and the sites' k
    centers each. A call whose centers operand is padded to fewer rows
    than k is a seeding sweep against one new center."""
    cfg = ctx.config
    if any(p in op.module for p in ROUND1):
        rows = cfg["n"]
    elif any(p in op.module for p in FINAL):
        rows = cfg["t"] + cfg["sites"] * cfg["k"]
    else:
        return None
    k = cfg["k"]
    if len(op.shapes) >= 2 and op.shapes[1] and op.shapes[1][-2] < k:
        k = 1
    return rows, k


def share(ctx, kernel: str, flops_bytes) -> float | None:
    """The kernel's roofline share in %, or None where the window ran no
    call of it that this cell can size."""
    r = ctx.reduced
    if r is None:
        return None
    ops = r.kernel_ops(kernel)
    if not ops:
        return None
    peak_flops, peak_bytes = device_peaks(ctx.device_kind)
    need = took = 0.0
    for op in ops:
        size = job_call(ctx, op)
        if size is None:
            return None
        f, b = flops_bytes(size[0], size[1], ctx.config["d"])
        need += max(f / peak_flops, b / peak_bytes)
        took += op.dur * 1e-9
    return 100.0 * need / took if took > 0 else None
