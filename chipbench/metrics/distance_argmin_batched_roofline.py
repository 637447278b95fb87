"""Roofline share of ``distance_argmin_batched`` in serving: every fused
dispatch of the window, sized by the engine's counters (real query rows
and real tenant-chunks), since a dispatch's real rows are not in the
trace. All its calls are bound by bytes (k = 50, d = 90: 25 flop/byte
against a ridge of 240), so the sum of the per-call bounds is the bound of
the summed work."""
from chipbench.peaks import device_peaks

KERNEL = "distance_argmin_batched"


def flops_bytes(rows, chunks, k, d):
    """Logical work of ``rows`` real query rows against their tenants' k
    centers, where ``chunks`` tenant-chunks each read one center set:
    distances 2 rows k d + 3 rows k + rows d; bytes: the rows, the
    centers per chunk, a distance and an index per row."""
    flops = 2 * rows * k * d + 3 * rows * k + rows * d
    bytes_ = 4 * (rows * d + chunks * k * d) + 8 * rows
    return flops, bytes_


def read(ctx):
    r = ctx.reduced
    rows, chunks = ctx.stats.get("n_queries"), ctx.stats.get(
        "n_tenant_dispatches")
    if r is None or not rows or not chunks:
        return None
    ops = r.kernel_ops(KERNEL)
    took = sum(o.dur for o in ops) * 1e-9
    if took <= 0:
        return None
    peak_flops, peak_bytes = device_peaks(ctx.device_kind)
    f, b = flops_bytes(rows, chunks, ctx.config["k"], ctx.config["d"])
    return 100.0 * max(f / peak_flops, b / peak_bytes) / took
