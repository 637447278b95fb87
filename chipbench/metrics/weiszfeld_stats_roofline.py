"""Roofline share of the fused ``weiszfeld_stats`` kernel (one k-median
refinement pass: assignment, exact distance to the assigned center,
smoothed inverse-distance weighted sums)."""
from chipbench import rooflines

KERNEL = "weiszfeld_stats"


def flops_bytes(n, k, d):
    """Logical work of one call: the distances (2nkd + 3nk + nd), the
    exact distance to the assigned center (3nd), the smoothed inverse
    weight (4n) and its weighted add into the cluster's numerator (2nd)
    and denominator and cost (4n). Same bytes as ``lloyd_stats``."""
    flops = 2 * n * k * d + 3 * n * k + n * d + 5 * n * d + 8 * n
    bytes_ = 4 * (n * d + n + k * d) + 4 * (k * d + k + 1)
    return flops, bytes_


def read(ctx):
    return rooflines.share(ctx, KERNEL, flops_bytes)
