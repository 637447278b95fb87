"""Roofline share of the fused ``lloyd_stats`` kernel (one k-means
update's assignment, per-cluster sums, counts and cost)."""
from chipbench import rooflines

KERNEL = "lloyd_stats"


def flops_bytes(n, k, d):
    """Logical work of one call: the distances (2nkd + 3nk + nd), then each
    row's weighted add into its cluster's sum (2nd) plus its count and
    cost (4n). Reads the points, their weights and the centers; writes the
    sums, counts and cost."""
    flops = 2 * n * k * d + 3 * n * k + n * d + 2 * n * d + 4 * n
    bytes_ = 4 * (n * d + n + k * d) + 4 * (k * d + k + 1)
    return flops, bytes_


def read(ctx):
    return rooflines.share(ctx, KERNEL, flops_bytes)
