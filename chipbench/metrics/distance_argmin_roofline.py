"""Roofline share of the ``distance_argmin`` kernel in a job: seeding
sweeps (one center) and sensitivity passes (k centers)."""
from chipbench import rooflines

KERNEL = "distance_argmin"


def flops_bytes(n, k, d):
    """Logical work of one call on n rows, k centers in d dimensions:
    the cross term 2nkd plus the norms and the min (3nk + nd), reading the
    points and the centers once and writing a distance and an index per
    row (4 bytes each)."""
    flops = 2 * n * k * d + 3 * n * k + n * d
    bytes_ = 4 * (n * d + k * d) + 8 * n
    return flops, bytes_


def read(ctx):
    return rooflines.share(ctx, KERNEL, flops_bytes)
