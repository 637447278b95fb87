"""Fused Weiszfeld-statistics Pallas TPU kernel (the k-median peer of
``lloyd_update.py``).

One pass over the points produces everything a fused k-median refinement
pass (assign + one Weiszfeld geometric-median update) needs:

    nums[c]   = sum_{p : argmin(p) = c} max(w_p, 0) * p / d(p, y_c)   (k, d)
    denoms[c] = sum_{p : argmin(p) = c} max(w_p, 0) / d(p, y_c)       (k,)
    cost      = sum_p w_p * d(p, Y)                                   ()

where d(p, y_c) = sqrt(d2(p) + eta^2) is the smoothed euclidean distance of
a point to its *nearest* center -- the only distance a Weiszfeld step over
the argmin partition ever divides by, which is why the (n, k) distance
matrix never needs to exist. Membership mass is clamped to max(w, 0)
(optimizing against the negative part of a signed coreset measure admits
spurious minima) while the reported cost keeps the signed weights, matching
``repro.core.clustering`` semantics (DESIGN.md Sec. 10).

Numerics: the argmin is selected on the MXU |p|^2 + |c|^2 - 2 p.c distance
block (robust -- ties are the only casualties of its cancellation noise),
but the distance fed to the *inverse* is recomputed in the exact
subtraction form sum((p - c_arg)^2): near zero the matmul trick is pure
cancellation noise (~1e-6 at unit scale), and 1/sqrt amplifies that into
orders-of-magnitude cross-backend disagreement exactly where k-means++
seeds sit (seeds are data points). ``ref.WEISZFELD_ETA2`` bounds the pull
of a truly coincident point at w/eta.

Per point tile: the distance block is computed on the MXU, the argmin is
converted to a one-hot matrix with an iota compare, the assigned center is
gathered back with a one-hot matmul (exact: one 1.0 per row), and the
numerator accumulation is a third MXU matmul (1/d-scaled one_hot)^T @
points -- the two-matmul structure of the Lloyd-statistics kernel plus one
gather matmul.

The centers (k, d) stay fully resident in VMEM, so this kernel targets the
clustering regime (k*d <= ~1M f32 = 4 MB); ops.py falls back to the two-pass
formulation when the resident block would not fit.

Grid: (n/bn,). All three outputs use constant index maps: they are revisited
by every grid step and accumulated in VMEM, written back once at the end.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import F32_DOT, WEISZFELD_ETA2

Array = jax.Array


def _kernel(p_ref, c_ref, w_ref, nums_ref, denoms_ref, cost_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        nums_ref[...] = jnp.zeros_like(nums_ref)
        denoms_ref[...] = jnp.zeros_like(denoms_ref)
        cost_ref[...] = jnp.zeros_like(cost_ref)

    p = p_ref[...].astype(jnp.float32)            # (bn, d)
    c = c_ref[...].astype(jnp.float32)            # (k, d)
    w = w_ref[...].astype(jnp.float32)            # (bn, 1)

    p2 = jnp.sum(p * p, axis=1, keepdims=True)
    c2 = jnp.sum(c * c, axis=1)
    prod = jax.lax.dot_general(
        p, c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=F32_DOT)
    d2 = jnp.maximum(p2 + c2[None, :] - 2.0 * prod, 0.0)     # (bn, k)
    arg = jnp.argmin(d2, axis=1).astype(jnp.int32)           # (bn,)

    k = c.shape[0]
    iota = jax.lax.broadcasted_iota(jnp.int32, (p.shape[0], k), 1)
    onehot = jnp.where(iota == arg[:, None], 1.0, 0.0)       # (bn, k)

    # exact-form distance to the assigned center: gather on the MXU
    # (exactly one 1.0 per row, padded sentinel rows multiplied by 0.0),
    # then subtract -- no cancellation near zero.
    c_at = jax.lax.dot_general(
        onehot, c, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=F32_DOT)                                   # (bn, d)
    diff = p - c_at
    min_d2 = jnp.sum(diff * diff, axis=1, keepdims=True)     # (bn, 1)
    dist = jnp.sqrt(min_d2 + WEISZFELD_ETA2)                 # (bn, 1)
    inv = jnp.maximum(w, 0.0) / dist                         # (bn, 1)
    onehot = onehot * inv                                    # (bn, k)

    # MXU: (k, bn) @ (bn, d)
    nums_ref[...] += jax.lax.dot_general(
        onehot, p, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=F32_DOT)
    denoms_ref[...] += jnp.sum(onehot, axis=0, keepdims=True).T   # (k, 1)
    cost_ref[...] += jnp.sum(w * jnp.sqrt(min_d2), keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def weiszfeld_stats(points: Array, centers: Array, weights: Array,
                    block_n: int = 256, interpret: bool = False):
    """Raw kernel entry; shapes pre-padded (n % block_n == 0, padded points
    have weight 0, padded center rows huge). Returns (nums (k,d) f32,
    denoms (k,1) f32, cost (1,1) f32)."""
    n, d = points.shape
    k, _ = centers.shape
    assert n % block_n == 0, (n, block_n)
    grid = (n // block_n,)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((k, d), lambda i: (0, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((k, d), lambda i: (0, 0)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, d), jnp.float32),
            jax.ShapeDtypeStruct((k, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
        name="weiszfeld_stats",
    )(points, centers, weights)
