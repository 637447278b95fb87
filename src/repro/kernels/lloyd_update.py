"""Fused Lloyd-statistics Pallas TPU kernel.

One pass over the points produces everything a weighted Lloyd iteration (and
Algorithm 1's sensitivity/cost accounting) needs:

    sums[c]   = sum_{p : argmin(p) = c} w_p * p        (k, d)
    counts[c] = sum_{p : argmin(p) = c} w_p            (k,)
    cost      = sum_p w_p * min_d2(p)                  ()

Per point tile: the distance block is computed on the MXU, the argmin is
converted to a one-hot matrix with an iota compare, and the center
accumulation is a second MXU matmul one_hot^T @ points -- i.e. the classic
two-matmul fused E+M statistics step, never materializing (n, k) in HBM.

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

from repro.kernels.ref import F32_DOT

Array = jax.Array


def _kernel(p_ref, c_ref, w_ref, sums_ref, counts_ref, cost_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        counts_ref[...] = jnp.zeros_like(counts_ref)
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

    min_d2 = jnp.min(d2, axis=1, keepdims=True)              # (bn, 1)
    arg = jnp.argmin(d2, axis=1).astype(jnp.int32)           # (bn,)
    k = c.shape[0]
    iota = jax.lax.broadcasted_iota(jnp.int32, (p.shape[0], k), 1)
    onehot = jnp.where(iota == arg[:, None], 1.0, 0.0) * w   # (bn, k)

    # MXU: (k, bn) @ (bn, d)
    sums_ref[...] += jax.lax.dot_general(
        onehot, p, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=F32_DOT)
    counts_ref[...] += jnp.sum(onehot, axis=0, keepdims=True).T   # (k, 1)
    cost_ref[...] += jnp.sum(w * min_d2, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def lloyd_stats(points: Array, centers: Array, weights: Array,
                block_n: int = 256, interpret: bool = False):
    """Raw kernel entry; shapes pre-padded (n % block_n == 0, padded points
    have weight 0, padded center rows huge). Returns (sums (k,d) f32,
    counts (k,1) f32, cost (1,1) f32)."""
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
        name="lloyd_stats",
    )(points, centers, weights)
