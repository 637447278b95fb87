"""Fused pairwise-distance + online arg-min Pallas TPU kernel.

The assignment step of Lloyd's algorithm and the sensitivity computation
m_p = cost(p, B_i) of Algorithm 1 both reduce to: for every point, the
min/argmin squared distance over k centers. The naive
formulation materializes an (n, k) distance matrix in HBM; this kernel tiles
points x centers into VMEM, computes the distance tile via a single MXU
matmul (d^2 = |p|^2 + |c|^2 - 2 p.c) and keeps a *running* min/argmin across
center tiles (flash-attention-style online reduction) so the (n, k) matrix
never exists.

Grid layout: (n/bn, k/bk), center axis minor. The two output blocks depend
only on the point-tile index i, so they stay resident in VMEM across the
entire sweep over center tiles j (standard revisiting accumulation).

VMEM per step ~ bn*d + bk*d + bn*bk floats: (256, 256) tiles at d<=512 are
~0.8 MB, comfortably inside the ~16 MB v5e budget; MXU work is the
(bn x d) @ (d x bk) matmul with all dims >= 128-aligned after ops.py padding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import F32_DOT

Array = jax.Array


def _kernel(p_ref, c_ref, min_ref, arg_ref, *, block_k: int):
    j = pl.program_id(1)

    p = p_ref[...].astype(jnp.float32)          # (bn, d)
    c = c_ref[...].astype(jnp.float32)          # (bk, d)
    p2 = jnp.sum(p * p, axis=1, keepdims=True)  # (bn, 1)
    c2 = jnp.sum(c * c, axis=1)                 # (bk,)
    # MXU: (bn, d) @ (d, bk)
    prod = jax.lax.dot_general(
        p, c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=F32_DOT)
    d2 = jnp.maximum(p2 + c2[None, :] - 2.0 * prod, 0.0)   # (bn, bk)

    local_min = jnp.min(d2, axis=1, keepdims=True)                  # (bn, 1)
    local_arg = jnp.argmin(d2, axis=1).astype(jnp.int32)[:, None]   # (bn, 1)
    local_arg = local_arg + j * block_k

    @pl.when(j == 0)
    def _init():
        min_ref[...] = local_min
        arg_ref[...] = local_arg

    @pl.when(j > 0)
    def _update():
        prev = min_ref[...]
        better = local_min < prev    # strict: first tile wins ties, matching
        min_ref[...] = jnp.where(better, local_min, prev)   # jnp.argmin
        arg_ref[...] = jnp.where(better, local_arg, arg_ref[...])


def _kernel_batched(p_ref, c_ref, min_ref, arg_ref, *, block_k: int):
    """Stacked-tenant variant: identical math, one extra (leading) grid axis
    selecting the tenant. Block shapes carry a unit tenant dim."""
    j = pl.program_id(2)

    p = p_ref[0].astype(jnp.float32)            # (bn, d)
    c = c_ref[0].astype(jnp.float32)            # (bk, d)
    p2 = jnp.sum(p * p, axis=1, keepdims=True)
    c2 = jnp.sum(c * c, axis=1)
    prod = jax.lax.dot_general(
        p, c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=F32_DOT)
    d2 = jnp.maximum(p2 + c2[None, :] - 2.0 * prod, 0.0)

    local_min = jnp.min(d2, axis=1, keepdims=True)
    local_arg = jnp.argmin(d2, axis=1).astype(jnp.int32)[:, None]
    local_arg = local_arg + j * block_k

    @pl.when(j == 0)
    def _init():
        min_ref[0] = local_min
        arg_ref[0] = local_arg

    @pl.when(j > 0)
    def _update():
        prev = min_ref[0]
        better = local_min < prev
        min_ref[0] = jnp.where(better, local_min, prev)
        arg_ref[0] = jnp.where(better, local_arg, arg_ref[0])


@functools.partial(jax.jit,
                   static_argnames=("block_n", "block_k", "interpret"))
def distance_argmin_batched(points: Array, centers: Array,
                            block_n: int = 256, block_k: int = 256,
                            interpret: bool = False):
    """Stacked-tenant raw kernel entry: ``(T, m, d), (T, k, d) ->
    (min_d2 (T, m, 1) f32, argmin (T, m, 1) i32)`` in ONE launch over grid
    ``(T, m/bn, k/bk)`` -- the serving tier's fused dispatch (one kernel
    call for T tenants instead of T calls). Same pre-padding contract as
    :func:`distance_argmin` per tenant: m % block_n == 0, k % block_k == 0,
    padded/masked center rows set to a huge sentinel coordinate so they
    never win. Use :func:`repro.kernels.ops.min_dist_argmin_batched` for
    the safe wrapper. The two output blocks depend only on (t, i), so they
    stay VMEM-resident across the center-tile sweep exactly like the
    single-tenant kernel."""
    T, n, d = points.shape
    Tc, k, _ = centers.shape
    assert T == Tc, (T, Tc)
    assert n % block_n == 0 and k % block_k == 0, (n, k, block_n, block_k)
    grid = (T, n // block_n, k // block_k)
    return pl.pallas_call(
        functools.partial(_kernel_batched, block_k=block_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_n, d), lambda t, i, j: (t, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda t, i, j: (t, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_n, 1), lambda t, i, j: (t, i, 0)),
            pl.BlockSpec((1, block_n, 1), lambda t, i, j: (t, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, n, 1), jnp.float32),
            jax.ShapeDtypeStruct((T, n, 1), jnp.int32),
        ],
        interpret=interpret,
        name="distance_argmin_batched",
    )(points, centers)


@functools.partial(jax.jit,
                   static_argnames=("block_n", "block_k", "interpret"))
def distance_argmin(points: Array, centers: Array, block_n: int = 256,
                    block_k: int = 256, interpret: bool = False):
    """Raw kernel entry. Requires pre-padded shapes: n % block_n == 0,
    k % block_k == 0 and padded center rows set to a huge coordinate so they
    never win the argmin. Use :func:`repro.kernels.ops.min_dist_argmin` for
    the safe wrapper. Returns (min_d2 (n,1) f32, argmin (n,1) i32)."""
    n, d = points.shape
    k, _ = centers.shape
    assert n % block_n == 0 and k % block_k == 0, (n, k, block_n, block_k)
    grid = (n // block_n, k // block_k)
    return pl.pallas_call(
        functools.partial(_kernel, block_k=block_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_k, d), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
        ],
        interpret=interpret,
        name="distance_argmin",
    )(points, centers)
