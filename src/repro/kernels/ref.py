"""Pure-jnp oracles for the Pallas kernels.

These are the semantics the kernels must match (assert_allclose in
tests/test_kernels.py across shape/dtype sweeps). They materialize the full
(n, k) distance matrix -- exactly what the fused kernels avoid.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array


def min_dist_argmin_ref(points: Array, centers: Array
                        ) -> Tuple[Array, Array]:
    """(n,d),(k,d) -> min squared distance (n,) f32 and argmin (n,) i32."""
    p = points.astype(jnp.float32)
    c = centers.astype(jnp.float32)
    p2 = jnp.sum(p * p, axis=-1, keepdims=True)
    c2 = jnp.sum(c * c, axis=-1)
    d2 = jnp.maximum(p2 + c2[None, :] - 2.0 * (p @ c.T), 0.0)
    return jnp.min(d2, axis=-1), jnp.argmin(d2, axis=-1).astype(jnp.int32)


# Masking sentinel for padded / masked-out center rows: a center at
# coordinate 1e15 is ~30 orders of magnitude farther than any real data, so
# it can never win an argmin, yet its squared distance stays finite in f32
# (d * 1e30 << 3.4e38) -- no inf/NaN propagation through min reductions.
# Shared by ops.py shape padding and the stacked-tenant masking contract of
# backend.query_assignments_batched (DESIGN.md Sec. 13).
CENTER_SENTINEL = 1.0e15

# Precision of every dot inside the Pallas kernels. Mosaic runs an f32 dot
# as one bf16 MXU pass unless told otherwise: on a TPU v5e that put the
# kernels' squared distances ~1.5e-3 of |p|^2 + |c|^2 off and flipped
# argmins at the paper's YearPredictionMSD widths. HIGHEST keeps them f32,
# which the Weiszfeld kernel's one-hot gather needs to be exact.
F32_DOT = jax.lax.Precision.HIGHEST


def min_dist_argmin_batched_ref(points: Array, centers: Array
                                ) -> Tuple[Array, Array]:
    """Stacked-tenant oracle: ``(T, m, d), (T, k, d) -> ((T, m) f32,
    (T, m) i32)`` -- tenant t's queries reduced over tenant t's centers
    only, as a plain per-tenant loop over :func:`min_dist_argmin_ref`.
    Masked-out / ragged center rows are expected pre-filled with
    :data:`CENTER_SENTINEL` (they never win the argmin)."""
    outs = [min_dist_argmin_ref(points[t], centers[t])
            for t in range(points.shape[0])]
    return (jnp.stack([md for md, _ in outs]),
            jnp.stack([am for _, am in outs]))


def lloyd_stats_ref(points: Array, centers: Array,
                    weights: Optional[Array] = None
                    ) -> Tuple[Array, Array, Array]:
    """One fused Lloyd statistics pass.

    Returns (sums (k,d) f32, counts (k,) f32, cost () f32) where
    sums[c] = sum_{p: argmin(p)=c} w_p * p, counts[c] = sum w_p,
    cost = sum_p w_p * min_d2(p).
    """
    p = points.astype(jnp.float32)
    w = (jnp.ones((p.shape[0],), jnp.float32) if weights is None
         else weights.astype(jnp.float32))
    min_d2, assign = min_dist_argmin_ref(points, centers)
    k = centers.shape[0]
    oh = jax.nn.one_hot(assign, k, dtype=jnp.float32) * w[:, None]
    sums = oh.T @ p
    counts = jnp.sum(oh, axis=0)
    cost = jnp.sum(w * min_d2)
    return sums, counts, cost


# Squared smoothing length eta^2 of the Weiszfeld inverse distance:
# dist = sqrt(d2 + eta^2). The classic iteration is undefined at data
# points, and k-means++ seeds ARE data points; eta bounds the pull of a
# center-coincident point at w/eta instead of an unbounded (and float32-
# noise-amplified) spike, so the iterate escapes its seed in O(1) passes
# and all backends agree bit-for-bit on the clamp (DESIGN.md Sec. 10).
WEISZFELD_ETA2 = 1e-6


def weiszfeld_reduce(points: Array, centers: Array,
                     weights: Optional[Array], assign: Array
                     ) -> Tuple[Array, Array, Array]:
    """The normative Weiszfeld reduction given an assignment (DESIGN.md
    Sec. 10), shared by the jnp backends, the ops.py two-pass fallback and
    the oracle so the numerics rules cannot desynchronize:

    * exact-form assigned distance d2(p) = sum((p - c_assign(p))^2) -- the
      |p|^2 + |c|^2 - 2 p.c matmul trick cancels catastrophically near
      zero and the inverse distance amplifies that float32 noise by orders
      of magnitude across backends;
    * eta-smoothed inverse dist(p) = sqrt(d2(p) + WEISZFELD_ETA2) with
      max(w, 0) membership mass and the signed, unsmoothed cost.

    Returns (nums (k,d) f32, denoms (k,) f32, cost () f32).
    """
    p = points.astype(jnp.float32)
    c = centers.astype(jnp.float32)
    w = (jnp.ones((p.shape[0],), jnp.float32) if weights is None
         else weights.astype(jnp.float32))
    diff = p - c[assign]
    d2 = jnp.sum(diff * diff, axis=-1)
    dist = jnp.sqrt(d2 + WEISZFELD_ETA2)
    inv = jnp.maximum(w, 0.0) / dist
    k = centers.shape[0]
    # segment sums, as in the jnp backend's Lloyd statistics (see there)
    nums = jax.ops.segment_sum(inv[:, None] * p, assign, num_segments=k)
    denoms = jax.ops.segment_sum(inv, assign, num_segments=k)
    cost = jnp.sum(w * jnp.sqrt(d2))
    return nums, denoms, cost


def weiszfeld_stats_ref(points: Array, centers: Array,
                        weights: Optional[Array] = None
                        ) -> Tuple[Array, Array, Array]:
    """One fused Weiszfeld statistics pass (k-median).

    Returns (nums (k,d) f32, denoms (k,) f32, cost () f32) where, with
    dist(p) = sqrt(d2(p) + eta^2) the smoothed exact-form distance to the
    nearest center,
    nums[c] = sum_{p: argmin(p)=c} max(w_p, 0) * p / dist(p),
    denoms[c] = sum_{p: argmin(p)=c} max(w_p, 0) / dist(p),
    cost = sum_p w_p * sqrt(d2(p))  (signed weights, unsmoothed metric).
    """
    _, assign = min_dist_argmin_ref(points, centers)
    return weiszfeld_reduce(points, centers, weights, assign)
