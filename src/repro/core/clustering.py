"""Centralized *weighted* clustering primitives (k-means / k-median).

Used both by the paper's algorithms (local constant approximation solves on
each site, Algorithm 1 Round 1) and by the final clustering of the global
coreset (Algorithm 2 Round 2). Every function supports per-point weights --
the coreset is a *weighted* instance, possibly with negative center weights
-- and is jit-compatible with static ``k`` and iteration counts.

Every distance/statistics hot loop but the seeding's one-center sweep
dispatches through the backend registry
(:mod:`repro.core.backend`): ``backend`` accepts a registry name
(``"jnp"``, ``"jnp_chunked"``, ``"pallas"``), a :class:`ClusteringBackend`
instance, or ``None`` for the ambient default (``use_backend`` /
auto-detection). The *objective* dispatches the same way through
:mod:`repro.core.objective`: ``objective`` accepts a registry name
(``"kmeans"``, ``"kmedian"``, parametrized ``"kmeans_trimmed(<t>)"`` /
``"power(<z>)"``) or an :class:`Objective` instance, resolved once at the
public boundary (unknown names raise). Center updates, seeding masses, and
per-point costs all come from the descriptor's hooks: the k-means instance
consumes the fused one-pass ``lloyd_stats`` primitive and the k-median
instance the fused ``weiszfeld_stats`` primitive -- on the Pallas backend
the (n, k) distance matrix never exists in HBM for any objective
(DESIGN.md Sec. 8, 10, 15).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import backend as backend_mod
from repro.core import objective as objective_mod
from repro.core.backend import BackendLike
from repro.core.objective import ObjectiveLike

Array = jax.Array

_TINY = 1e-30
_EPS = 1e-12


def pairwise_sq_dists(points: Array, centers: Array) -> Array:
    """Squared euclidean distances. points (n,d), centers (k,d) -> (n,k)."""
    p2 = jnp.sum(points * points, axis=-1, keepdims=True)
    c2 = jnp.sum(centers * centers, axis=-1)
    d2 = p2 + c2[None, :] - 2.0 * (points @ centers.T)
    return jnp.maximum(d2, 0.0)


def min_dist_argmin(
    points: Array,
    centers: Array,
    chunk: Optional[int] = None,
    backend: BackendLike = None,
) -> Tuple[Array, Array]:
    """Min squared distance and argmin center per point, via the dispatch
    layer. ``chunk`` bounds the materialized (chunk, k) distance block of
    the dense jnp path for large n: it upgrades a resolved ``jnp`` backend
    (explicit or ambient) to a chunked one, and is ignored by backends that
    already bound their memory (pallas tiles, jnp_chunked's own chunk)."""
    b = backend_mod.get_backend(backend)
    if chunk is not None and type(b) is backend_mod.JnpBackend:
        b = backend_mod.JnpChunkedBackend(chunk)
    return b.min_dist_argmin(points, centers)


def lloyd_stats(
    points: Array,
    centers: Array,
    weights: Optional[Array] = None,
    backend: BackendLike = None,
) -> Tuple[Array, Array, Array]:
    """Fused weighted Lloyd statistics (sums (k,d), counts (k,), cost ())
    via the dispatch layer."""
    return backend_mod.get_backend(backend).lloyd_stats(
        points, centers, weights)


def weiszfeld_stats(
    points: Array,
    centers: Array,
    weights: Optional[Array] = None,
    backend: BackendLike = None,
) -> Tuple[Array, Array, Array]:
    """Fused weighted Weiszfeld statistics (nums (k,d), denoms (k,),
    cost ()) for one k-median refinement pass, via the dispatch layer
    (DESIGN.md Sec. 10)."""
    return backend_mod.get_backend(backend).weiszfeld_stats(
        points, centers, weights)


def _costing_backend(chunk, backend):
    """Resolve a backend instance for a costing call, applying the ``chunk``
    upgrade of :func:`min_dist_argmin`."""
    b = backend_mod.get_backend(backend)
    if chunk is not None and type(b) is backend_mod.JnpBackend:
        b = backend_mod.JnpChunkedBackend(chunk)
    return b


def cost(
    points: Array,
    centers: Array,
    weights: Optional[Array] = None,
    objective: ObjectiveLike = "kmeans",
    chunk: Optional[int] = None,
    backend: BackendLike = None,
) -> Array:
    """Weighted clustering cost: sum_p w_p d(p, X)^z in the objective's
    metric (z=2 k-means, z=1 k-median, trimmed variants exclude their
    top-t residual points)."""
    obj = objective_mod.get_objective(objective)
    per_point, _ = obj.costs(_costing_backend(chunk, backend),
                             points, centers, weights)
    if weights is not None:
        per_point = per_point * weights
    return jnp.sum(per_point)


def point_costs(
    points: Array,
    centers: Array,
    objective: ObjectiveLike = "kmeans",
    chunk: Optional[int] = None,
    backend: BackendLike = None,
    weights: Optional[Array] = None,
) -> Tuple[Array, Array]:
    """Per-point cost to the nearest center and the assignment (n,), (n,).

    ``weights`` only feeds the objective's liveness mask (trimmed
    objectives never count weight-0 padding against the trim budget); the
    returned costs are *unweighted*.
    """
    obj = objective_mod.get_objective(objective)
    return obj.costs(_costing_backend(chunk, backend),
                     points, centers, weights)


def kmeans_pp_init(
    key: Array,
    points: Array,
    k: int,
    weights: Optional[Array] = None,
    objective: ObjectiveLike = "kmeans",
    backend: BackendLike = None,
) -> Array:
    """D^z seeding (k-means++ for z=2, k-median++ for z=1) with optional
    weights. The seeding mass of each step comes from the objective's
    ``seeding_mass`` hook: plain objectives use ``w * D^z`` (weight-0
    padding is never selected -- its logit is -inf), trimmed objectives
    additionally zero the mass of the current top-t residual points so
    seeds avoid far-field outliers.

    Seeding is backend-independent: each step updates every point's
    distance with one fused sweep against the new center, in plain
    ``jnp``, on every backend. ``backend`` is accepted and ignored.
    """
    del backend
    return _kmeans_pp_init(key, points, weights, k=k,
                           objective=objective_mod.resolve_name(objective))


def _masked_choice(key, mass):
    """Categorical draw proportional to ``mass``, deterministic row 0 when
    the total mass is zero. All-zero mass (a fully masked site under vmap,
    or every remaining point coinciding with a chosen center) would make
    every logit equal and seed uniformly from padding rows; those rows are
    weight-0 and inert downstream, but the draw must be deterministic, not
    an accident of the key."""
    idx = jax.random.categorical(key, jnp.log(mass + _TINY))
    return jnp.where(jnp.sum(mass) > 0.0, idx, 0).astype(jnp.int32)


def _one_center_sq_dists(points: Array, c: Array) -> Array:
    """Exact-form f32 squared distance of every point to one center, (n,):
    one fused read of ``points`` with no pad, matmul or argmin, and exactly
    0 at a point equal to ``c``."""
    return jnp.sum(jnp.square(points.astype(jnp.float32)
                              - c.astype(jnp.float32)), axis=-1)


@functools.partial(jax.jit, static_argnames=("k", "objective"))
@jax.named_scope("seed")
def _kmeans_pp_init(key, points, weights, k, objective):
    obj = objective_mod.get_objective(objective)
    n, d = points.shape
    w = jnp.ones((n,), points.dtype) if weights is None else weights
    w = jnp.maximum(w, 0.0)

    def dist_to(c):
        return obj.clamped_cost(_one_center_sq_dists(points, c))

    key, k0 = jax.random.split(key)
    with jax.named_scope("draw"):
        first = _masked_choice(k0, w)
    centers = jnp.zeros((k, d), points.dtype).at[0].set(points[first])
    mind = dist_to(points[first])

    def body(i, carry):
        centers, mind, key = carry
        key, ki = jax.random.split(key)
        mass = obj.seeding(w, mind)
        with jax.named_scope("draw"):
            idx = _masked_choice(ki, mass)
        c = points[idx]
        centers = centers.at[i].set(c)
        mind = jnp.minimum(mind, dist_to(c))
        return centers, mind, key

    centers, _, _ = jax.lax.fori_loop(1, k, body, (centers, mind, key))
    return centers


def lloyd(
    points: Array,
    centers: Array,
    weights: Optional[Array] = None,
    iters: int = 10,
    objective: ObjectiveLike = "kmeans",
    k: Optional[int] = None,
    backend: BackendLike = None,
) -> Tuple[Array, Array]:
    """Weighted center-update iterations in the objective's metric (Lloyd
    steps for k-means, fused Weiszfeld passes for k-median, trimmed /
    IRLS passes for the registered extensions). Returns
    (centers, cost_history (iters,)).

    Handles negative weights (signed coreset measures): clusters whose total
    weight is <= eps keep their previous center.
    """
    k = centers.shape[0] if k is None else k
    return _lloyd(points, centers, weights, iters=iters,
                  objective=objective_mod.resolve_name(objective),
                  k=k, backend=backend_mod.resolve_name(backend))


@functools.partial(jax.jit,
                   static_argnames=("iters", "objective", "k", "backend"))
@jax.named_scope("update")
def _lloyd(points, centers, weights, iters, objective, k, backend):
    obj = objective_mod.get_objective(objective)
    b = backend_mod.get_backend(backend)
    w = jnp.ones((points.shape[0],), points.dtype) if weights is None \
        else weights

    def body(centers, _):
        new, c = obj.update(b, points, w, centers)
        return new, c

    centers, hist = jax.lax.scan(body, centers, None, length=iters)
    return centers, hist


def lloyd_converged(
    points: Array,
    centers: Array,
    weights: Optional[Array] = None,
    iters: int = 10,
    tol: float = 0.0,
    objective: ObjectiveLike = "kmeans",
    k: Optional[int] = None,
    backend: BackendLike = None,
) -> Tuple[Array, Array]:
    """:func:`lloyd` with an early exit: stop refining once the relative
    cost improvement of a pass drops to ``tol`` (or after ``iters`` passes,
    whichever comes first). Returns (centers, iters_run).

    ``tol == 0.0`` is the strict mode: it delegates to the fixed-length
    scan of :func:`lloyd`, so centers are bit-identical to the lockstep
    path (the staged coreset engine's parity contract; DESIGN.md Sec. 17).
    ``tol > 0.0`` trades bit-parity for wall-clock -- sites whose local
    solve converges early skip the remaining passes entirely (while_loop),
    which is where the staged engine's per-site overlap win comes from.
    """
    k = centers.shape[0] if k is None else k
    return _lloyd_converged(points, centers, weights, iters=iters,
                            tol=float(tol),
                            objective=objective_mod.resolve_name(objective),
                            k=k, backend=backend_mod.resolve_name(backend))


@functools.partial(jax.jit,
                   static_argnames=("iters", "tol", "objective", "k",
                                    "backend"))
@jax.named_scope("update")
def _lloyd_converged(points, centers, weights, iters, tol, objective, k,
                     backend):
    if tol == 0.0:
        centers, _ = _lloyd(points, centers, weights, iters=iters,
                            objective=objective, k=k, backend=backend)
        return centers, jnp.asarray(iters, jnp.int32)
    obj = objective_mod.get_objective(objective)
    b = backend_mod.get_backend(backend)
    w = jnp.ones((points.shape[0],), points.dtype) if weights is None \
        else weights

    def cond(carry):
        i, _, _, done = carry
        return (i < iters) & ~done

    def body(carry):
        i, centers, prev, _ = carry
        new, c = obj.update(b, points, w, centers)
        # relative improvement of this pass; prev starts at +inf so the
        # first pass never exits (inf <= tol * c is false for finite c)
        done = (prev - c) <= tol * jnp.maximum(c, _TINY)
        return i + 1, new, c, done

    i, centers, _, _ = jax.lax.while_loop(
        cond, body, (jnp.asarray(0, jnp.int32), centers,
                     jnp.asarray(jnp.inf, points.dtype),
                     jnp.asarray(False)))
    return centers, i


def solve(
    key: Array,
    points: Array,
    k: int,
    weights: Optional[Array] = None,
    lloyd_iters: int = 10,
    objective: ObjectiveLike = "kmeans",
    restarts: int = 1,
    backend: BackendLike = None,
) -> Tuple[Array, Array]:
    """Constant-approximation solver: D^z seeding + iterative refinement,
    best of ``restarts`` independent seedings (k-means++ is only O(log k) in
    expectation; restarts make the constant-approximation assumption of
    Theorem 1 hold in practice). Restart selection uses the objective's own
    cost, so trimmed objectives pick the best *trimmed* restart.

    This is the ``A_alpha`` subroutine of Algorithm 2 and the local solver
    ``B_i`` of Algorithm 1. Returns (centers (k,d), final cost scalar).
    """
    return _solve(key, points, weights, k=k, lloyd_iters=lloyd_iters,
                  objective=objective_mod.resolve_name(objective),
                  restarts=restarts,
                  backend=backend_mod.resolve_name(backend))


@functools.partial(jax.jit,
                   static_argnames=("k", "lloyd_iters", "objective",
                                    "restarts", "backend"))
def _solve(key, points, weights, k, lloyd_iters, objective, restarts,
           backend):
    def one(ki):
        centers = kmeans_pp_init(ki, points, k, weights=weights,
                                 objective=objective, backend=backend)
        centers, _ = lloyd(points, centers, weights=weights,
                           iters=lloyd_iters, objective=objective,
                           backend=backend)
        c = cost(points, centers, weights=weights, objective=objective,
                 backend=backend)
        return centers, c

    if restarts == 1:
        return one(key)
    all_centers, costs = jax.lax.map(one, jax.random.split(key, restarts))
    best = jnp.argmin(costs)
    return all_centers[best], costs[best]
