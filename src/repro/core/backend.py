"""Unified clustering-backend dispatch layer (DESIGN.md Sec. 8).

Every hot path of the pipeline -- Algorithm 1's local solves, sensitivity
computation, and the final coreset solve of Algorithm 2, for *every*
registered objective (:mod:`repro.core.objective`) -- reduces to the same
three primitive ops over a (possibly weighted) point set (D^2 seeding's
one-center sweep is plain ``jnp``, the same on every backend):

* ``min_dist_argmin(points, centers)``
    ``(n, d), (k, d) -> (min_d2 (n,) f32, argmin (n,) i32)``
* ``lloyd_stats(points, centers, weights)``
    ``(n, d), (k, d), (n,) -> (sums (k, d) f32, counts (k,) f32, cost () f32)``
  where ``sums[c] = sum_{p: argmin(p)=c} w_p p``, ``counts[c] = sum w_p``
  and ``cost = sum_p w_p min_d2(p)`` -- one fused E+M statistics pass
  (the k-means Lloyd step).
* ``weiszfeld_stats(points, centers, weights)``
    ``(n, d), (k, d), (n,) -> (nums (k, d) f32, denoms (k,) f32, cost () f32)``
  where, with ``dist(p) = sqrt(d2(p) + eta^2)`` the smoothed exact-form
  distance to the assigned center,
  ``nums[c] = sum_{p: argmin(p)=c} max(w_p, 0) p / dist(p)``,
  ``denoms[c] = sum max(w_p, 0) / dist(p)`` and
  ``cost = sum_p w_p sqrt(d2(p))`` -- one fused assign+Weiszfeld
  statistics pass (the k-median refinement step; DESIGN.md Sec. 10).

A :class:`ClusteringBackend` supplies all three; the registry maps names to
singleton instances:

* ``"jnp"``         -- dense XLA formulation, materializes the (n, k)
                       distance block (fastest on CPU for small n*k).
* ``"jnp_chunked"`` -- ``lax.map`` over fixed-size point chunks: bounded
                       memory for large n, same numerics as ``"jnp"``.
* ``"pallas"``      -- the fused TPU kernels in :mod:`repro.kernels`
                       (flash-style online argmin + one-pass statistics;
                       interpret mode on CPU via ``ops._auto_interpret``).

Selection precedence: explicit argument (name or instance) > ambient
default set by :func:`use_backend` > auto-detection (``"pallas"`` on TPU,
``"jnp"`` elsewhere).

All accumulation is float32 regardless of input dtype (the kernels' dtype
policy); callers cast results back as needed.

jit interaction: backend choice must be a *static* trace property, so the
public entry points in :mod:`repro.core.clustering` etc. resolve the
ambient default to a concrete registry name *outside* their jitted inner
functions and pass the name through ``static_argnames``. Never call
:func:`get_backend` with ``None`` from inside a jitted function -- the
ambient default would be baked into a stale cache entry.
"""
from __future__ import annotations

import functools
import threading
from typing import Dict, Optional, Protocol, Tuple, Union, runtime_checkable

import jax
import jax.numpy as jnp

from repro.core import objective as objective_mod
from repro.kernels.ref import CENTER_SENTINEL as _CENTER_SENTINEL

Array = jax.Array

_EPS = 1e-12


@runtime_checkable
class ClusteringBackend(Protocol):
    """The primitive ops every numerical path dispatches through.

    ``min_dist_argmin_batched`` is the *stacked-tenant* sibling of
    ``min_dist_argmin``: ``(T, m, d), (T, k, d) -> ((T, m) f32, (T, m)
    i32)`` where tenant t's queries reduce over tenant t's centers only --
    the multi-tenant serving tier fuses T tenants' query traffic into one
    such dispatch (DESIGN.md Sec. 13). Ragged center sets arrive sentinel-
    masked (see :func:`query_assignments_batched`)."""

    name: str

    def min_dist_argmin(self, points: Array, centers: Array
                        ) -> Tuple[Array, Array]:
        ...

    def min_dist_argmin_batched(self, points: Array, centers: Array
                                ) -> Tuple[Array, Array]:
        ...

    def lloyd_stats(self, points: Array, centers: Array,
                    weights: Optional[Array] = None
                    ) -> Tuple[Array, Array, Array]:
        ...

    def weiszfeld_stats(self, points: Array, centers: Array,
                        weights: Optional[Array] = None
                        ) -> Tuple[Array, Array, Array]:
        ...


BackendLike = Union[str, ClusteringBackend, None]


# ---------------------------------------------------------------------------
# implementations
# ---------------------------------------------------------------------------

def _dense_min_dist_argmin(points: Array, centers: Array
                           ) -> Tuple[Array, Array]:
    p = points.astype(jnp.float32)
    c = centers.astype(jnp.float32)
    p2 = jnp.sum(p * p, axis=-1, keepdims=True)
    c2 = jnp.sum(c * c, axis=-1)
    d2 = jnp.maximum(p2 + c2[None, :] - 2.0 * (p @ c.T), 0.0)
    assign = jnp.argmin(d2, axis=-1).astype(jnp.int32)
    # the minimum is read at the argmin, the same element jnp.min returns.
    # A separate jnp.min is merged by XLA into argmin's (value, index)
    # reduce; on a TPU v5e that merged reduce, fused with the vmapped
    # distance matmul, returned NaN and inf minima for the full-scale
    # YearPredictionMSD sites (Round-1 sensitivities, 100 x 21,280 x 50)
    min_d2 = jnp.take_along_axis(d2, assign[:, None], axis=-1)[:, 0]
    return min_d2, assign


def _dense_lloyd_stats(points: Array, centers: Array,
                       weights: Optional[Array] = None
                       ) -> Tuple[Array, Array, Array]:
    p = points.astype(jnp.float32)
    w = (jnp.ones((p.shape[0],), jnp.float32) if weights is None
         else weights.astype(jnp.float32))
    min_d2, assign = _dense_min_dist_argmin(points, centers)
    k = centers.shape[0]
    # segment sums, not a one-hot (k, n) @ (n, d) matmul: on a TPU an f32
    # matmul runs as one bf16 pass at default precision; these add in f32
    # at any precision setting
    sums = jax.ops.segment_sum(w[:, None] * p, assign, num_segments=k)
    counts = jax.ops.segment_sum(w, assign, num_segments=k)
    cost = jnp.sum(w * min_d2)
    return sums, counts, cost


# Batched tenant axis via vmap: on every platform this lowers to one
# batched dot_general, and each tenant slice runs the *same* arithmetic as
# a standalone _dense_min_dist_argmin call, so batched results are
# bit-identical to the per-tenant serial loop (asserted in
# tests/test_serve_cluster.py).
_dense_min_dist_argmin_batched = jax.vmap(_dense_min_dist_argmin)


def _dense_weiszfeld_stats(points: Array, centers: Array,
                           weights: Optional[Array] = None
                           ) -> Tuple[Array, Array, Array]:
    # the normative reduction (exact-form assigned distance + eta-smoothed
    # inverse, DESIGN.md Sec. 10) is shared with the ops.py fallback and
    # the oracle; only the argmin source differs per backend
    from repro.kernels.ref import weiszfeld_reduce

    _, assign = _dense_min_dist_argmin(points, centers)
    return weiszfeld_reduce(points, centers, weights, assign)


class JnpBackend:
    """Dense XLA-fused matmul formulation d^2 = |p|^2 + |c|^2 - 2 p.c."""

    name = "jnp"

    def min_dist_argmin(self, points, centers):
        return _dense_min_dist_argmin(points, centers)

    def min_dist_argmin_batched(self, points, centers):
        return _dense_min_dist_argmin_batched(points, centers)

    def lloyd_stats(self, points, centers, weights=None):
        return _dense_lloyd_stats(points, centers, weights)

    def weiszfeld_stats(self, points, centers, weights=None):
        return _dense_weiszfeld_stats(points, centers, weights)


class JnpChunkedBackend:
    """Bounded-memory variant: ``lax.map`` over ``chunk``-point blocks, so
    the materialized distance block is (chunk, k) instead of (n, k). Padded
    tail points carry weight 0 and never contribute."""

    def __init__(self, chunk: int = 65536, name: str = "jnp_chunked"):
        self.chunk = int(chunk)
        self.name = name

    def _blocks(self, points: Array, weights: Array
                ) -> Tuple[Array, Array]:
        n, d = points.shape
        pad = (-n) % self.chunk
        pts = jnp.pad(points, ((0, pad), (0, 0)))
        w = jnp.pad(weights, (0, pad))
        return (pts.reshape(-1, self.chunk, d),
                w.reshape(-1, self.chunk))

    def min_dist_argmin(self, points, centers):
        n = points.shape[0]
        if n <= self.chunk:
            return _dense_min_dist_argmin(points, centers)
        pts, _ = self._blocks(points, jnp.zeros((n,), jnp.float32))
        md, am = jax.lax.map(
            lambda blk: _dense_min_dist_argmin(blk, centers), pts)
        return md.reshape(-1)[:n], am.reshape(-1)[:n]

    def min_dist_argmin_batched(self, points, centers):
        T, m, d = points.shape
        if T * m <= self.chunk:
            return _dense_min_dist_argmin_batched(points, centers)
        # lax.map over fixed-size tenant blocks: the materialized distance
        # block is (blk, m, k) instead of (T, m, k). Padding tenants carry
        # sentinel centers (never win) and are sliced off.
        blk = max(1, self.chunk // max(m, 1))
        pad = (-T) % blk
        pts = jnp.pad(points, ((0, pad), (0, 0), (0, 0)))
        ctr = jnp.pad(centers, ((0, pad), (0, 0), (0, 0)),
                      constant_values=_CENTER_SENTINEL)
        k = centers.shape[1]
        md, am = jax.lax.map(
            lambda args: _dense_min_dist_argmin_batched(args[0], args[1]),
            (pts.reshape(-1, blk, m, d), ctr.reshape(-1, blk, k, d)))
        return md.reshape(-1, m)[:T], am.reshape(-1, m)[:T]

    def lloyd_stats(self, points, centers, weights=None):
        n = points.shape[0]
        w = (jnp.ones((n,), jnp.float32) if weights is None
             else weights.astype(jnp.float32))
        if n <= self.chunk:
            return _dense_lloyd_stats(points, centers, w)
        pts, ws = self._blocks(points, w)
        sums, counts, cost = jax.lax.map(
            lambda args: _dense_lloyd_stats(args[0], centers, args[1]),
            (pts, ws))
        return sums.sum(axis=0), counts.sum(axis=0), cost.sum()

    def weiszfeld_stats(self, points, centers, weights=None):
        n = points.shape[0]
        w = (jnp.ones((n,), jnp.float32) if weights is None
             else weights.astype(jnp.float32))
        if n <= self.chunk:
            return _dense_weiszfeld_stats(points, centers, w)
        pts, ws = self._blocks(points, w)
        nums, denoms, cost = jax.lax.map(
            lambda args: _dense_weiszfeld_stats(args[0], centers, args[1]),
            (pts, ws))
        return nums.sum(axis=0), denoms.sum(axis=0), cost.sum()


class PallasBackend:
    """Fused Pallas TPU kernels (interpret mode on CPU). Thin delegation to
    the safe padded wrappers in :mod:`repro.kernels.ops`."""

    def __init__(self, block_n: int = 256, block_k: int = 256,
                 interpret: Optional[bool] = None, name: str = "pallas"):
        self.block_n = block_n
        self.block_k = block_k
        self.interpret = interpret
        self.name = name

    def min_dist_argmin(self, points, centers):
        from repro.kernels import ops as kops

        return kops.min_dist_argmin(points, centers, block_n=self.block_n,
                                    block_k=self.block_k,
                                    interpret=self.interpret)

    def min_dist_argmin_batched(self, points, centers):
        from repro.kernels import ops as kops

        return kops.min_dist_argmin_batched(points, centers,
                                            block_n=self.block_n,
                                            block_k=self.block_k,
                                            interpret=self.interpret)

    def lloyd_stats(self, points, centers, weights=None):
        from repro.kernels import ops as kops

        return kops.lloyd_stats(points, centers, weights,
                                block_n=self.block_n,
                                interpret=self.interpret)

    def weiszfeld_stats(self, points, centers, weights=None):
        from repro.kernels import ops as kops

        return kops.weiszfeld_stats(points, centers, weights,
                                    block_n=self.block_n,
                                    interpret=self.interpret)


# ---------------------------------------------------------------------------
# registry + ambient default
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, ClusteringBackend] = {}
_local = threading.local()


def register_backend(backend: ClusteringBackend, name: Optional[str] = None
                     ) -> ClusteringBackend:
    """Add a backend instance to the registry (future GPU/Triton or sparse
    backends are one ``register_backend`` call).

    Overriding an existing name is allowed here (explicitly) but note that
    jitted entry points cache compiled traces keyed on the *name*: traces
    already compiled against the old instance are not invalidated."""
    _REGISTRY[name or backend.name] = backend
    return backend


def available_backends() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


register_backend(JnpBackend())
register_backend(JnpChunkedBackend())
register_backend(PallasBackend())


def _auto_name() -> str:
    """Pallas on TPU (the kernels' target); dense jnp elsewhere (interpret
    mode is orders of magnitude slower than XLA on CPU, so it is opt-in)."""
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def default_backend_name() -> str:
    name = getattr(_local, "default", None)
    return name if name is not None else _auto_name()


def resolve_name(backend: BackendLike) -> str:
    """Resolve a selection to a concrete registry name (for use as a static
    jit argument). Must be called *outside* jit for ``None`` to track the
    ambient default correctly."""
    if backend is None:
        return default_backend_name()
    if isinstance(backend, str):
        if backend not in _REGISTRY:
            raise KeyError(
                f"unknown clustering backend {backend!r}; "
                f"available: {available_backends()}")
        return backend
    name = getattr(backend, "name", None)
    if not name:
        raise TypeError(f"backend must be a name or ClusteringBackend, got "
                        f"{type(backend).__name__}")
    existing = _REGISTRY.get(name)
    if existing is None:
        register_backend(backend, name)
    elif existing is not backend:
        # never silently shadow: jit caches key on the name, so a second
        # instance under the same name would hit the first instance's
        # compiled traces and be silently ignored.
        raise ValueError(
            f"a different backend is already registered as {name!r}; give "
            f"this instance a unique .name or call register_backend() "
            f"explicitly to override")
    return name


def get_backend(backend: BackendLike = None) -> ClusteringBackend:
    """Resolve a selection to a backend instance."""
    if backend is not None and not isinstance(backend, str):
        resolve_name(backend)  # validate + register
        return backend
    return _REGISTRY[resolve_name(backend)]


def query_assignments(points: Array, centers: Array,
                      objective: objective_mod.ObjectiveLike = "kmeans",
                      backend: BackendLike = None) -> Tuple[Array, Array]:
    """Batched cluster-query entry point: nearest center and distance per
    query point, ``(n, d), (k, d) -> (assign (n,) i32, dist (n,) f32)``.

    This is the serving hot path of :mod:`repro.stream.service` -- one
    fused ``min_dist_argmin`` pass through the registry (the Pallas
    ``distance_argmin`` kernel on TPU), with the distance reported in the
    objective's metric (``dist^z``: squared for z=2, euclidean for z=1;
    trimmed objectives report the plain z=2 metric -- trimming is a
    training-time notion, queries always get their true nearest center).
    """
    return _query_assignments(
        points, centers, objective=objective_mod.resolve_name(objective),
        backend=resolve_name(backend))


@functools.partial(jax.jit, static_argnames=("objective", "backend"))
def _query_assignments(points, centers, objective, backend):
    d2, assign = _REGISTRY[backend].min_dist_argmin(points, centers)
    dist = objective_mod.get_objective(objective).clamped_cost(d2)
    return assign, dist


def query_assignments_batched(queries: Array, centers: Array,
                              center_mask: Optional[Array] = None,
                              objective: objective_mod.ObjectiveLike = "kmeans",
                              backend: BackendLike = None
                              ) -> Tuple[Array, Array]:
    """Stacked-tenant cluster-query entry point: ``(T, m, d), (T, k, d)[,
    (T, k) bool] -> (assign (T, m) i32, dist (T, m) f32)`` -- T tenants'
    nearest-center queries fused into ONE device dispatch (one Pallas
    ``distance_argmin_batched`` launch on TPU, one batched dot_general on
    the jnp backends). This is the multi-tenant serving hot path of
    :mod:`repro.serve.cluster` (DESIGN.md Sec. 13).

    **Masking contract**: tenants with ragged center counts are stacked
    into the common ``(T, k, d)`` buffer and described by ``center_mask``
    (True = live row). Masked-out rows are substituted with the
    ``CENTER_SENTINEL`` coordinate *here*, uniformly for every backend, so
    they can never win an argmin and all backends see identical operands
    -- batched results are bit-identical to a per-tenant serial loop over
    the same stacked buffers on the jnp backends (and ~1e-7 on pallas,
    whose padded-k tiling differs). Padded *query* rows are the caller's
    to slice off. ``dist`` is the objective's metric ``dist^z`` (squared
    for z=2 -- including trimmed variants -- euclidean for z=1).
    """
    return _query_assignments_batched(
        queries, centers, center_mask,
        objective=objective_mod.resolve_name(objective),
        backend=resolve_name(backend))


@functools.partial(jax.jit, static_argnames=("objective", "backend"))
def _query_assignments_batched(queries, centers, center_mask, objective,
                               backend):
    if center_mask is not None:
        centers = jnp.where(center_mask[..., None], centers,
                            jnp.asarray(_CENTER_SENTINEL, centers.dtype))
    d2, assign = _REGISTRY[backend].min_dist_argmin_batched(queries, centers)
    dist = objective_mod.get_objective(objective).clamped_cost(d2)
    return assign, dist


_UNSET = object()


class use_backend:
    """Set the ambient default backend.

    Works both as a plain call (``use_backend("pallas")`` -- sticky) and as
    a context manager (restores the previous default on exit)::

        with use_backend("jnp_chunked"):
            lloyd(points, centers)          # runs chunked

    The restorable mutation lives in ``__enter__``, not ``__init__``: each
    entry captures the default *at entry time* and restores exactly that on
    exit, so a stored instance can be (re-)entered later -- even nested
    inside other contexts -- without restoring a stale snapshot. The
    ``__init__`` sticky set (the plain-call contract) records the
    pre-construction default; the first entry immediately following
    construction consumes it, so ``with use_backend(...)`` restores the
    default from *before* the expression ran. ``__exit__`` without a
    matching ``__enter__`` is a no-op.
    """

    def __init__(self, backend: BackendLike):
        self._name = resolve_name(backend)
        # plain-call stickiness: constructing the object sets the ambient
        # default; _pending remembers what it replaced for the first enter.
        self._pending = getattr(_local, "default", None)
        self._stack = []
        _local.default = self._name

    def __enter__(self) -> ClusteringBackend:
        cur = getattr(_local, "default", None)
        if self._pending is not _UNSET and cur == self._name:
            # entering right after construction: the __init__ mutation was
            # this entry's set; restore the pre-construction default.
            prev = self._pending
        else:
            # stored instance entered later (ambient changed since
            # construction): capture the current default, not the stale one.
            prev = cur
        self._pending = _UNSET
        self._stack.append(prev)
        _local.default = self._name
        return get_backend(self._name)

    def __exit__(self, *exc) -> bool:
        if self._stack:
            _local.default = self._stack.pop()
        return False
