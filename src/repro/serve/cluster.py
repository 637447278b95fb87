"""Multi-tenant coreset-query serving engine (DESIGN.md Sec. 13).

The paper's deployment story makes nearest-center *queries* the hot path: a
small coreset summary stands in for the full data, so a serving tier pays
for assignment dispatches, not solves. :class:`ClusterServeEngine` serves
many concurrent (stream, k, model) tenants by fusing their query traffic
into single device dispatches -- admit requests, batch them into one jit
call per step, free capacity as they finish -- around the stacked-center
assignment primitive :func:`repro.core.backend.query_assignments_batched`:

* **admission queue + continuous batching**: ``enqueue(tenant, points)``
  is non-blocking and returns a :class:`QueryTicket`; each ``step()``
  drains the queue, splits oversized batches into ``max_bucket`` chunks
  (:func:`repro.kernels.ops.chunk_queries`), and buckets chunks by
  ``(d, k-bucket, padded-size, objective)`` so arbitrary ragged traffic
  assembles into full stacked batches over a *bounded* set of compiled
  specializations (``compiled_shapes`` records the set).
* **stacked-center dispatch**: each assembled group stacks up to
  ``max_group`` tenants' centers into one ``(T, k_pad, d)`` buffer with a
  live-row mask and launches ONE fused kernel for all of them (the Pallas
  ``distance_argmin_batched`` grid on TPU) instead of T per-tenant calls.
* **per-tenant staleness SLOs**: center freshness is the tenant source's
  policy (e.g. :class:`repro.stream.service.ClusterQueryService`'s
  staleness bound); the engine schedules at most ``refresh_budget``
  re-solves per step, most-stale-first, so one tenant's center re-solve
  never blocks another tenant's query path -- tenants whose refresh is
  deferred keep serving their cached centers (bounded extra staleness),
  and only a tenant that has *never* solved holds its queries to a later
  step.

A center source is any object with ``cached_centers() -> (k, d) | None``,
``is_stale() -> bool`` and ``refresh() -> (k, d)`` (optionally
``staleness() -> float`` for the scheduling order);
:class:`StaticCenters` adapts a fixed center array and
``ClusterQueryService`` conforms directly (single-tenant serving delegates
here -- see ``stream/service.py``).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import backend as backend_mod
from repro.core import objective as objective_mod
from repro.kernels.ops import query_bucket

Array = jax.Array


class StaticCenters:
    """Minimal center source: a fixed center set, never stale."""

    def __init__(self, centers):
        self._centers = jnp.asarray(centers, jnp.float32)

    def cached_centers(self) -> Array:
        return self._centers

    def is_stale(self) -> bool:
        return False

    def refresh(self) -> Array:
        return self._centers


@dataclasses.dataclass(slots=True)
class QueryTicket:
    """Handle for one enqueued query batch. ``assign`` / ``dist`` fill in
    as the engine's steps serve the batch's chunks (``None`` until the
    first chunk lands -- a ticket served whole by one dispatch gets
    zero-copy views of the fused result); ``done`` flips once every row is
    written. ``n_padded`` counts the padding rows the engine shipped on
    this ticket's behalf (the bucket/assembly overhead)."""

    tenant_id: int
    n: int
    assign: np.ndarray = dataclasses.field(default=None, repr=False)
    dist: np.ndarray = dataclasses.field(default=None, repr=False)
    n_padded: int = 0
    _left: int = 0

    @property
    def done(self) -> bool:
        return self._left == 0


@dataclasses.dataclass
class EngineStats:
    """Engine-level serving counters (the benchmark surface)."""

    n_queries: int = 0          # real query rows served
    n_padded: int = 0           # padding rows shipped to fill buckets
    n_tickets: int = 0
    n_steps: int = 0
    n_dispatches: int = 0       # fused device dispatches issued
    n_tenant_dispatches: int = 0  # tenant-chunks served (serial equivalent)
    n_refreshes: int = 0        # center re-solves run by the step loop
    n_deferred_refreshes: int = 0  # stale tenants served cached centers
    refresh_s: float = 0.0
    assign_s: float = 0.0

    def as_dict(self) -> Dict[str, float]:
        return dataclasses.asdict(self)


class _Tenant:
    """Internal per-tenant record: source + pending work + cached host-side
    padded centers (invalidated by object identity of the source's cached
    array, so an engine-run or out-of-band refresh both re-stage)."""

    __slots__ = ("tid", "k", "d", "objective", "source", "pending",
                 "k_bucket", "stage_epoch", "_staged_from", "_centers_np")

    def __init__(self, tid: int, k: int, d: int, objective: str, source):
        self.tid = tid
        self.k = int(k)
        self.d = int(d)
        self.objective = objective
        self.source = source
        self.pending: List[Tuple[QueryTicket, np.ndarray]] = []
        self.k_bucket = max(8, 1 << (self.k - 1).bit_length())
        self.stage_epoch = 0      # bumps on every re-stage (cache key)
        self._staged_from = None
        self._centers_np: Optional[np.ndarray] = None

    def staged_centers(self) -> Optional[np.ndarray]:
        """Host-staged ``(k_bucket, d)`` centers (rows >= k are dead and
        masked at dispatch); ``None`` until the source first solves."""
        cur = self.source.cached_centers()
        if cur is None:
            return None
        if cur is not self._staged_from:
            c = np.zeros((self.k_bucket, self.d), np.float32)
            c[:self.k] = np.asarray(cur, np.float32)
            self._staged_from = cur
            self._centers_np = c
            self.stage_epoch += 1
        return self._centers_np


class ClusterServeEngine:
    """Continuous-batching serving engine over stacked-center dispatches.

    ``max_bucket`` caps the per-chunk padded query rows (larger enqueues
    split), ``max_group`` caps tenants per fused dispatch, and
    ``refresh_budget`` caps center re-solves per step (``None`` =
    unbounded). The tenant-count axis of each dispatch is padded to a
    power of two as well, so the compiled-specialization set stays bounded
    by O(log max_group * log max_bucket * #distinct (k_bucket, d)) under
    any traffic pattern."""

    def __init__(self, backend: backend_mod.BackendLike = None,
                 min_bucket: int = 8, max_bucket: int = 1024,
                 max_group: int = 256,
                 refresh_budget: Optional[int] = None):
        if max_bucket < min_bucket:
            raise ValueError(f"max_bucket {max_bucket} < min_bucket "
                             f"{min_bucket}")
        self.backend = backend_mod.resolve_name(backend)
        self.min_bucket = int(min_bucket)
        self.max_bucket = int(max_bucket)
        self.max_group = int(max_group)
        self.refresh_budget = refresh_budget
        self.stats = EngineStats()
        self.compiled_shapes: set = set()   # (T_pad, bucket, k_pad, d, obj)
        self._tenants: Dict[int, _Tenant] = {}
        self._next_tid = 0
        self._bucket_cache: Dict[int, int] = {}
        # steady-state traffic re-assembles the same tenant composition
        # every step: cache the stacked (centers, mask) device buffers per
        # composition, invalidated by the tenants' stage epochs
        self._center_cache: Dict[tuple, tuple] = {}

    # -- tenant admission ----------------------------------------------------

    def add_tenant(self, source, k: int, d: int,
                   objective: objective_mod.ObjectiveLike = "kmeans",
                   tenant_id: Optional[int] = None) -> int:
        """Register a center source serving ``k`` centers in R^``d``.
        ``objective`` is any registered objective (name or instance; unknown
        names raise here, before any traffic) -- its *canonical* name rides
        in the bucket/grouping keys and picks the query-distance metric.
        Returns the tenant id (auto-assigned when not given)."""
        objective = objective_mod.resolve_name(objective)
        if tenant_id is None:
            while self._next_tid in self._tenants:
                self._next_tid += 1
            tenant_id = self._next_tid
        tenant_id = int(tenant_id)
        if tenant_id in self._tenants:
            raise ValueError(f"tenant {tenant_id} already registered")
        if k < 1 or d < 1:
            raise ValueError(f"need k >= 1 and d >= 1, got k={k} d={d}")
        for attr in ("cached_centers", "is_stale", "refresh"):
            if not callable(getattr(source, attr, None)):
                raise TypeError(f"center source must provide {attr}()")
        self._tenants[tenant_id] = _Tenant(tenant_id, k, d, objective,
                                           source)
        return tenant_id

    def tenant_ids(self) -> Tuple[int, ...]:
        return tuple(self._tenants)

    # -- admission queue -----------------------------------------------------

    def enqueue(self, tenant_id: int, points) -> QueryTicket:
        """Queue a ``(n, d)`` query batch for a tenant (non-blocking). The
        returned ticket fills in as subsequent :meth:`step` calls serve it;
        an empty batch completes immediately."""
        t = self._tenants.get(int(tenant_id))
        if t is None:
            raise KeyError(f"unknown tenant {tenant_id}")
        q = np.asarray(points, np.float32)
        if q.ndim != 2 or q.shape[1] != t.d:
            raise ValueError(f"expected (n, {t.d}) query points for tenant "
                             f"{tenant_id}, got shape {q.shape}")
        n = q.shape[0]
        # result buffers stay lazy: a single-chunk ticket gets zero-copy
        # views of the fused dispatch output, multi-chunk tickets allocate
        # at first scatter
        ticket = QueryTicket(tenant_id=t.tid, n=n, _left=n)
        self.stats.n_tickets += 1
        if n > 0:
            t.pending.append((ticket, q))
        else:
            ticket.assign = np.zeros((0,), np.int32)
            ticket.dist = np.zeros((0,), np.float32)
        return ticket

    def pending_queries(self) -> int:
        """Query rows currently admitted but not yet served."""
        return sum(q.shape[0] for t in self._tenants.values()
                   for _, q in t.pending)

    # -- step loop -----------------------------------------------------------

    def _refresh_phase(self, budget: Optional[int]) -> None:
        """Budgeted center refresh across tenants with queued work:
        never-solved tenants first (they cannot serve at all), then
        most-stale-first. Deferred tenants keep serving cached centers."""
        need = []
        for t in self._tenants.values():
            if not t.pending:
                continue
            uninit = t.source.cached_centers() is None
            if uninit or t.source.is_stale():
                stale_fn = getattr(t.source, "staleness", None)
                s = float(stale_fn()) if callable(stale_fn) else 0.0
                need.append((not uninit, -s, t))
        if not need:
            return
        need.sort(key=lambda x: x[:2])
        t0 = time.perf_counter()
        n = len(need) if budget is None else min(budget, len(need))
        for _, _, t in need[:n]:
            t.source.refresh()
            self.stats.n_refreshes += 1
        self.stats.n_deferred_refreshes += len(need) - n
        self.stats.refresh_s += time.perf_counter() - t0

    def step(self, refresh_budget: Optional[int] = -1) -> int:
        """Run one serving step: budgeted refresh phase, then assemble and
        launch fused dispatches for everything serveable in the queue.
        Returns the number of query rows served; an empty queue is a
        complete no-op (no refresh, no dispatch, no compilation)."""
        if not any(t.pending for t in self._tenants.values()):
            return 0
        self.stats.n_steps += 1
        self._refresh_phase(self.refresh_budget if refresh_budget == -1
                            else refresh_budget)

        # assembly: tenant-chunks bucketed by (d, k_bucket, padded-size,
        # objective); a tenant whose source has never solved stays queued
        groups: Dict[tuple, list] = {}
        buckets = self._bucket_cache
        for t in self._tenants.values():
            if not t.pending or t.staged_centers() is None:
                continue
            work, t.pending = t.pending, []
            for ticket, q in work:
                n = q.shape[0]
                if n <= self.max_bucket:        # common case: one chunk
                    b = buckets.get(n)
                    if b is None:
                        b = buckets[n] = query_bucket(n, self.min_bucket,
                                                      self.max_bucket)
                    groups.setdefault((t.d, t.k_bucket, b, t.objective),
                                      []).append((t, ticket, 0, q))
                    continue
                off = 0
                while off < n:
                    part = q[off:off + self.max_bucket]
                    m = part.shape[0]
                    b = buckets.get(m)
                    if b is None:
                        b = buckets[m] = query_bucket(m, self.min_bucket,
                                                      self.max_bucket)
                    key = (t.d, t.k_bucket, b, t.objective)
                    groups.setdefault(key, []).append(
                        (t, ticket, off, part))
                    off += m

        served = 0
        t0 = time.perf_counter()
        for (d, kb, b, objective), items in sorted(
                groups.items(), key=lambda kv: kv[0][:3]):
            for s0 in range(0, len(items), self.max_group):
                served += self._dispatch(items[s0:s0 + self.max_group],
                                         d, kb, b, objective)
        self.stats.assign_s += time.perf_counter() - t0
        return served

    def _staged_group_centers(self, items: list, Tp: int, kb: int, d: int):
        """Stacked ``(Tp, kb, d)`` centers + live mask for one dispatch
        group, as device arrays cached per tenant composition: steady
        traffic re-assembles the same group every step, so re-stacking T
        center sets (and re-transferring them) is paid only when a
        tenant's centers actually change (its ``stage_epoch`` bumps)."""
        sig = tuple((t.tid, t.stage_epoch) for t, _, _, _ in items)
        cached = self._center_cache.get((Tp, kb, d))
        if cached is not None and cached[0] == sig:
            return cached[1], cached[2]
        c = np.zeros((Tp, kb, d), np.float32)
        mask = np.zeros((Tp, kb), bool)
        for i, (t, _, _, _) in enumerate(items):
            c[i] = t.staged_centers()
            mask[i, :t.k] = True
        cj, mj = jnp.asarray(c), jnp.asarray(mask)
        self._center_cache[(Tp, kb, d)] = (sig, cj, mj)
        return cj, mj

    def _dispatch(self, items: list, d: int, kb: int, b: int,
                  objective: str) -> int:
        """Launch one fused stacked-center dispatch for up to ``max_group``
        same-bucket tenant-chunks and scatter results into tickets."""
        T = len(items)
        Tp = 1 << (T - 1).bit_length() if T > 1 else 1
        if T == Tp and all(p.shape[0] == b for _, _, _, p in items):
            # full buckets: one vectorized stack, no padding rows
            q = np.stack([p for _, _, _, p in items])
        else:
            q = np.zeros((Tp, b, d), np.float32)
            for i, (_, _, _, part) in enumerate(items):
                q[i, :part.shape[0]] = part
        # padding tenant rows keep mask all-False: every center row becomes
        # the sentinel, the reduction stays finite, results are discarded
        cj, mj = self._staged_group_centers(items, Tp, kb, d)
        assign, dist = backend_mod.query_assignments_batched(
            jnp.asarray(q), cj, mj,
            objective=objective, backend=self.backend)
        assign = np.asarray(assign)
        dist = np.asarray(dist)
        self.stats.n_dispatches += 1
        self.stats.n_tenant_dispatches += T
        self.compiled_shapes.add((Tp, b, kb, d, objective))
        served = 0
        for i, (_, ticket, off, part) in enumerate(items):
            n = part.shape[0]
            if off == 0 and n == ticket.n:
                # ticket served whole by this dispatch: alias the result
                # rows instead of copying them out
                ticket.assign = assign[i, :n]
                ticket.dist = dist[i, :n]
            else:
                if ticket.assign is None:
                    ticket.assign = np.empty((ticket.n,), np.int32)
                    ticket.dist = np.empty((ticket.n,), np.float32)
                ticket.assign[off:off + n] = assign[i, :n]
                ticket.dist[off:off + n] = dist[i, :n]
            ticket.n_padded += b - n
            ticket._left -= n
            served += n
        self.stats.n_queries += served
        self.stats.n_padded += Tp * b - served
        return served

    def run(self, max_steps: int = 10_000) -> int:
        """Step until the admission queue drains; returns rows served.
        Raises if the queue cannot make progress within ``max_steps``
        (e.g. a refresh budget of 0 against a never-solved tenant)."""
        total = 0
        for _ in range(max_steps):
            if not any(t.pending for t in self._tenants.values()):
                return total
            r0 = self.stats.n_refreshes
            s = self.step()
            total += s
            if s == 0 and self.stats.n_refreshes == r0:
                raise RuntimeError(
                    "serve queue cannot make progress (refresh budget 0 "
                    "against a never-solved tenant?)")
        raise RuntimeError(f"serve queue failed to drain in {max_steps} "
                           f"steps")
