"""Coreset constructions (paper Sec. 3, Algorithm 1).

Two entry points:

* :func:`build_coreset` -- the centralized sensitivity-sampling construction of
  Feldman-Langberg [10] on a (possibly weighted) point set. Used as the
  subroutine of the COMBINE and Zhang-et-al. baselines and as the reference
  centralized construction.

* :func:`distributed_coreset` -- **Algorithm 1**: every site solves its local
  instance, the only communicated quantities are the ``n`` scalar local costs,
  and each site then samples ``t_i = t * cost_i / sum_j cost_j`` points from
  its own data with probability proportional to the local sensitivity
  surrogate ``m_p = cost(p, B_i)``. (The paper writes ``m_p = 2 cost(p,B_i)``;
  the constant cancels in both the sampling distribution and the weight
  formula ``w_q = sum m / (t * m_q)``, so we drop it.) The union of all local
  portions ``S_i \\cup B_i`` is an eps-coreset of the *global* data set
  (Theorem 1).

Center weights ``w_b = |P_b| - sum_{q in P_b \\cap S} w_q`` may be negative --
the coreset is a signed measure (faithful to the paper); ``clip_negative``
opts into the common non-negative heuristic.

Everything is fixed-shape: sites sample into a ``t_buffer``-slot buffer with a
validity mask (XLA static shapes; see DESIGN.md Sec. 7).

Both constructions dispatch their distance/statistics hot loops through the
backend registry (``backend=`` accepts ``"jnp"``/``"jnp_chunked"``/
``"pallas"`` or ``None`` for the ambient default; DESIGN.md Sec. 8) and are
objective-generic through the objective registry (``objective=`` accepts
any registered :class:`Objective` name -- ``"kmeans"``, ``"kmedian"``,
``"kmeans_trimmed(<t>)"``, ``"power(<z>)"`` -- resolved once at the public
boundary; DESIGN.md Sec. 15). The objective's ``sensitivity_rule`` supplies
both the sampling masses and the *effective weights* Round 2 must use --
trimmed objectives zero their outliers' weights so trimmed mass never
reaches the sampled portions or the center weights.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import backend as backend_mod
from repro.core import clustering
from repro.core import objective as objective_mod
from repro.core.backend import BackendLike
from repro.core.objective import ObjectiveLike

Array = jax.Array
_TINY = 1e-30


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["points", "weights"], meta_fields=[])
@dataclasses.dataclass
class Coreset:
    """Weighted summary: invalid slots carry weight exactly 0."""

    points: Array    # (M, d)
    weights: Array   # (M,)

    @property
    def size(self) -> int:
        return int(self.points.shape[0])

    def effective_size(self) -> Array:
        return jnp.sum(self.weights != 0.0)

    def cost(self, centers: Array,
             objective: ObjectiveLike = "kmeans") -> Array:
        return clustering.cost(self.points, centers, weights=self.weights,
                               objective=objective)

    @staticmethod
    def concat(*coresets: "Coreset") -> "Coreset":
        """Weight-preserving union of summaries (mask discipline of
        DESIGN.md Sec. 7 makes this exact: invalid slots carry weight
        exactly 0 and stay inert in the union). jit/vmap-compatible --
        the merge-and-reduce stream tree and ``distributed_coreset`` both
        stitch their buffers through here."""
        if not coresets:
            raise ValueError("Coreset.concat needs at least one coreset")
        return Coreset(
            points=jnp.concatenate([c.points for c in coresets], axis=-2),
            weights=jnp.concatenate([c.weights for c in coresets], axis=-1))

    @functools.partial(jax.jit, static_argnums=1)
    def compact(self, size: Optional[int] = None) -> "Coreset":
        """Move weight-carrying slots to the front (stable) and truncate to
        ``size`` slots (default: same size); slots past the nonzero count
        carry weight 0. An index from a cumsum and one gather of ``size``
        rows: no sort over the buffer. Caller contract: ``size`` must be
        >= the number of nonzero-weight slots, otherwise mass is silently
        dropped -- check ``effective_size()`` first when in doubt."""
        size = self.size if size is None else size
        nonzero = jnp.cumsum(self.weights != 0.0, dtype=jnp.int32)
        slot = jnp.arange(size, dtype=jnp.int32)
        # row of the (slot+1)-th nonzero weight; past the last, out of range
        idx = jnp.minimum(jnp.searchsorted(nonzero, slot + 1, side="left"),
                          self.size - 1)
        return Coreset(points=self.points[idx],
                       weights=jnp.where(slot < nonzero[-1],
                                         self.weights[idx], 0.0))


@jax.named_scope("sensitivity")
def sensitivities(points: Array, centers: Array, weights: Array,
                  objective: ObjectiveLike = "kmeans",
                  backend: BackendLike = None
                  ) -> Tuple[Array, Array, Array]:
    """Per-point sampling masses, assignments, and *effective weights*
    ``(m, assign, w_eff)`` via the objective's ``sensitivity_rule``.

    Plain objectives: the paper's m_p = |w_p| * cost(p, B) with
    ``w_eff = weights`` passed through unchanged. The absolute value
    matters only for *signed* instances (re-sampling a coreset whose
    center weights went negative, as the streaming merge-and-reduce tree
    does): masses must be a valid sampling distribution, while the
    sample-weight formula keeps the original sign, so
    ``E[sum_q w_q f(q)] = sum_p w_p f(p)`` still holds and the total
    weight identity stays exact.

    Trimmed objectives additionally zero both the mass *and* ``w_eff`` on
    their top-t residual points -- downstream sampling and center
    weighting must consume ``w_eff``, not the raw weights, so outlier mass
    never folds back into the coreset."""
    obj = objective_mod.get_objective(objective)
    b = backend_mod.get_backend(backend)
    return obj.sensitivities(b, points, centers, weights)


def weighted_choice(key: Array, masses: Array, n_draws: int) -> Array:
    """``n_draws`` i.i.d. draws proportional to ``masses`` via inverse-CDF
    (O(M + t log M); jax.random.categorical would materialize a
    (n_draws, M) gumbel tensor). Zero-mass entries are never drawn."""
    cdf = jnp.cumsum(masses)
    total = cdf[-1]
    u = jax.random.uniform(key, (n_draws,), masses.dtype) * total
    idx = jnp.searchsorted(cdf, u, side="right")
    return jnp.clip(idx, 0, masses.shape[0] - 1).astype(jnp.int32)


def _sample_and_weight(key: Array, points: Array, m: Array, weights: Array,
                       assign: Array, k: int, t_local: Array, t_buffer: int,
                       total_m: Array, t_total: Array):
    """Draw ``t_local`` (<= t_buffer) points ~ m_p; compute sample + center
    weights. Shared by the centralized and distributed constructions."""
    n = points.shape[0]
    idx = weighted_choice(key, m, t_buffer)
    valid = (jnp.arange(t_buffer) < t_local) & (total_m > _TINY)
    # w_q = (sum_z m_z) * w_q_orig / (t * m_q); zero for invalid slots
    m_q = m[idx]
    w_s = jnp.where(
        valid & (m_q > _TINY),
        total_m * weights[idx] / (jnp.maximum(t_total, 1.0) * jnp.maximum(m_q, _TINY)),
        0.0,
    )
    sampled = points[idx]
    # center weights: w_b = W(P_b) - sum_{q in P_b cap S} w_q
    oh = jax.nn.one_hot(assign, k, dtype=points.dtype)          # (n, k)
    w_pb = (weights[:, None] * oh).sum(0)                        # (k,)
    sampled_assign = assign[idx]
    w_sb = jnp.zeros((k,), points.dtype).at[sampled_assign].add(w_s)
    w_b = w_pb - w_sb
    return sampled, w_s, w_b


def build_coreset(
    key: Array,
    points: Array,
    k: int,
    t: int,
    weights: Optional[Array] = None,
    objective: ObjectiveLike = "kmeans",
    lloyd_iters: int = 5,
    clip_negative: bool = False,
    backend: BackendLike = None,
) -> Coreset:
    """Centralized [10]-style coreset of ``t`` samples + ``k`` solution
    centers on a weighted instance. Output size t + k."""
    return _build_coreset(key, points, weights, k=k, t=t,
                          objective=objective_mod.resolve_name(objective),
                          lloyd_iters=lloyd_iters,
                          clip_negative=clip_negative,
                          backend=backend_mod.resolve_name(backend))


@functools.partial(
    jax.jit, static_argnames=("k", "t", "objective", "lloyd_iters",
                              "clip_negative", "backend"))
def _build_coreset(key, points, weights, k, t, objective, lloyd_iters,
                   clip_negative, backend):
    n = points.shape[0]
    w = jnp.ones((n,), points.dtype) if weights is None else weights
    # solve the approximation B on the non-negative part of the measure
    # (identity for mask/raw instances); optimizing centers against
    # negative mass admits spurious minima (DESIGN.md Sec. 9). The signed
    # w stays authoritative for sensitivities and the weight identities.
    w_solve = jnp.maximum(w, 0.0)
    key, ks = jax.random.split(key)
    centers = clustering.kmeans_pp_init(key, points, k, weights=w_solve,
                                        objective=objective, backend=backend)
    centers, _ = clustering.lloyd(points, centers, weights=w_solve,
                                  iters=lloyd_iters, objective=objective,
                                  backend=backend)
    m, assign, w_eff = sensitivities(points, centers, w, objective=objective,
                                     backend=backend)
    total_m = jnp.sum(m)
    sampled, w_s, w_b = _sample_and_weight(
        ks, points, m, w_eff, assign, k, jnp.asarray(t), t, total_m,
        jnp.asarray(float(t)))
    if clip_negative:
        w_b = jnp.maximum(w_b, 0.0)
    return Coreset.concat(Coreset(sampled, w_s), Coreset(centers, w_b))


def merge_coresets(
    key: Array,
    a: Coreset,
    b: Coreset,
    k: int,
    t: int,
    objective: ObjectiveLike = "kmeans",
    lloyd_iters: int = 5,
    backend: BackendLike = None,
) -> Coreset:
    """Merge-and-reduce step: re-run sensitivity sampling on the union of
    two summaries. This is the reduction of the streaming coreset tree
    (``repro.stream.tree``); composability of eps-coresets (union of
    coresets is a coreset of the union) makes it sound, and the signed
    weights of ``a``/``b`` are handled by the |w| sampling mass in
    :func:`sensitivities`. Output size t + k regardless of input sizes."""
    u = Coreset.concat(a, b)
    return build_coreset(key, u.points, k, t, weights=u.weights,
                         objective=objective, lloyd_iters=lloyd_iters,
                         backend=backend)


def proportional_allocation(costs: Array, t: int) -> Array:
    """Largest-remainder allocation of ``t`` samples proportional to local
    costs: sum_i t_i == t exactly, t_i >= 0, t_i ~= t * cost_i / sum_j cost_j.

    Degenerate all-zero costs (every site already solves its data exactly)
    fall back to the uniform allocation -- the sum-to-``t`` invariant must
    hold for any input, since Round 2 draws exactly ``t_i`` samples.

    The remainder correction is sign-safe: float error in ``t * cost_i /
    total`` can drive ``rem = t - sum(floor(frac))`` *negative* at extreme
    cost scales (every fraction rounding up), and the one-sided bonus would
    then leave ``sum(t_i) > t``. A negative remainder is taken back from
    the sites with the smallest fractional parts, capped per-site at its
    floor so no allocation goes negative (greedy over the sorted capacity
    prefix -- total capacity is ``sum(base) = t - rem >= -rem``, so the
    take-back always completes). The positive branch likewise survives
    ``rem > n_sites`` (uniform ``rem // n`` plus largest-remainder on the
    rest)."""
    n_sites = costs.shape[0]
    total = jnp.sum(costs)
    # ratio-first: costs/total <= 1 never overflows, while t*costs can hit
    # inf around 1e36 in f32 (an inf fraction floors to garbage and drives
    # the remainder arbitrarily negative)
    frac = jnp.where(total > _TINY,
                     t * (costs / jnp.maximum(total, _TINY)),
                     jnp.full_like(costs, t / n_sites))
    base = jnp.floor(frac)
    rem = t - jnp.sum(base).astype(jnp.int32)
    fr = frac - base
    # rem > 0: rank sites by fractional part, award the remainder to the
    # top-`rem` (cycling via // when rem exceeds n_sites)
    rank_hi = jnp.argsort(jnp.argsort(-fr))
    pos = jnp.maximum(rem, 0)
    award = pos // n_sites + (rank_hi < pos % n_sites).astype(jnp.int32)
    # rem < 0: take back from the smallest fractional parts first, at most
    # `base_i` each (keeps t_i >= 0); greedy prefix over sorted capacities
    need = jnp.maximum(-rem, 0)
    order = jnp.argsort(fr)
    cap = base[order].astype(jnp.int32)
    before = jnp.cumsum(cap) - cap
    take_sorted = jnp.clip(need - before, 0, cap)
    take = jnp.zeros_like(cap).at[order].set(take_sorted)
    return base.astype(jnp.int32) + award - take


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["points", "weights", "t_i", "local_costs"],
                   meta_fields=[])
@dataclasses.dataclass
class DistributedCoreset:
    """Per-site local portions (Algorithm 1 output, before any sharing).

    ``points``: (n_sites, t_buffer + k, d); ``weights``: (n_sites, t_buffer+k)
    with exact zeros on invalid slots; ``t_i``: realized per-site sample
    counts; ``local_costs``: cost(P_i, B_i) -- the Round-1 scalars.
    """

    points: Array
    weights: Array
    t_i: Array
    local_costs: Array

    def flatten(self) -> Coreset:
        d = self.points.shape[-1]
        return Coreset(points=self.points.reshape(-1, d),
                       weights=self.weights.reshape(-1))


def gathered_live_rows(rows: int, t: int, k: int) -> int:
    """Most rows of a gathered ``rows``-row buffer that can carry weight.

    The buffer is the sites' portions of ``t_buffer = t`` sample slots
    then ``k`` centers, and the portions' valid samples number ``sum t_i
    == t`` (``proportional_allocation``'s largest remainders), so at most
    ``t + portions * k`` rows are nonzero. Static: from shapes alone."""
    return t + (rows // (t + k)) * k


def distributed_coreset(
    key: Array,
    site_points: Array,          # (n_sites, M, d) padded
    site_mask: Array,            # (n_sites, M) bool
    k: int,
    t: int,
    t_buffer: Optional[int] = None,
    objective: ObjectiveLike = "kmeans",
    lloyd_iters: int = 5,
    clip_negative: bool = False,
    backend: BackendLike = None,
    site_weights: Optional[Array] = None,   # (n_sites, M) overrides mask
    strategy: "strategy_mod.StrategyLike" = None,
) -> DistributedCoreset:
    """The distributed coreset rounds over all sites at once (vmapped host
    simulation), driven by a registered
    :class:`~repro.core.strategy.CoresetStrategy` (default
    ``"algorithm1"``, the paper's protocol -- bit-identical to the
    pre-strategy-layer implementation).

    For exchanging strategies the only cross-site quantities used are
    ``local_costs`` (Round 1: n scalars) and their sum -- exactly the
    paper's communication pattern; single-shuffle strategies
    (``"mapreduce"``) use none at all. The SPMD/mesh execution of the same
    math lives in :mod:`repro.core.distributed`.

    ``site_weights`` generalizes each site's instance from masked raw points
    to an arbitrary *weighted* (possibly signed) local summary -- the
    streaming aggregation rounds run Algorithm 1 over per-site coreset-tree
    summaries this way. When given, ``site_mask`` is ignored (a zero weight
    is an invalid slot).
    """
    from repro.core import strategy as strategy_mod
    t_buffer = t if t_buffer is None else t_buffer
    backend = backend_mod.resolve_name(backend)
    objective = objective_mod.resolve_name(objective)
    strat = strategy_mod.get_strategy(strategy)
    n_sites = site_points.shape[0]
    w_site = (site_mask.astype(site_points.dtype) if site_weights is None
              else site_weights.astype(site_points.dtype))
    keys = strat.keys(key, n_sites)

    r1 = strat.summary(keys[:, 0], site_points, w_site, k=k,
                       objective=objective, lloyd_iters=lloyd_iters,
                       backend=backend)
    local_costs = r1.local_costs

    # -- the single communicated aggregate (exchanging strategies only) ------
    # (the topology execution engine in repro.core.distributed runs these
    # same two stages but moves local_costs / the portions through executed
    # message-passing rounds instead of touching them globally here)
    t_i = strat.allocate(local_costs, t)
    if strat.needs_exchange:
        totals = jnp.broadcast_to(jnp.sum(local_costs), (n_sites,))
    else:
        totals = strat.local_totals(local_costs)

    portions = strat.contribute(keys[:, 1], site_points, r1, t_i, totals,
                                k=k, t=t, t_buffer=t_buffer,
                                clip_negative=clip_negative)
    return DistributedCoreset(points=portions.points,
                              weights=portions.weights, t_i=t_i,
                              local_costs=local_costs)


# ---------------------------------------------------------------------------
# staged Round-1/Round-2 engine (per-site dispatch instead of lockstep vmap)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StagedDetail:
    """Measurement sidecar of :func:`staged_distributed_coreset`.

    ``site_lengths``: the per-site padded solve lengths actually compiled
    (all equal to the lockstep pad length M unless ``site_buckets``);
    ``iters_run``: per-site realized refinement passes (== ``lloyd_iters``
    everywhere unless ``tol > 0`` let a site exit early)."""

    site_lengths: Tuple[int, ...]
    iters_run: Array


@functools.partial(
    jax.jit, static_argnames=("k", "objective", "lloyd_iters", "tol",
                              "backend", "strategy"))
def _staged_solve_site(key, pts, w, k, objective, lloyd_iters, tol, backend,
                       strategy):
    """One site's Round-1 stage, unbatched: same math as the vmapped
    ``local_solve`` of :func:`round1_local_solves` (bit-identical at
    ``tol == 0``), plus the strategy's sampling-mass rule and the realized
    refinement-pass count."""
    from repro.core import strategy as strategy_mod
    strat = strategy_mod.get_strategy(strategy)
    w_solve = jnp.maximum(w, 0.0)
    centers = clustering.kmeans_pp_init(key, pts, k, weights=w_solve,
                                        objective=objective, backend=backend)
    centers, iters_run = clustering.lloyd_converged(
        pts, centers, weights=w_solve, iters=lloyd_iters, tol=tol,
        objective=objective, backend=backend)
    m, assign, w_eff = strat.site_sensitivities(pts, centers, w,
                                                objective=objective,
                                                backend=backend)
    return centers, m, assign, jnp.sum(m), w_eff, iters_run


@functools.partial(jax.jit, static_argnames=("k", "t_buffer"))
def _staged_round2_precompute(key, pts, m, w_eff, assign, k, t_buffer):
    """The allocation-independent prefix of :func:`_sample_and_weight`:
    the ``t_buffer`` draws, their masses/weights/assignments, and the
    per-cluster weight totals depend only on Round-1 locals -- so a site
    can run this *before* its ``t_i`` arrives, overlapping slower sites'
    Round-1 solves. Expressions match ``_sample_and_weight`` term for term
    (bit-parity contract; DESIGN.md Sec. 17)."""
    idx = weighted_choice(key, m, t_buffer)
    m_q = m[idx]
    w_idx = w_eff[idx]
    sampled = pts[idx]
    sampled_assign = assign[idx]
    oh = jax.nn.one_hot(assign, k, dtype=pts.dtype)
    w_pb = (w_eff[:, None] * oh).sum(0)
    return sampled, m_q, w_idx, sampled_assign, w_pb


@functools.partial(jax.jit,
                   static_argnames=("k", "t_buffer", "clip_negative"))
def _staged_round2_finalize(sampled, m_q, w_idx, sampled_assign, w_pb,
                            centers, t_local, total_m, t_total, k, t_buffer,
                            clip_negative):
    """The allocation-dependent suffix of :func:`_sample_and_weight` +
    portion assembly: validity mask, sample weights, residual center
    weights, concat. Cheap (O(t_buffer + k)); runs after the exchange."""
    valid = (jnp.arange(t_buffer) < t_local) & (total_m > _TINY)
    w_s = jnp.where(
        valid & (m_q > _TINY),
        total_m * w_idx / (jnp.maximum(t_total, 1.0)
                           * jnp.maximum(m_q, _TINY)),
        0.0,
    )
    w_sb = jnp.zeros((k,), sampled.dtype).at[sampled_assign].add(w_s)
    w_b = w_pb - w_sb
    if clip_negative:
        w_b = jnp.maximum(w_b, 0.0)
    return (jnp.concatenate([sampled, centers], axis=0),
            jnp.concatenate([w_s, w_b], axis=0))


def _site_valid_lengths(w_site: Array) -> Tuple[int, ...]:
    """Per-site count covering every nonzero-weight slot (1 + its last
    index). ``pad_partition`` packs valid slots first, so this equals the
    true site size there; arbitrary weighted summaries stay covered
    because slicing ``[:count]`` keeps every weight-carrying slot."""
    w = np.asarray(w_site)
    nz = (w != 0.0)[:, ::-1].argmax(axis=1)
    any_nz = (w != 0.0).any(axis=1)
    return tuple(int(w.shape[1] - z) if a else 1
                 for z, a in zip(nz, any_nz))


def staged_distributed_coreset(
    key: Array,
    site_points: Array,          # (n_sites, M, d) padded
    site_mask: Array,            # (n_sites, M) bool
    k: int,
    t: int,
    t_buffer: Optional[int] = None,
    objective: ObjectiveLike = "kmeans",
    lloyd_iters: int = 5,
    clip_negative: bool = False,
    backend: BackendLike = None,
    site_weights: Optional[Array] = None,
    strategy: "strategy_mod.StrategyLike" = None,
    tol: float = 0.0,
    site_buckets: bool = False,
    min_bucket: int = 64,
) -> Tuple[DistributedCoreset, StagedDetail]:
    """:func:`distributed_coreset` with Round 1 broken out of the lockstep
    vmap: sites are dispatched one jitted solve at a time, each site's
    Round-1 scalar starts moving to the allocator the moment its own solve
    converges (async device-to-host copy), and its allocation-independent
    Round-2 sampling prefix (:func:`_staged_round2_precompute`) is
    interleaved between the following site's fused ``lloyd_stats`` /
    ``weiszfeld_stats`` passes -- double-buffered dispatch, so fast sites'
    Round-2 work overlaps slow sites' refinement. Only the validity mask /
    weight scaling / portion assembly (:func:`_staged_round2_finalize`)
    waits for the exchange barrier -- and for single-shuffle strategies
    the allocation is locally derivable, so even that runs inside the
    dispatch loop with no barrier at all.

    Two knobs trade strictness for wall-clock (DESIGN.md Sec. 17):

    * ``tol`` -- early-exit threshold for the local refinement
      (:func:`~repro.core.clustering.lloyd_converged`). ``0.0`` keeps the
      lockstep iteration count.
    * ``site_buckets`` -- solve each site at its own power-of-two padded
      length (:func:`repro.kernels.ops.site_bucket_lengths`) instead of
      the lockstep pad M, so small sites stop paying the largest site's
      FLOPs. Changes draw indices (the sampling CDF has fewer slots), so
      results are deterministic but not bit-equal to lockstep.

    With both off (the default, "strict" mode) every output field of the
    returned :class:`DistributedCoreset` is bit-identical to
    :func:`distributed_coreset` for every registered strategy -- the
    frozen ``algorithm1`` key-derivation and digest contracts survive
    because the key table, draw indices, and weight formulas are shared
    term for term.

    Returns ``(coreset, StagedDetail)`` -- the sidecar carries the
    realized per-site lengths/iterations for
    ``bench_collectives``.
    """
    from repro.core import strategy as strategy_mod
    from repro.kernels.ops import site_bucket_lengths
    t_buffer = t if t_buffer is None else t_buffer
    backend = backend_mod.resolve_name(backend)
    objective = objective_mod.resolve_name(objective)
    strategy = strategy_mod.resolve_name(strategy)
    strat = strategy_mod.get_strategy(strategy)
    n_sites, M = site_points.shape[0], site_points.shape[1]
    w_site = (site_mask.astype(site_points.dtype) if site_weights is None
              else site_weights.astype(site_points.dtype))
    lengths = (site_bucket_lengths(_site_valid_lengths(w_site), M,
                                   min_bucket=min_bucket)
               if site_buckets else (M,) * n_sites)
    keys = strat.keys(key, n_sites)
    tol = float(tol)

    if not strat.needs_exchange:
        # locally derivable split: no barrier anywhere in the loop below
        t_i = strat.allocate(jnp.ones((n_sites,), site_points.dtype), t)
        t_totals = strat.sample_t_total(t, t_i)

    solves: list = []
    pre: list = []
    final: list = []

    def dispatch_round2(i):
        c_i, m_i, a_i, cost_i, w_eff_i, _ = solves[i]
        pre.append(_staged_round2_precompute(
            keys[i, 1], site_points[i, :lengths[i]], m_i, w_eff_i, a_i,
            k=k, t_buffer=t_buffer))
        if not strat.needs_exchange:
            final.append(_staged_round2_finalize(
                *pre[i], c_i, t_i[i], cost_i, t_totals[i], k=k,
                t_buffer=t_buffer, clip_negative=clip_negative))

    for i in range(n_sites):
        solves.append(_staged_solve_site(
            keys[i, 0], site_points[i, :lengths[i]], w_site[i, :lengths[i]],
            k=k, objective=objective, lloyd_iters=lloyd_iters, tol=tol,
            backend=backend, strategy=strategy))
        # the site's Round-1 scalar starts its exchange immediately ...
        solves[-1][3].copy_to_host_async()
        # ... and the previous site's Round-2 prefix overlaps this solve
        if i:
            dispatch_round2(i - 1)
    dispatch_round2(n_sites - 1)

    local_costs = jnp.stack([s[3] for s in solves])
    if strat.needs_exchange:
        t_i = strat.allocate(local_costs, t)
        totals = jnp.broadcast_to(jnp.sum(local_costs), (n_sites,))
        t_totals = strat.sample_t_total(t, t_i)
        for i in range(n_sites):
            final.append(_staged_round2_finalize(
                *pre[i], solves[i][0], t_i[i], totals[i], t_totals[i],
                k=k, t_buffer=t_buffer, clip_negative=clip_negative))
    points = jnp.stack([f[0] for f in final])
    weights = jnp.stack([f[1] for f in final])

    detail = StagedDetail(
        site_lengths=lengths,
        iters_run=jnp.stack([s[5] for s in solves]))
    return (DistributedCoreset(points=points, weights=weights, t_i=t_i,
                               local_costs=local_costs), detail)


@functools.partial(
    jax.jit, static_argnames=("k", "objective", "lloyd_iters", "backend"))
@jax.named_scope("round1")
def round1_local_solves(keys, site_points, w_site, k, objective, lloyd_iters,
                        backend):
    """Algorithm 1 Round 1, the purely-local stage: every site solves its
    own weighted instance. Returns (centers (n,k,d), sensitivities m (n,M),
    assignments (n,M), local_costs (n,), w_eff (n,M)) -- ``local_costs``
    are the only values any communication round needs to move, and
    ``w_eff`` are the objective's effective weights Round 2 must sample
    and center-weight with (identical to ``w_site`` for plain objectives;
    zeroed on trimmed-out points for trimmed ones). Shared verbatim by the
    host-simulation path, the topology execution engine, and the streaming
    aggregation rounds, so their numerics are identical by construction."""

    def local_solve(ki, pts, w):
        # as in _build_coreset: solve B_i on max(w, 0) (identity for masked
        # sites), signed w for the sensitivities
        w_solve = jnp.maximum(w, 0.0)
        centers = clustering.kmeans_pp_init(ki, pts, k, weights=w_solve,
                                            objective=objective,
                                            backend=backend)
        centers, _ = clustering.lloyd(pts, centers, weights=w_solve,
                                      iters=lloyd_iters, objective=objective,
                                      backend=backend)
        m, assign, w_eff = sensitivities(pts, centers, w,
                                         objective=objective,
                                         backend=backend)
        return centers, m, assign, w_eff

    centers, m, assign, w_eff = jax.vmap(local_solve)(
        keys, site_points, w_site)
    # costs == trimmed/plain cost(P_i, B_i) in the objective's own metric
    return centers, m, assign, m.sum(axis=1), w_eff


@functools.partial(
    jax.jit, static_argnames=("k", "t", "t_buffer", "clip_negative"))
@jax.named_scope("round2")
def round2_local_samples(keys, site_points, m, w_eff, assign, centers, t_i,
                         total_m, k, t, t_buffer, clip_negative):
    """Algorithm 1 Round 2, the purely-local stage: every site draws its
    ``t_i`` samples and assembles its portion S_i u B_i. ``w_eff`` are the
    Round-1 effective weights (raw site weights for plain objectives).
    ``total_m`` is per-site (n,) -- each site uses the global sensitivity
    total *it received* (all entries are bit-identical copies on every
    path, but the execution engine genuinely delivers one per node)."""

    def local_sample(ki, pts, m_i, w_i, a_i, ti, tm):
        return _sample_and_weight(ki, pts, m_i, w_i, a_i, k, ti, t_buffer,
                                  tm, jnp.asarray(float(t)))

    sampled, w_s, w_b = jax.vmap(local_sample)(
        keys, site_points, m, w_eff, assign, t_i, total_m)
    if clip_negative:
        w_b = jnp.maximum(w_b, 0.0)
    # per-site portion S_i u B_i, stitched via the shared mask-aware union
    return jax.vmap(Coreset.concat)(Coreset(sampled, w_s),
                                    Coreset(centers, w_b))


@functools.partial(
    jax.jit, static_argnames=("k", "t_buffer", "clip_negative"))
@jax.named_scope("round2")
def round2_local_samples_localized(keys, site_points, m, w_eff, assign,
                                   centers, t_i, total_m, k, t_buffer,
                                   clip_negative):
    """Round 2 with *per-site* normalization: each site's weight formula
    uses its own sensitivity total (``total_m`` carries each site's own
    scalar) and its own realized draw count ``t_i`` -- the site's portion
    is a standalone coreset of its local data, no cross-site quantity
    anywhere. This is the mapreduce strategy's local stage
    (:mod:`repro.core.strategy`); composability of eps-coresets makes the
    union of the portions a coreset of the union."""

    def local_sample(ki, pts, m_i, w_i, a_i, ti, tm):
        return _sample_and_weight(ki, pts, m_i, w_i, a_i, k, ti, t_buffer,
                                  tm, ti.astype(jnp.float32))

    sampled, w_s, w_b = jax.vmap(local_sample)(
        keys, site_points, m, w_eff, assign, t_i, total_m)
    if clip_negative:
        w_b = jnp.maximum(w_b, 0.0)
    return jax.vmap(Coreset.concat)(Coreset(sampled, w_s),
                                    Coreset(centers, w_b))
