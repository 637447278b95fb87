"""First-class coreset round protocols (DESIGN.md Sec. 16).

Algorithm 1's two-round choreography -- local solve, scalar exchange,
proportional allocation, local sample -- used to be re-implemented inline
by every engine (host sim, gossip exec, tree exec, SPMD collectives, WAN
async, streaming aggregation). A :class:`CoresetStrategy` is that
choreography as a frozen, hashable descriptor: the registry maps canonical
names to instances, mirroring :mod:`repro.core.backend` and
:mod:`repro.core.objective`, and every engine now consumes the
descriptor's hooks instead of hard-coding the paper's round structure.
Engines own the *transport* (how payloads physically move); strategies own
the *protocol* (what is computed locally, what is exchanged, how the
sample budget is split, and how the sampled portions are weighted).

**Descriptor hooks** (every hook takes the descriptor itself first, so
parametrized instances stay plain module-level functions and instance
equality/hashability hold):

* ``derive_keys(strat, key, n_sites)`` -- the all-site PRNG discipline:
  one ``(n_sites, 2, ...)`` key table covering Round 1 (column 0) and
  Round 2 (column 1) for *every* site, dead or alive. Consolidated here
  because the sim, exec, tree, and async engines each used to re-derive
  it independently (a silent-skew hazard: any drift broke the
  engine-bit-parity contract); now they all consume this one hook and a
  regression test asserts the keys agree per ``(seed, strategy)``.
* ``local_summary(strat, keys, site_points, w_site, *, k, objective,
  lloyd_iters, backend)`` -- Round 1's purely-local stage, vmapped over
  sites: returns ``(centers, m, assign, local_costs, w_eff)`` where ``m``
  is the strategy's per-point sampling mass and ``local_costs`` the
  per-site scalar the exchange round moves (if any).
* ``exchange_spec(strat)`` -- the declared communication shape of
  Round 1: an :class:`ExchangeSpec` (each site contributes
  ``unit_scalars`` scalars that must reach the allocator), or ``None``
  for single-shuffle strategies whose allocation is locally derivable --
  engines skip the scalar round entirely and price zero Round-1 traffic.
* ``allocate(strat, costs, t)`` -- split the global budget ``t`` into
  per-site draws ``t_i`` from the (received or locally-known) scalars;
  must satisfy ``sum(t_i) == t`` exactly.
* ``local_contribution(strat, keys, site_points, r1, t_i, totals, *, k,
  t, t_buffer, clip_negative)`` -- Round 2's purely-local stage: each
  site draws its ``t_i`` samples and assembles its fixed-shape portion
  (``t_buffer + k`` slots: samples plus the local solution centers
  carrying the exact residual weights, so total mass is preserved bit
  for bit by every registered strategy). ``totals`` is the per-site
  normalizer each site uses in the weight formula: the *global* scalar
  total it received for exchanging strategies, its *own* local total for
  single-shuffle ones.
* ``assemble(strat, points, weights)`` -- stitch moved portions into one
  :class:`~repro.core.coreset.Coreset`.
* ``site_sensitivities(strat, pts, centers, w, *, objective, backend)``
  -- the unbatched sampling-mass rule, consumed by the SPMD per-device
  path (which runs one site per device and cannot use the vmapped
  ``local_summary``) and by the *staged* coreset engine's per-site
  solves (``repro.core.coreset.staged_distributed_coreset``).
* ``sample_t_total(strat, t, t_i)`` -- the per-site ``t_total``
  normalizer of the sample-weight formula (the global ``t`` for
  exchanging strategies, each site's own ``t_i`` for single-shuffle
  ones); the staged engine's split sample/finalize stages consume this
  instead of re-entering the batched ``contribute`` hook.

**Registered strategies**:

* ``"algorithm1"`` -- the paper's protocol, bit-identical to the
  pre-strategy-layer engines: sampling mass ``m_p = |w_p| cost(p, B_i)``
  (through the objective's ``sensitivity_rule``), one scalar exchanged
  per site, largest-remainder cost-proportional allocation, and the
  global-total weight formula ``w_q = (sum_j cost_j) w_q / (t m_q)``.
* ``"cohen_addad"`` -- the (1+eps)-coreset construction in the style of
  Cohen-Addad et al. (arXiv 2603.08615): the sampling mass is the
  *refined two-term sensitivity* ``s_p = m_p / cost(P_i, B_i) +
  |w_p| / W(cluster(p))`` (cost share plus inverse cluster mass -- the
  bound that upgrades constant-factor to (1+eps) guarantees), computed
  from the same fused backend primitives (one ``min_dist_argmin``
  assignment pass plus an O(n) scatter-add; no (n, k) materialization).
  Same two-round shape and byte cost as ``"algorithm1"``; the exchanged
  scalar and the allocation are the per-site refined-sensitivity totals.
* ``"mapreduce"`` -- the one-shuffle MapReduce-shaped rounds of Mazzetto
  et al. (arXiv 1904.12728): **no scalar exchange** (``exchange_spec``
  is ``None``) -- the budget splits uniformly by largest remainder,
  which every site derives locally -- and each site builds a standalone
  local coreset of its own data (weight formula normalized by its *own*
  sensitivity total and its *own* ``t_i``); composability of
  eps-coresets makes the union a coreset of the union. One gather of
  the per-site portions (map -> shuffle -> reduce) replaces Algorithm
  1's two diameter floods, so its byte cost strictly undercuts
  ``"algorithm1"`` on every topology.

**Registry resolution rules**: public APIs accept strategy names (or
instances, or ``None`` for ``"algorithm1"``); :func:`resolve_name` maps a
selection to a canonical registry name -- the jit-static currency, exactly
like the backend and objective registries -- and raises ``ValueError``
listing the registered names on anything unknown.

**Bit-compat discipline** (DESIGN.md Sec. 16): ``"algorithm1"``'s hooks
delegate to the exact pre-refactor stage functions
(:func:`~repro.core.coreset.round1_local_solves` /
:func:`~repro.core.coreset.round2_local_samples`) with the same key
derivation, so every engine's centers, coresets, and ledgers are
bit-identical through the descriptor indirection.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp

Array = jax.Array

_TINY = 1e-30


class Round1State(NamedTuple):
    """Per-site output of a strategy's Round-1 local stage (all arrays
    site-major). ``m`` is the strategy's sampling mass (the paper's
    ``m_p`` for ``"algorithm1"``, the refined sensitivity for
    ``"cohen_addad"``); ``local_costs`` the per-site exchange scalar
    (``m.sum(axis=1)``); ``w_eff`` the objective's effective weights
    Round 2 must sample and center-weight with."""

    centers: Array      # (n_sites, k, d)
    m: Array            # (n_sites, M)
    assign: Array       # (n_sites, M)
    local_costs: Array  # (n_sites,)
    w_eff: Array        # (n_sites, M)


@dataclasses.dataclass(frozen=True)
class ExchangeSpec:
    """Declared shape of the Round-1 exchange: every site contributes
    ``unit_scalars`` scalars that must reach every allocator (flooded on
    graphs, gathered+scattered on trees, all-gathered on meshes)."""

    unit_scalars: float = 1.0


# ---------------------------------------------------------------------------
# hook implementations (module-level so instances compare/hash equal)
# ---------------------------------------------------------------------------

def _split_keys(strat: "CoresetStrategy", key: Array, n_sites: int) -> Array:
    """The all-site key table: ``split(key, 2 n)`` reshaped to
    ``(n, 2, ...)`` -- column 0 drives Round 1, column 1 Round 2. Spanning
    *all* sites (dead or not) is what keeps survivor-site values
    bit-identical however many peers fault out (DESIGN.md Sec. 14)."""
    return jax.random.split(key, n_sites * 2).reshape(n_sites, 2, -1)


def _alg1_local_summary(strat, keys, site_points, w_site, *, k, objective,
                        lloyd_iters, backend) -> Round1State:
    from repro.core.coreset import round1_local_solves
    return Round1State(*round1_local_solves(
        keys, site_points, w_site, k=k, objective=objective,
        lloyd_iters=lloyd_iters, backend=backend))


def _refined_sensitivities(m: Array, assign: Array, w_eff: Array,
                           k: int) -> Array:
    """The two-term (1+eps) sensitivity bound from the plain masses: per
    point, its share of the local cost plus its share of its cluster's
    mass. O(n) on top of the fused assignment pass (a scatter-add over k
    cluster slots); zero-mass (padding / trimmed-out) slots keep exactly
    zero sampling mass."""
    aw = jnp.abs(w_eff)
    cluster_mass = jnp.zeros((k,), aw.dtype).at[assign].add(aw)
    total = jnp.sum(m)
    s = (m / jnp.maximum(total, _TINY)
         + aw / jnp.maximum(cluster_mass[assign], _TINY))
    return jnp.where(aw > 0.0, s, 0.0)


def _cohen_addad_local_summary(strat, keys, site_points, w_site, *, k,
                               objective, lloyd_iters, backend
                               ) -> Round1State:
    from repro.core.coreset import round1_local_solves
    centers, m, assign, _, w_eff = round1_local_solves(
        keys, site_points, w_site, k=k, objective=objective,
        lloyd_iters=lloyd_iters, backend=backend)
    s = _refine_batch(m, assign, w_eff, k=k)
    return Round1State(centers, s, assign, s.sum(axis=1), w_eff)


@functools.partial(jax.jit, static_argnames=("k",))
@jax.named_scope("sensitivity")
def _refine_batch(m, assign, w_eff, k):
    return jax.vmap(lambda mi, ai, wi: _refined_sensitivities(mi, ai, wi, k)
                    )(m, assign, w_eff)


def _scalar_exchange(strat) -> Optional[ExchangeSpec]:
    return ExchangeSpec(unit_scalars=1.0)


def _no_exchange(strat) -> Optional[ExchangeSpec]:
    return None


def _proportional_allocate(strat, costs: Array, t: int) -> Array:
    from repro.core.coreset import proportional_allocation
    return proportional_allocation(costs, t)


def _uniform_allocate(strat, costs: Array, t: int) -> Array:
    """Largest-remainder over uniform shares: locally derivable at every
    site from ``n_sites`` and ``t`` alone (``costs`` contributes only its
    length), which is what lets the mapreduce strategy skip the scalar
    exchange entirely."""
    from repro.core.coreset import proportional_allocation
    return proportional_allocation(jnp.ones_like(costs), t)


def _alg1_local_contribution(strat, keys, site_points, r1: Round1State,
                             t_i, totals, *, k, t, t_buffer, clip_negative):
    from repro.core.coreset import round2_local_samples
    return round2_local_samples(
        keys, site_points, r1.m, r1.w_eff, r1.assign, r1.centers, t_i,
        totals, k=k, t=t, t_buffer=t_buffer, clip_negative=clip_negative)


def _mapreduce_local_contribution(strat, keys, site_points, r1: Round1State,
                                  t_i, totals, *, k, t, t_buffer,
                                  clip_negative):
    """Per-site *standalone* coresets: the weight formula normalizes by
    the site's own sensitivity total (``totals`` carries each site's own
    scalar on no-exchange strategies) and its own ``t_i`` -- each portion
    is an eps-coreset of its site's data alone, and the union is a
    coreset of the union by composability. No cross-site quantity
    appears anywhere, which is what makes the single shuffle sufficient."""
    from repro.core.coreset import round2_local_samples_localized
    return round2_local_samples_localized(
        keys, site_points, r1.m, r1.w_eff, r1.assign, r1.centers, t_i,
        totals, k=k, t_buffer=t_buffer, clip_negative=clip_negative)


def _flatten_assemble(strat, points: Array, weights: Array):
    from repro.core.coreset import Coreset
    d = points.shape[-1]
    return Coreset(points=points.reshape(-1, d),
                   weights=weights.reshape(-1))


def _plain_site_sensitivities(strat, pts, centers, w, *, objective, backend):
    from repro.core.coreset import sensitivities
    return sensitivities(pts, centers, w, objective=objective,
                         backend=backend)


def _refined_site_sensitivities(strat, pts, centers, w, *, objective,
                                backend):
    from repro.core.coreset import sensitivities
    m, assign, w_eff = sensitivities(pts, centers, w, objective=objective,
                                     backend=backend)
    k = centers.shape[0]
    return _refined_sensitivities(m, assign, w_eff, k), assign, w_eff


def _global_t_total(strat, t: int, t_i: Array) -> Array:
    """Exchanging strategies normalize the sample-weight formula by the
    *global* budget ``t`` (round2_local_samples' rule), replicated
    per site."""
    return jnp.full(t_i.shape, float(t), jnp.float32)


def _own_t_total(strat, t: int, t_i: Array) -> Array:
    """Single-shuffle strategies normalize by each site's *own* realized
    draw count (round2_local_samples_localized's rule)."""
    return t_i.astype(jnp.float32)


def _no_validate(strat) -> None:
    pass


# ---------------------------------------------------------------------------
# the descriptor
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CoresetStrategy:
    """A registered distributed-coreset round protocol. Frozen and
    hashable -- instances are valid static jit arguments, though the
    plumbing passes canonical *names* (resolved once at the public
    boundary), exactly like the backend and objective registries."""

    name: str
    derive_keys_fn: Callable = _split_keys
    local_summary_fn: Callable = _alg1_local_summary
    exchange_spec_fn: Callable = _scalar_exchange
    allocate_fn: Callable = _proportional_allocate
    local_contribution_fn: Callable = _alg1_local_contribution
    assemble_fn: Callable = _flatten_assemble
    site_sensitivities_fn: Callable = _plain_site_sensitivities
    sample_t_total_fn: Callable = _global_t_total
    validate: Callable = _no_validate

    def __post_init__(self):
        self.validate(self)

    # -- convenience wrappers (hooks take the descriptor first) --------------

    def keys(self, key: Array, n_sites: int) -> Array:
        """The all-site ``(n_sites, 2, ...)`` Round-1/Round-2 key table."""
        return self.derive_keys_fn(self, key, n_sites)

    def summary(self, keys: Array, site_points: Array, w_site: Array, *,
                k: int, objective: str, lloyd_iters: int,
                backend: str) -> Round1State:
        """Round 1's local stage over all sites. The host span ``round1``
        carries the rows the stage sweeps (every site padded to ``M``)."""
        n_sites, m, d = site_points.shape
        with jax.profiler.TraceAnnotation("round1", sites=n_sites,
                                          rows=n_sites * m, d=d, k=k):
            return self.local_summary_fn(self, keys, site_points, w_site,
                                         k=k, objective=objective,
                                         lloyd_iters=lloyd_iters,
                                         backend=backend)

    def exchange_spec(self) -> Optional[ExchangeSpec]:
        """The declared Round-1 communication shape (``None`` == no
        exchange round at all)."""
        return self.exchange_spec_fn(self)

    @property
    def needs_exchange(self) -> bool:
        return self.exchange_spec() is not None

    def allocate(self, costs: Array, t: int) -> Array:
        """Split the budget: ``sum == t`` exactly, every strategy."""
        with jax.profiler.TraceAnnotation("allocate"):
            return self.allocate_fn(self, costs, t)

    def contribute(self, keys: Array, site_points: Array, r1: Round1State,
                   t_i: Array, totals: Array, *, k: int, t: int,
                   t_buffer: int, clip_negative: bool):
        """Round 2's local stage: batched per-site portions (a vmapped
        :class:`~repro.core.coreset.Coreset`). The host span ``round2``
        carries the rows the stage samples from."""
        n_sites, m = site_points.shape[:2]
        with jax.profiler.TraceAnnotation("round2", sites=n_sites,
                                          rows=n_sites * m):
            return self.local_contribution_fn(
                self, keys, site_points, r1, t_i, totals, k=k, t=t,
                t_buffer=t_buffer, clip_negative=clip_negative)

    def assemble(self, points: Array, weights: Array):
        """Stitch moved portions into one flat coreset."""
        return self.assemble_fn(self, points, weights)

    def site_sensitivities(self, pts: Array, centers: Array, w: Array, *,
                           objective: str, backend: str):
        """Unbatched sampling-mass rule (the SPMD per-device stage)."""
        return self.site_sensitivities_fn(self, pts, centers, w,
                                          objective=objective,
                                          backend=backend)

    def local_totals(self, local_costs: Array) -> Array:
        """The per-site ``totals`` vector engines must feed
        :meth:`contribute` when no exchange round runs: each site
        normalizes by its *own* scalar."""
        return local_costs

    def sample_t_total(self, t: int, t_i: Array) -> Array:
        """The per-site ``t_total`` normalizer of the sample-weight
        formula ``w_q = total_m * w / (t_total * m_q)``: the global
        budget ``t`` for exchanging strategies, each site's own realized
        ``t_i`` for single-shuffle ones. The *staged* coreset engine
        (``repro.core.coreset.staged_distributed_coreset``) consumes this
        hook to finalize per-site weights without re-entering the batched
        ``contribute`` path -- it must stay consistent with
        ``local_contribution_fn``'s normalization rule."""
        return self.sample_t_total_fn(self, t, t_i)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, CoresetStrategy] = {}

StrategyLike = Union[str, CoresetStrategy, None]


def register_strategy(strat: CoresetStrategy) -> CoresetStrategy:
    """Add a strategy to the registry (a new round protocol is one
    ``register_strategy`` call). Re-registering an equal instance is a
    no-op; shadowing a name with a different strategy raises -- jitted
    entry points cache compiled traces keyed on the name, so a silent
    swap would serve stale round protocols."""
    existing = _REGISTRY.get(strat.name)
    if existing is not None and existing != strat:
        raise ValueError(
            f"a different strategy is already registered as "
            f"{strat.name!r}; give this instance a unique name")
    _REGISTRY[strat.name] = strat
    return strat


def available_strategies() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


ALGORITHM1 = register_strategy(CoresetStrategy(name="algorithm1"))

COHEN_ADDAD = register_strategy(CoresetStrategy(
    name="cohen_addad",
    local_summary_fn=_cohen_addad_local_summary,
    site_sensitivities_fn=_refined_site_sensitivities))

MAPREDUCE = register_strategy(CoresetStrategy(
    name="mapreduce",
    exchange_spec_fn=_no_exchange,
    allocate_fn=_uniform_allocate,
    local_contribution_fn=_mapreduce_local_contribution,
    sample_t_total_fn=_own_t_total))


def resolve_name(strategy: StrategyLike) -> str:
    """Resolve a selection (canonical name, :class:`CoresetStrategy`
    instance, or ``None`` for the Algorithm-1 default) to a registry
    name, raising ``ValueError`` on unknown strings -- the single
    boundary where the string API meets the descriptor layer, exactly
    like ``objective.resolve_name``."""
    if strategy is None:
        return ALGORITHM1.name
    if isinstance(strategy, CoresetStrategy):
        return register_strategy(strategy).name
    if not isinstance(strategy, str):
        raise TypeError(f"strategy must be a name or CoresetStrategy, got "
                        f"{type(strategy).__name__}")
    if strategy in _REGISTRY:
        return strategy
    raise ValueError(
        f"unknown strategy {strategy!r}; known strategies: "
        f"{', '.join(available_strategies())}")


def get_strategy(strategy: StrategyLike = None) -> CoresetStrategy:
    """Resolve a selection to the descriptor instance. Pure registry
    lookup for already-canonical names -- safe at trace time inside
    jitted functions."""
    if isinstance(strategy, CoresetStrategy):
        register_strategy(strategy)
        return strategy
    return _REGISTRY[resolve_name(strategy)]
