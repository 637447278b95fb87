"""Algorithm 2 -- distributed clustering, end to end.

Three execution paths over the same math:

* :func:`graph_distributed_kmeans` -- Algorithm 2 over an arbitrary
  ``Graph``. ``engine="sim"`` is the host-level oracle with an *analytic*
  :class:`CommLedger` (Theorem 2 accounting); ``engine="exec"`` routes the
  identical math through the topology execution engine
  (:mod:`repro.core.message_passing`): the Round-1 scalars and Round-2
  portions physically move through jitted flood rounds, every node ends
  holding the bit-identical global coreset, and the returned ledger is
  *measured* from the executed schedule (it equals the analytic one
  exactly -- tests assert this).
* :func:`distributed_kmeans_tree` -- same over a rooted spanning tree
  (Theorem 3 accounting: everything moves O(h) edges, no flooding), with
  the same ``engine="sim"|"exec"`` choice (gather/scatter/broadcast tree
  schedules).
* :func:`spmd_distributed_kmeans` -- the production SPMD path: sites are
  devices along a mesh axis; ``collectives="all_gather"`` shares Round 1's
  scalars and Round 2's portions via ``lax.all_gather``, while
  ``collectives="neighbor_rounds"`` swaps both gathers for the explicit
  ring ``ppermute`` primitives of Algorithm 3
  (:func:`~repro.core.message_passing.neighbor_rounds_gather`) --
  bit-identical results, neighbour-only traffic. Runs under ``shard_map``
  on real meshes (and under the 512-device dry run).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import backend as backend_mod
from repro.core import clustering
from repro.core import objective as objective_mod
from repro.core import strategy as strategy_mod
from repro.core.backend import BackendLike
from repro.core.objective import ObjectiveLike
from repro.core.strategy import StrategyLike
from repro.core.comm import (CommLedger, flood_cost, flood_portions_cost,
                             tree_allocation_cost, tree_broadcast_cost,
                             tree_up_cost)
from repro.core.coreset import (Coreset, DistributedCoreset,
                                distributed_coreset, gathered_live_rows,
                                proportional_allocation,
                                round1_local_solves, round2_local_samples,
                                sensitivities, _sample_and_weight)
from repro.core.message_passing import (ExecResult, GossipSchedule,
                                        TreeSchedule, flood_exec,
                                        gossip_schedule,
                                        neighbor_rounds_gather, pack_payload,
                                        torus_mesh_shape, torus_rounds_gather,
                                        tree_broadcast_exec, tree_gather_exec,
                                        tree_scatter_exec, unpack_payload)
from repro.core.topology import Graph, SpanningTree, spanning_tree

from repro.compat import shard_map as _shard_map

Array = jax.Array


@dataclasses.dataclass
class ExecDetail:
    """Per-node state after the executed communication rounds -- the
    verification surface for engine-vs-simulation parity tests.

    Graph engine: ``node_points``/``node_weights`` are every node's
    assembled global coreset (n, n*S, d) / (n, n*S) and ``node_alloc`` the
    (n, n) allocation vector each node computed from its received scalars
    (all rows bit-identical). Tree engine: ``node_centers`` (n, k, d) holds
    the solution every node received from the root's broadcast and
    ``node_alloc`` the (n,) per-node allocations delivered by the scatter.
    ``node_totals`` is the global cost total as known at each node."""

    node_points: Optional[Array] = None
    node_weights: Optional[Array] = None
    node_centers: Optional[Array] = None
    node_alloc: Optional[Array] = None
    node_totals: Optional[Array] = None
    rounds: Dict[str, ExecResult] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class ClusteringResult:
    centers: Array
    coreset: Coreset
    ledger: CommLedger
    local_costs: Array
    exec_detail: Optional[ExecDetail] = None


def _solve_on_coreset(key: Array, cs: Coreset, k: int, objective: str,
                      lloyd_iters: int, backend: BackendLike = None,
                      live: Optional[int] = None) -> Array:
    """Seed and update k centers on the gathered coreset; the host span
    ``final_solve`` carries the rows the solve sweeps.

    ``live`` bounds the rows that can carry weight (the buffer's producer
    knows it: ``coreset.gathered_live_rows``); the solve then runs on the
    first ``live`` rows of ``cs.compact(live)``, the nonzero rows in their
    order. That is the same weighted instance: weight-0 rows carry no
    seeding mass and add nothing to any update's statistics (DESIGN.md
    Sec. 7). ``None`` solves on every row."""
    if live is not None:
        cs = cs.compact(live)
    with jax.profiler.TraceAnnotation("final_solve",
                                      rows=cs.points.shape[0], k=k):
        centers = clustering.kmeans_pp_init(
            key, cs.points, k, weights=jnp.maximum(cs.weights, 0.0),
            objective=objective, backend=backend)
        centers, _ = clustering.lloyd(cs.points, centers, weights=cs.weights,
                                      iters=lloyd_iters, objective=objective,
                                      backend=backend)
    return centers


def graph_distributed_kmeans(
    key: Array,
    site_points: Array,
    site_mask: Array,
    k: int,
    t: int,
    graph: Graph,
    objective: ObjectiveLike = "kmeans",
    lloyd_iters: int = 8,
    backend: BackendLike = None,
    engine: str = "sim",
    routing: str = "flood",
    root: int = 0,
    faults=None,
    wan_mode: Optional[str] = None,
    wan_seed: int = 0,
    wan_p: float = 0.5,
    strategy: StrategyLike = None,
) -> ClusteringResult:
    """Algorithm 2 on a general graph. With the default ``routing="flood"``
    Round 1 floods n scalars (2mn messages) and Round 2 floods the n local
    portions (2m * sum_i |D_i| points); every node then solves the
    identical weighted instance. ``routing="bfs"`` / ``"min_cost"``
    restrict communication to a spanning tree of the graph (hop-minimal
    BFS vs Prim over ``edge_costs``) rooted at ``root`` and run the
    Theorem-3 tree protocol instead -- same math, same centers, but the
    ledger prices only tree edges; on heterogeneous links min-cost routing
    is what makes the cost-weighted ledger (``link_cost``) small.

    ``engine="sim"`` computes the rounds globally and prices them with the
    analytic Theorem-2 ledger (the oracle). ``engine="exec"`` executes them
    on a compiled :class:`GossipSchedule` -- same local stages, same keys,
    so the result is bit-identical, but the scalars and portions physically
    move edge by edge and the ledger is measured from the schedule.

    ``engine="async"`` routes both rounds through the WAN runtime
    (:mod:`repro.wan.runtime`): asynchronous activation (``wan_mode``:
    ``"clock"`` default, or ``"random"``/``"full"``; ``wan_seed`` /
    ``wan_p`` parameterize it) and an optional ``faults=``
    :class:`~repro.wan.faults.FaultPlan`. Passing ``faults`` with
    ``engine="exec"`` runs the synchronous schedule under the fault plan
    (WAN mode ``"full"``). Either way the allocation and coreset are
    restricted to surviving sites and the returned centers are
    bit-identical to the sim oracle restricted to the survivors
    (:func:`repro.wan.runtime.restricted_sim_coreset`); the measured
    ledger carries the ``staleness`` axis. Flood routing only."""
    objective = objective_mod.resolve_name(objective)
    strategy = strategy_mod.resolve_name(strategy)
    strat = strategy_mod.get_strategy(strategy)
    if faults is not None or engine == "async":
        if routing != "flood":
            raise ValueError(f"faulty/async runs support routing='flood' "
                             f"only, got {routing!r}")
        if engine not in ("exec", "async"):
            raise ValueError(f"faults require engine='exec'|'async', got "
                             f"{engine!r} (the fault-free sim oracle is "
                             f"repro.wan.runtime.restricted_sim_coreset)")
        mode = wan_mode if wan_mode is not None else (
            "full" if engine == "exec" else "clock")
        return _graph_async(key, site_points, site_mask, k, t, graph,
                            objective, lloyd_iters, backend, mode=mode,
                            faults=faults, seed=wan_seed, p=wan_p,
                            strategy=strategy)
    if not strat.needs_exchange and routing == "flood":
        # single-shuffle strategies never flood: with no scalar round to
        # disseminate, the portions move map->shuffle->reduce along a
        # hop-minimal spanning tree (Theorem-3 pricing on tree edges only)
        routing = "bfs"
    if routing in ("bfs", "min_cost"):
        tree = spanning_tree(graph, root=root, routing=routing)
        return distributed_kmeans_tree(key, site_points, site_mask, k, t,
                                       tree, objective=objective,
                                       lloyd_iters=lloyd_iters,
                                       backend=backend, engine=engine,
                                       strategy=strategy)
    if routing != "flood":
        raise ValueError(f"unknown routing {routing!r}: expected "
                         f"'flood'|'bfs'|'min_cost'")
    if engine == "exec":
        return _graph_exec(key, site_points, site_mask, k, t, graph,
                           objective, lloyd_iters, backend, strategy)
    if engine != "sim":
        raise ValueError(f"unknown engine {engine!r}: expected 'sim'|'exec'")
    n_sites, _, d = site_points.shape
    backend = backend_mod.resolve_name(backend)
    k1, k2 = jax.random.split(key)
    dc = distributed_coreset(k1, site_points, site_mask, k, t,
                             objective=objective, lloyd_iters=lloyd_iters,
                             backend=backend, strategy=strategy)
    cs = dc.flatten()
    centers = _solve_on_coreset(k2, cs, k, objective, lloyd_iters, backend,
                                live=gathered_live_rows(cs.size, t, k))

    spec = strat.exchange_spec()
    ledger = flood_cost(graph, n_messages=graph.n,
                        unit_scalars=spec.unit_scalars).tag("round1")
    ledger = ledger.add(flood_portions_cost(graph, np.asarray(dc.t_i), k,
                                            d).tag("round2"))
    return ClusteringResult(centers, cs, ledger, dc.local_costs)


# the original name stays as an alias (the sim path was the only mode once)
distributed_kmeans = graph_distributed_kmeans


def exec_algorithm1_rounds(
    sched: GossipSchedule,
    key: Array,
    site_points: Array,
    w_site: Array,
    k: int,
    t: int,
    t_buffer: int,
    objective: str,
    lloyd_iters: int,
    clip_negative: bool,
    backend: str,
    strategy: StrategyLike = None,
) -> Tuple[ExecDetail, Array]:
    """A strategy's two rounds with the communication *executed* on a
    gossip schedule. Same descriptor hooks and key derivation as
    ``distributed_coreset``, so every node's assembled coreset is
    bit-identical to the host path's; the ``ExecDetail`` ledgers are
    measured per transmission. Shared by :func:`graph_distributed_kmeans`
    and the streaming aggregation rounds. Exchange strategies only: a
    single-shuffle strategy has no scalar round to flood, so it routes to
    the tree protocol instead (:func:`graph_distributed_kmeans` reroutes).
    Returns (detail, local_costs)."""
    strat = strategy_mod.get_strategy(strategy)
    if not strat.needs_exchange:
        raise ValueError(
            f"strategy {strat.name!r} has no exchange round; the gossip "
            f"flood engine only runs exchange strategies (single-shuffle "
            f"strategies run the tree protocol)")
    n_sites, _, d = site_points.shape
    keys = strat.keys(key, n_sites)

    r1 = strat.summary(keys[:, 0], site_points, w_site, k=k,
                       objective=objective, lloyd_iters=lloyd_iters,
                       backend=backend)
    local_costs = r1.local_costs

    # -- Round 1 executed: flood the n exchange scalars ----------------------
    spec = strat.exchange_spec()
    cost_tables, r1x = flood_exec(sched, local_costs[:, None],
                                  unit_scalars=spec.unit_scalars)
    costs_at = cost_tables[:, :, 0]                        # (node, origin)
    node_alloc = jax.vmap(lambda c: strat.allocate(c, t))(costs_at)
    t_i = jnp.diagonal(node_alloc)            # node v uses its own share
    node_totals = jax.vmap(jnp.sum)(costs_at)

    portions = strat.contribute(
        keys[:, 1], site_points, r1, t_i, node_totals, k=k, t=t,
        t_buffer=t_buffer, clip_negative=clip_negative)

    # -- Round 2 executed: flood the fixed-size local portions ---------------
    payload = pack_payload(portions.points, portions.weights)
    unit_pts = (np.asarray(t_i) + k).astype(np.float64)
    port_tables, r2 = flood_exec(sched, payload, unit_points=unit_pts,
                                 dim=d)
    slots = payload.shape[1]
    node_pts, node_w = unpack_payload(port_tables)
    detail = ExecDetail(
        node_points=node_pts.reshape(n_sites, n_sites * slots, d),
        node_weights=node_w.reshape(n_sites, n_sites * slots),
        node_alloc=node_alloc, node_totals=node_totals,
        rounds={"round1": r1x, "round2": r2})
    return detail, local_costs


def _graph_exec(key, site_points, site_mask, k, t, graph, objective,
                lloyd_iters, backend,
                strategy: StrategyLike = None) -> ClusteringResult:
    """Execute Algorithm 2's communication on a compiled gossip schedule.

    Identical math to the sim path stage for stage (same key derivation,
    same jitted stage functions), but the n Round-1 scalars and the n
    Round-2 portions move through executed flood rounds: every node ends
    holding bit-identical copies of all n cost scalars (from which it
    replays the exact largest-remainder allocation locally) and of the
    global coreset. The returned ledger is measured per transmission."""
    n_sites, _, d = site_points.shape
    if graph.n != n_sites:
        raise ValueError(f"graph has {graph.n} nodes for {n_sites} sites")
    backend = backend_mod.resolve_name(backend)
    sched = gossip_schedule(graph)
    k1, k2 = jax.random.split(key)
    detail, local_costs = exec_algorithm1_rounds(
        sched, k1, site_points, site_mask.astype(site_points.dtype), k, t,
        t_buffer=t, objective=objective, lloyd_iters=lloyd_iters,
        clip_negative=False, backend=backend, strategy=strategy)

    # every node holds the identical instance; solve it once (node 0's copy)
    cs = Coreset(detail.node_points[0], detail.node_weights[0])
    centers = _solve_on_coreset(k2, cs, k, objective, lloyd_iters, backend,
                                live=gathered_live_rows(cs.size, t, k))
    ledger = detail.rounds["round1"].ledger.tag("round1").add(
        detail.rounds["round2"].ledger.tag("round2"))
    return ClusteringResult(centers, cs, ledger, local_costs,
                            exec_detail=detail)


def _graph_async(key, site_points, site_mask, k, t, graph, objective,
                 lloyd_iters, backend, mode, faults, seed, p,
                 strategy: StrategyLike = None) -> ClusteringResult:
    """Execute Algorithm 2's communication on the asynchronous WAN runtime
    (imported lazily -- :mod:`repro.wan` layers on this module).

    Every *surviving* node assembles the bit-identical survivor-restricted
    coreset; the solve uses the first survivor's copy with the same final
    key split as every other engine, so on a trivial fault plan the
    centers equal the synchronous paths' bit-for-bit, and under faults
    they equal the restricted sim oracle's. ``exec_detail`` holds the
    :class:`repro.wan.runtime.AsyncDetail` (survivor-indexed)."""
    from repro.wan.runtime import async_algorithm1_rounds

    n_sites, _, d = site_points.shape
    if graph.n != n_sites:
        raise ValueError(f"graph has {graph.n} nodes for {n_sites} sites")
    backend = backend_mod.resolve_name(backend)
    k1, k2 = jax.random.split(key)
    detail, local_costs = async_algorithm1_rounds(
        graph, k1, site_points, site_mask.astype(site_points.dtype), k, t,
        t_buffer=t, objective=objective, lloyd_iters=lloyd_iters,
        clip_negative=False, backend=backend, mode=mode, faults=faults,
        seed=seed, p=p, strategy=strategy)

    cs = Coreset(detail.node_points[0], detail.node_weights[0])
    centers = _solve_on_coreset(k2, cs, k, objective, lloyd_iters, backend,
                                live=gathered_live_rows(cs.size, t, k))
    ledger = detail.rounds["round2"].ledger.tag("round2")
    if "round1" in detail.rounds:   # single-shuffle strategies skip it
        ledger = detail.rounds["round1"].ledger.tag("round1").add(ledger)
    return ClusteringResult(centers, cs, ledger, local_costs,
                            exec_detail=detail)


def distributed_kmeans_tree(
    key: Array,
    site_points: Array,
    site_mask: Array,
    k: int,
    t: int,
    tree: SpanningTree,
    objective: ObjectiveLike = "kmeans",
    lloyd_iters: int = 8,
    backend: BackendLike = None,
    engine: str = "sim",
    strategy: StrategyLike = None,
) -> ClusteringResult:
    """Algorithm 2 restricted to a rooted tree (Theorem 3): the raw cost
    scalars are gathered to the root along parent edges (sum_v depth(v)
    scalars), the root replays the exact largest-remainder allocation and
    scatters each site's share back down its subtree path (sum_v depth(v)
    scalars) plus broadcasts the cost total (n-1 scalars); portions travel
    depth(v) edges to the root, and the solution (k points) is broadcast
    back.

    (The 2(n-1)-scalar up-sum-only accounting previously used here priced a
    protocol that cannot compute the exact allocation: largest-remainder
    needs all n scalars at one place, and a tree-structured partial-sum
    reduction neither delivers them nor reproduces the host's float-exact
    total. The ledger now prices the executable gather/scatter protocol --
    the ``engine="exec"`` path runs it and measures the same numbers.)"""
    objective = objective_mod.resolve_name(objective)
    strategy = strategy_mod.resolve_name(strategy)
    strat = strategy_mod.get_strategy(strategy)
    if engine == "exec":
        return _tree_exec(key, site_points, site_mask, k, t, tree,
                          objective, lloyd_iters, backend, strategy)
    if engine != "sim":
        raise ValueError(f"unknown engine {engine!r}: expected 'sim'|'exec'")
    n_sites, _, d = site_points.shape
    backend = backend_mod.resolve_name(backend)
    k1, k2 = jax.random.split(key)
    dc = distributed_coreset(k1, site_points, site_mask, k, t,
                             objective=objective, lloyd_iters=lloyd_iters,
                             backend=backend, strategy=strategy)
    cs = dc.flatten()
    centers = _solve_on_coreset(k2, cs, k, objective, lloyd_iters, backend,
                                live=gathered_live_rows(cs.size, t, k))

    t_i = [float(x) for x in dc.t_i]
    per_node = [t_i[v] + k for v in range(tree.n)]
    up = tree_up_cost(tree, per_node, dim=d).tag("round2_gather")
    if strat.needs_exchange:
        ledger = tree_allocation_cost(tree).tag("round1").add(up)
    else:
        # single shuffle: no scalar round, no allocation traffic -- the
        # uniform split is derived locally at every site
        ledger = up
    ledger = ledger.add(tree_broadcast_cost(tree, unit_points=float(k),
                                            dim=d).tag("round2_broadcast"))
    return ClusteringResult(centers, cs, ledger, dc.local_costs)


def exec_algorithm1_tree_rounds(
    sched: TreeSchedule,
    key: Array,
    site_points: Array,
    w_site: Array,
    k: int,
    t: int,
    t_buffer: int,
    objective: str,
    lloyd_iters: int,
    clip_negative: bool,
    backend: str,
    strategy: StrategyLike = None,
):
    """A strategy's two rounds with the communication *executed* on a tree
    schedule. For exchange strategies: gather the raw Round-1 scalars to
    the root, replay the strategy's exact allocation there, scatter each
    site's share down its subtree path, broadcast the total; gather the
    fixed-size Round-2 portions to the root. Single-shuffle strategies
    skip the Round-1 gather/scatter/broadcast entirely -- every site
    derives the identical uniform split locally and normalizes by its own
    scalar -- so the only traffic is the portions gather (map -> shuffle
    -> reduce). Same descriptor hooks and key derivation as
    ``distributed_coreset``, so the root's assembled table is
    bit-identical to the host path's coreset. Shared by
    :func:`distributed_kmeans_tree` and the streaming tree-transport
    aggregation rounds. Returns ``(root_points, root_weights, t_i,
    node_totals, rounds, local_costs)`` where ``rounds`` maps phase label
    to the measured :class:`ExecResult`."""
    strat = strategy_mod.get_strategy(strategy)
    n_sites, _, d = site_points.shape
    keys = strat.keys(key, n_sites)

    r1 = strat.summary(keys[:, 0], site_points, w_site, k=k,
                       objective=objective, lloyd_iters=lloyd_iters,
                       backend=backend)
    local_costs = r1.local_costs

    if strat.needs_exchange:
        # -- Round 1 executed: scalars up, allocations + total down ----------
        spec = strat.exchange_spec()
        root_costs, r1a = tree_gather_exec(sched, local_costs[:, None],
                                           unit_scalars=spec.unit_scalars)
        t_root = strat.allocate(root_costs[:, 0], t)
        total = jnp.sum(root_costs[:, 0])
        own_t, r1b = tree_scatter_exec(sched, t_root[:, None],
                                       unit_scalars=1.0)
        node_totals, r1c = tree_broadcast_exec(sched, total[None],
                                               unit_scalars=1.0)
        t_i = own_t[:, 0]
        totals = node_totals[:, 0]
        rounds = {"round1_gather": r1a, "round1_scatter": r1b,
                  "round1_broadcast": r1c}
    else:
        # no Round-1 traffic at all: the split is locally derivable and
        # each site's weight formula uses its own scalar
        t_i = strat.allocate(local_costs, t)
        totals = strat.local_totals(local_costs)
        rounds = {}

    portions = strat.contribute(
        keys[:, 1], site_points, r1, t_i, totals, k=k, t=t,
        t_buffer=t_buffer, clip_negative=clip_negative)

    # -- Round 2 executed: portions up ---------------------------------------
    payload = pack_payload(portions.points, portions.weights)
    unit_pts = (np.asarray(t_i) + k).astype(np.float64)
    root_table, r2a = tree_gather_exec(sched, payload, unit_points=unit_pts,
                                       dim=d)
    root_pts, root_w = unpack_payload(root_table)
    rounds["round2_gather"] = r2a
    return (root_pts, root_w, t_i, totals, rounds, local_costs)


def _tree_exec(key, site_points, site_mask, k, t, tree, objective,
               lloyd_iters, backend,
               strategy: StrategyLike = None) -> ClusteringResult:
    """Execute Algorithm 2's communication on a compiled tree schedule:
    the Round-1/Round-2 tree protocol of
    :func:`exec_algorithm1_tree_rounds`, then solve at the root and
    broadcast the k centers. Bit-identical to the sim path; measured
    ledger."""
    n_sites, _, d = site_points.shape
    if tree.n != n_sites:
        raise ValueError(f"tree has {tree.n} nodes for {n_sites} sites")
    backend = backend_mod.resolve_name(backend)
    sched = TreeSchedule.from_tree(tree)
    k1, k2 = jax.random.split(key)
    w_site = site_mask.astype(site_points.dtype)

    root_pts, root_w, t_i, node_totals, rounds, local_costs = \
        exec_algorithm1_tree_rounds(
            sched, k1, site_points, w_site, k, t, t_buffer=t,
            objective=objective, lloyd_iters=lloyd_iters,
            clip_negative=False, backend=backend, strategy=strategy)

    cs = Coreset(root_pts.reshape(-1, d), root_w.reshape(-1))
    centers = _solve_on_coreset(k2, cs, k, objective, lloyd_iters, backend,
                                live=gathered_live_rows(cs.size, t, k))
    node_centers, r2b = tree_broadcast_exec(sched, centers,
                                            unit_points=float(k), dim=d)
    rounds = dict(rounds, round2_broadcast=r2b)

    if "round1_gather" in rounds:
        ledger = (rounds["round1_gather"].ledger
                  .add(rounds["round1_scatter"].ledger)
                  .add(rounds["round1_broadcast"].ledger).tag("round1")
                  .add(rounds["round2_gather"].ledger.tag("round2_gather")))
    else:   # single-shuffle strategies have no Round-1 phases
        ledger = rounds["round2_gather"].ledger.tag("round2_gather")
    ledger = ledger.add(r2b.ledger.tag("round2_broadcast"))
    detail = ExecDetail(node_centers=node_centers, node_alloc=t_i,
                        node_totals=node_totals, rounds=rounds)
    return ClusteringResult(centers, cs, ledger, local_costs,
                            exec_detail=detail)


# ---------------------------------------------------------------------------
# SPMD / mesh path (production)
# ---------------------------------------------------------------------------

def spmd_distributed_kmeans_fn(
    axis_name: str,
    axis_size: int,
    k: int,
    t: int,
    t_buffer: int,
    objective: ObjectiveLike = "kmeans",
    lloyd_iters: int = 8,
    final_lloyd_iters: int = 10,
    backend: BackendLike = None,
    collectives: str = "all_gather",
    strategy: StrategyLike = None,
    mesh_shape: Optional[Tuple[int, int]] = None,
):
    """Build the per-device function for Algorithm 1+2 under ``shard_map``.

    Each device holds one site's (M, d) shard + mask (the mesh wrapper
    reshape-merges multiple site blocks per device, so ``axis_size`` devices
    participate as ``axis_size`` sites). Cross-device traffic is exactly:
    one gather of the ``axis_size`` Round-1 cost scalars + one gather of the
    fixed-size local portion (Round 2) -- the paper's communication pattern
    mapped onto mesh collectives. ``collectives`` picks the lowering:
    ``"all_gather"`` uses ``lax.all_gather`` (XLA lowers it to neighbour
    rounds on the ICI torus itself); ``"neighbor_rounds"`` uses the explicit
    ring ``ppermute`` schedule of Algorithm 3
    (:func:`~repro.core.message_passing.neighbor_rounds_gather`) -- the
    gathered buffers are pure relays, so results are bit-identical;
    ``"torus_2d"`` folds the flat axis onto an (R, C) torus
    (:func:`~repro.core.message_passing.torus_rounds_gather`, row phase
    then column phase, (R-1)+(C-1) hops instead of R*C-1) -- also a pure
    relay in flat row-major order, so still bit-identical. ``mesh_shape``
    picks (R, C); the default is the most-square factorization of
    ``axis_size`` (:func:`~repro.core.message_passing.torus_mesh_shape`).
    (The cost *total* is always reduced from the gathered vector, never
    via ``neighbor_rounds_sum``/``torus_rounds_sum``: a ring-order
    accumulation starts at a different shard on every device, which breaks
    both cross-device and gather-path bit-equality of the float total.)

    The two communication points are wrapped in ``jax.named_scope("round1")``
    / ``("round2")`` so compiled-HLO collectives carry phase-attributable
    ``op_name`` metadata (consumed by ``roofline/hlo.py``'s per-phase
    collective ledger).

    Gathering the scalars (rather than psum-ing them) lets every device run
    the *exact* largest-remainder ``proportional_allocation`` the host path
    uses, so ``sum_i t_i == t`` holds on this path too (a rounded per-site
    share can collectively over/under-draw; DESIGN.md Sec. 7's allocation
    invariant). The ``backend`` hot-loop selection composes with
    ``shard_map``: the Pallas kernels run per-device on that device's shard.
    """
    backend = backend_mod.resolve_name(backend)
    objective = objective_mod.resolve_name(objective)
    strat = strategy_mod.get_strategy(strategy_mod.resolve_name(strategy))
    if collectives not in ("all_gather", "neighbor_rounds", "torus_2d"):
        raise ValueError(f"unknown collectives {collectives!r}: expected "
                         f"'all_gather'|'neighbor_rounds'|'torus_2d'")
    if collectives == "torus_2d":
        mesh_shape = (torus_mesh_shape(axis_size) if mesh_shape is None
                      else tuple(mesh_shape))
        if mesh_shape[0] * mesh_shape[1] != axis_size:
            raise ValueError(f"mesh_shape {mesh_shape} does not tile "
                             f"axis_size {axis_size}")
    elif mesh_shape is not None:
        raise ValueError("mesh_shape is only meaningful with "
                         "collectives='torus_2d'")

    def gather(x: Array) -> Array:
        if collectives == "all_gather":
            out = jax.lax.all_gather(x, axis_name)
        elif collectives == "torus_2d":
            out = torus_rounds_gather(x, axis_name, mesh_shape)
        else:
            out = neighbor_rounds_gather(x, axis_name, axis_size)
        # every mode relays bit-identical values, but without a barrier XLA
        # may fuse the *consumer* differently per producer graph (observed:
        # the torus reshape shifted weiszfeld fusion by ~1e-6 at 16
        # devices) -- the barrier pins the consumer graph so cross-mode
        # bit-parity is structural, not luck
        return jax.lax.optimization_barrier(out)

    def per_device(key: Array, pts: Array, mask: Array):
        w = mask.astype(pts.dtype)
        site = jax.lax.axis_index(axis_name)
        ki = jax.random.fold_in(key, site)
        k_solve, k_sample = jax.random.split(ki)

        # Round 1: local solve + single-scalar communication
        centers = clustering.kmeans_pp_init(k_solve, pts, k, weights=w,
                                            objective=objective,
                                            backend=backend)
        centers, _ = clustering.lloyd(pts, centers, weights=w,
                                      iters=lloyd_iters, objective=objective,
                                      backend=backend)
        m, assign, w_eff = strat.site_sensitivities(
            pts, centers, w, objective=objective, backend=backend)
        local_cost = jnp.sum(m)
        if strat.needs_exchange:
            with jax.named_scope("round1"):
                all_costs = gather(local_cost)                 # <- Round 1
            total_cost = jnp.sum(all_costs)

            # exact largest-remainder allocation over the gathered scalars
            # -- identical math to the host path, replicated per device.
            # t_local is NOT clamped to t_buffer here, also matching the
            # host: _sample_and_weight truncates the realized draws at its
            # t_buffer slots, and the weight formula keeps using the full
            # allocation.
            t_all = strat.allocate(all_costs, t)
            t_local = t_all[site]
            t_total = jnp.sum(t_all).astype(pts.dtype)   # == t exactly
        else:
            # single shuffle: the uniform split is derivable on-device and
            # the standalone weight formula uses the local scalar + share
            t_all = strat.allocate(jnp.ones((axis_size,), pts.dtype), t)
            t_local = t_all[site]
            total_cost = local_cost
            t_total = t_local.astype(pts.dtype)

        sampled, w_s, w_b = _sample_and_weight(
            k_sample, pts, m, w_eff, assign, k, t_local, t_buffer,
            total_cost, t_total)
        portion_pts = jnp.concatenate([sampled, centers], axis=0)
        portion_w = jnp.concatenate([w_s, w_b], axis=0)

        # Round 2: share the fixed-size portions
        with jax.named_scope("round2"):
            all_pts = gather(portion_pts)                       # <- Round 2
            all_w = gather(portion_w)
        cs_pts = all_pts.reshape(-1, pts.shape[-1])
        cs_w = all_w.reshape(-1)

        # every device solves the identical weighted instance (replicated)
        k_final = jax.random.fold_in(key, 0)
        fc = clustering.kmeans_pp_init(k_final, cs_pts, k,
                                       weights=jnp.maximum(cs_w, 0.0),
                                       objective=objective, backend=backend)
        fc, _ = clustering.lloyd(cs_pts, fc, weights=cs_w,
                                 iters=final_lloyd_iters, objective=objective,
                                 backend=backend)
        return fc, local_cost[None], t_local[None]

    return per_device


def spmd_distributed_kmeans(
    mesh: Mesh,
    axis_name: str,
    key: Array,
    site_points: Array,   # (n_sites, M, d) -- sharded over axis_name
    site_mask: Array,
    k: int,
    t: int,
    t_buffer: Optional[int] = None,
    objective: ObjectiveLike = "kmeans",
    lloyd_iters: int = 8,
    backend: BackendLike = None,
    collectives: str = "all_gather",
    strategy: StrategyLike = None,
    mesh_shape: Optional[Tuple[int, int]] = None,
) -> Tuple[Array, Array, Array]:
    """Run the SPMD path on a mesh. Returns (centers (k,d), local_costs,
    t_i) -- ``t_i`` are the per-site sample allocations, which satisfy
    ``sum(t_i) == t`` exactly (largest-remainder allocation, identical to
    the host path's, including its behavior when an allocation exceeds
    ``t_buffer``: realized draws are truncated at the buffer while the
    weight formula keeps the full allocation).

    The default ``t_buffer`` is sized off ``axis_size``, not ``n_sites``:
    ``device_fn`` reshape-merges each device's site blocks into one site,
    so only ``axis_size`` sites participate in the allocation and each
    draws ``t_i ~ t / axis_size``. (Sizing off ``n_sites`` silently
    truncated draws whenever ``n_sites > axis_size``.)"""
    n_sites = site_points.shape[0]
    axis_size = mesh.shape[axis_name]
    if n_sites % axis_size:
        raise ValueError(f"n_sites={n_sites} must divide over {axis_name}="
                         f"{axis_size}")
    t_buffer = t_buffer if t_buffer is not None else max(
        4 * t // max(axis_size, 1), 64)
    fn = spmd_distributed_kmeans_fn(axis_name, axis_size, k, t, t_buffer,
                                    objective, lloyd_iters, backend=backend,
                                    collectives=collectives,
                                    strategy=strategy, mesh_shape=mesh_shape)

    def device_fn(key, pts, mask):
        # collapse the per-device leading site-block dim (sites/device >= 1)
        pts = pts.reshape(-1, pts.shape[-1])
        mask = mask.reshape(-1)
        return fn(key, pts, mask)

    shard = _shard_map(
        device_fn, mesh=mesh,
        in_specs=(P(), P(axis_name), P(axis_name)),
        out_specs=(P(), P(axis_name), P(axis_name)),
    )
    # the per-site scalars come back replicated: on a mesh with Explicit
    # axes (``jax.make_mesh``'s default) an axis-sharded result cannot be
    # indexed by host code
    replicated = NamedSharding(mesh, P())
    return jax.jit(shard, out_shardings=replicated)(key, site_points,
                                                    site_mask)
