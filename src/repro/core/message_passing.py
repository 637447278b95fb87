"""Algorithm 3 -- Message-Passing on a general communication graph.

Three implementations:

1. :func:`flood` -- a faithful host-level simulation over an arbitrary
   connected ``Graph``: each node initially knows one message and forwards
   every newly seen message to all neighbours exactly once. Used to *verify*
   the O(mn) bound and to drive the paper's experiments with exact per-edge
   message ledgers.

2. **The topology execution engine** (DESIGN.md Sec. 11):
   :class:`GossipSchedule` / :class:`TreeSchedule` compile a ``Graph`` /
   ``SpanningTree`` into static per-round schedules (padded neighbor-index
   arrays, per-level segment maps), and :func:`flood_exec`,
   :func:`tree_gather_exec`, :func:`tree_scatter_exec`,
   :func:`tree_up_sum_exec`, :func:`tree_broadcast_exec` *execute* the
   message-passing rounds as jitted vmapped gather + segment-scatter steps
   over per-node state. Payloads physically move edge by edge (every copy a
   node ends up holding is a bit-identical relay of the origin's payload),
   and each primitive returns a *measured* :class:`~repro.core.comm
   .CommLedger` counted from the schedule execution -- by construction it
   must equal the corresponding analytic ``flood_cost`` /
   ``tree_up_cost``-style ledger, and tests assert exactly that. The
   schedules carry the graph's per-link costs, so every measured ledger
   also prices each transmission by the edge it crossed
   (``CommLedger.link_cost``; DESIGN.md Sec. 12).

3. :func:`neighbor_rounds_sum` / :func:`neighbor_rounds_gather` -- the
   TPU-native counterpart: on a physical torus/mesh, the same information
   pattern is a sequence of ``jax.lax.ppermute`` neighbour exchanges; after
   ``diameter`` rounds every device holds the global reduction. These back
   the ``collectives="neighbor_rounds"`` mode of
   ``spmd_distributed_kmeans`` (and demonstrate the mapping XLA applies
   when lowering ``psum``/``all_gather`` to the ICI torus).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.comm import CommLedger, link_cost_of
from repro.core.topology import Graph, SpanningTree, diameter, spanning_tree


@dataclasses.dataclass
class FloodResult:
    received: List[set]          # per node: set of message ids known
    rounds: int                  # synchronous rounds until quiescence
    transmissions: int           # total edge-messages sent
    per_round_transmissions: List[int]


def flood(g: Graph, payload_ids: Sequence[int] | None = None) -> FloodResult:
    """Synchronous simulation of Algorithm 3.

    Every node starts with its own message id; in each round, each node sends
    every message it learned in the previous round to all neighbours. A node
    never forwards the same message twice. Terminates when no new message is
    delivered anywhere (<= diameter rounds).
    """
    ids = list(payload_ids) if payload_ids is not None else list(range(g.n))
    adj = g.adjacency()
    known: List[set] = [{ids[v]} for v in range(g.n)]
    fresh: List[set] = [{ids[v]} for v in range(g.n)]
    transmissions = 0
    per_round: List[int] = []
    rounds = 0
    while any(fresh):
        sent_this_round = 0
        incoming: List[set] = [set() for _ in range(g.n)]
        for v in range(g.n):
            for msg in fresh[v]:
                for u in adj[v]:
                    incoming[u].add(msg)
                    sent_this_round += 1
        fresh = [incoming[v] - known[v] for v in range(g.n)]
        for v in range(g.n):
            known[v] |= fresh[v]
        transmissions += sent_this_round
        per_round.append(sent_this_round)
        rounds += 1
    return FloodResult(known, rounds, transmissions, per_round)


def flood_scalars(g: Graph, values: Sequence[float]) -> Tuple[List[Dict[int, float]], FloodResult]:
    """Flood real scalar payloads (the per-site costs of Algorithm 1 Round 1).

    Returns per-node {origin: value} tables plus the flood statistics.
    """
    if len(values) != g.n:
        raise ValueError(f"flood_scalars needs one value per node: got "
                         f"{len(values)} values for a {g.n}-node graph")
    res = flood(g)
    tables = [{origin: float(values[origin]) for origin in res.received[v]}
              for v in range(g.n)]
    return tables, res


# ---------------------------------------------------------------------------
# Topology execution engine: compiled schedules + jitted message rounds
# ---------------------------------------------------------------------------

Units = Union[float, Sequence[float], np.ndarray, jax.Array]


@dataclasses.dataclass
class ExecResult:
    """Outcome of one executed communication primitive.

    ``rounds`` is the static schedule length that ran; for floods,
    ``rounds_to_complete`` is the first round after which every node knew
    every payload (<= diameter on a connected graph -- the schedule runs one
    extra round so the final fresh messages are forwarded, which is what
    makes the measured transmission count equal the analytic 2mn).
    ``ledger`` is *measured*: every scalar/point/message was counted from an
    actual executed transmission, never from a formula."""

    rounds: int
    rounds_to_complete: int
    ledger: CommLedger
    per_round_transmissions: List[int]


def pack_payload(points: jax.Array, weights: jax.Array) -> jax.Array:
    """Pack weighted points into an engine payload: ``(..., S, d)`` points +
    ``(..., S)`` weights -> ``(..., S, d+1)`` with the weight as the
    trailing column. Every exec path that ships coreset portions uses this
    layout; :func:`unpack_payload` is its inverse, so the two stay in sync
    by construction."""
    return jnp.concatenate([points, weights[..., None]], axis=-1)


def unpack_payload(table: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Inverse of :func:`pack_payload`: ``(..., S, d+1)`` ->
    ``((..., S, d), (..., S))``."""
    return table[..., :-1], table[..., -1]


def _units_ledger(per_origin_msgs: np.ndarray, unit_scalars: Units,
                  unit_points: Units, dim: int,
                  count_all_messages: bool,
                  per_origin_link: np.ndarray | None = None) -> CommLedger:
    """Price measured per-origin transmission counts. ``count_all_messages``
    distinguishes flooding (a message id is forwarded whether or not it
    carries metered payload; analytic ``flood_cost`` counts all 2mn) from
    tree routing (only payload-carrying origins move; analytic
    ``tree_up_cost`` counts only unit>0 nodes). ``per_origin_link`` is the
    measured per-origin *edge-cost* total (the sum of link costs each
    origin's payload crossed); defaults to the hop counts, i.e. uniform
    unit links."""
    per = np.asarray(per_origin_msgs, np.float64)
    us = np.broadcast_to(np.asarray(unit_scalars, np.float64), per.shape)
    up = np.broadcast_to(np.asarray(unit_points, np.float64), per.shape)
    if count_all_messages or not (us + np.abs(up)).any():
        msgs = float(per.sum())
    else:
        msgs = float(per[(us + np.abs(up)) > 0].sum())
    link = per if per_origin_link is None else per_origin_link
    return CommLedger(scalars=float((per * us).sum()),
                      points=float((per * up).sum()),
                      messages=msgs, dim=dim,
                      link_cost=link_cost_of(link, us, up, dim))


@dataclasses.dataclass(frozen=True, eq=False)
class GossipSchedule:
    """Static flood schedule for a connected :class:`Graph`: padded
    neighbor-index arrays (from ``adjacency()``) plus the round count to
    quiescence. Compile once per graph, execute many times. Carries the
    graph's per-link costs (``neighbor_costs`` aligned with ``neighbors``,
    plus the per-node ``weighted_degrees``) so executed floods can be
    priced per edge crossed."""

    n: int
    m: int
    n_rounds: int               # diameter + 1: last fresh set still forwards
    neighbors: np.ndarray       # (n, max_deg) int32 out-neighbors, 0-padded
    neighbor_mask: np.ndarray   # (n, max_deg) bool
    degrees: np.ndarray         # (n,) int32 out-degrees (send pricing)
    neighbor_costs: np.ndarray  # (n, max_deg) float64, padded with 0
    weighted_degrees: np.ndarray  # (n,) float64 (== Graph.weighted_degrees)
    in_neighbors: np.ndarray    # (n, max_in) int32: the receive gather side
    in_neighbor_mask: np.ndarray  # (n, max_in) bool (== out side undirected)

    @classmethod
    def from_graph(cls, g: Graph) -> "GossipSchedule":
        adj, adjc = g.adjacency(), g.adjacency_costs()
        max_deg = max((len(a) for a in adj), default=0)
        if g.n > 1 and min(len(a) for a in adj) == 0:
            raise ValueError("graph is not connected (isolated node)")
        max_deg = max(max_deg, 1)
        nb = np.zeros((g.n, max_deg), np.int32)
        mask = np.zeros((g.n, max_deg), bool)
        nc = np.zeros((g.n, max_deg), np.float64)
        for v, (a, cs) in enumerate(zip(adj, adjc)):
            nb[v, :len(a)] = a
            mask[v, :len(a)] = True
            nc[v, :len(a)] = cs
        if g.directed:
            # a node *receives* along its in-links; sends meter out-links
            in_adj: list = [[] for _ in range(g.n)]
            for i, j in g.edges:
                in_adj[j].append(i)
            max_in = max(1, max(len(a) for a in in_adj))
            in_nb = np.zeros((g.n, max_in), np.int32)
            in_mask = np.zeros((g.n, max_in), bool)
            for v, a in enumerate(in_adj):
                in_nb[v, :len(a)] = a
                in_mask[v, :len(a)] = True
        else:
            in_nb, in_mask = nb, mask
        return cls(n=g.n, m=g.m, n_rounds=diameter(g) + 1, neighbors=nb,
                   neighbor_mask=mask,
                   degrees=mask.sum(axis=1).astype(np.int32),
                   neighbor_costs=nc,
                   weighted_degrees=np.asarray(g.weighted_degrees()),
                   in_neighbors=in_nb, in_neighbor_mask=in_mask)


@functools.lru_cache(maxsize=128)
def gossip_schedule(g: Graph) -> GossipSchedule:
    """Cached :meth:`GossipSchedule.from_graph`: ``Graph`` is a frozen
    (hashable) dataclass, so identical graphs -- including directed and
    cost-annotated WAN ones -- compile their padded-neighbor tables once
    per process. Streaming aggregation and the WAN runtime call this every
    round; the returned schedule is shared, treat it as read-only."""
    return GossipSchedule.from_graph(g)


@functools.partial(jax.jit, static_argnames=("n_rounds",))
def _flood_exec_rounds(in_neighbors, in_neighbor_mask, out_degrees, payload,
                       n_rounds):
    """Execute ``n_rounds`` synchronous flood rounds over per-node state.

    State: ``known``/``fresh`` (n, n) bool tables (node x origin) and
    ``table`` (n, n, F) payload copies. Each round every node relays the
    payloads it learned last round to all its (out-)neighbours -- the
    receive side is a vmapped gather over *in*-neighbors (identical to the
    out side on undirected graphs; the distinction is what keeps a directed
    flood moving along link directions rather than the transpose graph);
    the payload copy is selected from the first fresh-holding in-neighbour,
    so every copy is a bit-exact relay. ``fwd[v, o]`` counts how often node
    v forwarded origin o's message (exactly once each on a connected graph)
    -- the (node, origin) resolution the cost-weighted ledger prices from,
    with ``out_degrees`` as the per-forward transmission count."""
    n, f = payload.shape
    eye = jnp.eye(n, dtype=bool)
    table = jnp.where(eye[:, :, None], payload[None, :, :],
                      jnp.zeros((), payload.dtype))

    def body(carry, _):
        known, fresh, table, fwd = carry
        # transmissions this round: each fresh holder sends on every out-link
        sends = jnp.sum(fresh.sum(axis=1) * out_degrees)
        fwd = fwd + fresh.astype(jnp.int32)
        f_nb = fresh[in_neighbors] & in_neighbor_mask[:, :, None]
        incoming = jnp.any(f_nb, axis=1)                      # (n, n)
        src = jnp.argmax(f_nb, axis=1)                        # (n, n)
        recv = jnp.take_along_axis(table[in_neighbors],
                                   src[:, None, :, None], axis=1)[:, 0]
        new = incoming & ~known
        table = jnp.where(new[:, :, None], recv, table)
        known = known | new
        return (known, new, table, fwd), (sends, jnp.all(known))

    fwd0 = jnp.zeros((n, n), jnp.int32)
    (known, _, table, fwd), (sends, complete) = jax.lax.scan(
        body, (eye, eye, table, fwd0), None, length=n_rounds)
    return table, known, sends, fwd, complete


def flood_exec(schedule: Union[GossipSchedule, Graph], payload: jax.Array,
               unit_scalars: Units = 0.0, unit_points: Units = 0.0,
               dim: int = 0) -> Tuple[jax.Array, ExecResult]:
    """Execute Algorithm 3 on a compiled gossip schedule.

    ``payload``: (n, ...) origin-indexed array -- node v starts knowing only
    ``payload[v]``. Returns ``(tables, result)`` where ``tables[v, o]`` is
    node v's relayed copy of origin o's payload (on a connected graph every
    node ends holding all n payloads, bit-identical to the originals).

    ``unit_scalars`` / ``unit_points`` price each *transmission* of origin
    o's message (scalar, or (n,) per-origin -- Round 2 portions have
    per-site sizes ``t_i + k``); the returned ledger is measured from the
    executed schedule and equals the analytic
    ``flood_cost(g, n_messages=n, ...)`` exactly.
    """
    if isinstance(schedule, Graph):
        schedule = gossip_schedule(schedule)
    payload = jnp.asarray(payload)
    if payload.shape[0] != schedule.n:
        raise ValueError(f"payload must be origin-indexed: got leading dim "
                         f"{payload.shape[0]} for a {schedule.n}-node graph")
    trailing = payload.shape[1:]
    flat = payload.reshape(schedule.n, -1)
    table, known, sends, fwd, complete = _flood_exec_rounds(
        jnp.asarray(schedule.in_neighbors),
        jnp.asarray(schedule.in_neighbor_mask),
        jnp.asarray(schedule.degrees), flat, n_rounds=schedule.n_rounds)
    if not bool(jnp.all(known)):
        raise RuntimeError("flood did not complete: graph disconnected?")
    flags = np.asarray(complete)
    done = int(np.argmax(flags)) + 1 if flags.any() else schedule.n_rounds
    if schedule.n == 1:
        done = 0
    # price the measured (node, origin) forward counts: hop counts with the
    # node's degree, link costs with its weighted degree (each forward is
    # one transmission per incident link)
    fwd_np = np.asarray(fwd, np.int64)
    deg = np.asarray(schedule.degrees, np.int64)
    per_origin = (fwd_np * deg[:, None]).sum(axis=0)
    wdeg = np.asarray(schedule.weighted_degrees, np.float64)
    per_origin_link = np.asarray(
        [float((fwd_np[:, o].astype(np.float64) * wdeg).sum())
         for o in range(schedule.n)], np.float64)
    ledger = _units_ledger(per_origin, unit_scalars, unit_points,
                           dim, count_all_messages=True,
                           per_origin_link=per_origin_link)
    res = ExecResult(rounds=schedule.n_rounds, rounds_to_complete=done,
                     ledger=ledger,
                     per_round_transmissions=[int(s) for s in
                                              np.asarray(sends)])
    return table.reshape((schedule.n, schedule.n) + trailing), res


@dataclasses.dataclass(frozen=True, eq=False)
class TreeSchedule:
    """Static per-level schedule for a rooted :class:`SpanningTree`:
    ``levels[l]`` are the nodes at depth ``l+1`` (a segment map derived from
    ``bottom_up_order()``), ``subtree`` the per-node descendant masks that
    route scatter payloads. The up passes iterate levels deepest-first (a
    node transmits only after all its children have), the down passes
    shallowest-first."""

    n: int
    root: int
    height: int
    parent: np.ndarray      # (n,) int32; parent[root] == root (self-loop)
    depth: np.ndarray       # (n,) int32
    levels: np.ndarray      # (height, width) int32, padded with root
    level_mask: np.ndarray  # (height, width) bool
    subtree: np.ndarray     # (n, n) bool; subtree[v, o]: o in subtree of v
    parent_cost: np.ndarray  # (n,) float64; cost of v's parent link (0 @root)

    @classmethod
    def from_tree(cls, tree: SpanningTree) -> "TreeSchedule":
        depth = np.asarray(tree.depth, np.int32)
        parent = np.asarray(tree.parent, np.int32).copy()
        parent[tree.root] = tree.root
        height = tree.height
        by_level = [[] for _ in range(height)]
        for v in range(tree.n):
            if depth[v] > 0:
                by_level[depth[v] - 1].append(v)
        width = max((len(l) for l in by_level), default=1)
        width = max(width, 1)
        levels = np.full((height, width), tree.root, np.int32)
        mask = np.zeros((height, width), bool)
        for l, nodes in enumerate(by_level):
            levels[l, :len(nodes)] = nodes
            mask[l, :len(nodes)] = True
        sub = np.eye(tree.n, dtype=bool)
        for v in tree.bottom_up_order():
            if tree.parent[v] >= 0:
                sub[tree.parent[v]] |= sub[v]
        return cls(n=tree.n, root=tree.root, height=height, parent=parent,
                   depth=depth, levels=levels, level_mask=mask, subtree=sub,
                   parent_cost=np.asarray(tree.parent_costs()))

    @classmethod
    def from_graph(cls, g: Graph, root: int = 0,
                   routing: str = "bfs") -> "TreeSchedule":
        """Compile a tree schedule straight from a graph under a routing
        policy (``"bfs"`` hop-minimal | ``"min_cost"`` Prim)."""
        return cls.from_tree(spanning_tree(g, root=root, routing=routing))


@functools.lru_cache(maxsize=128)
def tree_schedule(g: Graph, root: int = 0,
                  routing: str = "bfs") -> TreeSchedule:
    """Cached :meth:`TreeSchedule.from_graph` (same contract as
    :func:`gossip_schedule`: one compile per (graph, root, routing))."""
    return TreeSchedule.from_graph(g, root=root, routing=routing)


def _path_link_costs(schedule: TreeSchedule,
                     hop_counts: np.ndarray) -> np.ndarray:
    """Measured per-origin link-cost totals for a gather/scatter: origin o
    moved ``hop_counts[o]`` edges along its root path; price them with the
    schedule's parent costs, deepest edge first (the same float64 order
    ``SpanningTree.path_costs`` accumulates in, so measured == analytic
    bit-for-bit for fully-routed origins)."""
    pc = np.asarray(schedule.parent_cost, np.float64)
    parent = np.asarray(schedule.parent, np.int64)
    out = np.zeros(schedule.n, np.float64)
    for o in range(schedule.n):
        acc, v = 0.0, o
        for _ in range(int(hop_counts[o])):
            acc += float(pc[v])
            v = int(parent[v])
        out[o] = acc
    return out


def _level_edge_cost_total(schedule: TreeSchedule) -> float:
    """Total scheduled-edge cost, accumulated level-major / ascending node
    id -- the same float64 order ``SpanningTree.edge_cost_total`` uses, so
    executed broadcast / up-sum pricing equals the analytic
    ``tree_broadcast_cost`` bit-for-bit."""
    total = 0.0
    pc = np.asarray(schedule.parent_cost, np.float64)
    for l in range(schedule.height):
        for w in range(schedule.levels.shape[1]):
            if schedule.level_mask[l, w]:
                total += float(pc[schedule.levels[l, w]])
    return total


def _level_scan(schedule: TreeSchedule, body, carry, bottom_up: bool):
    levels = jnp.asarray(schedule.levels)
    mask = jnp.asarray(schedule.level_mask)
    if bottom_up:
        levels, mask = jnp.flip(levels, 0), jnp.flip(mask, 0)
    return jax.lax.scan(body, carry, (levels, mask))


def tree_gather_exec(schedule: TreeSchedule, payload: jax.Array,
                     unit_scalars: Units = 0.0, unit_points: Units = 0.0,
                     dim: int = 0) -> Tuple[jax.Array, ExecResult]:
    """Route every node's payload up to the root (up-concat): origin o's
    copy travels ``depth(o)`` edges. Returns the root's origin-ordered
    table ``(n, ...)`` (bit-identical to ``payload``) and the measured
    ledger (equals ``tree_up_cost(tree, units)``)."""
    payload = jnp.asarray(payload)
    if payload.shape[0] != schedule.n:
        raise ValueError(f"payload must be origin-indexed: got leading dim "
                         f"{payload.shape[0]} for a {schedule.n}-node tree")
    trailing = payload.shape[1:]
    flat = payload.reshape(schedule.n, -1)

    def body(carry, lvl):
        known, table = carry
        nodes, lmask = lvl
        par = jnp.asarray(schedule.parent)[nodes]
        contrib = (known[nodes] > 0) & lmask[:, None]
        hops = contrib.astype(jnp.int32).sum(axis=0)
        tvals = jnp.where(contrib[:, :, None], table[nodes],
                          jnp.zeros((), flat.dtype))
        table = table.at[par].add(tvals)
        known = known.at[par].add(contrib.astype(jnp.int32))
        return (known, table), hops

    eye = jnp.eye(schedule.n, dtype=jnp.int32)
    table0 = jnp.where((eye > 0)[:, :, None], flat[None, :, :],
                       jnp.zeros((), flat.dtype))
    (known, table), hops = _level_scan(schedule, body, (eye, table0),
                                       bottom_up=True)
    per_origin = np.asarray(hops.sum(axis=0) if schedule.height else
                            np.zeros(schedule.n, np.int64))
    ledger = _units_ledger(per_origin, unit_scalars, unit_points, dim,
                           count_all_messages=False,
                           per_origin_link=_path_link_costs(schedule,
                                                            per_origin))
    res = ExecResult(rounds=schedule.height,
                     rounds_to_complete=schedule.height, ledger=ledger,
                     per_round_transmissions=[int(x) for x in
                                              np.asarray(hops.sum(axis=1))]
                     if schedule.height else [])
    return table[schedule.root].reshape((schedule.n,) + trailing), res


def tree_scatter_exec(schedule: TreeSchedule, root_values: jax.Array,
                      unit_scalars: Units = 0.0, unit_points: Units = 0.0,
                      dim: int = 0) -> Tuple[jax.Array, ExecResult]:
    """Route per-origin values from the root back down: entry o travels the
    root->o path (``depth(o)`` edges; at each hop a parent forwards to each
    child exactly the entries for that child's subtree). Returns each node's
    own entry ``(n, ...)`` and the measured ledger (symmetric to
    :func:`tree_gather_exec`)."""
    root_values = jnp.asarray(root_values)
    if root_values.shape[0] != schedule.n:
        raise ValueError(f"root_values must be origin-indexed: got leading "
                         f"dim {root_values.shape[0]} for a {schedule.n}-"
                         f"node tree")
    trailing = root_values.shape[1:]
    flat = root_values.reshape(schedule.n, -1)
    n = schedule.n
    vals0 = jnp.zeros((n, n, flat.shape[1]), flat.dtype).at[
        schedule.root].set(flat)
    sub = jnp.asarray(schedule.subtree)

    def body(carry, lvl):
        vals = carry
        nodes, lmask = lvl
        par = jnp.asarray(schedule.parent)[nodes]
        want = sub[nodes] & lmask[:, None]                     # (W, n)
        hops = want.astype(jnp.int32).sum(axis=0)
        vals = vals.at[nodes].set(
            jnp.where(want[:, :, None], vals[par], vals[nodes]))
        return vals, hops

    vals, hops = _level_scan(schedule, body, vals0, bottom_up=False)
    per_origin = np.asarray(hops.sum(axis=0) if schedule.height else
                            np.zeros(n, np.int64))
    own = vals[jnp.arange(n), jnp.arange(n)]
    ledger = _units_ledger(per_origin, unit_scalars, unit_points, dim,
                           count_all_messages=False,
                           per_origin_link=_path_link_costs(schedule,
                                                            per_origin))
    res = ExecResult(rounds=schedule.height,
                     rounds_to_complete=schedule.height, ledger=ledger,
                     per_round_transmissions=[int(x) for x in
                                              np.asarray(hops.sum(axis=1))]
                     if schedule.height else [])
    return own.reshape((n,) + trailing), res


def tree_up_sum_exec(schedule: TreeSchedule, values: jax.Array,
                     broadcast: bool = True, unit_scalars: Units = 0.0,
                     unit_points: Units = 0.0, dim: int = 0
                     ) -> Tuple[jax.Array, ExecResult]:
    """Up-*sum*: each node sends one aggregated payload to its parent after
    hearing from all children (n-1 fixed-size transmissions); with
    ``broadcast`` the root's total is then sent down every edge (n-1 more),
    so every node ends holding the global sum. ``unit_*`` price one
    transmission (the aggregate has the same size everywhere).

    Note the tree-structured accumulation order differs from a flat
    ``jnp.sum`` in float, so exact-replay protocols (the distributed
    Round-1 allocation) route the raw scalars via gather/scatter instead
    and use this primitive only where a sum is the final answer."""
    values = jnp.asarray(values)
    if values.shape[0] != schedule.n:
        raise ValueError(f"values must be node-indexed: got leading dim "
                         f"{values.shape[0]} for a {schedule.n}-node tree")
    trailing = values.shape[1:]
    flat = values.reshape(schedule.n, -1)

    def up(acc, lvl):
        nodes, lmask = lvl
        par = jnp.asarray(schedule.parent)[nodes]
        contrib = jnp.where(lmask[:, None], acc[nodes],
                            jnp.zeros((), flat.dtype))
        acc = acc.at[par].add(contrib)
        return acc, lmask.sum()

    acc, up_sends = _level_scan(schedule, up, flat, bottom_up=True)
    total = acc[schedule.root]
    sends = int(np.asarray(up_sends).sum()) if schedule.height else 0
    w_sends = _level_edge_cost_total(schedule) if sends else 0.0
    per_round = ([int(x) for x in np.asarray(up_sends)]
                 if schedule.height else [])
    if broadcast:
        out, bres = tree_broadcast_exec(schedule, total,
                                        unit_scalars=unit_scalars,
                                        unit_points=unit_points, dim=dim)
        sends_total = sends + int(bres.ledger.messages)
        w_sends = w_sends + (_level_edge_cost_total(schedule)
                             if bres.ledger.messages else 0.0)
        per_round = per_round + bres.per_round_transmissions
    else:
        out = jnp.broadcast_to(total, (schedule.n,) + total.shape)
        sends_total = sends
    ledger = _units_ledger(np.asarray([sends_total], np.float64),
                           unit_scalars, unit_points, dim,
                           count_all_messages=False,
                           per_origin_link=np.asarray([w_sends], np.float64))
    res = ExecResult(rounds=schedule.height * (2 if broadcast else 1),
                     rounds_to_complete=schedule.height, ledger=ledger,
                     per_round_transmissions=per_round)
    return out.reshape((schedule.n,) + trailing), res


def tree_broadcast_exec(schedule: TreeSchedule, value: jax.Array,
                        unit_scalars: Units = 0.0, unit_points: Units = 0.0,
                        dim: int = 0) -> Tuple[jax.Array, ExecResult]:
    """Root sends one payload down every tree edge, level by level (n-1
    transmissions). Returns every node's (bit-identical) copy ``(n, ...)``
    and the measured ledger (equals ``tree_broadcast_cost``)."""
    value = jnp.asarray(value)
    flat = value.reshape(-1)
    vals0 = jnp.zeros((schedule.n, flat.shape[0]), flat.dtype).at[
        schedule.root].set(flat)

    def body(vals, lvl):
        nodes, lmask = lvl
        par = jnp.asarray(schedule.parent)[nodes]
        vals = vals.at[nodes].set(
            jnp.where(lmask[:, None], vals[par], vals[nodes]))
        return vals, lmask.sum()

    vals, sends = _level_scan(schedule, body, vals0, bottom_up=False)
    n_sends = int(np.asarray(sends).sum()) if schedule.height else 0
    w_sends = _level_edge_cost_total(schedule) if n_sends else 0.0
    ledger = _units_ledger(np.asarray([n_sends], np.float64), unit_scalars,
                           unit_points, dim, count_all_messages=False,
                           per_origin_link=np.asarray([w_sends], np.float64))
    res = ExecResult(rounds=schedule.height,
                     rounds_to_complete=schedule.height, ledger=ledger,
                     per_round_transmissions=[int(x) for x in
                                              np.asarray(sends)]
                     if schedule.height else [])
    return vals.reshape((schedule.n,) + value.shape), res


# ---------------------------------------------------------------------------
# SPMD ring + 2-D torus collectives (shard_map primitives)
# ---------------------------------------------------------------------------

def _check_axis_size(axis_name: str, axis_size: int, fn: str) -> None:
    """Fail loudly when the caller's ``axis_size`` disagrees with the mesh.

    The ring/torus permutations are built from the *claimed* ``axis_size``;
    a mismatch used to produce a silently wrong answer (the fori_loop runs
    the wrong number of hops and the permutation indexes phantom devices).
    ``psum(1, axis)`` is static under ``shard_map`` in this jax version, so
    the check costs nothing at runtime; if a future tracer makes it dynamic
    we skip rather than mis-raise.
    """
    if axis_size < 1:
        raise ValueError(f"{fn}: axis_size must be >= 1, got {axis_size}")
    actual = jax.lax.psum(1, axis_name)
    if isinstance(actual, (int, np.integer)) and int(actual) != axis_size:
        raise ValueError(
            f"{fn}: axis_size={axis_size} disagrees with the actual size "
            f"{int(actual)} of mesh axis {axis_name!r}; the ppermute "
            "schedule would be silently wrong")


def neighbor_rounds_sum(x: jax.Array, axis_name: str, axis_size: int) -> jax.Array:
    """Global sum via ring neighbour exchanges only (collective_permute),
    demonstrating Algorithm 3 on a physical ring: after ``axis_size - 1``
    rounds each device has accumulated every shard's value.

    Must be called inside ``shard_map`` over ``axis_name``. The hop-by-hop
    accumulation order is fixed by the ring schedule, so repeated runs are
    bit-identical to each other (deterministic reduction order), but the
    float total may differ from ``psum`` in the last ulps.
    """
    _check_axis_size(axis_name, axis_size, "neighbor_rounds_sum")
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def body(i, carry):
        acc, buf = carry
        buf = jax.lax.ppermute(buf, axis_name, perm)
        return acc + buf, buf

    acc, _ = jax.lax.fori_loop(0, axis_size - 1, body, (x, x))
    return acc


def neighbor_rounds_gather(x: jax.Array, axis_name: str, axis_size: int) -> jax.Array:
    """All-gather via ring neighbour exchanges (Algorithm 3 Round 2 on a
    physical ring): returns (axis_size, *x.shape) on every device.

    Every slot of the output is a pure ppermute relay of the origin shard,
    so the result is bit-identical to ``jax.lax.all_gather``.
    """
    _check_axis_size(axis_name, axis_size, "neighbor_rounds_gather")
    idx = jax.lax.axis_index(axis_name)
    out = jnp.zeros((axis_size,) + x.shape, x.dtype)
    out = jax.lax.dynamic_update_index_in_dim(out, x, idx, 0)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def body(i, carry):
        out, buf, src = carry
        buf = jax.lax.ppermute(buf, axis_name, perm)
        src = (src - 1) % axis_size
        out = jax.lax.dynamic_update_index_in_dim(out, buf, src, 0)
        return out, buf, src

    out, _, _ = jax.lax.fori_loop(0, axis_size - 1, body, (out, x, idx))
    return out


def torus_mesh_shape(axis_size: int) -> Tuple[int, int]:
    """Most-square (R, C) factorization of ``axis_size`` (R <= C).

    Default ``mesh_shape`` for ``collectives="torus_2d"``: the squarest
    factorization minimizes (R - 1) + (C - 1) hops over all 2-D foldings
    of a flat axis. Prime sizes degenerate to (1, axis_size) == the ring.
    """
    if axis_size < 1:
        raise ValueError(f"axis_size must be >= 1, got {axis_size}")
    r = int(np.sqrt(axis_size))
    while axis_size % r:
        r -= 1
    return r, axis_size // r


def _torus_perms(axis_name: str, mesh_shape: Tuple[int, int], fn: str):
    """Validate (R, C) against the flat mesh axis and build the two
    single-hop permutations: row phase (r, c) -> (r, (c+1) % C) and column
    phase (r, c) -> ((r+1) % R, c), in row-major flat indexing
    i = r * C + c (the order ``jax.make_mesh`` assigns devices)."""
    R, C = mesh_shape
    if R < 1 or C < 1:
        raise ValueError(f"{fn}: mesh_shape must be positive, got {mesh_shape}")
    _check_axis_size(axis_name, R * C, fn)
    row_perm = [(r * C + c, r * C + (c + 1) % C)
                for r in range(R) for c in range(C)]
    col_perm = [(r * C + c, ((r + 1) % R) * C + c)
                for r in range(R) for c in range(C)]
    return row_perm, col_perm


def torus_rounds_gather(x: jax.Array, axis_name: str,
                        mesh_shape: Tuple[int, int]) -> jax.Array:
    """All-gather on a 2-D torus folding of the flat mesh axis.

    Two phases: (C - 1) row-ring hops gather each device's row of C shards,
    then (R - 1) column-ring hops gather the per-row buffers -- a total of
    (R - 1) + (C - 1) sequential hops instead of the 1-D ring's R*C - 1.
    Returns (R * C, *x.shape) in flat row-major order, bit-identical to
    ``jax.lax.all_gather`` (every output slot is a pure ppermute relay).

    Must be called inside ``shard_map`` over ``axis_name`` with
    ``R * C == axis_size``.
    """
    R, C = mesh_shape
    row_perm, col_perm = _torus_perms(axis_name, mesh_shape,
                                      "torus_rounds_gather")
    idx = jax.lax.axis_index(axis_name)
    r, c = idx // C, idx % C

    # row phase: gather the C shards of this device's row
    row = jnp.zeros((C,) + x.shape, x.dtype)
    row = jax.lax.dynamic_update_index_in_dim(row, x, c, 0)

    def rbody(j, carry):
        row, buf, src = carry
        buf = jax.lax.ppermute(buf, axis_name, row_perm)
        src = (src - 1) % C
        row = jax.lax.dynamic_update_index_in_dim(row, buf, src, 0)
        return row, buf, src

    row, _, _ = jax.lax.fori_loop(0, C - 1, rbody, (row, x, c))

    # column phase: gather the R row-buffers of this device's column
    out = jnp.zeros((R,) + row.shape, x.dtype)
    out = jax.lax.dynamic_update_index_in_dim(out, row, r, 0)

    def cbody(j, carry):
        out, buf, src = carry
        buf = jax.lax.ppermute(buf, axis_name, col_perm)
        src = (src - 1) % R
        out = jax.lax.dynamic_update_index_in_dim(out, buf, src, 0)
        return out, buf, src

    out, _, _ = jax.lax.fori_loop(0, R - 1, cbody, (out, row, r))
    # (R, C, ...) row-major == flat device order i = r * C + c
    return out.reshape((R * C,) + x.shape)


def torus_rounds_sum(x: jax.Array, axis_name: str,
                     mesh_shape: Tuple[int, int]) -> jax.Array:
    """Global sum on a 2-D torus folding: row-ring partial sums in C - 1
    hops, then column-ring reduction of the row totals in R - 1 hops.

    Deterministic reduction order (bit-identical across repeated runs) but,
    like ``neighbor_rounds_sum``, the grouping differs from ``psum`` so the
    float total may differ in the last ulps; it may also differ from the
    1-D ring's total (different association order).
    """
    row_perm, col_perm = _torus_perms(axis_name, mesh_shape,
                                      "torus_rounds_sum")
    R, C = mesh_shape

    def ring_sum(v, perm, hops):
        def body(i, carry):
            acc, buf = carry
            buf = jax.lax.ppermute(buf, axis_name, perm)
            return acc + buf, buf
        acc, _ = jax.lax.fori_loop(0, hops, body, (v, v))
        return acc

    return ring_sum(ring_sum(x, row_perm, C - 1), col_perm, R - 1)


def collective_hops(collectives: str, axis_size: int,
                    mesh_shape: Optional[Tuple[int, int]] = None) -> int:
    """Sequential ppermute-hop depth of one gather under each schedule.

    ``all_gather`` is counted at the ring depth axis_size - 1 (XLA's ICI
    lowering of a flat-axis all-gather is the same ring); ``torus_2d`` is
    (R - 1) + (C - 1). Used by bench_collectives and the roofline ledgers.
    """
    if collectives in ("all_gather", "neighbor_rounds"):
        return axis_size - 1
    if collectives == "torus_2d":
        R, C = torus_mesh_shape(axis_size) if mesh_shape is None else mesh_shape
        if R * C != axis_size:
            raise ValueError(
                f"mesh_shape {mesh_shape} does not tile axis_size {axis_size}")
        return (R - 1) + (C - 1)
    raise ValueError(f"unknown collectives mode: {collectives!r}")
