"""Topology execution engine tests (DESIGN.md Sec. 11).

The engine contract, asserted here for every topology generator:

* executed floods/tree routes deliver bit-identical payload copies to the
  nodes the protocol says should hold them;
* the *measured* CommLedger (counted transmission by transmission from the
  compiled schedule) equals the *analytic* ledger exactly;
* ``engine="exec"`` of Algorithm 2 is bit-identical to the host-simulation
  oracle on every node, for both objectives.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import topology
from repro.core.comm import (flood_cost, tree_broadcast_cost,
                             tree_gather_cost, tree_up_cost)
from repro.core.distributed import (distributed_kmeans_tree,
                                    graph_distributed_kmeans)
from repro.core.message_passing import (GossipSchedule, TreeSchedule, flood,
                                        flood_exec, tree_broadcast_exec,
                                        tree_gather_exec, tree_scatter_exec,
                                        tree_up_sum_exec)
from repro.core.partition import pad_partition, partition_indices

KEY = jax.random.PRNGKey(0)

# every generator, all on 9 nodes so the end-to-end runs share jit caches
# (wan is the heterogeneous-link one: integer 1.0/16.0 costs)
TOPOLOGIES = {
    "ring": lambda: topology.ring(9),
    "star": lambda: topology.star(9),
    "grid": lambda: topology.grid(3, 3),
    "er": lambda: topology.erdos_renyi(9, 0.3, seed=3),
    "preferential": lambda: topology.preferential(9, 2, seed=0),
    "wan": lambda: topology.wan_clusters(3, 3, cross_links=2, seed=0),
}

LEDGER_UNITS = ("scalars", "points", "messages", "link_cost")


def _graph(name):
    return TOPOLOGIES[name]()


@pytest.fixture(scope="module")
def site_data():
    rng = np.random.default_rng(0)
    k, d, n_sites = 3, 5, 9
    centers = 3.0 * rng.standard_normal((k, d))
    pts = np.concatenate(
        [centers[i] + 0.2 * rng.standard_normal((150, d)) for i in range(k)]
    ).astype(np.float32)
    idx = partition_indices(pts, n_sites, "weighted", seed=1)
    sp, sm = pad_partition(pts, idx)
    return jnp.asarray(sp), jnp.asarray(sm), k


# -- generators --------------------------------------------------------------

def test_ring_star_shapes():
    r = topology.ring(6)
    assert r.m == 6 and all(len(a) == 2 for a in r.adjacency())
    assert topology.diameter(r) == 3
    s = topology.star(6)
    assert s.m == 5 and topology.diameter(s) == 2
    assert len(s.adjacency()[0]) == 5
    with pytest.raises(ValueError):
        topology.ring(1)
    with pytest.raises(ValueError):
        topology.star(1)


@pytest.mark.parametrize("name", sorted(TOPOLOGIES))
def test_new_generators_flood_connected(name):
    g = _graph(name)
    res = flood(g)
    assert all(r == set(range(g.n)) for r in res.received)


# -- flood_exec: delivery, quiescence, measured == analytic ------------------

@pytest.mark.parametrize("name", sorted(TOPOLOGIES))
def test_flood_exec_delivers_and_meters_exactly(name):
    g = _graph(name)
    vals = jnp.asarray(
        np.random.default_rng(1).standard_normal((g.n, 3)).astype(np.float32))
    tables, res = flood_exec(g, vals, unit_scalars=1.0)
    # every node holds every origin's payload, bit-identical
    for v in range(g.n):
        np.testing.assert_array_equal(np.asarray(tables[v]),
                                      np.asarray(vals))
    # quiescence: knowledge complete within diameter rounds
    assert res.rounds_to_complete <= topology.diameter(g)
    assert res.rounds == topology.diameter(g) + 1
    # measured == analytic, exactly (link_cost included: every message
    # crosses every link, priced by the weighted degree sum)
    analytic = flood_cost(g, n_messages=g.n, unit_scalars=1.0)
    assert res.ledger.scalars == analytic.scalars
    assert res.ledger.messages == analytic.messages == 2 * g.m * g.n
    assert res.ledger.link_cost == analytic.link_cost
    if g.is_uniform_cost:
        assert res.ledger.link_cost == res.ledger.bytes
    else:
        assert res.ledger.link_cost > res.ledger.bytes
    assert sum(res.per_round_transmissions) == 2 * g.m * g.n
    # executed profile matches the host simulation round for round
    sim = flood(g)
    assert res.per_round_transmissions == sim.per_round_transmissions


@pytest.mark.parametrize("name", sorted(TOPOLOGIES))
def test_flood_exec_per_origin_units(name):
    g = _graph(name)
    vals = jnp.zeros((g.n, 1))
    units = np.arange(g.n, dtype=np.float64)   # origin o ships o points
    _, res = flood_exec(g, vals, unit_points=units, dim=4)
    analytic = flood_cost(g, n_messages=1, unit_points=float(units.sum()),
                          dim=4)
    assert res.ledger.points == analytic.points == 2 * g.m * units.sum()
    assert res.ledger.dim == 4


def test_flood_exec_rejects_wrong_payload_length():
    g = topology.ring(5)
    with pytest.raises(ValueError):
        flood_exec(g, jnp.zeros((4, 1)))


def test_gossip_schedule_static_shapes():
    g = topology.star(7)
    sched = GossipSchedule.from_graph(g)
    assert sched.neighbors.shape == (7, 6)       # hub degree pads everyone
    assert sched.neighbor_mask.sum() == 2 * g.m
    assert sched.n_rounds == topology.diameter(g) + 1


# -- tree primitives ---------------------------------------------------------

@pytest.mark.parametrize("name", sorted(TOPOLOGIES))
def test_tree_gather_scatter_roundtrip_and_ledger(name):
    g = _graph(name)
    tree = topology.bfs_spanning_tree(g, root=0)
    sched = TreeSchedule.from_tree(tree)
    vals = jnp.asarray(
        np.random.default_rng(2).standard_normal((g.n, 2)).astype(np.float32))
    root_table, gres = tree_gather_exec(sched, vals, unit_scalars=1.0)
    np.testing.assert_array_equal(np.asarray(root_table), np.asarray(vals))
    analytic = tree_gather_cost(tree, unit_scalars_per_node=1.0)
    assert gres.ledger.scalars == analytic.scalars == sum(tree.depth)
    assert gres.ledger.messages == analytic.messages
    assert gres.ledger.link_cost == analytic.link_cost \
        == 4.0 * tree.path_costs().sum()

    own, sres = tree_scatter_exec(sched, vals, unit_scalars=1.0)
    np.testing.assert_array_equal(np.asarray(own), np.asarray(vals))
    assert sres.ledger.scalars == analytic.scalars  # path symmetry
    assert sres.ledger.link_cost == analytic.link_cost


@pytest.mark.parametrize("name", sorted(TOPOLOGIES))
def test_tree_up_sum_and_broadcast(name):
    g = _graph(name)
    tree = topology.bfs_spanning_tree(g, root=0)
    sched = TreeSchedule.from_tree(tree)
    vals = jnp.asarray(
        np.random.default_rng(3).standard_normal((g.n, 2)).astype(np.float32))
    totals, ures = tree_up_sum_exec(sched, vals, broadcast=True,
                                    unit_scalars=1.0)
    expect = np.asarray(vals.sum(axis=0))
    for v in range(g.n):
        np.testing.assert_allclose(np.asarray(totals[v]), expect, rtol=1e-5)
    # up n-1 sends + broadcast n-1 sends, one scalar-unit each
    assert ures.ledger.scalars == 2.0 * (g.n - 1)
    assert ures.ledger.messages == 2.0 * (g.n - 1)

    payload = jnp.asarray(np.random.default_rng(4).standard_normal(
        (4, 2)).astype(np.float32))
    out, bres = tree_broadcast_exec(sched, payload, unit_points=4.0, dim=2)
    for v in range(g.n):
        np.testing.assert_array_equal(np.asarray(out[v]),
                                      np.asarray(payload))
    analytic = tree_broadcast_cost(tree, unit_points=4.0, dim=2)
    assert bres.ledger.points == analytic.points == 4.0 * (g.n - 1)
    assert bres.ledger.messages == analytic.messages == g.n - 1
    assert bres.ledger.link_cost == analytic.link_cost \
        == 4.0 * 3.0 * 4.0 * tree.edge_cost_total()


# -- Algorithm 2: engine == simulation, measured == analytic -----------------

@pytest.mark.parametrize("name", sorted(TOPOLOGIES))
def test_graph_engine_matches_simulation(site_data, name):
    sp, sm, k = site_data
    g = _graph(name)
    t = 90
    sim = graph_distributed_kmeans(KEY, sp, sm, k, t=t, graph=g)
    ex = graph_distributed_kmeans(KEY, sp, sm, k, t=t, graph=g,
                                  engine="exec")
    # bit-identical centers and coreset
    np.testing.assert_array_equal(np.asarray(sim.centers),
                                  np.asarray(ex.centers))
    np.testing.assert_array_equal(np.asarray(sim.coreset.points),
                                  np.asarray(ex.coreset.points))
    np.testing.assert_array_equal(np.asarray(sim.coreset.weights),
                                  np.asarray(ex.coreset.weights))
    # measured ledger == analytic ledger, exactly (all axes incl. link_cost)
    for unit in LEDGER_UNITS:
        assert getattr(ex.ledger, unit) == getattr(sim.ledger, unit), unit
    # every node assembled the identical global instance and allocation
    det = ex.exec_detail
    npts, nw = np.asarray(det.node_points), np.asarray(det.node_weights)
    alloc = np.asarray(det.node_alloc)
    for v in range(g.n):
        np.testing.assert_array_equal(npts[v], npts[0])
        np.testing.assert_array_equal(nw[v], nw[0])
        np.testing.assert_array_equal(alloc[v], alloc[0])
    assert alloc[0].sum() == t


def test_graph_engine_every_node_solves_identically(site_data):
    """Acceptance: every node, solving its own received copy, produces the
    same centers the engine reports."""
    sp, sm, k = site_data
    g = _graph("er")
    from repro.core.coreset import Coreset, gathered_live_rows
    from repro.core.distributed import _solve_on_coreset
    ex = graph_distributed_kmeans(KEY, sp, sm, k, t=90, graph=g,
                                  engine="exec")
    _, k2 = jax.random.split(KEY)
    det = ex.exec_detail
    for v in range(g.n):
        cs_v = Coreset(det.node_points[v], det.node_weights[v])
        centers_v = _solve_on_coreset(
            k2, cs_v, k, "kmeans", 8, None,
            live=gathered_live_rows(cs_v.size, 90, k))
        np.testing.assert_array_equal(np.asarray(centers_v),
                                      np.asarray(ex.centers))


@pytest.mark.parametrize("name", sorted(TOPOLOGIES))
def test_tree_engine_matches_simulation(site_data, name):
    sp, sm, k = site_data
    g = _graph(name)
    tree = topology.bfs_spanning_tree(g, root=0)
    t = 90
    sim = distributed_kmeans_tree(KEY, sp, sm, k, t=t, tree=tree)
    ex = distributed_kmeans_tree(KEY, sp, sm, k, t=t, tree=tree,
                                 engine="exec")
    np.testing.assert_array_equal(np.asarray(sim.centers),
                                  np.asarray(ex.centers))
    np.testing.assert_array_equal(np.asarray(sim.coreset.points),
                                  np.asarray(ex.coreset.points))
    np.testing.assert_array_equal(np.asarray(sim.coreset.weights),
                                  np.asarray(ex.coreset.weights))
    for unit in LEDGER_UNITS:
        assert getattr(ex.ledger, unit) == getattr(sim.ledger, unit), unit
    # the broadcast delivered the identical solution to every node
    nc = np.asarray(ex.exec_detail.node_centers)
    for v in range(g.n):
        np.testing.assert_array_equal(nc[v], np.asarray(ex.centers))
    assert np.asarray(ex.exec_detail.node_alloc).sum() == t


@pytest.mark.parametrize("objective", ["kmeans", "kmedian"])
def test_engine_both_objectives(site_data, objective):
    sp, sm, k = site_data
    g = _graph("grid")
    sim = graph_distributed_kmeans(KEY, sp, sm, k, t=60, graph=g,
                                   objective=objective, lloyd_iters=4)
    ex = graph_distributed_kmeans(KEY, sp, sm, k, t=60, graph=g,
                                  objective=objective, lloyd_iters=4,
                                  engine="exec")
    np.testing.assert_array_equal(np.asarray(sim.centers),
                                  np.asarray(ex.centers))
    tree = topology.bfs_spanning_tree(g, root=0)
    sim_t = distributed_kmeans_tree(KEY, sp, sm, k, t=60, tree=tree,
                                    objective=objective, lloyd_iters=4)
    ex_t = distributed_kmeans_tree(KEY, sp, sm, k, t=60, tree=tree,
                                   objective=objective, lloyd_iters=4,
                                   engine="exec")
    np.testing.assert_array_equal(np.asarray(sim_t.centers),
                                  np.asarray(ex_t.centers))


def test_unknown_engine_raises(site_data):
    sp, sm, k = site_data
    g = _graph("ring")
    with pytest.raises(ValueError):
        graph_distributed_kmeans(KEY, sp, sm, k, t=30, graph=g,
                                 engine="warp")
    with pytest.raises(ValueError):
        distributed_kmeans_tree(KEY, sp, sm, k, t=30,
                                tree=topology.bfs_spanning_tree(g),
                                engine="warp")


# -- heterogeneous links: weighted ledgers and min-cost routing ---------------

def test_tree_exec_weighted_ledgers_exact_on_noninteger_costs():
    """Tree gather/scatter/broadcast pricing is structurally identical to
    the analytic path-cost summation, so measured == analytic bit-for-bit
    even for arbitrary float costs (floods only guarantee that for
    integer-valued costs; DESIGN.md Sec. 12)."""
    g = topology.heterogeneous(topology.grid(3, 3),
                               lambda i, j: 0.3 + 0.7 / (1 + i + j))
    tree = topology.mst_spanning_tree(g)
    sched = TreeSchedule.from_tree(tree)
    vals = jnp.asarray(np.random.default_rng(7).standard_normal(
        (g.n, 2)).astype(np.float32))
    units = np.arange(1.0, g.n + 1.0)
    _, gres = tree_gather_exec(sched, vals, unit_points=units, dim=2)
    analytic = tree_gather_cost(tree, unit_points_per_node=units, dim=2)
    assert gres.ledger.link_cost == analytic.link_cost
    _, sres = tree_scatter_exec(sched, vals, unit_points=units, dim=2)
    assert sres.ledger.link_cost == analytic.link_cost
    _, bres = tree_broadcast_exec(sched, vals[0], unit_points=2.0, dim=2)
    assert bres.ledger.link_cost == \
        tree_broadcast_cost(tree, unit_points=2.0, dim=2).link_cost


def test_flood_exec_weighted_per_origin_units():
    g = topology.wan_clusters(3, 3, cross_links=2, seed=0)
    vals = jnp.zeros((g.n, 1))
    units = np.arange(g.n, dtype=np.float64)
    _, res = flood_exec(g, vals, unit_points=units, dim=4)
    w = float(g.weighted_degrees().sum())
    # every message crosses every link: per-origin weighted price w * unit
    assert res.ledger.link_cost == 4.0 * 5.0 * w * units.sum()


@pytest.mark.parametrize("engine", ["sim", "exec"])
def test_min_cost_routing_beats_bfs_on_wan(site_data, engine):
    """Acceptance: on wan_clusters, routing="min_cost" strictly lowers the
    cost-weighted bytes vs routing="bfs", with identical centers, and the
    measured exec ledger equals the analytic min-cost ledger exactly."""
    sp, sm, k = site_data
    g = topology.wan_clusters(3, 3, cross_cost=16.0, cross_links=2, seed=0)
    t = 90
    res = {r: graph_distributed_kmeans(KEY, sp, sm, k, t=t, graph=g,
                                       routing=r, engine=engine)
           for r in ("bfs", "min_cost")}
    assert res["min_cost"].ledger.link_cost < res["bfs"].ledger.link_cost
    np.testing.assert_array_equal(np.asarray(res["bfs"].centers),
                                  np.asarray(res["min_cost"].centers))
    if engine == "exec":
        for routing in ("bfs", "min_cost"):
            sim = graph_distributed_kmeans(KEY, sp, sm, k, t=t, graph=g,
                                           routing=routing)
            for unit in LEDGER_UNITS:
                assert getattr(res[routing].ledger, unit) == \
                    getattr(sim.ledger, unit), (routing, unit)
    # the min-cost tree holds exactly n_racks - 1 cross links; BFS enters
    # remote racks through every shallow cross link it finds
    mst = topology.mst_spanning_tree(g)
    bfs = topology.bfs_spanning_tree(g)
    assert mst.edge_cost_total() < bfs.edge_cost_total()


def test_routing_knob_uniform_costs_match_bfs_exactly(site_data):
    """On a uniform-cost graph min-cost routing is the BFS tree, so the
    two routings produce bit-identical ledgers (PR 4 compatibility)."""
    sp, sm, k = site_data
    g = _graph("er")
    a = graph_distributed_kmeans(KEY, sp, sm, k, t=90, graph=g,
                                 routing="bfs")
    b = graph_distributed_kmeans(KEY, sp, sm, k, t=90, graph=g,
                                 routing="min_cost")
    assert a.ledger.as_dict() == b.ledger.as_dict()
    assert a.ledger.link_cost == a.ledger.bytes
    np.testing.assert_array_equal(np.asarray(a.centers),
                                  np.asarray(b.centers))


def test_routing_matches_explicit_tree_protocol(site_data):
    """The routing knob is sugar for the tree protocol on a spanning tree
    of the graph: same centers, same ledger."""
    sp, sm, k = site_data
    g = _graph("wan")
    via_knob = graph_distributed_kmeans(KEY, sp, sm, k, t=90, graph=g,
                                        routing="min_cost")
    tree = topology.mst_spanning_tree(g)
    direct = distributed_kmeans_tree(KEY, sp, sm, k, t=90, tree=tree)
    assert via_knob.ledger.as_dict() == direct.ledger.as_dict()
    np.testing.assert_array_equal(np.asarray(via_knob.centers),
                                  np.asarray(direct.centers))


def test_unknown_routing_raises(site_data):
    sp, sm, k = site_data
    with pytest.raises(ValueError, match="unknown routing"):
        graph_distributed_kmeans(KEY, sp, sm, k, t=30,
                                 graph=_graph("ring"), routing="warp")


def test_ledger_phase_breakdown_carries_link_cost(site_data):
    """Phase dicts expose the link_cost axis: every phase of an exec tree
    run prices its own transmissions (round1 scalars cheap, round2 points
    dominant), and phases decompose the total exactly."""
    sp, sm, k = site_data
    g = _graph("wan")
    ex = graph_distributed_kmeans(KEY, sp, sm, k, t=90, graph=g,
                                  routing="min_cost", engine="exec")
    d = ex.ledger.as_dict(by_phase=True)
    assert set(d["phases"]) == {"round1", "round2_gather",
                                "round2_broadcast"}
    for sub in d["phases"].values():
        assert "link_cost" in sub
    assert sum(p["link_cost"] for p in d["phases"].values()) \
        == pytest.approx(d["link_cost"])
    assert sum(p["points"] for p in d["phases"].values()) == d["points"]


def test_flood_exec_directed_follows_link_directions():
    """On a directed graph the executed flood must move payloads along
    out-links (receive = in-neighbor gather), not the transpose graph: on
    this asymmetric strongly-connected digraph the transpose has a
    different per-round profile, so profile equality with the (correct)
    host simulation catches any direction flip."""
    g = topology.Graph(4, ((0, 1), (1, 2), (1, 3), (2, 0), (3, 2)),
                       directed=True)
    vals = jnp.asarray(np.random.default_rng(5).standard_normal(
        (g.n, 2)).astype(np.float32))
    tables, res = flood_exec(g, vals, unit_scalars=1.0)
    for v in range(g.n):
        np.testing.assert_array_equal(np.asarray(tables[v]),
                                      np.asarray(vals))
    sim = flood(g)
    assert res.per_round_transmissions == sim.per_round_transmissions
    analytic = flood_cost(g, n_messages=g.n, unit_scalars=1.0)
    # directed: each message crosses each one-way link once => m per message
    assert res.ledger.messages == analytic.messages == g.m * g.n
    assert res.ledger.scalars == analytic.scalars
    assert res.ledger.link_cost == analytic.link_cost
    assert res.rounds_to_complete <= topology.diameter(g)


def test_tree_schedule_from_graph_routing():
    """TreeSchedule.from_graph compiles the routed spanning tree directly:
    identical schedule state to from_tree(spanning_tree(...))."""
    g = topology.wan_clusters(2, 3, cross_links=2, seed=1)
    for routing in ("bfs", "min_cost"):
        direct = TreeSchedule.from_graph(g, root=0, routing=routing)
        via_tree = TreeSchedule.from_tree(
            topology.spanning_tree(g, root=0, routing=routing))
        np.testing.assert_array_equal(direct.parent, via_tree.parent)
        np.testing.assert_array_equal(direct.parent_cost,
                                      via_tree.parent_cost)
        np.testing.assert_array_equal(direct.levels, via_tree.levels)


def test_directed_ring_relay_regression():
    """One-way ring: the tightest orientation regression for
    GossipSchedule.from_graph(directed=True). Every node has exactly one
    out-slot and one in-edge; payloads travel n-1 hops *with* the arrows
    (the transpose schedule would be caught by the asymmetric-digraph
    test above; this one pins the degenerate max_deg == 1 layout)."""
    n = 6
    g = topology.Graph(n, tuple((i, (i + 1) % n) for i in range(n)),
                       directed=True)
    sched = GossipSchedule.from_graph(g)
    assert sched.neighbors.shape == (n, 1) and sched.n_rounds >= n - 1
    np.testing.assert_array_equal(np.asarray(sched.in_neighbors)[:, 0],
                                  np.arange(-1, n - 1) % n)
    vals = jnp.arange(n, dtype=jnp.float32)[:, None] * 3.0 + 1.0
    tables, res = flood_exec(g, vals, unit_scalars=1.0)
    for v in range(n):
        np.testing.assert_array_equal(np.asarray(tables[v]),
                                      np.asarray(vals))
    sim = flood(g)
    m = min(len(res.per_round_transmissions),
            len(sim.per_round_transmissions))
    assert res.per_round_transmissions[:m] == \
        sim.per_round_transmissions[:m]
    assert res.rounds_to_complete == topology.diameter(g) == n - 1


def test_schedule_factories_cache_by_graph_value():
    """gossip_schedule / tree_schedule are lru-cached on the (hashable)
    Graph value: structurally equal graphs share one compiled schedule,
    different routings do not."""
    from repro.core.message_passing import gossip_schedule, tree_schedule
    g1 = topology.wan_clusters(2, 3, cross_links=2, seed=1)
    g2 = topology.Graph(g1.n, g1.edges, edge_costs=g1.edge_costs,
                        directed=g1.directed)
    assert g1 == g2 and hash(g1) == hash(g2)
    assert gossip_schedule(g1) is gossip_schedule(g2)
    assert tree_schedule(g1, root=0) is tree_schedule(g2, root=0)
    assert tree_schedule(g1, root=0, routing="bfs") is not \
        tree_schedule(g1, root=0, routing="min_cost")
    d = topology.Graph(3, ((0, 1), (1, 2), (2, 0)), directed=True)
    assert gossip_schedule(d) is gossip_schedule(
        topology.Graph(3, ((0, 1), (1, 2), (2, 0)), directed=True))
