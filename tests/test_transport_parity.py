"""Transport parity: every way of moving Algorithm 1's rounds reproduces
the ``engine="sim"`` flood oracle bit for bit.

One case per (graph, objective, strategy, transport). The transports are
the executed flood, the BFS and min-cost spanning-tree protocols (``sim``
and ``exec``), and the asynchronous WAN runtime (``wan_mode`` ``"full"``
under an empty fault plan, and ``"clock"``). Each case asserts the final
centers and the sorted coreset weights equal the oracle's exactly: tree
layouts order the gathered buffer differently, so the weights are compared
as a multiset. ``(kmeans, algorithm1)`` is left out;
``test_strategy.py::test_algorithm1_engines_agree_bit_for_bit`` and
``test_topology_exec.py`` already compare it across transports.
``mapreduce`` never floods: on ``routing="flood"`` it re-routes to ``bfs``
inside ``graph_distributed_kmeans``, and the oracle pins that too.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import topology
from repro.core.distributed import graph_distributed_kmeans
from repro.core.partition import pad_partition, partition_indices
from repro.wan.faults import FaultPlan

KEY = jax.random.PRNGKey(0)
T = 60
LLOYD_ITERS = 4

GRAPHS = {
    # the paper's Sec. 6 graph family
    "er": lambda: topology.erdos_renyi(9, 0.3, seed=3),
    # heterogeneous link costs: min-cost routing differs from bfs
    "wan": lambda: topology.wan_clusters(3, 3, cross_links=2, seed=0),
}

PAIRS = [(obj, strat)
         for obj in ("kmeans", "kmedian", "kmeans_trimmed(4)")
         for strat in ("algorithm1", "cohen_addad", "mapreduce")
         if (obj, strat) != ("kmeans", "algorithm1")]

TRANSPORTS = {
    "exec-flood": dict(engine="exec"),
    "sim-bfs": dict(engine="sim", routing="bfs"),
    "exec-bfs": dict(engine="exec", routing="bfs"),
    "sim-min_cost": dict(engine="sim", routing="min_cost"),
    "exec-min_cost": dict(engine="exec", routing="min_cost"),
    "async-full": dict(engine="async", wan_mode="full", faults=FaultPlan()),
    "async-clock": dict(engine="async", wan_mode="clock"),
}


@pytest.fixture(scope="module")
def site_data():
    """The sites of ``test_topology_exec.py``: 450 points in R^5 around
    k = 3 centers, weighted-partitioned over 9 sites."""
    rng = np.random.default_rng(0)
    k, d, n_sites = 3, 5, 9
    centers = 3.0 * rng.standard_normal((k, d))
    pts = np.concatenate(
        [centers[i] + 0.2 * rng.standard_normal((150, d)) for i in range(k)]
    ).astype(np.float32)
    idx = partition_indices(pts, n_sites, "weighted", seed=1)
    sp, sm = pad_partition(pts, idx)
    return jnp.asarray(sp), jnp.asarray(sm), k


def _run(site_data, graph, objective, strategy, **transport):
    sp, sm, k = site_data
    res = graph_distributed_kmeans(KEY, sp, sm, k, T, graph=GRAPHS[graph](),
                                   objective=objective, strategy=strategy,
                                   lloyd_iters=LLOYD_ITERS, **transport)
    return (np.asarray(res.centers),
            np.sort(np.asarray(res.coreset.weights)))


@pytest.fixture(scope="module")
def oracle(site_data):
    """The ``sim`` flood run of each (graph, objective, strategy), built
    once for the module."""
    cache = {}

    def get(graph, objective, strategy):
        key = (graph, objective, strategy)
        if key not in cache:
            cache[key] = _run(site_data, graph, objective, strategy)
        return cache[key]

    return get


@pytest.mark.parametrize("transport", sorted(TRANSPORTS))
@pytest.mark.parametrize("objective,strategy", PAIRS)
@pytest.mark.parametrize("graph", sorted(GRAPHS))
def test_transport_matches_sim_flood_oracle(site_data, oracle, graph,
                                            objective, strategy, transport):
    want_centers, want_weights = oracle(graph, objective, strategy)
    centers, weights = _run(site_data, graph, objective, strategy,
                            **TRANSPORTS[transport])
    np.testing.assert_array_equal(centers, want_centers)
    np.testing.assert_array_equal(weights, want_weights)
