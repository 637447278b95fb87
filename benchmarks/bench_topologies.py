"""Topology execution engine benchmarks: engine-vs-simulation wall time and
ledger parity for Algorithm 2 across every topology generator, as JSON rows
(``BENCH_topologies.json`` at the repo root is the CI artifact).

Rows: {ring, star, grid, torus, er(p=0.3), preferential, wan} x {sim, exec}
x backend, each with ``routing`` and ``link_cost`` (cost-weighted bytes)
columns. Each row reports the wall time of one full Algorithm-2 run, the
communication ledger (measured for the exec engine, analytic for sim --
``ledger_match`` asserts they agree on every axis incl. link_cost), the
schedule's round count, and a centers-bit-parity flag against the sim
oracle.

The weighted-routing payoff section runs Algorithm 2 on ``wan_clusters``
(cheap intra-rack cliques, 16x cross-rack links) under ``routing="bfs"``
vs ``"min_cost"``: the min-cost tree pays for one cross link per attached
rack where BFS pays for every shallow entry point, so its cost-weighted
ledger is strictly lower -- the ``topo/wan/routing-ratio`` row reports the
ratio (dominated by the cross-rack traffic the two trees carry).

On this CPU container the pallas rows run in interpret mode (wall times
are NOT TPU times); the engine itself is backend-agnostic -- only the
local solves dispatch through the registry.
"""
from __future__ import annotations

import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import json_row
from repro.core import topology
from repro.core.distributed import (distributed_kmeans_tree,
                                    graph_distributed_kmeans)
from repro.core.partition import pad_partition, partition_indices

BACKENDS = ("jnp", "pallas")
N_SITES = 9
LEDGER_UNITS = ("scalars", "points", "messages", "link_cost")


def _topologies():
    return {
        "ring": topology.ring(N_SITES),
        "star": topology.star(N_SITES),
        "grid": topology.grid(3, 3),
        "torus": topology.torus(3, 3),
        "er": topology.erdos_renyi(N_SITES, 0.3, seed=3),
        "preferential": topology.preferential(N_SITES, 2, seed=0),
        "wan": topology.wan_clusters(3, 3, cross_cost=16.0, cross_links=2,
                                     seed=0),
    }


def _site_data(scale: float):
    rng = np.random.default_rng(0)
    k, d = 4, 8
    per = max(int(400 * scale), 60)
    centers = 3.0 * rng.standard_normal((k, d))
    pts = np.concatenate(
        [centers[i] + 0.15 * rng.standard_normal((per, d)) for i in range(k)]
    ).astype(np.float32)
    idx = partition_indices(pts, N_SITES, "weighted", seed=1)
    sp, sm = pad_partition(pts, idx)
    return jnp.asarray(sp), jnp.asarray(sm), k


def _time(fn, n_runs: int) -> tuple:
    out = fn()                      # warm-up + result for parity checks
    jax.block_until_ready(out.centers)
    t0 = time.time()
    for _ in range(n_runs):
        r = fn()
        jax.block_until_ready(r.centers)
    return out, (time.time() - t0) / n_runs * 1e6


def _ledger_match(a, b) -> bool:
    return all(getattr(a.ledger, u) == getattr(b.ledger, u)
               for u in LEDGER_UNITS)


def run(scale: float = 1.0, n_runs: int = 2,
        out_rows: List[str] | None = None) -> List[str]:
    rows = out_rows if out_rows is not None else []
    interpreted = jax.default_backend() != "tpu"
    sp, sm, k = _site_data(scale)
    t = 120
    key = jax.random.PRNGKey(0)
    topos = _topologies()

    for backend in BACKENDS:
        for name, g in topos.items():
            runs = {}
            for engine in ("sim", "exec"):
                res, us = _time(
                    lambda e=engine: graph_distributed_kmeans(
                        key, sp, sm, k, t=t, graph=g, backend=backend,
                        engine=e),
                    n_runs)
                runs[engine] = (res, us)
            sim_res, sim_us = runs["sim"]
            ex_res, ex_us = runs["exec"]
            ledger_match = _ledger_match(sim_res, ex_res)
            r1 = ex_res.exec_detail.rounds["round1"]
            for engine, (res, us) in runs.items():
                json_row(
                    rows, f"topo/{name}/{engine}/{backend}", us,
                    topology=name, engine=engine, backend=backend,
                    routing="flood",
                    interpret=bool(interpreted and backend == "pallas"),
                    n_sites=g.n, m_edges=g.m,
                    diameter=topology.diameter(g),
                    scalars=res.ledger.scalars, points=res.ledger.points,
                    messages=res.ledger.messages,
                    link_cost=res.ledger.link_cost,
                    exec_rounds=(r1.rounds if engine == "exec" else None),
                    ledger_match=ledger_match,
                    centers_bit_equal=bool(np.array_equal(
                        np.asarray(res.centers),
                        np.asarray(sim_res.centers))),
                )

        # BFS tree over the ER graph (the paper's Zhang-et-al. setting)
        tree = topology.bfs_spanning_tree(topos["er"], root=0)
        tree_runs = {}
        for engine in ("sim", "exec"):
            res, us = _time(
                lambda e=engine: distributed_kmeans_tree(
                    key, sp, sm, k, t=t, tree=tree, backend=backend,
                    engine=e),
                n_runs)
            tree_runs[engine] = (res, us)
        sim_res = tree_runs["sim"][0]
        ledger_match = _ledger_match(sim_res, tree_runs["exec"][0])
        for engine, (res, us) in tree_runs.items():
            json_row(
                rows, f"topo/bfs-tree/{engine}/{backend}", us,
                topology="bfs-tree", engine=engine, backend=backend,
                routing="bfs",
                interpret=bool(interpreted and backend == "pallas"),
                n_sites=tree.n, height=tree.height,
                scalars=res.ledger.scalars, points=res.ledger.points,
                messages=res.ledger.messages,
                link_cost=res.ledger.link_cost,
                ledger_match=ledger_match,
                centers_bit_equal=bool(np.array_equal(
                    np.asarray(res.centers), np.asarray(sim_res.centers))),
            )

    # -- weighted routing payoff: min-cost vs BFS trees on WAN links --------
    g = topos["wan"]
    routing_link = {}
    for routing in ("bfs", "min_cost"):
        tree = topology.spanning_tree(g, routing=routing)
        runs = {}
        for engine in ("sim", "exec"):
            res, us = _time(
                lambda e=engine: graph_distributed_kmeans(
                    key, sp, sm, k, t=t, graph=g, backend="jnp",
                    routing=routing, engine=e),
                n_runs)
            runs[engine] = (res, us)
        sim_res = runs["sim"][0]
        ledger_match = _ledger_match(sim_res, runs["exec"][0])
        routing_link[routing] = sim_res.ledger.link_cost
        for engine, (res, us) in runs.items():
            json_row(
                rows, f"topo/wan/{routing}/{engine}", us,
                topology="wan", engine=engine, backend="jnp",
                routing=routing, n_sites=g.n, m_edges=g.m,
                height=tree.height,
                tree_edge_cost=tree.edge_cost_total(),
                scalars=res.ledger.scalars, points=res.ledger.points,
                messages=res.ledger.messages,
                link_cost=res.ledger.link_cost,
                ledger_match=ledger_match,
                centers_bit_equal=bool(np.array_equal(
                    np.asarray(res.centers), np.asarray(sim_res.centers))),
            )
    bfs_tree = topology.bfs_spanning_tree(g)
    mst_tree = topology.mst_spanning_tree(g)
    json_row(
        rows, "topo/wan/routing-ratio", 0.0,
        topology="wan", routing="min_cost_vs_bfs",
        link_cost_bfs=routing_link["bfs"],
        link_cost_min_cost=routing_link["min_cost"],
        link_ratio=routing_link["bfs"] / routing_link["min_cost"],
        tree_edge_cost_bfs=bfs_tree.edge_cost_total(),
        tree_edge_cost_min_cost=mst_tree.edge_cost_total(),
        cross_edge_ratio=(bfs_tree.edge_cost_total()
                          / mst_tree.edge_cost_total()),
        min_cost_wins=bool(routing_link["min_cost"] < routing_link["bfs"]),
    )
    return rows


if __name__ == "__main__":
    run(scale=0.1, n_runs=1)
