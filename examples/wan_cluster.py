"""Two-rack WAN demo: BFS vs min-cost routing for Algorithm 2, per phase.

Builds a ``wan_clusters`` topology -- two racks of cheap (cost-1)
intra-rack links joined by a handful of expensive (cost-16) cross-rack
links -- and runs the executed Algorithm-2 tree protocol under both
routing policies. Hop-count (BFS) routing enters the remote rack through
every shallow cross link it finds; min-cost (Prim) routing pays for
exactly one. The per-phase ledgers below show where that difference
lands: the gathers price each site's root path, the broadcasts price
every tree edge, and the ``link_cost`` column (cost-weighted bytes) is
what a WAN bill would charge.

    PYTHONPATH=src python examples/wan_cluster.py [--t 200] \
        [--rack-size 4] [--cross-links 3] [--cross-cost 16]
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.distributed import graph_distributed_kmeans
from repro.core.partition import pad_partition, partition_indices
from repro.core.topology import spanning_tree, wan_clusters
from repro.cache import enable_compilation_cache


def main(argv=None):
    enable_compilation_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--t", type=int, default=200, help="coreset budget")
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--rack-size", type=int, default=4)
    ap.add_argument("--cross-links", type=int, default=3)
    ap.add_argument("--cross-cost", type=float, default=16.0)
    ap.add_argument("--per-cluster", type=int, default=300)
    args = ap.parse_args(argv)

    g = wan_clusters(2, args.rack_size, cross_cost=args.cross_cost,
                     cross_links=args.cross_links, seed=0)
    print(f"wan_clusters: 2 racks x {args.rack_size} nodes, "
          f"{g.m} links ({sum(1 for c in g.costs if c > 1.0)} cross-rack "
          f"at cost {args.cross_cost:g})")

    rng = np.random.default_rng(0)
    k, d = args.k, 8
    centers = 3.0 * rng.standard_normal((k, d))
    pts = np.concatenate(
        [centers[i] + 0.15 * rng.standard_normal((args.per_cluster, d))
         for i in range(k)]).astype(np.float32)
    idx = partition_indices(pts, g.n, "weighted", seed=1)
    sp, sm = pad_partition(pts, idx)
    sp, sm = jnp.asarray(sp), jnp.asarray(sm)
    key = jax.random.PRNGKey(0)

    results = {}
    for routing in ("bfs", "min_cost"):
        tree = spanning_tree(g, routing=routing)
        cross = sum(1 for v in range(g.n)
                    if tree.parent[v] >= 0 and tree.parent_costs()[v] > 1.0)
        res = graph_distributed_kmeans(key, sp, sm, k, t=args.t, graph=g,
                                       routing=routing, engine="exec")
        results[routing] = res
        print(f"\nrouting={routing}: tree height {tree.height}, "
              f"{cross} cross-rack link(s) in tree, "
              f"total tree edge cost {tree.edge_cost_total():g}")
        print(f"  {'phase':18s} {'scalars':>8s} {'points':>8s} "
              f"{'bytes':>10s} {'link_cost':>10s}")
        d_l = res.ledger.as_dict(by_phase=True)
        for phase, sub in d_l["phases"].items():
            print(f"  {phase:18s} {sub['scalars']:8.0f} {sub['points']:8.0f}"
                  f" {sub['bytes']:10.0f} {sub['link_cost']:10.0f}")
        print(f"  {'total':18s} {d_l['scalars']:8.0f} {d_l['points']:8.0f}"
              f" {d_l['bytes']:10.0f} {d_l['link_cost']:10.0f}")

    bfs_l = results["bfs"].ledger.link_cost
    mc_l = results["min_cost"].ledger.link_cost
    same = np.array_equal(np.asarray(results["bfs"].centers),
                          np.asarray(results["min_cost"].centers))
    print(f"\nmin-cost routing ships {bfs_l / mc_l:.2f}x fewer "
          f"cost-weighted bytes than BFS ({mc_l:.0f} vs {bfs_l:.0f}), "
          f"centers bit-identical: {same}")
    assert same, "routing must not change the clustering result"
    if min(args.cross_links, args.rack_size) >= 2:   # effective link count
        # with a single cross link both trees must use it (BFS can even
        # edge out min-cost on gather paths); min-cost strictly wins once
        # BFS has multiple shallow entry points to pay for
        assert mc_l < bfs_l, "min-cost routing must beat BFS on WAN links"
    elif mc_l >= bfs_l:
        print("(single cross link: both trees must cross it, no routing "
              "freedom to exploit)")


if __name__ == "__main__":
    main()
