"""Quickstart: distributed k-means via coresets on a general topology.

Simulates 9 sites on a 3x3 grid network holding skewed shards of a Gaussian
mixture, builds the distributed coreset (Algorithm 1), clusters it
(Algorithm 2), and compares against centralized Lloyd on the full data --
while counting every transmitted point (Algorithm 3 ledger).

    PYTHONPATH=src python examples/quickstart.py [--backend jnp|jnp_chunked|pallas]

For the streaming counterpart -- merge-and-reduce ingestion, per-site
streams with periodic aggregation rounds, and live cluster queries -- see
``examples/streaming.py``.
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (clustering, distributed_kmeans, grid,
                        bfs_spanning_tree, distributed_kmeans_tree)
from repro.core.partition import pad_partition, partition_indices
from repro.cache import enable_compilation_cache


def main(argv=None):
    enable_compilation_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default=None,
                    help="clustering backend: jnp | jnp_chunked | pallas "
                         "(default: auto)")
    args = ap.parse_args(argv)
    rng = np.random.default_rng(0)
    k, d = 5, 10
    centers = 3.0 * rng.standard_normal((k, d))
    data = np.concatenate(
        [c + 0.2 * rng.standard_normal((4000, d)) for c in centers]
    ).astype(np.float32)
    print(f"dataset: {data.shape[0]} points in R^{d}, k={k}")

    g = grid(3, 3)
    print(f"network: 3x3 grid, {g.n} sites, {g.m} edges")
    idx = partition_indices(data, g.n, "weighted", seed=1)
    sp, sm = pad_partition(data, idx)
    print("site sizes:", [len(i) for i in idx])

    key = jax.random.PRNGKey(0)
    res = distributed_kmeans(key, jnp.asarray(sp), jnp.asarray(sm), k,
                             t=400, graph=g, backend=args.backend)

    _, central_cost = clustering.solve(key, jnp.asarray(data), k,
                                       restarts=4, backend=args.backend)
    dist_cost = clustering.cost(jnp.asarray(data), res.centers)
    print(f"\ncentralized Lloyd cost : {float(central_cost):12.1f} "
          f"(ships {data.shape[0]} points)")
    print(f"distributed coreset cost: {float(dist_cost):12.1f} "
          f"(ratio {float(dist_cost/central_cost):.4f})")
    print(f"communication: {res.ledger.points:.0f} points + "
          f"{res.ledger.scalars:.0f} scalars "
          f"= {res.ledger.bytes/1e3:.1f} KB "
          f"vs {data.nbytes/1e3:.1f} KB raw")

    tree = bfs_spanning_tree(g, root=0)
    res_t = distributed_kmeans_tree(key, jnp.asarray(sp), jnp.asarray(sm),
                                    k, t=400, tree=tree,
                                    backend=args.backend)
    print(f"\nrooted-tree variant (h={tree.height}): "
          f"ratio {float(clustering.cost(jnp.asarray(data), res_t.centers)/central_cost):.4f}, "
          f"{res_t.ledger.points:.0f} points moved")

    # pluggable round protocols: same API, different communication shape
    print(f"\n{'strategy':<12} {'ratio':>8} {'KB':>8}")
    for name in ("algorithm1", "cohen_addad", "mapreduce"):
        r = distributed_kmeans(key, jnp.asarray(sp), jnp.asarray(sm), k,
                               t=400, graph=g, backend=args.backend,
                               strategy=name)
        ratio = float(clustering.cost(jnp.asarray(data), r.centers)
                      / central_cost)
        print(f"{name:<12} {ratio:>8.4f} {r.ledger.bytes/1e3:>8.1f}")


if __name__ == "__main__":
    main()
