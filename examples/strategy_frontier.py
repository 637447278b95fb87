"""Coreset strategies side by side: the accuracy-vs-bytes frontier.

Runs each registered round protocol -- ``algorithm1`` (the paper's
two-round choreography), ``cohen_addad`` ((1+eps) refined sensitivities,
same communication shape), and ``mapreduce`` (one shuffle, no scalar
exchange, no diameter floods) -- over the same sites on a heterogeneous
WAN topology, and prints one frontier line per strategy: k-means cost
ratio vs a centralized solve, raw bytes, and cost-weighted link bytes.

    PYTHONPATH=src python examples/strategy_frontier.py \
        [--backend jnp|jnp_chunked|pallas] [--t 200]

The full sweep (budget curves, three topologies, the Zhang et al. lower
bound column) is ``python -m benchmarks.run --only frontier``.
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (available_strategies, clustering,
                        graph_distributed_kmeans, wan_clusters)
from repro.core.partition import pad_partition, partition_indices
from repro.cache import enable_compilation_cache


def main(argv=None):
    enable_compilation_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default=None,
                    help="clustering backend: jnp | jnp_chunked | pallas")
    ap.add_argument("--t", type=int, default=200, help="sample budget")
    args = ap.parse_args(argv)

    rng = np.random.default_rng(0)
    k, d = 4, 8
    centers = 3.0 * rng.standard_normal((k, d))
    data = np.concatenate(
        [c + 0.2 * rng.standard_normal((900, d)) for c in centers]
    ).astype(np.float32)

    g = wan_clusters(3, 3, cross_cost=16.0, cross_links=2, seed=0)
    idx = partition_indices(data, g.n, "weighted", seed=1)
    sp, sm = pad_partition(data, idx)
    sp, sm = jnp.asarray(sp), jnp.asarray(sm)
    print(f"dataset: {data.shape[0]} points in R^{d}, k={k}; "
          f"network: 3 racks x 3 (cross-rack links 16x), t={args.t}")

    key = jax.random.PRNGKey(0)
    _, central = clustering.solve(key, jnp.asarray(data), k, restarts=4,
                                  backend=args.backend)

    print(f"\n{'strategy':<12} {'cost ratio':>10} {'KB moved':>10} "
          f"{'link-KB':>10}")
    for name in available_strategies():
        r = graph_distributed_kmeans(key, sp, sm, k, t=args.t, graph=g,
                                     backend=args.backend, strategy=name)
        ratio = float(clustering.cost(jnp.asarray(data), r.centers) / central)
        print(f"{name:<12} {ratio:>10.4f} {r.ledger.bytes/1e3:>10.1f} "
              f"{r.ledger.link_cost/1e3:>10.1f}")
    print("\nmapreduce's single shuffle skips the scalar exchange and the "
          "diameter floods\nentirely -- same coreset weight mass, a "
          "fraction of the bytes.")


if __name__ == "__main__":
    main()
