"""Paper Sec. 5 end to end on one dataset: all three algorithms (ours /
COMBINE / Zhang et al.) across three topologies at equal communication.

    PYTHONPATH=src python examples/distributed_clustering.py [--scale 0.1]
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import baselines, clustering
from repro.core.coreset import distributed_coreset
from repro.core.distributed import _solve_on_coreset
from repro.core.partition import pad_partition, partition_indices
from repro.core.topology import bfs_spanning_tree, erdos_renyi, grid, preferential
from repro.data.synthetic import paper_dataset
from repro.cache import enable_compilation_cache


def main(argv=None):
    enable_compilation_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="colorhistogram")
    ap.add_argument("--scale", type=float, default=0.1)
    ap.add_argument("--t", type=int, default=600)
    args = ap.parse_args(argv)

    pts_np, k = paper_dataset(args.dataset, scale=args.scale)
    pts = jnp.asarray(pts_np)
    key = jax.random.PRNGKey(0)
    _, base = clustering.solve(key, pts, k, restarts=4)
    print(f"{args.dataset}: {pts.shape} k={k} "
          f"baseline cost {float(base):.1f}\n")
    print(f"{'topology':14s} {'partition':12s} {'ours':>8s} {'combine':>8s} "
          f"{'zhang':>8s}")

    for topo_name, g, part in [
        ("random", erdos_renyi(25, 0.3, seed=2), "weighted"),
        ("grid", grid(5, 5), "weighted"),
        ("preferential", preferential(25, 2, seed=2), "degree"),
    ]:
        idx = partition_indices(pts_np, g.n, part, seed=3,
                                degrees=g.degrees())
        sp, sm = pad_partition(pts_np, idx)
        sp, sm = jnp.asarray(sp), jnp.asarray(sm)

        dc = distributed_coreset(key, sp, sm, k, args.t)
        ours = _solve_on_coreset(key, dc.flatten(), k, "kmeans", 12)
        r_ours = float(clustering.cost(pts, ours) / base)

        cs = baselines.combine(key, sp, sm, k, t_total=args.t)
        comb = _solve_on_coreset(key, cs, k, "kmeans", 12)
        r_comb = float(clustering.cost(pts, comb) / base)

        tree = bfs_spanning_tree(g, root=0)
        s = max(args.t // g.n, k)
        zh, _ = baselines.zhang_tree(key, np.asarray(sp), np.asarray(sm),
                                     tree, k, s=s)
        zc = _solve_on_coreset(key, zh, k, "kmeans", 12)
        r_zh = float(clustering.cost(pts, zc) / base)

        print(f"{topo_name:14s} {part:12s} {r_ours:8.4f} {r_comb:8.4f} "
              f"{r_zh:8.4f}")


if __name__ == "__main__":
    main()
