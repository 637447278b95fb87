"""Streaming demo: merge-and-reduce ingestion + live cluster queries.

Feeds a drifting Gaussian-mixture stream (the centers random-walk, so no
prefix is representative) through the streaming subsystem, three ways:

1. a single-site :class:`CoresetTree` -- bounded O(log n) memory, exact
   total-weight preservation;
2. a :class:`ClusterQueryService` on top -- staleness-bounded center
   refreshes while answering nearest-center queries mid-stream;
3. a :class:`DistributedStream` over a grid topology -- per-node trees plus
   periodic Algorithm-1 aggregation rounds, with the per-round
   communication ledger.

    PYTHONPATH=src python examples/streaming.py [--backend pallas] \
        [--batches 50] [--batch-size 1000]

(On CPU the pallas backend runs the kernels in interpret mode.)
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import clustering
from repro.core.coreset import build_coreset
from repro.core.topology import grid
from repro.data.synthetic import drifting_mixture_stream
from repro.stream import (ClusterQueryService, DistributedStream, StreamState,
                          TreeConfig)
from repro.cache import enable_compilation_cache


def main(argv=None):
    enable_compilation_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default=None,
                    help="clustering backend: jnp | jnp_chunked | pallas")
    ap.add_argument("--batches", type=int, default=50)
    ap.add_argument("--batch-size", type=int, default=1000)
    ap.add_argument("--k", type=int, default=5)
    ap.add_argument("--dim", type=int, default=10)
    args = ap.parse_args(argv)

    k, d = args.k, args.dim
    cfg = TreeConfig(k=k, t=100, d=d, batch_size=args.batch_size, levels=20,
                     backend=args.backend)
    batches = list(drifting_mixture_stream(args.batches, args.batch_size,
                                           d=d, k=k, drift=0.08, seed=0))
    n_total = args.batches * args.batch_size
    print(f"stream: {args.batches} batches x {args.batch_size} pts in R^{d} "
          f"(drifting mixture), k={k}")

    # -- 1. single-site ingestion -------------------------------------------
    stream = StreamState(cfg)
    svc = ClusterQueryService(stream, k=k, staleness_frac=0.2,
                              key=jax.random.PRNGKey(1))
    probe = jnp.asarray(batches[0][:256])
    for i, b in enumerate(batches):
        svc.push(b)
        if (i + 1) % max(args.batches // 4, 1) == 0:
            assign, dist = svc.query(probe)   # live queries mid-stream
            print(f"  after batch {i+1:3d}: summary "
                  f"{stream.tree.max_summary_points():4d} pts in "
                  f"{stream.tree.occupied_levels()} buckets, "
                  f"refreshes={svc.stats.n_refreshes}, "
                  f"probe mean d^2={float(jnp.mean(dist)):.3f}")

    s = stream.summary()
    print(f"summary: {int(s.effective_size())} weighted points for "
          f"{n_total} ingested "
          f"(total weight {float(jnp.sum(s.weights)):.1f}); "
          f"bound {cfg.slot} * {stream.tree.occupied_levels()} buckets")

    # -- 2. streaming vs offline coreset quality ----------------------------
    full = jnp.asarray(np.concatenate(batches))
    centers_stream = svc.centers()
    stream_cost = float(clustering.cost(full, centers_stream,
                                        backend=args.backend))
    t_eq = max(int(s.effective_size()) - k, k + 1)
    off = build_coreset(jax.random.PRNGKey(2), full, k=k, t=t_eq,
                        backend=args.backend)
    c_off, _ = clustering.solve(jax.random.PRNGKey(3), off.points, k,
                                weights=off.weights, lloyd_iters=8,
                                restarts=2, backend=args.backend)
    off_cost = float(clustering.cost(full, c_off, backend=args.backend))
    print(f"k-means cost on full data: streaming {stream_cost:.1f} vs "
          f"offline coreset {off_cost:.1f} "
          f"(ratio {stream_cost / off_cost:.3f})")

    # -- 3. distributed streams over a topology -----------------------------
    g = grid(2, 2)
    ds = DistributedStream(g, cfg, key=jax.random.PRNGKey(4))
    agg_every = max(args.batches // (2 * g.n), 1) * g.n
    res = None
    for i, b in enumerate(batches):
        ds.push(i % g.n, b)                  # round-robin arrivals
        if (i + 1) % agg_every == 0:
            res = ds.aggregate(k=k, t=200)
    if res is None:
        res = ds.aggregate(k=k, t=200)
    dist_cost = float(clustering.cost(full, res.centers,
                                      backend=args.backend))
    led = ds.ledger.as_dict(by_phase=True)
    print(f"\ndistributed ({g.n} sites on a 2x2 grid, {ds.rounds} "
          f"aggregation rounds): cost ratio "
          f"{dist_cost / off_cost:.3f} vs offline")
    per_round = led["phases"][f"stream_round_{ds.rounds - 1}"]
    print(f"communication: {led['points']:.0f} points total "
          f"({per_round['points']:.0f} pts = {per_round['bytes']/1e3:.1f} KB "
          f"per round) vs {n_total} raw points/round for re-shipping")


if __name__ == "__main__":
    main()
