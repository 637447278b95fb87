"""Asynchronous failure-prone WAN deployment, end to end.

A 12-site deployment (3 racks, 16x cross-rack links) runs the paper's
Algorithm 1 while the network misbehaves: one cross link is down, one
node takes a 3-round outage, one node dies and never rejoins, and links
occasionally re-deliver old messages. The demo

1. certifies quiescence for every activation mode (synchronous-under-
   faults, per-edge clocks, randomized gossip),
2. runs ``graph_distributed_kmeans(engine="exec", faults=...)`` and
   checks the centers bit-match the host oracle restricted to the
   surviving sites, and
3. streams contaminated batches into a ``DistributedStream`` and runs
   one asynchronous aggregation round under the same plan.

    PYTHONPATH=src python examples/wan_faults.py [--backend pallas]
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.partition import pad_partition, partition_indices
from repro.core.topology import wan_clusters
from repro.data.synthetic import contaminated_stream
from repro.stream.ingest import DistributedStream
from repro.stream.tree import TreeConfig
from repro.wan import FaultPlan, certify_quiescence
from repro.cache import enable_compilation_cache


def main():
    enable_compilation_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default=None,
                    help="local-solve backend (e.g. pallas; interpret "
                         "mode on CPU)")
    args = ap.parse_args()
    g = wan_clusters(3, 4, cross_links=2, seed=0)
    plan = FaultPlan(drop=((0, 1),),           # one intra-rack link cut
                     churn=((5, 1, 3),         # node 5: rounds [1, 3) outage
                            (9, 0, -1)),       # node 9: dead from round 0
                     dup_rate=0.15, seed=3)
    surv = plan.surviving_nodes(g.n)
    print(f"topology: {g.n} sites, {g.m} edges; survivors {surv.tolist()}")

    # clustered site data
    rng = np.random.default_rng(2)
    centers = 3.0 * rng.standard_normal((3, 5))
    pts = np.concatenate(
        [c + 0.2 * rng.standard_normal((140, 5)) for c in centers]
    ).astype(np.float32)
    sp, sm = pad_partition(pts, partition_indices(pts, g.n, "weighted",
                                                  seed=1))
    sp, sm = jnp.asarray(sp), jnp.asarray(sm)
    key = jax.random.PRNGKey(17)

    print("\n-- quiescence certificates ------------------------------------")
    for mode in ("full", "clock", "random"):
        cert = certify_quiescence(g, plan, mode=mode, seed=4,
                                  check_clustering=True, key=key,
                                  site_points=sp, site_mask=sm, k=3, t=48,
                                  backend=args.backend)
        bound = "-" if cert.bound is None else cert.bound
        print(f"  mode={mode:6s} complete@{cert.rounds_to_complete:3d} "
              f"(bound {bound}), quiesce@{cert.rounds_to_quiesce:3d}, "
              f"staleness {cert.staleness_mean:5.2f}, "
              f"dup extra {cert.duplicate_messages_extra:7.0f} msgs "
              f"(tables unchanged: {cert.duplicates_idempotent}), "
              f"centers==oracle: {cert.centers_match}  "
              f"=> {'OK' if cert.ok else 'FAIL'}")

    print("\n-- one asynchronous stream round under the same faults --------")
    cfg = TreeConfig(k=4, t=60, d=6, batch_size=200, levels=12)
    ds = DistributedStream(g, cfg, key=jax.random.PRNGKey(5))
    batches = contaminated_stream(2 * g.n, cfg.batch_size, d=cfg.d, k=4,
                                  outlier_frac=0.05, burst_every=8, seed=5)
    for i, b in enumerate(batches):
        ds.push(i % g.n, b)
    res = ds.aggregate(k=4, t=120, mode="resample", engine="async",
                       faults=plan)
    d = res.ledger.as_dict()
    print(f"  coreset {tuple(res.coreset.points.shape)} from "
          f"{surv.size}/{g.n} surviving sites")
    print(f"  round ledger: {d['messages']:.0f} messages, "
          f"link_cost {d['link_cost']:.0f}, staleness {d['staleness']:.2f}")
    print(f"  centers:\n{np.asarray(res.centers).round(2)}")


if __name__ == "__main__":
    main()
