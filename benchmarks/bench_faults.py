"""WAN fault-injection benchmarks (``BENCH_faults.json`` is the CI
artifact).

Two curves, each asserted-while-measured (every row carries the
quiescence-certification flags, so a regression in the runtime shows up
as a flipped boolean in the artifact, not just a moved number):

* ``faults/staleness/*`` -- staleness vs link heterogeneity: per-edge
  clock mode on ``wan_clusters`` with the cross-rack cost swept 1x..16x.
  The period of an edge is its cost ratio, so the mean staleness (excess
  rounds past each node's lossless-flood eccentricity) climbs with the
  cost spread while the cost-weighted ledger stays schedule-independent
  (send-once relay: the same transmissions happen, later).

* ``faults/quiesce/*`` -- drop-rate vs rounds-to-quiesce: seeded fault
  plans of increasing edge-drop fraction (plus one churn outage) on
  three topologies, mode ``"full"``. Reported rounds are certified
  against the ``horizon + surviving-diameter`` bound.

``faults/cert/*`` rows run the full certificate (completion bound,
quiescence, duplicate idempotence, and -- at ``--full`` scale --
engine-vs-restricted-oracle bit-identity) once per activation mode on a
churn-under-duplication plan.
"""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import json_row
from repro.core import topology
from repro.core.partition import pad_partition, partition_indices
from repro.wan.faults import FaultPlan, random_fault_plan
from repro.wan.quiesce import certify_quiescence
from repro.wan.runtime import wan_flood_exec

CROSS_COSTS = (1.0, 2.0, 4.0, 8.0, 16.0)
DROP_FRACS = (0.0, 0.1, 0.2, 0.3)


def _quiesce_topologies():
    return {
        "grid": topology.grid(3, 3),
        "er": topology.erdos_renyi(12, 0.35, seed=3),
        "wan": topology.wan_clusters(3, 3, cross_cost=16.0, cross_links=2,
                                     seed=0),
    }


def _payload(n: int) -> jnp.ndarray:
    return jnp.arange(n, dtype=jnp.float32)[:, None] * 100.0 + 3.0


def run(scale: float = 1.0, n_runs: int = 1,
        out_rows: List[str] | None = None) -> List[str]:
    rows = out_rows if out_rows is not None else []
    del n_runs  # the rows count rounds and messages, not time

    # -- staleness vs link-cost heterogeneity (clock mode, fault-free) ------
    for cc in CROSS_COSTS:
        g = topology.wan_clusters(3, 3, cross_cost=cc, cross_links=2, seed=0)
        _, res = wan_flood_exec(g, _payload(g.n), mode="clock",
                                unit_scalars=1.0)
        d = res.ledger.as_dict()
        json_row(
            rows, f"faults/staleness/wan/cross_{cc:g}", 0.0,
            topology="wan", mode="clock", cross_cost=cc,
            n_sites=g.n, m_edges=g.m, diameter=topology.diameter(g),
            max_period=int(np.rint(cc)),
            staleness=d["staleness"],
            rounds_to_complete=res.rounds_to_complete,
            rounds_to_quiesce=res.rounds_to_quiesce,
            link_cost=d["link_cost"], messages=d["messages"],
        )

    # -- drop rate vs rounds to quiesce (full mode, certified) --------------
    for name, g in _quiesce_topologies().items():
        sync_rounds = topology.diameter(g)
        for df in DROP_FRACS:
            plan = random_fault_plan(g, seed=7, drop_frac=df, n_churn=1,
                                     churn_window=(1, 3))
            cert = certify_quiescence(g, plan, mode="full", seed=2)
            _, res = wan_flood_exec(g, _payload(g.n), mode="full",
                                    faults=plan, unit_scalars=1.0, seed=2)
            json_row(
                rows, f"faults/quiesce/{name}/drop_{df:g}", 0.0,
                topology=name, mode="full", drop_frac=df,
                edges_dropped=len(plan.drop), n_churn=len(plan.churn),
                horizon=plan.horizon(),
                sync_rounds=sync_rounds,
                surviving_diameter=cert.surviving_diameter,
                bound=cert.bound,
                rounds_to_complete=res.rounds_to_complete,
                rounds_to_quiesce=res.rounds_to_quiesce,
                staleness=res.ledger.staleness,
                messages=res.ledger.as_dict()["messages"],
                cert_ok=cert.ok,
            )

    # -- full certificates, one per activation mode -------------------------
    g = topology.wan_clusters(3, 4, cross_links=2, seed=0)
    plan = FaultPlan(drop=((0, 1),), churn=((5, 1, 3), (9, 0, -1)),
                     dup_rate=0.2, seed=3)
    clustering_kw = {}
    if scale >= 1.0:
        rng = np.random.default_rng(2)
        pts = np.concatenate(
            [c + 0.2 * rng.standard_normal((140, 5)) for c in
             3.0 * rng.standard_normal((3, 5))]).astype(np.float32)
        sp, sm = pad_partition(pts, partition_indices(pts, g.n, "weighted",
                                                      seed=1))
        clustering_kw = dict(check_clustering=True,
                             key=jax.random.PRNGKey(17),
                             site_points=jnp.asarray(sp),
                             site_mask=jnp.asarray(sm), k=3, t=48)
    for mode in ("full", "clock", "random"):
        cert = certify_quiescence(g, plan, mode=mode, seed=4,
                                  **clustering_kw)
        json_row(
            rows, f"faults/cert/{mode}", 0.0,
            topology="wan", mode=mode,
            horizon=cert.horizon,
            surviving_diameter=cert.surviving_diameter,
            max_period=cert.max_period,
            rounds_to_complete=cert.rounds_to_complete,
            rounds_to_quiesce=cert.rounds_to_quiesce,
            bound=cert.bound,
            completed_within_bound=cert.completed_within_bound,
            quiesced=cert.quiesced,
            duplicates_idempotent=cert.duplicates_idempotent,
            duplicate_messages_extra=cert.duplicate_messages_extra,
            centers_match=cert.centers_match,
            staleness=cert.staleness_mean,
            cert_ok=cert.ok,
        )
    return rows


if __name__ == "__main__":
    run(scale=0.1, n_runs=1)
