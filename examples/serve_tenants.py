"""Multi-tenant serving demo: many clustering models behind one engine.

Registers a mix of tenants on a shared :class:`ClusterServeEngine`:

* **static tenants** -- fixed center sets (offline-trained models, ragged
  k and d), the common read-only serving case;
* **live tenants** -- :class:`ClusterQueryService` streams whose centers
  go stale as data arrives and re-solve *through the engine's refresh
  budget*, so a re-solve never blocks other tenants' queries.

Each step the engine drains the admission queue, assembles same-shape
query chunks across tenants into stacked batches, and launches one fused
``query_assignments_batched`` dispatch per bucket (the Pallas
``distance_argmin_batched`` kernel on TPU) instead of one dispatch per
tenant.

    PYTHONPATH=src python examples/serve_tenants.py [--backend pallas] \
        [--tenants 64] [--steps 20] [--refresh-budget 1]

(On CPU the pallas backend runs the kernels in interpret mode.)
"""
import argparse

import numpy as np

from repro.data.synthetic import drifting_mixture_stream
from repro.serve import ClusterServeEngine, StaticCenters
from repro.stream import ClusterQueryService, StreamState, TreeConfig
from repro.cache import enable_compilation_cache


def main(argv=None):
    enable_compilation_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default=None,
                    help="clustering backend: jnp | jnp_chunked | pallas")
    ap.add_argument("--tenants", type=int, default=64,
                    help="static tenants (plus 2 live stream tenants)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--queries", type=int, default=8,
                    help="queries per active tenant per step")
    ap.add_argument("--refresh-budget", type=int, default=1,
                    help="max center re-solves per engine step")
    args = ap.parse_args(argv)

    rng = np.random.default_rng(0)
    eng = ClusterServeEngine(backend=args.backend, max_bucket=256,
                             refresh_budget=args.refresh_budget)

    # static tenants: ragged k/d mix, as offline-trained models would be
    dims = {}
    for _ in range(args.tenants):
        k = int(rng.integers(2, 9))
        d = int(rng.choice([8, 16]))
        tid = eng.add_tenant(
            StaticCenters(rng.standard_normal((k, d)).astype(np.float32)),
            k=k, d=d)
        dims[tid] = d

    # live tenants: streams whose centers re-solve under the engine budget
    d_live, k_live = 8, 4
    cfg = TreeConfig(k=k_live, t=60, d=d_live, batch_size=200, levels=12,
                     backend=args.backend)
    live = []
    for seed in (1, 2):
        stream = StreamState(cfg)
        svc = ClusterQueryService(stream, k=k_live, staleness_frac=0.3,
                                  backend=args.backend, engine=eng)
        tid = eng.add_tenant(svc, k=k_live, d=d_live)
        dims[tid] = d_live
        live.append((svc, tid, seed))

    print(f"{len(dims)} tenants ({args.tenants} static + {len(live)} live) "
          f"on one engine, backend={eng.backend}, "
          f"refresh_budget={args.refresh_budget}")

    tids = list(dims)
    for step in range(args.steps):
        # live tenants ingest (their centers drift stale mid-run)
        for svc, _, seed in live:
            batch = next(iter(drifting_mixture_stream(
                1, cfg.batch_size, d=d_live, k=k_live,
                seed=100 * seed + step)))
            svc.push(batch)
        # a random half of the tenants sends a query burst
        active = rng.choice(tids, size=len(tids) // 2, replace=False)
        tickets = [eng.enqueue(t, rng.standard_normal(
            (args.queries, dims[t])).astype(np.float32)) for t in active]
        served = eng.run()
        assert all(t.done for t in tickets) and served == len(
            tickets) * args.queries

    st = eng.stats
    fused = st.n_tenant_dispatches / max(st.n_dispatches, 1)
    print(f"served {st.n_queries} queries in {st.n_steps} steps: "
          f"{st.n_dispatches} fused dispatches for "
          f"{st.n_tenant_dispatches} tenant-chunks "
          f"({fused:.1f} tenants/dispatch)")
    print(f"refreshes: {st.n_refreshes} run, {st.n_deferred_refreshes} "
          f"deferred past a step (stale tenants kept serving cached "
          f"centers)")
    print(f"compiled specializations: {len(eng.compiled_shapes)} "
          f"(bounded by the pow2 bucket grid)")
    print(f"padding overhead: {st.n_padded} padded rows "
          f"({st.n_padded / (st.n_padded + st.n_queries):.1%}); "
          f"phase wall-clock: refresh {st.refresh_s:.2f}s / "
          f"assign {st.assign_s:.2f}s")
    for svc, tid, _ in live:
        print(f"  live tenant {tid}: {svc.stats.n_refreshes} re-solves, "
              f"staleness at exit {svc.staleness():.0f} pts")


if __name__ == "__main__":
    main()
