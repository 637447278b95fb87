"""Collective-schedule races on the SPMD mesh path + the staged-overlap
coreset engine (``BENCH_collectives.json`` at the repo root is the CI
artifact; DESIGN.md Sec. 17 documents how to read it).

Two sections:

* **Mesh races** -- {all_gather, neighbor_rounds, torus_2d} x axis sizes
  {8, 16} x {kmeans, kmedian} on forced-host-device subprocess meshes
  (``benchmarks/run.py`` imports jax long before flags could be set, so
  each axis size gets its own subprocess, same idiom as the SPMD tests).
  Each row carries the analytic sequential hop depth per phase
  (``hops_round1``/``hops_round2`` via
  :func:`repro.core.message_passing.collective_hops`: one gather in
  Round 1, two in Round 2), the *measured* per-phase collective ledger
  from compiled HLO (``ppermutes_round1`` etc. via
  :func:`repro.roofline.hlo.collective_phase_analysis` -- the cross-check
  that the schedule compiled to exactly its claimed hop count), the
  end-to-end wall, and a ``centers_bit_equal`` flag against the
  all_gather oracle. On a single-core CPU host the wall measures
  dispatch+copy, not ICI -- the hop columns are the hardware-relevant
  ranking; torus_2d's (R-1)+(C-1) must be strictly below the ring's N-1
  for every N >= 16.

* **Staged overlap** -- the host engine raced lockstep
  (:func:`repro.core.coreset.distributed_coreset`) vs staged
  (:func:`repro.core.coreset.staged_distributed_coreset`) on a skewed
  partition: ``strict`` mode (bit-parity flag vs lockstep) and ``overlap``
  mode (per-site power-of-two bucketing + convergence early-exit, the
  wall-clock win; draws differ by construction, so quality is reported
  as the coreset-solve cost ratio instead of bit-equality).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import json_row
from repro.core import clustering
from repro.core.coreset import distributed_coreset, staged_distributed_coreset
from repro.core.distributed import _solve_on_coreset
from repro.core.partition import pad_partition, partition_indices

AXIS_SIZES = (8, 16)
MODES = ("all_gather", "neighbor_rounds", "torus_2d")
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_MESH_SCRIPT = textwrap.dedent("""
    import os, sys, json, time
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + str(%(n)d))
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.compat import shard_map
    from repro.core import spmd_distributed_kmeans
    from repro.core.distributed import spmd_distributed_kmeans_fn
    from repro.core.message_passing import collective_hops, torus_mesh_shape
    from repro.core.partition import partition_indices, pad_partition
    from repro.roofline.hlo import collective_phase_analysis

    N, scale, n_runs = %(n)d, %(scale)f, %(n_runs)d
    rng = np.random.default_rng(0)
    k, d = 4, 8
    per = max(int(400 * scale), 60)
    c0 = 3.0 * rng.standard_normal((k, d))
    pts = np.concatenate([c0[i] + 0.15 * rng.standard_normal((per, d))
                          for i in range(k)]).astype(np.float32)
    idx = partition_indices(pts, N, "weighted", seed=1)
    sp, sm = pad_partition(pts, idx)
    sp, sm = jnp.asarray(sp), jnp.asarray(sm)
    mesh = jax.make_mesh((N,), ("sites",))
    t = 256
    t_buffer = max(4 * t // N, 64)
    key = jax.random.PRNGKey(0)

    out, oracle = [], {}
    for mode in ("all_gather", "neighbor_rounds", "torus_2d"):
        mesh_shape = torus_mesh_shape(N) if mode == "torus_2d" else None
        hops = collective_hops(mode, N, mesh_shape)
        # measured per-phase collective ledger from compiled HLO
        fn = spmd_distributed_kmeans_fn("sites", N, k, t, t_buffer,
                                        collectives=mode,
                                        mesh_shape=mesh_shape)
        def device_fn(key, p, m):
            return fn(key, p.reshape(-1, p.shape[-1]), m.reshape(-1))
        hlo = jax.jit(shard_map(
            device_fn, mesh=mesh,
            in_specs=(P(), P("sites"), P("sites")),
            out_specs=(P(), P("sites"), P("sites")),
        )).lower(key, sp, sm).compile().as_text()
        ph = collective_phase_analysis(hlo)
        def counts(phase):
            a = ph[phase]
            return (int(a.collective_counts.get("collective-permute", 0)),
                    int(sum(a.collective_counts.values())),
                    float(a.ici_collective_bytes
                          + a.dcn_collective_bytes))
        pp1, cc1, by1 = counts("round1")
        pp2, cc2, by2 = counts("round2")
        for objective in ("kmeans", "kmedian"):
            def run():
                return spmd_distributed_kmeans(
                    mesh, "sites", key, sp, sm, k, t=t,
                    objective=objective, collectives=mode,
                    mesh_shape=mesh_shape)
            c, lc, ti = run()
            jax.block_until_ready(c)
            t0 = time.perf_counter()
            for _ in range(n_runs):
                jax.block_until_ready(run()[0])
            e2e = (time.perf_counter() - t0) / n_runs * 1e6
            if mode == "all_gather":
                oracle[objective] = np.asarray(c)
            out.append(dict(
                mode=mode, objective=objective, axis_size=N,
                mesh_shape=list(mesh_shape) if mesh_shape else None,
                hops_round1=hops, hops_round2=2 * hops,
                ppermutes_round1=pp1, ppermutes_round2=pp2,
                collectives_round1=cc1, collectives_round2=cc2,
                link_bytes_round1=by1, link_bytes_round2=by2,
                e2e_us=e2e,
                centers_bit_equal=bool(
                    (np.asarray(c) == oracle[objective]).all()),
            ))
    print("BENCH_JSON:" + json.dumps(out))
""")


def _mesh_rows(rows: List[str], axis_size: int, scale: float,
               n_runs: int) -> None:
    if jax.default_backend() != "cpu":
        # the children's devices come from --xla_force_host_platform_device_
        # count, which only the CPU backend honours; on an accelerator this
        # process already holds the chips, so a child could not reach them
        raise RuntimeError(
            f"the collectives mesh section runs on forced CPU devices only "
            f"(default backend here is {jax.default_backend()!r}); run the "
            f"mesh path across chips in one process: "
            f"python chip_smoke.py --chips 4")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(_REPO_ROOT, "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    env.pop("XLA_FLAGS", None)
    script = _MESH_SCRIPT % dict(n=axis_size, scale=scale, n_runs=n_runs)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=1800,
                         cwd=_REPO_ROOT)
    payload = [ln for ln in out.stdout.splitlines()
               if ln.startswith("BENCH_JSON:")]
    if not payload:
        raise RuntimeError(
            f"collectives mesh bench (N={axis_size}) produced no rows:\n"
            + out.stdout + out.stderr)
    for rec in json.loads(payload[0][len("BENCH_JSON:"):]):
        name = (f"collectives/{rec['mode']}/{rec['objective']}"
                f"/n{rec['axis_size']}")
        json_row(rows, name, rec.pop("e2e_us"), **rec)


def _staged_data(scale: float):
    """A deliberately skewed partition (weighted ~ |N(0,1)| site shares):
    the lockstep vmap pads every site to the largest site's slot count,
    which is exactly the FLOP waste the bucketed staged path recovers."""
    rng = np.random.default_rng(7)
    k, d = 4, 32
    per = max(int(40000 * scale), 6000)
    c0 = 3.0 * rng.standard_normal((k, d))
    pts = np.concatenate([c0[i] + 0.15 * rng.standard_normal((per, d))
                          for i in range(k)]).astype(np.float32)
    idx = partition_indices(pts, 8, "weighted", seed=3)
    sp, sm = pad_partition(pts, idx)
    return jnp.asarray(pts), jnp.asarray(sp), jnp.asarray(sm), k


def _staged_rows(rows: List[str], scale: float, n_runs: int) -> None:
    pts, sp, sm, k = _staged_data(scale)
    t, lloyd_iters = 256, 8
    key = jax.random.PRNGKey(0)
    kw = dict(k=k, t=t, lloyd_iters=lloyd_iters)

    def time_run(fn):
        res = fn()                  # warm-up (compiles every bucket)
        jax.block_until_ready(jax.tree_util.tree_leaves(res)[0])
        t0 = time.perf_counter()
        for _ in range(n_runs):
            res = fn()
            jax.block_until_ready(jax.tree_util.tree_leaves(res)[0])
        return res, (time.perf_counter() - t0) / n_runs * 1e6

    def quality(dc):
        centers = _solve_on_coreset(jax.random.fold_in(key, 1),
                                    dc.flatten(), k, "kmeans", 10)
        return float(clustering.cost(pts, centers))

    lock, lock_us = time_run(
        lambda: distributed_coreset(key, sp, sm, **kw))
    base_cost = quality(lock)

    variants = {
        "strict": dict(tol=0.0, site_buckets=False),
        "overlap": dict(tol=1e-3, site_buckets=True),
    }
    json_row(rows, "collectives/staged/lockstep", lock_us,
             variant="lockstep", n_sites=int(sp.shape[0]),
             site_slots=int(sp.shape[1]), t=t, lloyd_iters=lloyd_iters,
             cost_ratio=1.0, bit_equal_lockstep=True,
             speedup_vs_lockstep=1.0)
    for variant, knobs in variants.items():
        (dc, det), us = time_run(
            lambda kn=knobs: staged_distributed_coreset(key, sp, sm, **kw,
                                                        **kn))
        bit_eq = all(
            np.array_equal(np.asarray(getattr(dc, f)),
                           np.asarray(getattr(lock, f)))
            for f in ("points", "weights", "t_i", "local_costs"))
        json_row(
            rows, f"collectives/staged/{variant}", us,
            variant=variant, n_sites=int(sp.shape[0]),
            site_slots=int(sp.shape[1]),
            site_lengths=list(det.site_lengths),
            iters_run=[int(x) for x in np.asarray(det.iters_run)],
            t=t, lloyd_iters=lloyd_iters, **knobs,
            cost_ratio=quality(dc) / base_cost,
            bit_equal_lockstep=bit_eq,
            speedup_vs_lockstep=lock_us / us)


def run(scale: float = 1.0, n_runs: int = 2,
        out_rows: List[str] | None = None) -> List[str]:
    rows = out_rows if out_rows is not None else []
    for axis_size in AXIS_SIZES:
        _mesh_rows(rows, axis_size, scale, n_runs)
    _staged_rows(rows, scale, max(n_runs, 3))
    return rows


if __name__ == "__main__":
    from benchmarks.common import write_json_rows
    out: List[str] = []
    run(scale=0.05, out_rows=out)
    write_json_rows(os.path.join(_REPO_ROOT, "BENCH_collectives.json"), out)
