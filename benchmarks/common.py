"""Shared experiment harness for the paper's evaluation (Sec. 5).

For a (dataset, topology, partition) triple and a communication budget, run
each algorithm, solve k-means on its summary, and report the cost of that
solution *on the full data*, normalized by the cost of solving on the full
data directly (the paper's "k-means cost ratio" vs the Lloyd baseline).
"""
from __future__ import annotations

import dataclasses
import json
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import baselines, clustering
from repro.core.coreset import distributed_coreset
from repro.core.distributed import _solve_on_coreset
from repro.core.partition import pad_partition, partition_indices
from repro.core.topology import (Graph, bfs_spanning_tree, erdos_renyi, grid,
                                 preferential)
from repro.data.synthetic import paper_dataset


@dataclasses.dataclass
class Setting:
    dataset: str
    topology: str          # "random" | "grid" | "preferential"
    partition: str         # "uniform" | "similarity" | "weighted" | "degree"
    n_sites: int
    scale: float = 1.0
    seed: int = 0


def make_graph(setting: Setting) -> Graph:
    n = setting.n_sites
    if setting.topology == "random":
        return erdos_renyi(n, 0.3, seed=setting.seed)
    if setting.topology == "grid":
        r = int(np.sqrt(n))
        assert r * r == n, "grid needs square n_sites"
        return grid(r, r)
    return preferential(n, 2, seed=setting.seed)


def load_setting_host(setting: Setting):
    """The setting's points, k, graph and padded sites, all on the host (a
    caller that shards the sites places them itself)."""
    pts, k = paper_dataset(setting.dataset, seed=setting.seed,
                           scale=setting.scale)
    g = make_graph(setting)
    idx = partition_indices(pts, g.n, setting.partition,
                            seed=setting.seed + 1, degrees=g.degrees())
    sp, sm = pad_partition(pts, idx)
    return pts, k, g, sp, sm


def load_setting(setting: Setting):
    pts, k, g, sp, sm = load_setting_host(setting)
    return pts, k, g, jnp.asarray(sp), jnp.asarray(sm)


def cost_on_full(pts: jnp.ndarray, centers: jnp.ndarray) -> float:
    return float(clustering.cost(pts, centers, chunk=65536))


def baseline_cost(key, pts, k, restarts=3, iters=12) -> float:
    _, c = clustering.solve(key, pts, k, lloyd_iters=iters,
                            restarts=restarts)
    return float(c)


def run_ours(key, sp, sm, k, t, pts) -> float:
    dc = distributed_coreset(key, sp, sm, k, t)
    cs = dc.flatten()
    centers = _solve_on_coreset(jax.random.fold_in(key, 1), cs, k,
                                "kmeans", 12)
    return cost_on_full(pts, centers)


def run_combine(key, sp, sm, k, t, pts) -> float:
    cs = baselines.combine(key, sp, sm, k, t_total=t)
    centers = _solve_on_coreset(jax.random.fold_in(key, 1), cs, k,
                                "kmeans", 12)
    return cost_on_full(pts, centers)


def run_zhang(key, sp, sm, tree, k, s, pts) -> float:
    cs, _ = baselines.zhang_tree(key, np.asarray(sp), np.asarray(sm), tree,
                                 k, s=s)
    centers = _solve_on_coreset(jax.random.fold_in(key, 1), cs, k,
                                "kmeans", 12)
    return cost_on_full(pts, centers)


def avg_over_runs(fn: Callable[[jax.Array], float], n_runs: int,
                  seed: int = 0) -> float:
    vals = [fn(jax.random.PRNGKey(seed + 100 * r)) for r in range(n_runs)]
    return float(np.mean(vals))


def json_row(rows: List[str], name: str, us_per_call: float,
             **payload) -> str:
    """Append one ``name,us_per_call,json={...}`` CSV row (the machine-
    readable format the perf trajectory parses; see bench_kernels /
    bench_stream) and echo it. Returns the row."""
    row = f"{name},{us_per_call:.0f},json={json.dumps(payload)}"
    rows.append(row)
    print(row, flush=True)
    return row


def write_json_rows(path: str, rows: List[str]) -> List[Dict]:
    """Materialize ``json_row`` output as a JSON artifact: parse every
    ``name,us_per_call,json={...}`` row into a record and dump the list to
    ``path`` (rows without an embedded json payload -- plain CSV rows like
    the roofline section's -- are skipped). This is the file CI uploads so
    the perf trajectory survives the run (see benchmarks/run.py)."""
    out: List[Dict] = []
    for row in rows:
        name, _, rest = row.partition(",")
        us, _, payload = rest.partition(",json=")
        if not payload:
            continue
        rec: Dict = {"name": name, "us_per_call": float(us)}
        rec.update(json.loads(payload))
        out.append(rec)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")
    return out
