"""Benchmark entry point -- one section per paper table/figure plus the LM
roofline. Prints ``name,us_per_call,derived`` CSV rows; the kernels section
additionally writes its rows to ``BENCH_kernels.json`` at the repo root
(the CI perf-trajectory artifact).

    PYTHONPATH=src python -m benchmarks.run             # CI scale (~minutes)
    PYTHONPATH=src python -m benchmarks.run --full      # paper scale
"""
from __future__ import annotations

import argparse
import os
import sys
import time

from benchmarks import (bench_collectives, bench_comm_scaling,
                        bench_coreset_size, bench_faults, bench_fig2_graphs,
                        bench_fig3_trees, bench_frontier, bench_kernels,
                        bench_roofline, bench_serve, bench_stream,
                        bench_topologies)
from benchmarks.common import write_json_rows
from repro.cache import enable_compilation_cache

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale datasets and run counts")
    ap.add_argument("--only", default="",
                    help="comma-separated subset: fig2,fig3,comm,size,"
                         "kernels,roofline,serve,stream,topologies,faults,"
                         "frontier,collectives")
    args = ap.parse_args(argv)
    enable_compilation_cache()
    scale = 1.0 if args.full else 0.05
    n_runs = 5 if args.full else 2
    only = set(args.only.split(",")) if args.only else None

    rows = ["name,us_per_call,derived"]
    print(rows[0])
    t0 = time.time()
    if only is None or "fig2" in only:
        bench_fig2_graphs.run(scale=scale, n_runs=n_runs, out_rows=rows)
    if only is None or "fig3" in only:
        bench_fig3_trees.run(scale=scale, n_runs=n_runs, out_rows=rows)
    if only is None or "comm" in only:
        bench_comm_scaling.run(out_rows=rows)
    if only is None or "size" in only:
        bench_coreset_size.run(scale=scale, out_rows=rows)
    if only is None or "kernels" in only:
        kernel_rows: list = []
        bench_kernels.run(out_rows=kernel_rows)
        rows.extend(kernel_rows)
        out_json = os.path.join(_REPO_ROOT, "BENCH_kernels.json")
        write_json_rows(out_json, kernel_rows)
        print(f"# wrote {out_json}", file=sys.stderr)
    if only is None or "serve" in only:
        serve_rows: list = []
        bench_serve.run(scale=scale, n_runs=n_runs, out_rows=serve_rows)
        rows.extend(serve_rows)
        out_json = os.path.join(_REPO_ROOT, "BENCH_serve.json")
        write_json_rows(out_json, serve_rows)
        print(f"# wrote {out_json}", file=sys.stderr)
    if only is None or "stream" in only:
        bench_stream.run(scale=scale, out_rows=rows)
    if only is None or "topologies" in only:
        topo_rows: list = []
        bench_topologies.run(scale=scale, n_runs=n_runs, out_rows=topo_rows)
        rows.extend(topo_rows)
        out_json = os.path.join(_REPO_ROOT, "BENCH_topologies.json")
        write_json_rows(out_json, topo_rows)
        print(f"# wrote {out_json}", file=sys.stderr)
    if only is None or "faults" in only:
        fault_rows: list = []
        bench_faults.run(scale=scale, n_runs=n_runs, out_rows=fault_rows)
        rows.extend(fault_rows)
        out_json = os.path.join(_REPO_ROOT, "BENCH_faults.json")
        write_json_rows(out_json, fault_rows)
        print(f"# wrote {out_json}", file=sys.stderr)
    if only is None or "collectives" in only:
        coll_rows: list = []
        bench_collectives.run(scale=scale, n_runs=n_runs,
                              out_rows=coll_rows)
        rows.extend(coll_rows)
        out_json = os.path.join(_REPO_ROOT, "BENCH_collectives.json")
        write_json_rows(out_json, coll_rows)
        print(f"# wrote {out_json}", file=sys.stderr)
    if only is None or "frontier" in only:
        frontier_rows: list = []
        bench_frontier.run(scale=scale, n_runs=n_runs,
                           out_rows=frontier_rows)
        rows.extend(frontier_rows)
        out_json = os.path.join(_REPO_ROOT, "BENCH_frontier.json")
        write_json_rows(out_json, frontier_rows)
        print(f"# wrote {out_json}", file=sys.stderr)
    if only is None or "roofline" in only:
        bench_roofline.run(out_rows=rows)
    print(f"# total {time.time()-t0:.1f}s, {len(rows)-1} rows",
          file=sys.stderr)


if __name__ == "__main__":
    main()
