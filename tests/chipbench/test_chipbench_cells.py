"""The job and serving traffic at a tiny size on the CPU, through the
harness's own functions (the command refuses to run without a TPU)."""
import json
import os
import subprocess
import sys
import time

import numpy as np

from chipbench import harness

from conftest import ROOT


def _run(tiny_root, workload, seed, capsys):
    cell = harness.Cell(workload, root=tiny_root,
                        bench_dir=os.path.join(tiny_root, "chipbench"))
    result = harness.run(cell, seed, 1.0, False, time.perf_counter())
    lines = capsys.readouterr().out.splitlines()
    window = json.loads(next(x for x in lines if x.startswith("window "))
                        .split(" ", 1)[1])
    return cell, result, window


def test_job_cell_runs_and_is_correct(tiny_root, capsys):
    cell, r, window = _run(tiny_root, "msd_kmeans.job", 2**33 + 5, capsys)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 2 and r["failed"] == 0
    assert set(r["metrics"]) == {"setup_s", "job_s", "cost_ratio"}
    assert 1.0 <= r["metrics"]["cost_ratio"]["value"] < 2.0
    assert window["compiles"] == 0
    assert list(r)[-1] == "checks"
    assert set(r["checks"]) == set(cell.limits())


def test_serve_cell_runs_and_is_correct(tiny_root, capsys):
    cell, r, window = _run(tiny_root, "msd_kmeans.serve", 7, capsys)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 50 and r["failed"] == 0
    assert set(r["metrics"]) == {"setup_s", "query_p99_ms"}
    assert window["compiles"] == 0


def test_serve_traffic_is_the_same_work_for_every_seed():
    from chipbench.kinds import serve
    tr = dict(rate_per_s=500.0, rows_min=1, rows_max=1024, tenants=16,
              zipf_s=1.0)
    u = serve.schedule(1, 2000, tr)
    v = serve.schedule(2**32 + 1, 2000, tr)
    for key in ("gap", "size", "tenant"):
        assert sorted(u[key]) == sorted(v[key])
        assert not (u[key] == v[key]).all()
    assert u["size"].min() == 1 and 1000 < u["size"].max() <= 1024
    # Zipf(1): tenant 0 takes the largest share, the last the smallest
    counts = np.bincount(u["tenant"], minlength=16)
    assert counts[0] == counts.max() and counts[-1] == counts.min()
    due = serve.requests(1, 4.0, tr, 10_000)
    assert due["arrival"].max() < 4.0
    assert len(due["arrival"]) >= 0.99 * 4.0 * tr["rate_per_s"]
    assert (due["offset"] + due["size"] <= 10_000).all()


def test_command_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "msd_kmeans.job",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_every_seed_gets_the_same_site_sizes():
    from chipbench import data
    sizes = data.site_sizes(515_345, 100)
    assert sizes.sum() == 515_345 and sizes.min() >= 1
    counts = []
    for seed in (1, 2**33 + 5):
        _, site, sp, sm = data.make_sites(seed, 5000, 4, 3, 0.8, 10)
        counts.append(np.bincount(np.asarray(site), minlength=10))
        assert sp.shape == (10, 1248, 4)
        assert (np.asarray(sm).sum(1) == counts[-1]).all()
    assert sorted(counts[0]) == sorted(counts[1])
    assert not (counts[0] == counts[1]).all()
