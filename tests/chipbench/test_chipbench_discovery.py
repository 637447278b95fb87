"""A new configuration, traffic and per-layer metric are found by name:
adding their files and one ``workloads`` entry makes a runnable cell with
no edit to any existing file."""
import json
import os
import time

from chipbench import harness


def test_new_files_make_a_new_cell(tiny_root, tmp_path):
    import shutil
    root = str(tmp_path / "checkout")
    shutil.copytree(tiny_root, root)
    bench = os.path.join(root, "chipbench")
    before = _files(bench)

    cfg = json.load(open(os.path.join(bench, "configs", "msd_kmeans.json")))
    cfg.update(name="msd_small", k=6, sites=4)
    json.dump(cfg, open(os.path.join(bench, "configs", "msd_small.json"),
                        "w"))
    json.dump({"kind": "job", "why": "the job loop again"},
              open(os.path.join(bench, "traffic", "job_again.json"), "w"))
    with open(os.path.join(bench, "metrics", "jobs_seen.py"), "w") as fh:
        fh.write("def read(ctx):\n    return float(ctx.stats['jobs'])\n")
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    spec["configs"].append(dict(spec["configs"][0], name="msd_small",
                                file="chipbench/configs/msd_small.json"))
    spec["workloads"].append(dict(name="msd_small.job_again",
                                  config="msd_small", traffic="job_again",
                                  chips=1, why="a new cell"))
    for m in spec["end_to_end"]:
        if "workloads" in m and "msd_kmeans.job" in m["workloads"]:
            m["workloads"].append("msd_small.job_again")
    spec["per_layer"].append(dict(
        name="jobs_seen", unit="jobs", better="higher",
        source="host_clock", layer="core/distributed.py", moves="job_s",
        workloads=["msd_small.job_again"]))
    json.dump(spec, open(os.path.join(root, "BENCHMARK.json"), "w"))

    cell = harness.Cell("msd_small.job_again", root=root, bench_dir=bench)
    assert cell.config["k"] == 6
    assert [m["name"] for m in cell.per_layer()] == ["jobs_seen"]
    r = harness.run(cell, 21, 0.5, True, time.perf_counter())
    assert r["correct"], r["checks"]
    assert r["metrics"]["jobs_seen"]["value"] >= 1
    assert r["device"]["window_s"] > 0
    # no file the benchmark had was edited
    after = _files(bench)
    assert {p: after[p] for p in before} == before


def _files(top):
    return {os.path.relpath(os.path.join(dp, f), top):
            open(os.path.join(dp, f), "rb").read()
            for dp, _, files in os.walk(top) for f in files
            if not f.endswith(".pyc")}
