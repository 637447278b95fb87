"""Fixtures for the benchmark's CPU tests: a copy of the benchmark at a
tiny size (the real widths' code paths, small counts)."""
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY = dict(n=6000, k=8, sites=6, t=240, components=10, noise=0.8)
TINY_SERVE = dict(rate_per_s=200.0, rows_max=64, tenants=4,
                  checked_requests=50)


# the serving cell and its end-to-end metric, for a copy of the benchmark
# whose BENCHMARK.json has none: its traffic, kind and readers are tested
# all the same
SERVE_CELL = {"name": "msd_kmeans.serve", "config": "msd_kmeans",
              "traffic": "serve", "chips": 1,
              "why": "open-loop nearest-center queries"}
SERVE_METRIC = {"name": "query_p99_ms", "unit": "ms", "better": "lower",
                "bound": 0.2, "source": "host_clock",
                "workloads": ["msd_kmeans.serve"]}


def _with_serving(spec: dict) -> None:
    if not any(w["name"] == SERVE_CELL["name"] for w in spec["workloads"]):
        spec["workloads"].append(dict(SERVE_CELL))
    if not any(m["name"] == SERVE_METRIC["name"]
               for m in spec["end_to_end"]):
        spec["end_to_end"].append(dict(SERVE_METRIC))


def make_tiny(dst: str) -> str:
    """A checkout at ``dst`` whose configurations and serving traffic are
    cut to a CPU test's size; everything else is the benchmark's own."""
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    os.path.join(dst, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    _with_serving(spec)
    json.dump(spec, open(os.path.join(dst, "BENCHMARK.json"), "w"))
    for c in spec["configs"]:
        path = os.path.join(dst, c["file"])
        cfg = json.load(open(path))
        cfg.update(TINY)
        json.dump(cfg, open(path, "w"))
    path = os.path.join(dst, "chipbench", "traffic", "serve.json")
    tr = json.load(open(path))
    tr.update(TINY_SERVE)
    json.dump(tr, open(path, "w"))
    return dst


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_tiny(str(tmp_path_factory.mktemp("tiny")))
