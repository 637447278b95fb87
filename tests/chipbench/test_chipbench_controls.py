"""The control and the planted faults must come out not correct: the
harness runs the cell at a tiny size with its timed path broken
underneath. On the CPU the control's lower matmul precision is emulated
from bf16 parts (``controls._emulated_on_cpu``)."""
import os
import time

import pytest

from chipbench import controls, harness

JOB_VARIANTS = ("control_high", "state_unchanged", "half_batch",
                "answer_altered")
SERVE_VARIANTS = ("control_high", "half_batch", "answer_altered")


def _run(tiny_root, workload, variant, seed):
    cell = harness.Cell(workload, root=tiny_root,
                        bench_dir=os.path.join(tiny_root, "chipbench"))
    with controls.plant(variant):
        return harness.run(cell, seed, 0.5, False, time.perf_counter())


@pytest.mark.parametrize("workload", ["msd_kmeans.job", "msd_kmedian.job"])
@pytest.mark.parametrize("variant", JOB_VARIANTS)
def test_job_control_and_faults_are_not_correct(tiny_root, workload,
                                                variant):
    r = _run(tiny_root, workload, variant, 11)
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("variant", SERVE_VARIANTS)
def test_serve_control_and_faults_are_not_correct(tiny_root, variant):
    r = _run(tiny_root, "msd_kmeans.serve", variant, 12)
    assert not r["correct"], r["checks"]


def test_sound_program_is_correct_under_plant(tiny_root):
    r = _run(tiny_root, "msd_kmedian.job", "program", 13)
    assert r["correct"], r["checks"]
