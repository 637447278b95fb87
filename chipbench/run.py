"""Run one benchmark cell once on the TPU this machine holds.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The last line of standard output is the result as one JSON object. With
no TPU, or fewer chips than the cell asks for, it exits 2 and prints no
result.
"""
import os
import sys
import time

_T0 = time.perf_counter()
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=_T0))
