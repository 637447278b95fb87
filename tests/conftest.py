import os

# Tests run on the single real CPU device; SPMD tests spawn subprocesses with
# their own XLA_FLAGS (a forced multi-device host must NOT leak in here).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import gc

import numpy as np
import pytest


def _vm_map_count() -> int:
    try:
        with open("/proc/self/maps", "rb") as f:
            return sum(1 for _ in f)
    except OSError:  # non-Linux: no map-count limit to guard against
        return 0


@pytest.fixture(scope="module", autouse=True)
def _bounded_jit_code_maps():
    """XLA's CPU JIT mmaps code pages per compiled executable and never
    consolidates them; a full-suite run accumulates enough live
    executables to exhaust ``vm.max_map_count`` (65530 default), at which
    point the next compile segfaults inside LLVM. Dropping
    compiled-executable references between modules once the process nears
    the limit keeps the suite bounded without recompiling on every module
    boundary."""
    yield
    if _vm_map_count() > 40_000:
        import jax

        jax.clear_caches()
        gc.collect()


@pytest.fixture(scope="session")
def gaussian_mixture():
    """Well-separated 5-cluster mixture in R^10 (paper's synthetic setup,
    scaled down)."""
    rng = np.random.default_rng(0)
    k, d, per = 5, 10, 800
    centers = 4.0 * rng.standard_normal((k, d))
    pts = np.concatenate(
        [centers[i] + 0.1 * rng.standard_normal((per, d)) for i in range(k)]
    ).astype(np.float32)
    return pts, centers
