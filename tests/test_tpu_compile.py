"""Compile the Pallas kernels (and the final solve's compaction) for a
described TPU v5e at the widths of the paper's full-scale job
(``chip_smoke.py``): YearPredictionMSD sites of 21,280 x 90 f32 rows,
k=50, 100 sites per chip. Nothing runs; the chip's compiler refuses here
what it would refuse on the chip (tiling, VMEM limits, device memory)."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

M, D, K, SITES = 21_280, 90, 50, 100
HBM_BYTES = 16e9          # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _lloyd(p, c, w):
    return ops.lloyd_stats(p, c, w, interpret=False)


def _weiszfeld(p, c, w):
    return ops.weiszfeld_stats(p, c, w, interpret=False)


def _argmin(p, c):
    return ops.min_dist_argmin(p, c, interpret=False)


def _batched(q, c):
    return ops.min_dist_argmin_batched(q, c, interpret=False)


CASES = {
    # one site
    "min_dist_argmin/k50": (_argmin, [(M, D), (K, D)]),
    "min_dist_argmin/k1": (_argmin, [(M, D), (1, D)]),     # a seeding sweep
    "lloyd_stats": (_lloyd, [(M, D), (K, D), (M,)]),
    "weiszfeld_stats": (_weiszfeld, [(M, D), (K, D), (M,)]),
    # every site of the chip at once, as Round 1 vmaps them
    "min_dist_argmin/k1/sites": (jax.vmap(_argmin),
                                 [(SITES, M, D), (SITES, 1, D)]),
    "lloyd_stats/sites": (jax.vmap(_lloyd),
                          [(SITES, M, D), (SITES, K, D), (SITES, M)]),
    "weiszfeld_stats/sites": (jax.vmap(_weiszfeld),
                              [(SITES, M, D), (SITES, K, D), (SITES, M)]),
    # serving: two tenants at the largest bucket, and a 64-tenant group
    "distance_argmin_batched/T2": (_batched, [(2, 1024, D), (2, 64, D)]),
    "distance_argmin_batched/T64": (_batched, [(64, 1024, D), (64, 64, D)]),
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, shapes = CASES[case]
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert total < HBM_BYTES, total


def test_final_solve_compaction_compiles_for_v5e(one_chip):
    """The final solve's move of the coreset's live rows to the front, at
    the job's buffer (100 sites x (t = 15,000 + k) slots, 20,000 live):
    an index from a cumsum and one gather, no sort over the buffer."""
    from repro.core.coreset import Coreset
    rows, live = SITES * (15_000 + K), 15_000 + SITES * K
    cs = Coreset(jax.ShapeDtypeStruct((rows, D), jnp.float32,
                                      sharding=one_chip),
                 jax.ShapeDtypeStruct((rows,), jnp.float32,
                                      sharding=one_chip))
    compiled = Coreset.compact.lower(cs, live).compile()
    assert " sort(" not in compiled.as_text()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert total < HBM_BYTES, total
