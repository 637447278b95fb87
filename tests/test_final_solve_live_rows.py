"""The final solve runs on the coreset's live rows (DESIGN.md Sec. 7).

Every single-chip engine gathers a buffer of ``t + k`` slots per site, of
which at most ``gathered_live_rows`` = ``t + sites * k`` rows carry
weight; ``_solve_on_coreset`` moves those rows to the front in order
(``Coreset.compact``) and seeds and updates on them alone. Weight-0 rows add nothing to a cost or an update, so the smaller
instance is the same weighted instance."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import clustering, topology
from repro.core.coreset import (Coreset, distributed_coreset,
                                gathered_live_rows)
from repro.core.distributed import (_solve_on_coreset,
                                    distributed_kmeans_tree,
                                    graph_distributed_kmeans)
from repro.core.partition import pad_partition, partition_indices
from repro.core.strategy import available_strategies
from repro.wan.faults import FaultPlan

KEY = jax.random.PRNGKey(23)
N_SITES, K, D, T = 6, 3, 4, 40
ENGINES = ("sim", "exec", "tree_sim", "tree_exec", "async", "async_churn")


@pytest.fixture(scope="module")
def sites():
    rng = np.random.default_rng(5)
    centers = 3.0 * rng.standard_normal((K, D))
    pts = np.concatenate([c + 0.3 * rng.standard_normal((90, D))
                          for c in centers]).astype(np.float32)
    sp, sm = pad_partition(pts, partition_indices(pts, N_SITES, "weighted",
                                                  seed=4))
    graph = topology.erdos_renyi(N_SITES, 0.6, seed=3)
    return jnp.asarray(sp), jnp.asarray(sm), graph


def _run(sites, engine, strategy):
    sp, sm, graph = sites
    kw = dict(objective="kmeans", lloyd_iters=3, strategy=strategy)
    if engine.startswith("tree_"):
        tree = topology.bfs_spanning_tree(graph, root=0)
        return distributed_kmeans_tree(KEY, sp, sm, K, T, tree,
                                       engine=engine[5:], **kw)
    if engine == "async":
        return graph_distributed_kmeans(KEY, sp, sm, K, T, graph,
                                        engine="async",
                                        faults=FaultPlan(seed=0), **kw)
    if engine == "async_churn":
        # site 5 dies before the first round: the buffer holds survivors
        return graph_distributed_kmeans(KEY, sp, sm, K, T, graph,
                                        engine="async",
                                        faults=FaultPlan(churn=((5, 0, -1),),
                                                         seed=1), **kw)
    return graph_distributed_kmeans(KEY, sp, sm, K, T, graph, engine=engine,
                                    **kw)


@pytest.mark.parametrize("strategy", available_strategies())
@pytest.mark.parametrize("engine", ENGINES)
def test_buffer_live_rows_fit_the_bound(sites, engine, strategy):
    """Each portion is t + k slots and the portions' valid samples sum to
    t, so the nonzero rows are at most t + portions * k: the bound the
    final solve sizes its instance by."""
    res = _run(sites, engine, strategy)
    rows = res.coreset.points.shape[0]
    portions = N_SITES - 1 if engine == "async_churn" else N_SITES
    assert rows == portions * (T + K)       # the uncompacted buffer
    live = int(np.count_nonzero(np.asarray(res.coreset.weights)))
    assert gathered_live_rows(rows, T, K) == T + portions * K
    assert 0 < live <= gathered_live_rows(rows, T, K)


def _signed_buffer(rows=50, zero_share=0.6, seed=0):
    rng = np.random.default_rng(seed)
    pts = rng.standard_normal((rows, D)).astype(np.float32)
    w = rng.standard_normal(rows).astype(np.float32)
    w[rng.random(rows) < zero_share] = 0.0
    return Coreset(jnp.asarray(pts), jnp.asarray(w)), pts, w


@pytest.mark.parametrize("seed,zero_share,live", [
    (0, 0.6, 30), (1, 0.9, 12), (2, 0.0, 50), (3, 1.0, 8)])
def test_live_rows_are_the_nonzero_rows_in_order(seed, zero_share, live):
    cs, pts, w = _signed_buffer(zero_share=zero_share, seed=seed)
    nz = np.flatnonzero(w)
    assert len(nz) <= live
    out = cs.compact(live)
    assert out.points.shape == (live, D) and out.weights.shape == (live,)
    ow, op = np.asarray(out.weights), np.asarray(out.points)
    np.testing.assert_array_equal(ow[:len(nz)], w[nz])
    np.testing.assert_array_equal(op[:len(nz)], pts[nz])
    np.testing.assert_array_equal(ow[len(nz):], 0.0)


@pytest.fixture(scope="module")
def gathered(sites):
    """A real gathered buffer (sim engine's construction) and its
    compacted instance."""
    sp, sm, _ = sites
    cs = distributed_coreset(KEY, sp, sm, K, T, lloyd_iters=3).flatten()
    live = T + N_SITES * K
    assert cs.size == N_SITES * (T + K)
    return cs, cs.compact(live)


@pytest.mark.parametrize("objective", ["kmeans", "kmedian"])
def test_cost_on_live_rows_equals_cost_on_buffer(gathered, objective):
    cs, small = gathered
    for s in range(3):
        centers = 3.0 * jax.random.normal(jax.random.PRNGKey(s), (K, D))
        full = clustering.cost(cs.points, centers, weights=cs.weights,
                               objective=objective)
        live = clustering.cost(small.points, centers, weights=small.weights,
                               objective=objective)
        np.testing.assert_allclose(float(live), float(full), rtol=1e-5)


@pytest.mark.parametrize("objective", ["kmeans", "kmedian"])
def test_lloyd_on_live_rows_equals_lloyd_on_buffer(gathered, objective):
    cs, small = gathered
    init = cs.points[jnp.flatnonzero(cs.weights, size=K)]
    c_full, h_full = clustering.lloyd(cs.points, init, weights=cs.weights,
                                      iters=4, objective=objective)
    c_live, h_live = clustering.lloyd(small.points, init,
                                      weights=small.weights, iters=4,
                                      objective=objective)
    np.testing.assert_allclose(np.asarray(c_live), np.asarray(c_full),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(h_live), np.asarray(h_full),
                               rtol=1e-5)


@pytest.mark.parametrize("objective", ["kmeans", "kmedian"])
def test_final_solve_seeds_and_updates_the_live_instance(gathered,
                                                         objective):
    """``live`` sizes the instance; without it the solve runs on every
    row of the buffer."""
    cs, small = gathered

    def direct(inst):
        c = clustering.kmeans_pp_init(KEY, inst.points, K,
                                      weights=jnp.maximum(inst.weights, 0.0),
                                      objective=objective)
        return clustering.lloyd(inst.points, c, weights=inst.weights,
                                iters=3, objective=objective)[0]

    np.testing.assert_array_equal(
        np.asarray(_solve_on_coreset(
            KEY, cs, K, objective, 3,
            live=gathered_live_rows(cs.size, T, K))),
        np.asarray(direct(small)))
    np.testing.assert_array_equal(
        np.asarray(_solve_on_coreset(KEY, cs, K, objective, 3)),
        np.asarray(direct(cs)))
