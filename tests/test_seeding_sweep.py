"""D^z seeding's one-center sweep: exact-form f32 distances, the same
program on every backend, and the draw's distribution."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import clustering
from repro.core import objective as objective_mod

KEY = jax.random.PRNGKey(0)

# chi-square critical values at p = 0.001 by degrees of freedom
_CHI2_999 = {29: 58.301}


@pytest.mark.parametrize("objective", ["kmeans", "kmedian"])
def test_one_center_distances_match_float64(objective):
    rng = np.random.default_rng(11)
    p = (rng.standard_normal((300, 90)) * 5 + 3).astype(np.float32)
    c = p[17]
    obj = objective_mod.get_objective(objective)
    got = np.asarray(obj.clamped_cost(
        clustering._one_center_sq_dists(jnp.asarray(p), jnp.asarray(c))))
    d2 = ((p.astype(np.float64) - c.astype(np.float64)) ** 2).sum(-1)
    want = d2 if objective == "kmeans" else np.sqrt(d2)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert got[17] == 0.0         # the center itself: exactly 0


@pytest.mark.parametrize("objective", ["kmeans", "kmedian"])
def test_seeding_is_bit_identical_on_every_backend(objective):
    rng = np.random.default_rng(12)
    pts = jnp.asarray(rng.standard_normal((200, 9)).astype(np.float32))
    w = jnp.asarray(rng.uniform(0.0, 2.0, 200).astype(np.float32))
    got = [np.asarray(clustering.kmeans_pp_init(KEY, pts, 6, weights=w,
                                                objective=objective,
                                                backend=b))
           for b in ("jnp", "jnp_chunked", "pallas")]
    np.testing.assert_array_equal(got[0], got[1])
    np.testing.assert_array_equal(got[0], got[2])


def test_seeding_calls_no_pallas_kernel():
    pts = jnp.ones((64, 9))
    seed = str(jax.make_jaxpr(lambda p: clustering.kmeans_pp_init(
        KEY, p, 4, backend="pallas"))(pts))
    assert "pallas_call" not in seed
    # the same check sees the kernel where one runs
    assign = str(jax.make_jaxpr(lambda p: clustering.min_dist_argmin(
        p, p[:4], backend="pallas"))(pts))
    assert "pallas_call" in assign


@pytest.mark.parametrize("objective,z", [("kmedian", 1), ("kmeans", 2)])
def test_second_center_is_drawn_by_weighted_dz(objective, z):
    """Over 2,000 keys the (first, second) pair of a k = 2 seeding follows
    w_f / sum w times w_j D(j, f)^z / sum_l w_l D(l, f)^z."""
    pts = np.array([[0, 0], [1, 0], [0, 2], [3, 1], [2, 3], [1.5, 1.5]],
                   np.float32)
    w = np.array([1.0, 2.0, 0.5, 1.5, 1.0, 3.0], np.float32)
    n_keys = 2000
    keys = jax.random.split(jax.random.PRNGKey(5), n_keys)
    centers = np.asarray(jax.vmap(lambda k: clustering.kmeans_pp_init(
        k, jnp.asarray(pts), 2, weights=jnp.asarray(w),
        objective=objective))(keys))
    idx = (centers[:, :, None, :] == pts[None, None]).all(-1).argmax(-1)
    counts = np.zeros((6, 6))
    np.add.at(counts, (idx[:, 0], idx[:, 1]), 1)

    p64, w64 = pts.astype(np.float64), w.astype(np.float64)
    dz = np.sqrt(((p64[:, None] - p64[None]) ** 2).sum(-1)) ** z
    cond = w64[None] * dz
    cond /= cond.sum(1, keepdims=True)
    expected = n_keys * (w64 / w64.sum())[:, None] * cond
    live = expected > 0
    assert counts[~live].sum() == 0       # never the first center again
    assert expected[live].min() >= 5      # the chi-square approximation
    chi2 = float((((counts - expected) ** 2)[live] / expected[live]).sum())
    assert chi2 < _CHI2_999[int(live.sum()) - 1]
