"""Unit tests for the weighted clustering primitives."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import clustering
from repro.data import paper_dataset, paper_dataset_names

KEY = jax.random.PRNGKey(0)


def test_pairwise_sq_dists_matches_numpy():
    rng = np.random.default_rng(1)
    p = rng.standard_normal((40, 7)).astype(np.float32)
    c = rng.standard_normal((6, 7)).astype(np.float32)
    got = np.asarray(clustering.pairwise_sq_dists(jnp.asarray(p), jnp.asarray(c)))
    want = ((p[:, None, :] - c[None, :, :]) ** 2).sum(-1)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_min_dist_argmin_chunked_equals_dense():
    rng = np.random.default_rng(2)
    p = jnp.asarray(rng.standard_normal((100, 5)).astype(np.float32))
    c = jnp.asarray(rng.standard_normal((8, 5)).astype(np.float32))
    md0, am0 = clustering.min_dist_argmin(p, c)
    md1, am1 = clustering.min_dist_argmin(p, c, chunk=32)
    np.testing.assert_allclose(np.asarray(md0), np.asarray(md1), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(am0), np.asarray(am1))


def test_kmeans_pp_never_selects_zero_weight_points():
    rng = np.random.default_rng(3)
    pts = jnp.asarray(rng.standard_normal((50, 3)).astype(np.float32))
    w = jnp.concatenate([jnp.ones(25), jnp.zeros(25)])
    for seed in range(5):
        centers = clustering.kmeans_pp_init(jax.random.PRNGKey(seed), pts, 4,
                                            weights=w)
        # every chosen center must be one of the first 25 points
        d2 = clustering.pairwise_sq_dists(centers, pts[:25])
        assert float(jnp.max(jnp.min(d2, axis=1))) < 1e-5


def test_kmeans_pp_all_zero_weights_is_deterministic():
    """Degenerate fully masked instance (an empty site under vmap in
    distributed_coreset): with every logit equal, categorical would seed
    uniformly from padding rows depending on the key; the guard must pin
    every chosen center to row 0 for any key."""
    rng = np.random.default_rng(7)
    pts = jnp.asarray(rng.standard_normal((40, 5)).astype(np.float32))
    w = jnp.zeros((40,))
    for seed in range(5):
        centers = clustering.kmeans_pp_init(jax.random.PRNGKey(seed), pts, 3,
                                            weights=w)
        np.testing.assert_array_equal(np.asarray(centers),
                                      np.tile(np.asarray(pts[0]), (3, 1)))


def test_kmeans_pp_single_positive_weight_point():
    """All remaining mass at distance 0 after the first pick: subsequent
    draws are degenerate too and must stay deterministic and in-range."""
    rng = np.random.default_rng(8)
    pts = jnp.asarray(rng.standard_normal((30, 4)).astype(np.float32))
    w = jnp.zeros((30,)).at[17].set(2.0)
    centers = clustering.kmeans_pp_init(KEY, pts, 4, weights=w)
    # first center is the only weighted point; the rest collapse to row 0
    np.testing.assert_array_equal(np.asarray(centers[0]),
                                  np.asarray(pts[17]))
    assert np.isfinite(np.asarray(centers)).all()


def test_lloyd_cost_nonincreasing(gaussian_mixture):
    pts, _ = gaussian_mixture
    pts = jnp.asarray(pts)
    centers = clustering.kmeans_pp_init(KEY, pts, 5)
    _, hist = clustering.lloyd(pts, centers, iters=8)
    h = np.asarray(hist)
    assert np.all(h[1:] <= h[:-1] + 1e-3 * h[0])


def test_solve_recovers_separated_clusters(gaussian_mixture):
    pts, true_centers = gaussian_mixture
    centers, c = clustering.solve(KEY, jnp.asarray(pts), 5, restarts=4)
    # each true center has a solution center within a small distance
    d2 = clustering.pairwise_sq_dists(jnp.asarray(true_centers.astype(np.float32)),
                                      centers)
    assert float(jnp.max(jnp.min(d2, axis=1))) < 0.1
    # cost close to the generative optimum n*d*sigma^2
    n, d = pts.shape
    assert float(c) < 1.5 * n * d * 0.01


@settings(max_examples=20, deadline=None)
@given(
    n=st.integers(20, 60),
    k=st.integers(2, 5),
    mult=st.integers(2, 4),
    seed=st.integers(0, 2**31 - 1),
)
def test_weighted_lloyd_equals_replicated_points(n, k, mult, seed):
    """Integer weight w == w replicated copies: the weighted k-means update
    must produce identical centers (invariance of the weighted instance)."""
    rng = np.random.default_rng(seed)
    pts = rng.standard_normal((n, 3)).astype(np.float32)
    w = rng.integers(1, mult + 1, size=n)
    rep = np.repeat(pts, w, axis=0)
    centers0 = pts[:k].copy()
    cw, _ = clustering.lloyd(jnp.asarray(pts), jnp.asarray(centers0),
                             weights=jnp.asarray(w.astype(np.float32)), iters=3)
    cr, _ = clustering.lloyd(jnp.asarray(rep), jnp.asarray(centers0), iters=3)
    np.testing.assert_allclose(np.asarray(cw), np.asarray(cr), rtol=2e-3,
                               atol=2e-3)


def test_kmedian_weiszfeld_decreases_cost(gaussian_mixture):
    pts, _ = gaussian_mixture
    pts = jnp.asarray(pts)
    centers = clustering.kmeans_pp_init(KEY, pts, 5, objective="kmedian")
    c0 = clustering.cost(pts, centers, objective="kmedian")
    centers1, _ = clustering.lloyd(pts, centers, iters=5, objective="kmedian")
    c1 = clustering.cost(pts, centers1, objective="kmedian")
    assert float(c1) <= float(c0) * 1.001


def test_negative_weights_do_not_nan():
    rng = np.random.default_rng(5)
    pts = jnp.asarray(rng.standard_normal((30, 4)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal(30).astype(np.float32))  # signed
    centers0 = pts[:3]
    centers, hist = clustering.lloyd(pts, centers0, weights=w, iters=4)
    assert np.isfinite(np.asarray(centers)).all()


def test_empty_cluster_keeps_previous_center():
    pts = jnp.asarray(np.array([[0.0, 0], [0, 0.1], [10, 10], [10, 10.1]],
                               dtype=np.float32))
    far = jnp.asarray(np.array([[0, 0], [10, 10], [100, 100]],
                               dtype=np.float32))
    centers, _ = clustering.lloyd(pts, far, iters=2)
    c = np.asarray(centers)
    np.testing.assert_allclose(c[2], [100, 100], atol=1e-6)  # untouched


def test_paper_dataset_shapes():
    for name in paper_dataset_names():
        pts, k = paper_dataset(name, scale=0.02)
        assert pts.ndim == 2 and np.isfinite(pts).all()
        assert k >= 5
