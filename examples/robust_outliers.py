"""Robust clustering demo: the trimmed objective on contaminated data.

Runs the contamination A/B that motivates the first-class objective layer
(DESIGN.md Sec. 15):

1. **Offline**: a Gaussian mixture with a few percent of far-field
   outliers. Plain ``kmeans`` spends centers chasing the contamination;
   ``kmeans_trimmed(t)`` excludes the top-t largest-residual points from
   every update and seeding step and recovers the true centers. Both run
   through the same registered descriptor machinery on the same backend.
2. **Streaming / distributed**: PR 7's ``contaminated_stream`` pushed
   round-robin into a :class:`DistributedStream` over a ring, aggregated
   with Algorithm 1. Recovered centers are scored on the *clean* stream
   (plain z=2 metric) -- the trimmed objective stays within a small factor
   of the uncontaminated run while plain k-means blows up by an order of
   magnitude.

    PYTHONPATH=src python examples/robust_outliers.py [--backend pallas] \
        [--outlier-frac 0.05] [--trim 0.08]

(On CPU the pallas backend runs the kernels in interpret mode.)
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import clustering, topology
from repro.core.coreset import build_coreset
from repro.data.synthetic import contaminated_stream, drifting_mixture_stream
from repro.stream import DistributedStream, TreeConfig
from repro.cache import enable_compilation_cache


def offline_demo(args):
    rng = np.random.default_rng(0)
    k, d = 3, 2
    true_centers = np.array([[0, 0], [10, 0], [0, 10]], np.float32)
    inliers = np.concatenate(
        [c + 0.3 * rng.standard_normal((200, d)) for c in true_centers])
    n_out = int(args.outlier_frac / (1 - args.outlier_frac) * len(inliers))
    outliers = 100.0 * rng.standard_normal((n_out, d))
    pts = jnp.asarray(np.concatenate([inliers, outliers]).astype(np.float32))
    print(f"offline: {len(inliers)} inliers in {k} tight clusters + "
          f"{n_out} far-field outliers (|x| ~ 100)")

    key = jax.random.PRNGKey(0)
    inl = jnp.asarray(inliers)
    for obj in ("kmeans", f"kmeans_trimmed({n_out})"):
        c, _ = clustering.solve(key, pts, k, restarts=3, lloyd_iters=8,
                                objective=obj, backend=args.backend)
        inlier_cost = float(clustering.cost(inl, c, backend=args.backend))
        worst = float(jnp.abs(c).max())
        print(f"  {obj:22s} inlier cost {inlier_cost:10.1f}   "
              f"max |center| {worst:6.1f}"
              + ("   <- dragged into the far field" if worst > 20 else ""))

    # the trimmed objective also flows through coreset construction: the
    # excluded points carry zero sensitivity mass and zero sample weight
    cs = build_coreset(jax.random.PRNGKey(1), pts, k, 64,
                       objective=f"kmeans_trimmed({n_out})",
                       backend=args.backend)
    print(f"  trimmed coreset keeps weight {float(cs.weights.sum()):.0f} "
          f"of {pts.shape[0]} raw points ({n_out} excluded)")


def stream_demo(args):
    k, d, n_batches, bs = 5, 10, 12, 128
    g = topology.ring(4)

    def recover(objective, contaminated):
        cfg = TreeConfig(k=k, t=48, d=d, batch_size=bs, objective=objective,
                         backend=args.backend)
        ds = DistributedStream(g, cfg, key=jax.random.PRNGKey(3))
        gen = (contaminated_stream(n_batches, bs, d=d, k=k,
                                   outlier_frac=args.outlier_frac, seed=0)
               if contaminated else
               drifting_mixture_stream(n_batches, bs, d=d, k=k, seed=0))
        for i, b in enumerate(gen):
            ds.push(i % g.n, b)
        res = ds.aggregate(k, 40, engine=args.engine)
        clean = jnp.asarray(np.concatenate(
            list(drifting_mixture_stream(n_batches, bs, d=d, k=k, seed=0))))
        return float(clustering.cost(clean, res.centers,
                                     backend=args.backend))

    print(f"\nstream: {n_batches} batches x {bs} pts in R^{d} over a "
          f"{g.n}-node ring, {args.outlier_frac:.0%} far-field "
          f"contamination, engine={args.engine}")
    base = recover("kmeans", contaminated=False)
    plain = recover("kmeans", contaminated=True)
    trimmed = recover(f"kmeans_trimmed({args.trim:g})", contaminated=True)
    print(f"  clean-stream k-means cost of recovered centers:")
    print(f"    kmeans on clean stream         {base:10.1f}  (1.00x)")
    print(f"    kmeans on contaminated         {plain:10.1f}  "
          f"({plain / base:.2f}x)")
    print(f"    kmeans_trimmed({args.trim:g}) on same  {trimmed:10.1f}  "
          f"({trimmed / base:.2f}x)")


def main(argv=None):
    enable_compilation_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default=None,
                    help="clustering backend: jnp | jnp_chunked | pallas")
    ap.add_argument("--outlier-frac", type=float, default=0.05)
    ap.add_argument("--trim", type=float, default=0.08,
                    help="trimmed fraction t for kmeans_trimmed(t)")
    ap.add_argument("--engine", default="sim", choices=["sim", "exec"])
    args = ap.parse_args(argv)
    offline_demo(args)
    stream_demo(args)


if __name__ == "__main__":
    main()
