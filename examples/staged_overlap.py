"""Staged-overlap coreset engine demo: Round 1 broken out of the lockstep
vmap (DESIGN.md Sec. 17).

Builds a deliberately skewed weighted partition (one dominant site, many
small ones -- exactly where the lockstep vmap wastes FLOPs padding every
site to the largest) and races three engines:

1. **lockstep** -- :func:`repro.core.coreset.distributed_coreset`, the
   batched Round-1 solve every site pays at the max pad length.
2. **staged strict** -- :func:`staged_distributed_coreset` with
   ``tol=0`` and no buckets: per-site dispatch with the Round-1 scalar
   exchange launched at each site's convergence, yet every output field
   bit-identical to lockstep (the parity contract).
3. **staged overlap** -- ``tol>0`` + ``site_buckets``: per-site
   power-of-two solve lengths and convergence early-exit; draws differ by
   construction, so it is scored by coreset quality instead.

    PYTHONPATH=src python examples/staged_overlap.py [--backend pallas] \
        [--sites 8] [--per 10000]

(On CPU the pallas backend runs the kernels in interpret mode; pass small
sizes there -- CI uses this as the staged-path interpret smoke.)
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import clustering
from repro.core.coreset import distributed_coreset, staged_distributed_coreset
from repro.core.partition import pad_partition, partition_indices
from repro.cache import enable_compilation_cache


def _skewed_sites(n_sites, per, d=32, k=4, seed=3):
    rng = np.random.default_rng(seed)
    centers = 3.0 * rng.standard_normal((k, d))
    pts = np.concatenate(
        [centers[i] + 0.15 * rng.standard_normal((per, d)) for i in range(k)]
    ).astype(np.float32)
    idx = partition_indices(pts, n_sites, "weighted", seed=seed + 1)
    sp, sm = pad_partition(pts, idx)
    sizes = [len(i) for i in idx]
    return pts, jnp.asarray(sp), jnp.asarray(sm), k, sizes


def main(argv=None):
    enable_compilation_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default=None,
                    help="clustering backend: jnp | jnp_chunked | pallas")
    ap.add_argument("--sites", type=int, default=8)
    ap.add_argument("--per", type=int, default=10000,
                    help="points per mixture component")
    ap.add_argument("--t", type=int, default=256)
    args = ap.parse_args(argv)

    pts, sp, sm, k, sizes = _skewed_sites(args.sites, args.per)
    print(f"{len(pts)} points over {args.sites} sites, "
          f"sizes {min(sizes)}..{max(sizes)} (lockstep pads all to "
          f"{sp.shape[1]})")
    key = jax.random.PRNGKey(0)

    def timed(fn, reps=3):
        out = fn()                                   # compile + warm
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn()
        return out, (time.perf_counter() - t0) / reps * 1e3

    base, ms_lock = timed(lambda: jax.block_until_ready(
        distributed_coreset(key, sp, sm, k, t=args.t,
                            backend=args.backend).weights))
    print(f"  lockstep vmap            {ms_lock:8.1f} ms")

    (strict, d_strict), ms_strict = timed(lambda: staged_distributed_coreset(
        key, sp, sm, k, t=args.t, backend=args.backend))
    bit = bool((np.asarray(strict.weights) == np.asarray(base)).all())
    print(f"  staged strict            {ms_strict:8.1f} ms   "
          f"bit_equal_lockstep={bit}")
    assert bit, "strict staged mode must be bit-identical to lockstep"

    (over, d_over), ms_over = timed(lambda: staged_distributed_coreset(
        key, sp, sm, k, t=args.t, backend=args.backend,
        tol=1e-3, site_buckets=True))
    flat = over.flatten()
    c, _ = clustering.solve(key, flat.points, k,
                            weights=jnp.maximum(flat.weights, 0.0),
                            restarts=3, backend=args.backend)
    _, full = clustering.solve(key, jnp.asarray(pts), k, restarts=3,
                               backend=args.backend)
    ratio = float(clustering.cost(jnp.asarray(pts), c,
                                  backend=args.backend) / full)
    print(f"  staged overlap           {ms_over:8.1f} ms   "
          f"speedup_vs_lockstep={ms_lock / ms_over:.2f}x   "
          f"cost_ratio={ratio:.4f}")
    print(f"    site solve lengths {d_over.site_lengths}")
    print(f"    refinement passes  {list(np.asarray(d_over.iters_run))} "
          f"(cap 5)")
    assert int(np.asarray(over.t_i).sum()) == args.t
    print("OK")


if __name__ == "__main__":
    main()
