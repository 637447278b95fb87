"""The stand-in data set, its partition over the sites, and the site graph.

This is the benchmark's own copy of the program's generators
(``repro.data.synthetic.paper_dataset``, ``repro.core.partition``'s
``weighted`` rule and ``pad_partition``, ``repro.core.topology.erdos_renyi``)
so that a change to the program cannot move the yardstick. The points are
made on the device in one jitted call from the seed: a seeded Gaussian
mixture at the source data set's shape, with a few far outliers as real
tables have. Same seed, same data.

The ``weighted`` rule draws site weights ~ |N(0, 1)|. Here every seed gets
the same multiset of site sizes (the weights are the quantiles of |N(0, 1)|),
dealt out to the sites in the seed's own order, so the padded site shape,
and with it every compiled program and the work of a job, is the same for
every seed.
"""
from __future__ import annotations

import functools
import statistics

import jax
import jax.numpy as jnp
import numpy as np


def base_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed. ``PRNGKey`` keeps only the
    low 32 bits of a larger seed, so the high bits are folded in."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def host_seed(seed: int, salt: int) -> np.random.Generator:
    """A NumPy generator for the host-side draws of a run."""
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  salt])


def site_sizes(n: int, n_sites: int) -> np.ndarray:
    """Points per site under the ``weighted`` rule, the same for every
    seed: weights at the quantiles of |N(0, 1)| (floored at 1e-3), ``n``
    split by largest remainder, every site at least one point."""
    normal = statistics.NormalDist()
    u = (np.arange(n_sites) + 0.5) / n_sites
    w = np.maximum([normal.inv_cdf((1.0 + x) / 2.0) for x in u], 1e-3)
    share = (n - n_sites) * w / w.sum()
    sizes = np.floor(share).astype(np.int64)
    rest = (n - n_sites) - int(sizes.sum())
    sizes[np.argsort(-(share - sizes), kind="stable")[:rest]] += 1
    return sizes + 1


@functools.partial(jax.jit, static_argnames=("n", "d", "components"))
def _points_and_sites(key, sizes, n, d, components, noise):
    kc, kw, kcomp, ks, kn, ko, kp, ksite = jax.random.split(key, 8)
    centers = 3.0 * jax.random.normal(kc, (components, d), jnp.float32)
    weights = jax.random.dirichlet(kw, jnp.full((components,), 2.0))
    comp = jax.random.categorical(kcomp, jnp.log(weights), shape=(n,))
    spread = noise * (0.5 + jax.random.uniform(ks, (components,)))
    pts = centers[comp] + spread[comp][:, None] * jax.random.normal(
        kn, (n, d), jnp.float32)
    n_out = max(n // 1000, 1)       # a few far outliers
    pts = pts.at[:n_out].add(20.0 * jax.random.normal(ko, (n_out, d)))
    pts = pts[jax.random.permutation(kp, n)]
    # the points are in random order: site i takes the next block of its
    # size, the sizes dealt to the sites in the seed's order
    dealt = sizes[jax.random.permutation(ksite, sizes.shape[0])]
    site = jnp.repeat(jnp.arange(sizes.shape[0], dtype=jnp.int32), dealt,
                      total_repeat_length=n)
    return pts, site


@functools.partial(jax.jit, static_argnames=("n_sites", "rows"))
def _pad_sites(pts, site, n_sites, rows):
    order = jnp.argsort(site, stable=True)
    s_sorted = site[order]
    counts = jnp.bincount(site, length=n_sites)
    start = jnp.cumsum(counts) - counts
    rank = jnp.arange(site.shape[0]) - start[s_sorted]
    sp = jnp.zeros((n_sites, rows, pts.shape[1]), pts.dtype)
    sp = sp.at[s_sorted, rank].set(pts[order])
    sm = jnp.zeros((n_sites, rows), bool).at[s_sorted, rank].set(True)
    return sp, sm


def make_sites(seed: int, n: int, d: int, components: int, noise: float,
               n_sites: int, pad_multiple: int = 8):
    """Points ``(n, d)`` f32, each point's site ``(n,)`` and the padded
    sites ``(n_sites, M, d)`` + mask ``(n_sites, M)``, all on the device.
    ``M`` is the largest site rounded up to ``pad_multiple`` rows."""
    sizes = site_sizes(n, n_sites)
    pts, site = _points_and_sites(base_key(seed), jnp.asarray(sizes), n, d,
                                  components, float(noise))
    rows = int(-(-sizes.max() // pad_multiple) * pad_multiple)
    sp, sm = _pad_sites(pts, site, n_sites, rows)
    return pts, site, sp, sm


def erdos_renyi_edges(n: int, p: float, seed: int):
    """G(n, p) as a sorted edge list, made connected by bridging
    components with random edges."""
    rng = host_seed(seed, 1)
    mask = rng.random((n, n)) < p
    edges = {(i, j) for i in range(n) for j in range(i + 1, n) if mask[i, j]}
    while True:
        comps = _components(n, edges)
        if len(comps) == 1:
            return tuple(sorted(edges))
        a, b = int(rng.choice(comps[0])), int(rng.choice(comps[1]))
        edges.add((min(a, b), max(a, b)))


def _components(n: int, edges) -> list:
    parent = list(range(n))

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for i, j in edges:
        parent[find(i)] = find(j)
    groups = {}
    for v in range(n):
        groups.setdefault(find(v), []).append(v)
    return list(groups.values())
