"""The plain reference: the centralized solve the cost ratio divides by,
the full-data cost, and the float64 checks of what the timed path
produced. Nothing here imports the program.

* :func:`central_solve` -- D^z seeding and 12 Lloyd (k-means) or
  Weiszfeld (k-median) updates on all points, best of 3 restarts, every
  dot at ``HIGHEST`` precision, on the device.
* :func:`full_cost` -- sum over all points of the distance^z to the
  nearest center, at ``HIGHEST`` precision.
* :func:`job_numbers` / :func:`serve_numbers` -- float64 NumPy on the
  host, the numbers ``correct`` compares with their limits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
# smoothing of the k-median (Weiszfeld) step: 1/sqrt(d^2 + ETA2)
ETA2 = 1e-6


def _sq_dists(p, c):
    p2 = jnp.sum(p * p, axis=1, keepdims=True)
    c2 = jnp.sum(c * c, axis=1)[None, :]
    return jnp.maximum(p2 + c2 - 2.0 * jnp.dot(p, c.T, precision=HI), 0.0)


def _metric(d2, z):
    return d2 if z == 2 else jnp.sqrt(d2)


@functools.partial(jax.jit, static_argnames=("z",))
def full_cost(pts, centers, z):
    """Cost of ``centers`` (k, d) on all points."""
    return jnp.sum(_metric(jnp.min(_sq_dists(pts, centers), axis=1), z))


def _update(pts, c, z):
    d2 = _sq_dists(pts, c)
    a = jnp.argmin(d2, axis=1)
    md = jnp.min(d2, axis=1)
    mass = (jnp.ones_like(md) if z == 2 else 1.0 / jnp.sqrt(md + ETA2))
    k = c.shape[0]
    num = jax.ops.segment_sum(pts * mass[:, None], a, num_segments=k)
    den = jax.ops.segment_sum(mass, a, num_segments=k)
    new = num / jnp.where(den > 0, den, 1.0)[:, None]
    return jnp.where((den > 0)[:, None], new, c)


def _seed(key, pts, k, z):
    n = pts.shape[0]
    key, k0 = jax.random.split(key)
    first = jax.random.randint(k0, (), 0, n)
    centers = jnp.zeros((k, pts.shape[1]), pts.dtype).at[0].set(pts[first])
    mind = _metric(_sq_dists(pts, pts[first][None])[:, 0], z)

    def body(i, carry):
        centers, mind, key = carry
        key, ki = jax.random.split(key)
        idx = jax.random.categorical(ki, jnp.log(mind + 1e-30))
        c = pts[idx]
        centers = centers.at[i].set(c)
        mind = jnp.minimum(mind, _metric(_sq_dists(pts, c[None])[:, 0], z))
        return centers, mind, key

    return jax.lax.fori_loop(1, k, body, (centers, mind, key))[0]


@functools.partial(jax.jit, static_argnames=("k", "z", "iters",
                                             "restarts"))
def central_solve(key, pts, k, z, iters=12, restarts=3):
    """Best of ``restarts`` (seeding + ``iters`` updates) on all points."""
    def one(ki):
        c = _seed(ki, pts, k, z)
        c = jax.lax.fori_loop(0, iters, lambda _, c: _update(pts, c, z), c)
        return c, full_cost(pts, c, z)

    cs, costs = jax.lax.map(one, jax.random.split(key, restarts))
    return cs[jnp.argmin(costs)]


@functools.partial(jax.jit, static_argnames=("k", "z"))
def seed_centers(key, pts, k, z):
    """D^z-sampled rows of ``pts`` (k-means++ seeding, no updates)."""
    return _seed(key, pts, k, z)


# ---------------------------------------------------------------------------
# float64 checks on the host
# ---------------------------------------------------------------------------

def _d2_64(p, c):
    """Squared distances (n, k) in float64."""
    p2 = np.einsum("nd,nd->n", p, p)[:, None]
    c2 = np.einsum("kd,kd->k", c, c)[None, :]
    return np.maximum(p2 + c2 - 2.0 * (p @ c.T), 0.0)


def _exact_min_d2(p, c, chunk=2048):
    """min_b |p - b|^2 by explicit differences, float64, (n,)."""
    return np.concatenate([
        np.min(((p[i:i + chunk, None, :] - c[None, :, :]) ** 2).sum(-1),
               axis=1) for i in range(0, len(p), chunk)] or [np.zeros(0)])


class SiteData:
    """The run's points grouped by site, in float64, for the checks."""

    def __init__(self, pts, site, n_sites: int):
        pts = np.asarray(pts, np.float64)
        site = np.asarray(site)
        order = np.argsort(site, kind="stable")
        bounds = np.searchsorted(site[order], np.arange(n_sites + 1))
        self.sites = [pts[order[bounds[i]:bounds[i + 1]]]
                      for i in range(n_sites)]
        self.pts = pts
        self.n = len(pts)


def _cost64(pts, centers, z, chunk=131072):
    total = 0.0
    for i in range(0, len(pts), chunk):
        md = _d2_64(pts[i:i + chunk], centers).min(1)
        total += float(np.sum(md if z == 2 else np.sqrt(md)))
    return total


def _step64(pts, centers, z, chunk=131072):
    """One reference update (Lloyd for z=2, one smoothed Weiszfeld step
    for z=1) of ``centers`` on all points, float64."""
    k, d = centers.shape
    num = np.zeros((k, d))
    den = np.zeros(k)
    for i in range(0, len(pts), chunk):
        p = pts[i:i + chunk]
        d2 = _d2_64(p, centers)
        a = d2.argmin(1)
        mass = (np.ones(len(p)) if z == 2
                else 1.0 / np.sqrt(d2[np.arange(len(p)), a] + ETA2))
        onehot = np.zeros((len(p), k))
        onehot[np.arange(len(p)), a] = mass
        num += onehot.T @ p
        den += onehot.sum(0)
    new = centers.copy()
    live = den > 0
    new[live] = num[live] / den[live, None]
    return new


def job_numbers(data: SiteData, job: dict, t: int, z: int) -> dict:
    """What ``correct`` compares for one clustering job.

    ``job`` holds the job's outputs on the host: ``centers`` (k, d),
    ``local_costs`` (n_sites,), ``site_centers`` (n_sites, k, d) -- each
    site's Round-1 solution as it travels in the coreset -- and the
    sampled rows ``samples`` (S, d), their ``sample_weights`` (S,) and
    ``sample_sites`` (S,), the coreset's rows of non-zero weight
    (``coreset_points``, ``coreset_weights``), and ``final_cost``, the
    program's coreset cost of ``centers``."""
    n_sites = len(data.sites)
    ref_cost = np.empty(n_sites)
    for i, p in enumerate(data.sites):
        md = _d2_64(p, np.asarray(job["site_centers"][i], np.float64)).min(1)
        ref_cost[i] = np.sum(md if z == 2 else np.sqrt(md))
    lc = np.asarray(job["local_costs"], np.float64)
    total = ref_cost.sum()
    t_i = np.bincount(job["sample_sites"], minlength=n_sites)

    # each sample's weight is sum(c) / (t m_q), where m_q is its distance
    # (k-means: squared) to its site's Round-1 centers: the mass the
    # weight implies, against the reference's, relative to the reference's
    # mass plus the site's mean cost per point (which keeps the share
    # finite where m_q is ~0)
    q = np.asarray(job["samples"], np.float64)
    s = np.asarray(job["sample_sites"])
    md_q = np.empty(len(q))
    for i in np.unique(s):
        rows = s == i
        md_q[rows] = _exact_min_d2(
            q[rows], np.asarray(job["site_centers"][i], np.float64))
    m_ref = md_q if z == 2 else np.sqrt(md_q)
    m_implied = total / (t * np.asarray(job["sample_weights"], np.float64))
    per_point = np.array([ref_cost[i] / len(p)
                          for i, p in enumerate(data.sites)])[s]

    # the final solve: the program's coreset cost of its centers against
    # the float64 cost, relative to the cost with every weight's size
    cp = np.asarray(job["coreset_points"], np.float64)
    cw = np.asarray(job["coreset_weights"], np.float64)
    c = np.asarray(job["centers"], np.float64)
    m_cs = _exact_min_d2(cp, c)
    m_cs = m_cs if z == 2 else np.sqrt(m_cs)
    cs_cost = float(np.sum(cw * m_cs))
    cs_scale = float(np.sum(np.abs(cw) * m_cs))

    cost0 = _cost64(data.pts, c, z)
    cost1 = _cost64(data.pts, _step64(data.pts, c, z), z)
    return dict(
        # against the mean site's cost: a site of fewer than k points has
        # a reference cost of 0 and the program's is rounding noise
        round1_cost_gap=float(np.max(np.abs(lc - ref_cost))
                              / np.mean(ref_cost)),
        weight_rms=float(np.sqrt(np.mean(
            ((m_implied - m_ref) / (m_ref + per_point)) ** 2))),
        alloc_gap=float(np.max(np.abs(t_i - t * ref_cost / total))),
        t_sum_gap=float(abs(int(t_i.sum()) - t)),
        final_cost_gap=abs(job["final_cost"] - cs_cost) / cs_scale,
        lloyd_gain=float((cost0 - cost1) / cost0),
    )


def serve_numbers(requests) -> dict:
    """What ``correct`` compares for served requests: ``requests`` is a
    list of (rows (n, d), centers (k, d), assign (n,), dist (n,))."""
    sq, worst_tie, n_rows = 0.0, 0.0, 0
    for rows, centers, assign, dist in requests:
        q = np.asarray(rows, np.float64)
        c = np.asarray(centers, np.float64)
        d2 = _d2_64(q, c)
        best = d2.argmin(1)
        md = d2[np.arange(len(q)), best]
        scale = np.einsum("nd,nd->n", q, q) + np.einsum(
            "kd,kd->k", c, c)[best]
        sq += float(np.sum(((np.asarray(dist, np.float64) - md) / scale)
                           ** 2))
        a = np.asarray(assign)
        picked = d2[np.arange(len(q)), np.clip(a, 0, len(c) - 1)]
        off = np.where((a >= 0) & (a < len(c)), (picked - md) / scale,
                       np.inf)
        worst_tie = max(worst_tie, float(off.max()))
        n_rows += len(q)
    return dict(dist_rms=float(np.sqrt(sq / max(n_rows, 1))),
                tie_gap=worst_tie)
