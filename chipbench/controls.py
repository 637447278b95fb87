"""The readings that the limits of ``correct`` are set from. The
benchmark's own runs never run this.

    python3 chipbench/controls.py --workload msd_kmeans.job \
        --seeds 11 12 13 --variants program control_high half_batch

For each seed it makes the cell's set-up once, then runs the timed path
once per variant and prints one ``reading {json}`` line with the numbers
``correct`` compares:

* ``program`` -- the timed path as it is (the lower readings);
* ``control_high`` / ``control_bf16`` -- the control: the program's own
  dense path (backend ``jnp``) with its matmuls at the precision below the
  configuration's (``high``, three bf16 passes) or at one bf16 pass;
* ``state_unchanged`` -- every Lloyd/Weiszfeld update returns its input;
* ``half_batch`` -- the distance pass computes the first half of its rows
  and repeats them for the rest;
* ``answer_altered`` -- one answer changed where it is produced: site 0's
  Round-1 cost (+1%) in a job, one row's assignment in serving.

A job variant runs the window's first job (key ``fold_in(key(seed), 0)``);
a serving variant runs a short window at the cell's own rate.
"""
from __future__ import annotations

import contextlib
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import harness, reference  # noqa: E402

CONTROL_PRECISION = {"control_high": "high", "control_bf16": "bfloat16"}


@contextlib.contextmanager
def _patched(obj, attr, new):
    old = getattr(obj, attr)
    setattr(obj, attr, new)
    jax.clear_caches()
    try:
        yield
    finally:
        setattr(obj, attr, old)
        jax.clear_caches()


def _half_rows(fn, axis):
    """``fn`` computed on the first half of the rows along ``axis``, its
    outputs repeated for the second half."""
    def half(points, centers):
        n = points.shape[axis]
        h = (n + 1) // 2
        outs = fn(jax.lax.slice_in_dim(points, 0, h, axis=axis), centers)
        return tuple(jnp.concatenate(
            [o, jax.lax.slice_in_dim(o, 0, n - h, axis=axis)], axis=axis)
            for o in outs)
    return half


def _split_dot(a, b, prec: str, spec: str):
    """``einsum(spec, a, b)`` as the TPU's MXU computes it at ``prec``
    from bf16 parts: one pass, or three (hi*hi + hi*lo + lo*hi)."""
    def parts(x):
        hi = x.astype(jnp.bfloat16).astype(jnp.float32)
        return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)
    (ah, al), (bh, bl) = parts(a), parts(b)
    hp = jax.lax.Precision.HIGHEST
    out = jnp.einsum(spec, ah, bh, precision=hp)
    if prec == "high":
        out = (out + jnp.einsum(spec, ah, bl, precision=hp)
               + jnp.einsum(spec, al, bh, precision=hp))
    return out


@contextlib.contextmanager
def _emulated_on_cpu(prec: str):
    """On the CPU, where matmul precision changes nothing, the dense
    backend's distance passes computed from bf16 parts as the chip would
    at ``prec``. On the chip the precision itself does it."""
    if jax.default_backend() != "cpu":
        yield
        return
    from repro.core import backend as backend_mod
    b = backend_mod.get_backend("jnp")

    def dists(p, c, spec):
        d2 = (jnp.sum(p * p, -1)[..., :, None] + jnp.sum(c * c, -1)[
            ..., None, :] - 2.0 * _split_dot(p, c, prec, spec))
        d2 = jnp.maximum(d2, 0.0)
        a = jnp.argmin(d2, axis=-1)
        return jnp.take_along_axis(d2, a[..., None], -1)[..., 0], \
            a.astype(jnp.int32)

    with _patched(b, "min_dist_argmin",
                  lambda p, c: dists(p, c, "nd,kd->nk")), \
            _patched(b, "min_dist_argmin_batched",
                     lambda p, c: dists(p, c, "tnd,tkd->tnk")):
        yield


@contextlib.contextmanager
def plant(variant: str, engine=None):
    """Switch the timed path to ``variant`` for the duration."""
    from repro.core import backend as backend_mod
    from repro.core import clustering, coreset
    if variant == "program":
        yield
    elif variant in CONTROL_PRECISION:
        old = engine.backend if engine is not None else None
        prec = CONTROL_PRECISION[variant]
        with backend_mod.use_backend("jnp"), \
                jax.default_matmul_precision(prec), _emulated_on_cpu(prec):
            if engine is not None:
                engine.backend = "jnp"
            try:
                yield
            finally:
                if engine is not None:
                    engine.backend = old
    elif variant == "state_unchanged":
        def unchanged(points, centers, weights, iters, objective, k,
                      backend):
            return centers, jnp.zeros((iters,), jnp.float32)
        with _patched(clustering, "_lloyd", unchanged):
            yield
    elif variant == "half_batch":
        b = backend_mod.get_backend(None)
        with _patched(b, "min_dist_argmin", _half_rows(b.min_dist_argmin,
                                                       0)), \
                _patched(b, "min_dist_argmin_batched",
                         _half_rows(b.min_dist_argmin_batched, 1)):
            yield
    elif variant == "answer_altered":
        solves = coreset.round1_local_solves
        query = backend_mod.query_assignments_batched

        def altered_solves(*args, **kwargs):
            c, m, assign, costs, w = solves(*args, **kwargs)
            return c, m, assign, costs.at[0].multiply(1.01), w

        def altered_query(queries, centers, *args, **kwargs):
            assign, dist = query(queries, centers, *args, **kwargs)
            return assign.at[0, 0].set((assign[0, 0] + 1) % 8), dist

        with _patched(coreset, "round1_local_solves", altered_solves), \
                _patched(backend_mod, "query_assignments_batched",
                         altered_query):
            yield
    else:
        raise ValueError(f"unknown variant {variant!r}")


def readings(cell: harness.Cell, seed: int, variants, seconds: float = 3.0):
    """(variant, numbers, failed) for each variant on one seed."""
    ctx = harness.Context(cell, seed, seconds, trace=False)
    ctx.devices = jax.devices()[:cell.chips]
    ctx.device_kind = ctx.devices[0].device_kind
    st = cell.kind.setup(ctx)
    out = []
    if cell.traffic["kind"] == "job":
        cfg = cell.config
        data = reference.SiteData(st["pts"], st["site"], cfg["sites"])
        for v in variants:
            with plant(v):
                res = cell.kind.run_job(ctx, st,
                                        jax.random.fold_in(st["key"], 0))
                o = cell.kind.job_outputs(res, cfg)
            out.append((v, reference.job_numbers(data, o, st["t"], st["z"]),
                        0))
    else:
        for v in variants:
            with plant(v, engine=st["engine"]):
                win = cell.kind.window(ctx, st)
                res = cell.kind.finish(ctx, st, win)
            out.append((v, res["numbers"], res["failed"]))
    return out


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--variants", nargs="+", default=["program"])
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    harness.enable_cache()
    cell = harness.Cell(args.workload)
    for seed in args.seeds:
        t0 = time.perf_counter()
        for v, numbers, failed in readings(cell, seed, args.variants,
                                           args.seconds):
            print("reading " + json.dumps(dict(
                workload=cell.name, seed=seed, variant=v, failed=failed,
                platform=jax.devices()[0].platform, **numbers)), flush=True)
        print(f"seed {seed} took {time.perf_counter() - t0:.1f} s",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
