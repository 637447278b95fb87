"""2-D torus collective schedule, staged-overlap coreset engine, and
per-phase roofline attribution tests (DESIGN.md Sec. 17).

The SPMD parity checks run in subprocesses with forced host devices (the
same idiom as test_core_distributed: jax is already imported in-process,
so device count must be set in a fresh interpreter). Host-side tests cover
the staged engine's strict bit-parity contract, the relaxed-mode
invariants, and the HLO phase parser on a synthetic module.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import clustering, topology
from repro.core.coreset import distributed_coreset, staged_distributed_coreset
from repro.core.message_passing import collective_hops, torus_mesh_shape
from repro.core.partition import pad_partition, partition_indices
from repro.kernels.ops import site_bucket_lengths
from repro.roofline.hlo import collective_phase_analysis

KEY = jax.random.PRNGKey(0)


def _run_spmd_script(script: str) -> None:
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "TORUS_OK" in out.stdout, out.stdout + out.stderr


# -- analytic hop model --------------------------------------------------------

def test_torus_mesh_shape_most_square():
    assert torus_mesh_shape(16) == (4, 4)
    assert torus_mesh_shape(8) == (2, 4)
    assert torus_mesh_shape(12) == (3, 4)
    assert torus_mesh_shape(6) == (2, 3)
    assert torus_mesh_shape(7) == (1, 7)       # prime degenerates to the ring
    assert torus_mesh_shape(1) == (1, 1)
    with pytest.raises(ValueError):
        torus_mesh_shape(0)


def test_collective_hops():
    # ring depth for the flat-axis schedules; (R-1)+(C-1) for the folding
    assert collective_hops("all_gather", 16) == 15
    assert collective_hops("neighbor_rounds", 16) == 15
    assert collective_hops("torus_2d", 16) == 6            # (4,4) default
    assert collective_hops("torus_2d", 16, (2, 8)) == 8
    assert collective_hops("torus_2d", 7) == 6             # ring fallback
    # every proper 2-D folding beats the ring once R*C >= 16
    for n in (16, 20, 24, 32, 64):
        assert collective_hops("torus_2d", n) < collective_hops(
            "all_gather", n)
    with pytest.raises(ValueError, match="does not tile"):
        collective_hops("torus_2d", 16, (3, 2))
    with pytest.raises(ValueError, match="unknown collectives"):
        collective_hops("warp", 8)


# -- SPMD parity: torus vs the all_gather oracle (acceptance criterion) -------

TORUS_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.compat import shard_map
    from repro.core import spmd_distributed_kmeans
    from repro.core.distributed import spmd_distributed_kmeans_fn
    from repro.core.message_passing import (collective_hops,
                                            neighbor_rounds_sum,
                                            torus_rounds_gather,
                                            torus_rounds_sum)
    from repro.core.partition import partition_indices, pad_partition
    from repro.roofline.hlo import collective_phase_analysis

    rng = np.random.default_rng(0)
    k, d = 4, 8
    c0 = 3.0 * rng.standard_normal((k, d))
    pts = np.concatenate([c0[i] + 0.15 * rng.standard_normal((400, d))
                          for i in range(k)]).astype(np.float32)
    idx = partition_indices(pts, 8, "weighted", seed=1)
    sp, sm = pad_partition(pts, idx)
    sp, sm = jnp.asarray(sp), jnp.asarray(sm)
    mesh = jax.make_mesh((8,), ("sites",))
    t = 256
    key = jax.random.PRNGKey(0)

    # centers/local_costs/t_i bit-identical to the all_gather oracle for
    # BOTH objectives, under the default (2,4) and the transposed (4,2)
    # foldings of the same flat axis
    for objective in ("kmeans", "kmedian"):
        c, lc, t_i = spmd_distributed_kmeans(
            mesh, "sites", key, sp, sm, k, t=t, t_buffer=t,
            objective=objective)
        for mesh_shape in (None, (4, 2)):
            c2, lc2, t_i2 = spmd_distributed_kmeans(
                mesh, "sites", key, sp, sm, k, t=t, t_buffer=t,
                objective=objective, collectives="torus_2d",
                mesh_shape=mesh_shape)
            tag = (objective, mesh_shape)
            assert (np.asarray(c2) == np.asarray(c)).all(), tag
            assert (np.asarray(lc2) == np.asarray(lc)).all(), tag
            assert (np.asarray(t_i2) == np.asarray(t_i)).all(), tag

    # knob validation: a non-tiling folding and a folding without the
    # torus mode both fail loudly
    try:
        spmd_distributed_kmeans(mesh, "sites", key, sp, sm, k, t=t,
                                collectives="torus_2d", mesh_shape=(3, 2))
        raise SystemExit("expected ValueError: mesh_shape does not tile")
    except ValueError as e:
        assert "does not tile" in str(e), e
    try:
        spmd_distributed_kmeans(mesh, "sites", key, sp, sm, k, t=t,
                                mesh_shape=(2, 4))
        raise SystemExit("expected ValueError: mesh_shape without torus")
    except ValueError as e:
        assert "torus" in str(e), e
    try:
        spmd_distributed_kmeans(mesh, "sites", key, sp, sm, k, t=t,
                                collectives="warp")
        raise SystemExit("expected ValueError: unknown collectives")
    except ValueError as e:
        assert "unknown collectives" in str(e), e

    # torus primitives: gather is an exact relay; both explicit sums agree
    # with psum within the documented float tolerance (rtol 1e-6 -- the
    # hop-by-hop association order differs from XLA's reduction) and are
    # bit-exact with themselves across repeated runs (fixed schedule =>
    # deterministic reduction order)
    x = jnp.arange(8, dtype=jnp.float32) * 1.7 + 0.3
    prim = jax.jit(shard_map(
        lambda v: (torus_rounds_gather(v[0], "sites", (2, 4))[None],
                   torus_rounds_sum(v[0], "sites", (2, 4))[None],
                   neighbor_rounds_sum(v[0], "sites", 8)[None],
                   jax.lax.psum(v[0], "sites")[None]),
        mesh=mesh, in_specs=P("sites"), out_specs=P("sites")))
    g1, ts1, ns1, ps = prim(x)
    assert (np.asarray(g1) == np.asarray(x)[None].repeat(8, 0)).all()
    np.testing.assert_allclose(np.asarray(ts1), np.asarray(ps), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(ns1), np.asarray(ps), rtol=1e-6)
    g2, ts2, ns2, _ = prim(x)
    assert (np.asarray(g1) == np.asarray(g2)).all()
    assert (np.asarray(ts1) == np.asarray(ts2)).all()
    assert (np.asarray(ns1) == np.asarray(ns2)).all()

    # axis-size / folding validation raises at trace time, not silently
    # wrong answers (the schedule is built from the *claimed* size)
    try:
        jax.jit(shard_map(
            lambda v: neighbor_rounds_sum(v[0], "sites", 4)[None],
            mesh=mesh, in_specs=P("sites"), out_specs=P("sites")))(x)
        raise SystemExit("expected ValueError: axis_size mismatch")
    except ValueError as e:
        assert "disagrees" in str(e), e
    try:
        jax.jit(shard_map(
            lambda v: torus_rounds_sum(v[0], "sites", (2, 2))[None],
            mesh=mesh, in_specs=P("sites"), out_specs=P("sites")))(x)
        raise SystemExit("expected ValueError: folding mismatch")
    except ValueError as e:
        pass

    # compiled-HLO cross-check: the torus program's Round-1 gather issues
    # exactly its analytic hop depth in sequential ppermutes, and Round 2
    # (two gathers) exactly twice that
    fn = spmd_distributed_kmeans_fn("sites", 8, k, t, t,
                                    collectives="torus_2d")
    def device_fn(key, p, m):
        return fn(key, p.reshape(-1, p.shape[-1]), m.reshape(-1))
    hlo = jax.jit(shard_map(
        device_fn, mesh=mesh,
        in_specs=(P(), P("sites"), P("sites")),
        out_specs=(P(), P("sites"), P("sites")),
    )).lower(key, sp, sm).compile().as_text()
    ph = collective_phase_analysis(hlo)
    hops = collective_hops("torus_2d", 8)
    pp1 = int(ph["round1"].collective_counts.get("collective-permute", 0))
    pp2 = int(ph["round2"].collective_counts.get("collective-permute", 0))
    assert pp1 == hops, (pp1, hops)
    assert pp2 == 2 * hops, (pp2, hops)
    print("TORUS_OK")
""")


def test_spmd_torus_parity_8dev():
    _run_spmd_script(TORUS_SCRIPT)


# Non-power-of-two regression: the ring/torus ppermute schedules make no
# power-of-two assumption (unlike recursive-doubling lowerings), so a
# 6-device axis must give the same exact relays and end-to-end parity.
NONPOW2_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=6"
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.compat import shard_map
    from repro.core import spmd_distributed_kmeans
    from repro.core.message_passing import (neighbor_rounds_gather,
                                            neighbor_rounds_sum,
                                            torus_mesh_shape,
                                            torus_rounds_gather,
                                            torus_rounds_sum)
    from repro.core.partition import partition_indices, pad_partition

    assert torus_mesh_shape(6) == (2, 3)
    mesh = jax.make_mesh((6,), ("sites",))
    x = jnp.arange(6, dtype=jnp.float32) * 0.9 - 1.1
    g_ring, g_torus, s_ring, s_torus, ps = jax.jit(shard_map(
        lambda v: (neighbor_rounds_gather(v[0], "sites", 6)[None],
                   torus_rounds_gather(v[0], "sites", (2, 3))[None],
                   neighbor_rounds_sum(v[0], "sites", 6)[None],
                   torus_rounds_sum(v[0], "sites", (2, 3))[None],
                   jax.lax.psum(v[0], "sites")[None]),
        mesh=mesh, in_specs=P("sites"), out_specs=P("sites")))(x)
    ref = np.asarray(x)[None].repeat(6, 0)
    assert (np.asarray(g_ring) == ref).all()
    assert (np.asarray(g_torus) == ref).all()
    np.testing.assert_allclose(np.asarray(s_ring), np.asarray(ps),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(s_torus), np.asarray(ps),
                               rtol=1e-6)

    rng = np.random.default_rng(0)
    k, d = 4, 8
    c0 = 3.0 * rng.standard_normal((k, d))
    pts = np.concatenate([c0[i] + 0.15 * rng.standard_normal((300, d))
                          for i in range(k)]).astype(np.float32)
    idx = partition_indices(pts, 6, "weighted", seed=1)
    sp, sm = pad_partition(pts, idx)
    sp, sm = jnp.asarray(sp), jnp.asarray(sm)
    key = jax.random.PRNGKey(0)
    t = 192
    c, lc, t_i = spmd_distributed_kmeans(mesh, "sites", key, sp, sm, k,
                                         t=t, t_buffer=t)
    for mode in ("neighbor_rounds", "torus_2d"):
        c2, lc2, t_i2 = spmd_distributed_kmeans(
            mesh, "sites", key, sp, sm, k, t=t, t_buffer=t,
            collectives=mode)
        assert (np.asarray(c2) == np.asarray(c)).all(), mode
        assert (np.asarray(lc2) == np.asarray(lc)).all(), mode
        assert (np.asarray(t_i2) == np.asarray(t_i)).all(), mode
    print("TORUS_OK")
""")


def test_spmd_collectives_nonpow2_6dev():
    _run_spmd_script(NONPOW2_SCRIPT)


# -- staged-overlap coreset engine --------------------------------------------

def _sites(n_sites=6, seed=0, per=150):
    rng = np.random.default_rng(seed)
    k, d = 4, 8
    centers = 3.0 * rng.standard_normal((k, d))
    pts = np.concatenate(
        [centers[i] + 0.15 * rng.standard_normal((per, d)) for i in range(k)]
    ).astype(np.float32)
    idx = partition_indices(pts, n_sites, "weighted", seed=seed + 1)
    sp, sm = pad_partition(pts, idx)
    return pts, jnp.asarray(sp), jnp.asarray(sm), k


_FIELDS = ("points", "weights", "t_i", "local_costs")


@pytest.mark.parametrize("strategy", ["algorithm1", "cohen_addad",
                                      "mapreduce"])
@pytest.mark.parametrize("objective", ["kmeans", "kmedian"])
def test_staged_strict_bit_parity(strategy, objective):
    """With tol=0 and no buckets, every output field of the staged engine
    is bit-identical to the lockstep vmap for every registered strategy --
    the frozen algorithm1 key-derivation/digest contract survives."""
    _, sp, sm, k = _sites()
    t = 200
    base = distributed_coreset(KEY, sp, sm, k, t=t, objective=objective,
                               strategy=strategy)
    staged, detail = staged_distributed_coreset(
        KEY, sp, sm, k, t=t, objective=objective, strategy=strategy)
    for f in _FIELDS:
        a, b = np.asarray(getattr(base, f)), np.asarray(getattr(staged, f))
        assert (a == b).all(), f"{strategy}/{objective}: {f} differs"
    assert detail.site_lengths == (sp.shape[1],) * sp.shape[0]
    assert (np.asarray(detail.iters_run) == 5).all()  # lockstep iter count


def test_staged_overlap_mode_deterministic_and_valid():
    """tol>0 + site_buckets trades bit-parity for wall-clock but keeps the
    hard invariants: deterministic across runs, sum(t_i) == t exactly,
    total weight == |P|, per-site lengths power-of-two <= the lockstep pad,
    and coreset quality stays competitive."""
    pts, sp, sm, k = _sites()
    t = 200
    run = lambda: staged_distributed_coreset(
        KEY, sp, sm, k, t=t, tol=1e-3, site_buckets=True)
    cs1, d1 = run()
    cs2, _ = run()
    for f in _FIELDS:
        a, b = np.asarray(getattr(cs1, f)), np.asarray(getattr(cs2, f))
        assert (a == b).all(), f"nondeterministic field {f}"
    assert int(np.asarray(cs1.t_i).sum()) == t
    np.testing.assert_allclose(float(jnp.sum(cs1.weights)), len(pts),
                               rtol=1e-3)
    M = sp.shape[1]
    for ln in d1.site_lengths:
        # each length is a power-of-two bucket, or the lockstep pad M when
        # the bucket would overshoot it (the clamp)
        assert ln <= M and ((ln & (ln - 1)) == 0 or ln == M)
    assert (np.asarray(d1.iters_run) <= 5).all()
    flat = cs1.flatten()
    c, _ = clustering.solve(KEY, flat.points, k,
                            weights=jnp.maximum(flat.weights, 0.0),
                            restarts=3)
    _, full = clustering.solve(KEY, jnp.asarray(pts), k, restarts=4)
    assert float(clustering.cost(jnp.asarray(pts), c) / full) < 1.3


def test_lloyd_converged_strict_matches_lloyd():
    rng = np.random.default_rng(0)
    pts = jnp.asarray(rng.standard_normal((300, 5)).astype(np.float32))
    init = clustering.kmeans_pp_init(KEY, pts, 4)
    ref, _ = clustering.lloyd(pts, init, iters=6)
    out, iters_run = clustering.lloyd_converged(pts, init, iters=6, tol=0.0)
    assert (np.asarray(out) == np.asarray(ref)).all()
    assert int(iters_run) == 6


def test_lloyd_converged_early_exit():
    # well-separated blobs converge in a couple of passes; the while_loop
    # must stop long before the iteration cap, at ~the fixed-point quality
    rng = np.random.default_rng(1)
    blobs = np.concatenate([c + 0.05 * rng.standard_normal((100, 3))
                            for c in (np.zeros(3), 10 * np.ones(3),
                                      -10 * np.ones(3))]).astype(np.float32)
    pts = jnp.asarray(blobs)
    init = clustering.kmeans_pp_init(KEY, pts, 3)
    ref, _ = clustering.lloyd(pts, init, iters=50)
    out, iters_run = clustering.lloyd_converged(pts, init, iters=50,
                                                tol=1e-3)
    assert int(iters_run) < 50
    np.testing.assert_allclose(float(clustering.cost(pts, out)),
                               float(clustering.cost(pts, ref)), rtol=1e-2)


def test_site_bucket_lengths():
    assert site_bucket_lengths((3, 70, 500), 512) == (64, 128, 512)
    # clamped at the lockstep pad even when the bucket would overshoot
    assert site_bucket_lengths((400,), 300) == (300,)
    assert site_bucket_lengths((1,), 512, min_bucket=16) == (16,)


# -- per-phase HLO attribution -------------------------------------------------

_PHASED_HLO = textwrap.dedent("""
    HloModule phases

    %wcond (p.0: (s32[], f32[4])) -> pred[] {
      %p.0 = (s32[], f32[4]) parameter(0)
      %i.0 = s32[] get-tuple-element((s32[], f32[4]) %p.0), index=0
      %t.0 = s32[] constant(3)
      ROOT %lt.0 = pred[] compare(s32[] %i.0, s32[] %t.0), direction=LT
    }

    %wbody (p.1: (s32[], f32[4])) -> (s32[], f32[4]) {
      %p.1 = (s32[], f32[4]) parameter(0)
      %i.1 = s32[] get-tuple-element((s32[], f32[4]) %p.1), index=0
      %b.1 = f32[4] get-tuple-element((s32[], f32[4]) %p.1), index=1
      %cp.1 = f32[4] collective-permute(f32[4] %b.1), source_target_pairs={{0,1},{1,0}}, metadata={op_name="jit(fn)/round1/ppermute"}
      %one.1 = s32[] constant(1)
      %ip.1 = s32[] add(s32[] %i.1, s32[] %one.1)
      ROOT %tup.1 = (s32[], f32[4]) tuple(s32[] %ip.1, f32[4] %cp.1)
    }

    ENTRY %main (x.2: f32[4]) -> f32[32] {
      %x.2 = f32[4] parameter(0)
      %c0.2 = s32[] constant(0)
      %tup.2 = (s32[], f32[4]) tuple(s32[] %c0.2, f32[4] %x.2)
      %w.2 = (s32[], f32[4]) while((s32[], f32[4]) %tup.2), condition=%wcond, body=%wbody
      %g.2 = f32[4] get-tuple-element((s32[], f32[4]) %w.2), index=1
      %ag.2 = f32[32] all-gather(f32[4] %g.2), replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}, metadata={op_name="jit(fn)/round2/all_gather"}
      ROOT %un.2 = f32[32] all-gather(f32[4] %g.2), replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
    }
""")


def test_collective_phase_analysis_loops_and_scopes():
    """A ppermute inside a 3-trip while body counts 3 sequential issues
    under its named_scope phase; collectives without a phase scope land in
    'other'; non-collective ops contribute nothing."""
    ph = collective_phase_analysis(_PHASED_HLO)
    r1, r2, other = ph["round1"], ph["round2"], ph["other"]
    assert r1.collective_counts == {"collective-permute": 3.0}
    assert r1.ici_collective_bytes > 0
    assert r2.collective_counts == {"all-gather": 1.0}
    assert other.collective_counts == {"all-gather": 1.0}
    # phase matching is by exact path segment: "round1" must not bleed
    # into a custom phase list that doesn't contain it
    ph2 = collective_phase_analysis(_PHASED_HLO, phases=("round2",))
    assert ph2["round2"].collective_counts == {"all-gather": 1.0}
    assert ph2["other"].collective_counts.get("collective-permute") == 3.0
