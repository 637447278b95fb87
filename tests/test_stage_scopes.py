"""Stage names inside the clustering job: ``jax.named_scope`` segments in
the compiled programs' ``op_name`` metadata, and the host spans
(``jax.profiler.TraceAnnotation``) a job emits with the rows each stage
sweeps."""
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import clustering, coreset, graph_distributed_kmeans
from repro.core import strategy as strategy_mod
from repro.core.partition import pad_partition, partition_indices
from repro.core.topology import erdos_renyi

N_SITES, M, D, K = 4, 40, 5, 3
STAGES = ("round1", "allocate", "round2", "final_solve")


def _scope_paths(compiled_text):
    """Every op_name of a compiled program as its tuple of scope names; a
    transform wraps the first name pushed under it (``vmap(sensitivity)``)
    and is unwrapped here."""
    out = set()
    for name in re.findall(r'op_name="([^"]*)"', compiled_text):
        segs = []
        for seg in name.split("/"):
            while (m := re.match(r"^[\w.-]+\((.*)\)$", seg)):
                seg = m.group(1)
            segs.append(seg)
        out.add(tuple(segs))
    return out


def _has(paths, *scopes):
    return any(all(s in p for s in scopes) for p in paths)


def _sites():
    sp = jax.random.normal(jax.random.PRNGKey(0), (N_SITES, M, D))
    w = jnp.ones((N_SITES, M))
    keys = jax.random.split(jax.random.PRNGKey(1), N_SITES)
    return keys, sp, w


@pytest.mark.parametrize("objective", ["kmeans", "kmedian"])
def test_round1_carries_seed_update_and_sensitivity(objective):
    keys, sp, w = _sites()
    paths = _scope_paths(coreset.round1_local_solves.lower(
        keys, sp, w, k=K, objective=objective, lloyd_iters=2,
        backend="jnp").compile().as_text())
    for scope in ("seed", "update", "sensitivity"):
        assert _has(paths, "round1", scope), scope
    # the program's own name is not a scope: only the named_scope matches
    assert ("round1_local_solves", "round1") in {p[:2] for p in paths}
    assert not _has(paths, "round2")


@pytest.mark.parametrize("localized", [False, True])
def test_round2_carries_its_scope(localized):
    keys, sp, w = _sites()
    m = jnp.ones((N_SITES, M))
    assign = jnp.zeros((N_SITES, M), jnp.int32)
    centers = sp[:, :K]
    t_i = jnp.full((N_SITES,), 5, jnp.int32)
    totals = jnp.full((N_SITES,), float(M))
    if localized:
        low = coreset.round2_local_samples_localized.lower(
            keys, sp, m, w, assign, centers, t_i, totals, k=K, t_buffer=8,
            clip_negative=False)
    else:
        low = coreset.round2_local_samples.lower(
            keys, sp, m, w, assign, centers, t_i, totals, k=K, t=20,
            t_buffer=8, clip_negative=False)
    paths = _scope_paths(low.compile().as_text())
    assert _has(paths, "round2")
    assert not _has(paths, "round1") and not _has(paths, "seed")


def test_final_solve_programs_carry_seed_and_update():
    pts = jax.random.normal(jax.random.PRNGKey(2), (64, D))
    w = jnp.ones((64,))
    seed = _scope_paths(clustering._kmeans_pp_init.lower(
        jax.random.PRNGKey(3), pts, w, k=K,
        objective="kmeans").compile().as_text())
    assert _has(seed, "seed") and not _has(seed, "round1")
    # the categorical draws, nested in the seeding scope
    assert any(p.index("seed") < p.index("draw")
               for p in seed if "seed" in p and "draw" in p)
    c = pts[:K]
    upd = _scope_paths(clustering._lloyd.lower(
        pts, c, w, iters=2, objective="kmeans", k=K,
        backend="jnp").compile().as_text())
    assert _has(upd, "update") and not _has(upd, "seed")
    conv = _scope_paths(clustering._lloyd_converged.lower(
        pts, c, w, iters=2, tol=1e-3, objective="kmeans", k=K,
        backend="jnp").compile().as_text())
    assert _has(conv, "update")


def test_refined_sensitivities_carry_their_scope():
    m = jnp.ones((N_SITES, M))
    assign = jnp.zeros((N_SITES, M), jnp.int32)
    paths = _scope_paths(strategy_mod._refine_batch.lower(
        m, assign, m, k=K).compile().as_text())
    assert _has(paths, "sensitivity")


def _host_spans(trace_dir):
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    pd = jax.profiler.ProfileData.from_file(path)
    spans = [(e.start_ns, e.name, {str(k): v for k, v in e.stats})
             for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name in STAGES]
    return [(name, args) for _, name, args in sorted(spans,
                                                     key=lambda s: s[0])]


@pytest.mark.parametrize("engine,routing", [("sim", "flood"),
                                            ("exec", "flood"),
                                            ("sim", "bfs"),
                                            ("exec", "bfs")])
def test_a_job_emits_each_stage_span_once_in_order(tmp_path, engine,
                                                   routing):
    rng = np.random.default_rng(0)
    pts = rng.standard_normal((300, D)).astype(np.float32)
    g = erdos_renyi(N_SITES, 0.6, seed=1)
    sp, sm = pad_partition(pts, partition_indices(pts, g.n, "weighted",
                                                  seed=2))
    sp, sm = jnp.asarray(sp), jnp.asarray(sm)
    t = 30

    def job():
        res = graph_distributed_kmeans(jax.random.PRNGKey(4), sp, sm, K, t,
                                       g, engine=engine, routing=routing,
                                       backend="jnp")
        jax.block_until_ready(res.centers)
        return res

    job()                       # compile outside the traced job
    d = str(tmp_path / "trace")
    jax.profiler.start_trace(d)
    try:
        res = job()
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(d)
    assert [name for name, _ in spans] == list(STAGES)
    args = dict(spans)
    rows = sp.shape[0] * sp.shape[1]
    assert args["round1"] == dict(sites=N_SITES, rows=rows, d=D, k=K)
    assert args["round2"] == dict(sites=N_SITES, rows=rows)
    # the final solve sweeps the rows that can carry weight: t samples and
    # each site's K centers, not the whole buffer
    assert res.coreset.points.shape[0] == N_SITES * (t + K)
    assert args["final_solve"] == dict(rows=t + N_SITES * K, k=K)
