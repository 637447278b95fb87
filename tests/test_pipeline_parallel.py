"""GPipe-style pipeline parallelism: schedule math + numerical equivalence
against the unpipelined stack (subprocess with 4 host devices)."""
import os
import subprocess
import sys
import textwrap

from repro.train.pipeline import PipelineSchedule


def test_schedule_bubble_math():
    s = PipelineSchedule(n_stages=4, n_microbatches=12)
    assert s.ticks == 15
    assert abs(s.bubble_fraction - 3 / 15) < 1e-9
    s2 = PipelineSchedule(n_stages=1, n_microbatches=8)
    assert s2.bubble_fraction == 0.0


PP_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.compat import shard_map
    from repro.launch.mesh import make_mesh
    from repro.train.pipeline import pipeline_forward

    S, M, mb, d = 4, 8, 2, 16
    rng = np.random.default_rng(0)
    Ws = rng.standard_normal((S, d, d)).astype(np.float32) / np.sqrt(d)
    xs = rng.standard_normal((M, mb, d)).astype(np.float32)

    def stage_fn(w, x):
        return jnp.tanh(x @ w)

    # reference: sequential application of all stages
    ref = jnp.asarray(xs)
    for s in range(S):
        ref = jax.vmap(lambda x: stage_fn(jnp.asarray(Ws[s]), x))(ref)

    mesh = make_mesh((4,), ("stage",))
    def run(w_all, mbs):
        return pipeline_forward(stage_fn, w_all[0], mbs, "stage", S)

    out = jax.jit(shard_map(
        run, mesh=mesh,
        in_specs=(P("stage"), P()),
        out_specs=P("stage")))(jnp.asarray(Ws), jnp.asarray(xs))
    # output lives on the last stage's shard
    got = out.reshape(4, M, mb, d)[-1]
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    print("PP_OK")
""")


def test_pipeline_matches_sequential():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    cwd = os.path.dirname(os.path.dirname(__file__))
    out = subprocess.run([sys.executable, "-c", PP_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=300,
                         cwd=cwd)
    assert "PP_OK" in out.stdout, out.stdout + out.stderr
