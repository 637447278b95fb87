"""chip_smoke.py: its refusal without a TPU, and the checks of each phase run
at a small size on the CPU (the kernels in interpret mode, the mesh path on
four virtual devices). Also the entry points' compile cache directory and
the benchmark guards that refuse to guess on a device."""
import importlib.util
import os
import shutil
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def small(smoke):
    """The smoke's setting cut to 4 sites and 1% of the rows."""
    st = smoke.SETTING
    return smoke.load_setting_host(smoke.Setting(
        st.dataset, st.topology, st.partition, 4, scale=0.01))


def _run(argv, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable] + argv, env=env, cwd=cwd,
                          capture_output=True, text=True, timeout=300)


def test_refuses_without_tpu():
    out = _run([SMOKE], ROOT)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"ok"' not in out.stdout


def test_refuses_outside_the_repo(tmp_path):
    shutil.copy(SMOKE, tmp_path)
    out = _run([str(tmp_path / "chip_smoke.py")], str(tmp_path))
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_kernel_numerics_pass_at_f32(smoke, small):
    pts, k = small[0], small[1]
    rng = np.random.default_rng(0)
    x = pts[:512]
    c = (x[:k] + 0.5 * rng.standard_normal((k, x.shape[1]))).astype(
        np.float32)
    w = rng.uniform(0.0, 2.0, len(x)).astype(np.float32)
    out = smoke.kernel_numerics(x, c, w)
    assert out["distance_err"] <= 1e-5 and out["argmin_mismatch"] == 0


def test_kernel_numerics_catch_a_bf16_distance(smoke, small, monkeypatch):
    """A distance computed from bf16-rounded operands (one bf16 MXU pass)
    must fail the distance check."""
    pts, k = small[0], small[1]
    x = pts[:512]
    c = x[1:k + 1] + 0.5

    def bf16_min_dist_argmin(p, cc):
        pb = p.astype(jnp.bfloat16).astype(jnp.float32)
        cb = cc.astype(jnp.bfloat16).astype(jnp.float32)
        d2 = (jnp.sum(p * p, 1)[:, None] + jnp.sum(cc * cc, 1)[None, :]
              - 2.0 * pb @ cb.T)
        return jnp.min(d2, 1), jnp.argmin(d2, 1)

    monkeypatch.setattr(smoke.ops, "min_dist_argmin", bf16_min_dist_argmin)
    with pytest.raises(smoke.SmokeFailure, match="f32 precision"):
        smoke.kernel_numerics(x, c, np.ones(len(x), np.float32))


@pytest.mark.parametrize("objective", ["kmeans", "kmedian"])
def test_objective_phase_checks_ratio_and_reference(smoke, small, objective,
                                                    capsys):
    pts, k, g, sp, sm = small
    centers = smoke.objective_phase(
        jax.random.PRNGKey(0), jnp.asarray(pts), jnp.asarray(sp),
        jnp.asarray(sm), k, g, 3 * k * g.n, objective, smoke.CompileClock(),
        backend="jnp")
    assert centers.shape == (k, pts.shape[1])
    line = capsys.readouterr().out.strip().splitlines()[-1]
    assert line.startswith(objective + " ") and '"cost_ratio"' in line


def test_serving_phase_matches_float64_argmin(smoke, small):
    pts, k = small[0], small[1]
    centers = {"kmeans": pts[:k], "kmedian": pts[k:2 * k]}
    out = smoke.serving_phase(centers, pts, seed=0, rows_per_tenant=500)
    assert out["agreement"] >= smoke.SERVE_AGREEMENT
    assert out["queries"] >= 1000 and out["tenants"] == 2


MESH_SCRIPT = textwrap.dedent("""
    import os, sys, importlib.util
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    st = smoke.SETTING
    pts, k, g, sp, sm = smoke.load_setting_host(smoke.Setting(
        st.dataset, st.topology, st.partition, 8, scale=0.01))
    mesh = jax.make_mesh((4,), ("sites",))
    out = smoke.mesh_phase(mesh, jax.random.PRNGKey(0), pts, k, sp, sm,
                           3 * k * g.n, smoke.CompileClock())
    assert set(out) == {"kmeans", "kmedian", "peak_bytes_in_use"}, out
    print("MESH_OK")
""")


CACHE_SCRIPT = textwrap.dedent("""
    import jax
    from repro.cache import REPO_CACHE_DIR, enable_compilation_cache
    path = enable_compilation_cache()
    print(path, jax.config.jax_compilation_cache_dir, REPO_CACHE_DIR,
          sep="|")
""")


@pytest.mark.parametrize("env_dir", [None, "given"])
def test_compilation_cache_dir(env_dir, tmp_path):
    """``$JAX_COMPILATION_CACHE_DIR`` is used as it is and no other
    directory is set; without it the cache sits at <repo>/.jax_cache."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    out = subprocess.run([sys.executable, "-c", CACHE_SCRIPT], env=env,
                         cwd=str(tmp_path), capture_output=True, text=True,
                         timeout=120)
    path, config_dir, repo_dir = out.stdout.strip().split("|")
    assert repo_dir == os.path.join(ROOT, ".jax_cache")
    if env_dir:
        assert path == str(tmp_path / env_dir)
        assert config_dir in ("None", path)   # JAX reads the variable
    else:
        assert path == config_dir == repo_dir


def test_roofline_peaks_are_keyed_by_device(smoke):
    from benchmarks import bench_kernels
    assert bench_kernels.device_peaks("TPU v5 lite") == (197e12, 819e9)
    with pytest.raises(ValueError, match="no roofline peaks"):
        bench_kernels.device_peaks("cpu")


def test_collectives_mesh_section_refuses_an_accelerator(smoke, monkeypatch):
    """On a chip the parent holds the devices: the section must raise
    instead of spawning children that cannot reach them."""
    from benchmarks import bench_collectives
    monkeypatch.setattr(bench_collectives.jax, "default_backend",
                        lambda: "tpu")

    def no_child(*a, **k):
        raise AssertionError("spawned a child process")

    monkeypatch.setattr(bench_collectives.subprocess, "run", no_child)
    with pytest.raises(RuntimeError, match="chip_smoke.py --chips 4"):
        bench_collectives._mesh_rows([], 8, 0.05, 1)


def test_mesh_phase_on_four_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", MESH_SCRIPT, SMOKE], env=env,
                         cwd=ROOT, capture_output=True, text=True,
                         timeout=600)
    assert "MESH_OK" in out.stdout, out.stdout + out.stderr
    # all three lowerings ran for both objectives
    for obj in ("kmeans", "kmedian"):
        for low in ("all_gather", "neighbor_rounds", "torus_2d"):
            assert f"mesh_{obj}_{low} " in out.stdout
