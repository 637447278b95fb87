"""CI-scale dry-run machinery test: the same build_cell/lower/compile/
roofline path as the production 512-device dry run, on an 8-device mesh with
reduced configs (subprocess so the device count doesn't leak)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, sys
    import jax
    import dataclasses
    from repro import configs
    from repro.launch.mesh import make_mesh
    from repro.launch.shapes import ShapeSpec
    from repro.launch import specs as S
    from repro.roofline.report import build_report

    arch, kind = sys.argv[1], sys.argv[2]
    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = configs.get_reduced(arch)
    shape = ShapeSpec("ci", kind, seq_len=64,
                      global_batch=8 if kind != "decode" else 8)
    S.SHAPES["ci"] = shape
    cell = S.build_cell(arch, "ci", mesh, cfg_override=cfg)
    compiled = cell.lower().compile()
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    rep = build_report(arch, "ci", "small", cfg, kind, 64, 8, 8,
                       compiled.as_text(),
                       dict(ca or {}),
                       float(ma.temp_size_in_bytes), None)
    out = {"flops": rep.hlo_dot_flops, "ici": rep.ici_bytes,
           "bottleneck": rep.bottleneck,
           "counts": rep.collective_counts}
    print("CELL_OK " + json.dumps(out))
""")


@pytest.mark.parametrize("arch,kind", [
    ("llama3_8b", "train"),
    ("dbrx_132b", "train"),
    ("mamba2_370m", "train"),
    ("gemma3_27b", "prefill"),
    ("recurrentgemma_2b", "decode"),
    ("qwen2_vl_2b", "decode"),
])
def test_dryrun_cell_small_mesh(arch, kind):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    cwd = os.path.dirname(os.path.dirname(__file__))
    r = subprocess.run([sys.executable, "-c", SCRIPT, arch, kind], env=env,
                       capture_output=True, text=True, timeout=600, cwd=cwd)
    assert "CELL_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]
    payload = json.loads(r.stdout.split("CELL_OK ")[1])
    assert payload["flops"] > 0
    if kind == "train":
        # sharded training must communicate something
        assert payload["ici"] > 0


def test_hlo_parser_loop_awareness():
    """Unit check of the trip-count-aware parse on a hand-built module."""
    from repro.roofline import hlo
    txt = """
HloModule test

%body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,8]{1,0} get-tuple-element(%p), index=1
  %d = f32[8,8]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %one = s32[] constant(1)
  %i2 = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[8,8]{1,0}) tuple(%i2, %d)
}

%cond (p2: (s32[], f32[8,8])) -> pred[] {
  %p2 = (s32[], f32[8,8]{1,0}) parameter(0)
  %i3 = s32[] get-tuple-element(%p2), index=0
  %n = s32[] constant(5)
  ROOT %lt = pred[] compare(%i3, %n), direction=LT
}

ENTRY %main (x0: f32[8,8]) -> f32[8,8] {
  %x0 = f32[8,8]{1,0} parameter(0)
  %z = s32[] constant(0)
  %t0 = (s32[], f32[8,8]{1,0}) tuple(%z, %x0)
  %w = (s32[], f32[8,8]{1,0}) while(%t0), condition=%cond, body=%body
  ROOT %out = f32[8,8]{1,0} get-tuple-element(%w), index=1
}
"""
    ana = hlo.analyze(txt)
    assert ana.dot_flops == 5 * 2 * 8 * 8 * 8, ana.dot_flops


def test_collective_factors():
    from repro.roofline import hlo
    txt = """
HloModule test

ENTRY %main (x0: f32[64,64]) -> f32[64,64] {
  %x0 = f32[64,64]{1,0} parameter(0)
  %ar = f32[64,64]{1,0} all-reduce(%x0), replica_groups={{0,1,2,3}}, to_apply=%add
  %ag = f32[64,64]{1,0} all-gather(%ar), replica_groups={{0,1,2,3}}, dimensions={0}
  ROOT %cp = f32[64,64]{1,0} collective-permute(%ag), source_target_pairs={{0,1}}
}
"""
    ana = hlo.analyze(txt)
    b = 64 * 64 * 4
    assert abs(ana.collective_bytes_by_kind["all-reduce"]
               - 2 * 3 / 4 * b) < 1
    assert abs(ana.collective_bytes_by_kind["all-gather"] - 3 / 4 * b) < 1
    assert abs(ana.collective_bytes_by_kind["collective-permute"] - b) < 1
