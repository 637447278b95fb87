import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry run: for every (architecture x input shape) cell, lower +
compile the real step function against the production mesh with abstract
inputs (ShapeDtypeStruct -- zero device allocation), print the memory and
cost analysis, and persist the roofline quantities parsed from the
post-SPMD HLO.

    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3_8b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

The XLA_FLAGS line above MUST run before any jax import (jax locks the
device count at first init); this module is the only place it is set.
"""

import argparse
import json
import time
import traceback

import jax

from repro import configs
from repro.launch.mesh import make_production_mesh
from repro.launch.shapes import SHAPES, all_cells, cells_for
from repro.launch.specs import build_cell
from repro.roofline.report import HBM_PER_CHIP, build_report


def run_cell(arch: str, shape_name: str, mesh_name: str, out_dir: str,
             verbose: bool = True) -> dict:
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    pod_block = 256 if mesh_name == "multi" else None
    cell = build_cell(arch, shape_name, mesh)

    lowered = cell.lower()
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    ma = compiled.memory_analysis()
    try:
        ca = compiled.cost_analysis()
    except Exception:
        ca = {}
    hlo_text = compiled.as_text()

    peak = float(getattr(ma, "temp_size_in_bytes", 0)
                 + getattr(ma, "argument_size_in_bytes", 0)
                 + getattr(ma, "output_size_in_bytes", 0)
                 - getattr(ma, "alias_size_in_bytes", 0))
    rep = build_report(
        arch, shape_name, mesh_name, cell.cfg, cell.shape.kind,
        cell.shape.seq_len, cell.shape.global_batch,
        n_devices=mesh.size, hlo_text=hlo_text, xla_cost=dict(ca) if ca else {},
        peak_memory=peak, pod_block=pod_block,
        microbatches=cell.microbatches)

    result = rep.to_dict()
    result.update({
        "lower_s": t_lower, "compile_s": t_compile,
        "arg_bytes": float(getattr(ma, "argument_size_in_bytes", 0)),
        "out_bytes": float(getattr(ma, "output_size_in_bytes", 0)),
        "temp_bytes": float(getattr(ma, "temp_size_in_bytes", 0)),
        "alias_bytes": float(getattr(ma, "alias_size_in_bytes", 0)),
        "fits_hbm": peak <= HBM_PER_CHIP,
        "status": "ok",
    })
    if verbose:
        print(f"[{arch} x {shape_name} x {mesh_name}] "
              f"compile={t_compile:.1f}s peak={peak/1e9:.2f}GB "
              f"fits={result['fits_hbm']} "
              f"compute={rep.compute_s:.3e}s memory={rep.memory_s:.3e}s "
              f"collective={rep.collective_s:.3e}s -> {rep.bottleneck} "
              f"useful={rep.useful_flop_ratio:.2f} "
              f"roofline={rep.roofline_fraction:.2f}")
        print(f"  memory_analysis: args={result['arg_bytes']/1e9:.2f}GB "
              f"out={result['out_bytes']/1e9:.2f}GB "
              f"temp={result['temp_bytes']/1e9:.2f}GB "
              f"aliased={result['alias_bytes']/1e9:.2f}GB")
        print(f"  cost_analysis: xla_flops={rep.xla_flops:.3e} "
              f"hlo_dot_flops={rep.hlo_dot_flops:.3e} "
              f"model_flops/dev="
              f"{rep.model_flops_total/mesh.size:.3e}")
        print(f"  collectives: {rep.collective_counts} "
              f"ici={rep.ici_bytes/1e6:.1f}MB dcn={rep.dcn_bytes/1e6:.1f}MB")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        fname = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_name}.json")
        with open(fname, "w") as f:
            json.dump(result, f, indent=1)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=configs.ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape) cell")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        cells = all_cells()
    else:
        assert args.arch, "--arch required unless --all"
        shapes = [args.shape] if args.shape else cells_for(args.arch)
        cells = [(args.arch, s) for s in shapes]

    failures = []
    for arch, shape_name in cells:
        for mesh_name in meshes:
            try:
                run_cell(arch, shape_name, mesh_name, args.out)
            except Exception as e:
                traceback.print_exc()
                failures.append((arch, shape_name, mesh_name, str(e)))
                if args.out:
                    os.makedirs(args.out, exist_ok=True)
                    with open(os.path.join(
                            args.out,
                            f"{arch}__{shape_name}__{mesh_name}.json"),
                            "w") as f:
                        json.dump({"status": "fail", "error": str(e)}, f)
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print("\nALL DRY-RUN CELLS PASSED")


if __name__ == "__main__":
    main()
