"""CPU-only tool: lower and compile a model cell on 512 fake CPU devices.

It forces the host platform's device count before JAX starts, so it
never touches an accelerator; roofline terms are computed against the
peaks of the pod it models (:data:`TARGET_KIND`), not of the CPU.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

# ^ MUST precede every other import: jax locks the device count on first
# backend initialization.  This module is the ONLY place the 512-device
# fake topology is created; tests and benches see the real (1-CPU) world.

import argparse
import json
import math
import subprocess
import sys
import time

TARGET_KIND = "TPU v5 lite"   # the modeled production pod (v5e)


def run_cell(arch: str, shape: str, multi_pod: bool, variant: str = "baseline") -> dict:
    """Lower + compile one (arch × shape × mesh) cell; return the record."""
    import jax  # deferred: after XLA_FLAGS

    from repro.configs import get_arch
    from repro.launch.flops import trace_cost
    from repro.launch.mesh import make_production_mesh
    from repro.launch.roofline import hardware_for, roofline_terms

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = math.prod(mesh.devices.shape)
    spec = get_arch(arch).build_dryrun(shape, mesh, variant=variant)
    t0 = time.time()
    with mesh:
        lowered = spec.lower()
        compiled = lowered.compile()
        walker = trace_cost(spec.step_fn, *spec.args)
    compile_s = time.time() - t0
    mem = None
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            mem = {
                "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
                "output_bytes": getattr(ma, "output_size_in_bytes", None),
                "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
                "generated_code_bytes": getattr(ma, "generated_code_size_in_bytes", None),
            }
    except Exception as e:  # CPU backend may not implement it
        mem = {"unavailable": str(e)}
    report = roofline_terms(compiled, chips, spec.model_flops,
                            hardware_for(TARGET_KIND), walker_cost=walker)
    rec = {
        "arch": arch,
        "shape": shape,
        "variant": variant,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "multi_pod": multi_pod,
        "chips": chips,
        "description": spec.description,
        "compile_s": round(compile_s, 1),
        "memory_analysis": mem,
        "n_params": spec.n_params,
        "tokens_per_step": spec.tokens_per_step,
        **report.to_dict(),
    }
    return rec


def _fmt(rec: dict) -> str:
    return (
        f"{rec['arch']:22s} {rec['shape']:14s} mesh={rec['mesh']:8s} "
        f"compute={rec['compute_s']:.3e}s memory={rec['memory_s']:.3e}s "
        f"collective={rec['collective_s']:.3e}s bottleneck={rec['bottleneck']:10s} "
        f"roofline_frac={rec['roofline_fraction']:.3f} compile={rec['compile_s']}s"
    )


def main() -> None:
    ap = argparse.ArgumentParser(description="multi-pod dry-run driver")
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--variant", default="baseline",
                    choices=["baseline", "opt", "opt2", "nodeshard"])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true", help="run every cell (subprocess-isolated)")
    ap.add_argument("--both-meshes", action="store_true", help="with --all: single+multi pod")
    ap.add_argument("--json", help="append JSONL records here")
    args = ap.parse_args()

    if args.all:
        from repro.configs import ALL_CELLS

        meshes = [False, True] if args.both_meshes else [False]
        failures = []
        for arch, shape in ALL_CELLS:
            for mp in meshes:
                cmd = [
                    sys.executable, "-m", "repro.launch.dryrun",
                    "--arch", arch, "--shape", shape,
                ]
                if mp:
                    cmd.append("--multi-pod")
                if args.json:
                    cmd += ["--json", args.json]
                r = subprocess.run(cmd)
                if r.returncode != 0:
                    failures.append((arch, shape, mp))
        if failures:
            print("FAILED CELLS:", failures)
            sys.exit(1)
        print("ALL CELLS PASSED")
        return

    if not args.arch or not args.shape:
        ap.error("--arch and --shape required (or --all)")
    rec = run_cell(args.arch, args.shape, args.multi_pod, variant=args.variant)
    print(_fmt(rec))
    print("memory_analysis:", rec["memory_analysis"])
    if args.json:
        with open(args.json, "a") as f:
            f.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    main()
