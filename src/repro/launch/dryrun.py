"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the production meshes, and emit roofline terms from the compiled artifacts.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b \
      --shape train_4k --mesh both --fl

The FIRST two lines below MUST run before any other import: jax locks the
device count at first init, and the dry-run needs 512 placeholder host
devices to build the 2x16x16 production mesh. (Smoke tests and benchmarks do
NOT set this — they see the real single CPU device.)
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse                                    # noqa: E402
import json                                        # noqa: E402
import time                                        # noqa: E402
import traceback                                   # noqa: E402

import dataclasses                                 # noqa: E402

import jax                                         # noqa: E402

from repro.configs import ARCH_IDS, SHAPES, get_config, get_shape  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.steps import build_step          # noqa: E402
from repro.models import sharding as shard_lib     # noqa: E402
from repro.roofline.analysis import analyze_compiled, model_flops  # noqa: E402
from repro.roofline.peaks import V5E              # noqa: E402

REPORT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                          "reports", "dryrun")


def run_one(arch: str, shape_name: str, *, multi_pod: bool,
            fl: bool = False, verbose: bool = True,
            constrain: bool = True, bf16_grads: bool = False) -> dict:
    cfg = get_config(arch)
    if bf16_grads:
        cfg = dataclasses.replace(cfg, grad_reduce_dtype="bfloat16")
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    tag = f"{cfg.name}|{shape.name}|{mesh_name}" + ("|fl" if fl else "")

    t0 = time.time()
    bundle = build_step(cfg, shape, mesh, fl=fl, constrain=constrain)
    with mesh:
        jitted = jax.jit(
            bundle.fn,
            in_shardings=shard_lib.named(mesh, bundle.in_shardings),
            out_shardings=shard_lib.named(mesh, bundle.out_shardings),
            donate_argnums=bundle.donate_argnums)
        lowered = jitted.lower(*bundle.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    # the production meshes model v5e pods (launch/mesh.py)
    report = analyze_compiled(tag, compiled, mesh.size,
                              model_flops(cfg, shape), device_kind=V5E,
                              pod_boundary=256 if multi_pod else 0)
    row = report.row()
    row.update({
        "arch": cfg.name, "shape": shape.name, "mesh": mesh_name,
        "fl": fl, "mode": shape.mode,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "collective_breakdown_gb": {
            k: round(v / 2**30, 4)
            for k, v in report.collective_breakdown.items()},
    })
    if verbose:
        print(f"[ok] {tag:55s} compute={row['compute_ms']:9.3f}ms "
              f"memory={row['memory_ms']:9.3f}ms "
              f"memF={row['memory_fused_ms']:9.3f}ms "
              f"coll={row['collective_ms']:9.3f}ms "
              f"dom={row['dominant']:10s} hbm={row['hbm_gb_per_dev']:7.2f}GB "
              f"useful={row['model_flops_frac']:.3f}", flush=True)
    return row


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--fl", action="store_true",
                    help="lower the federated (AE-compressed) round instead "
                         "of the baseline train step (train shapes only)")
    ap.add_argument("--out", default=None, help="write JSONL results here")
    ap.add_argument("--bf16-grads", action="store_true",
                    help="bf16 gradient reductions (§Perf iteration 3)")
    ap.add_argument("--no-constrain", action="store_true",
                    help="disable activation-sharding constraints "
                         "(the pre-optimization §Perf baseline)")
    ap.add_argument("--keep-going", action="store_true")
    args = ap.parse_args()

    archs = list(ARCH_IDS) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    rows, failures = [], []
    for arch in archs:
        for shape in shapes:
            if args.fl and SHAPES[shape].mode != "train":
                continue
            for multi_pod in meshes:
                try:
                    rows.append(run_one(
                        arch, shape, multi_pod=multi_pod, fl=args.fl,
                        constrain=not args.no_constrain,
                        bf16_grads=args.bf16_grads))
                except Exception as e:           # noqa: BLE001
                    failures.append((arch, shape, multi_pod, repr(e)))
                    print(f"[FAIL] {arch}|{shape}|"
                          f"{'multi' if multi_pod else 'single'}: {e}",
                          flush=True)
                    if not args.keep_going:
                        traceback.print_exc()
                        raise

    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")
    print(f"\n{len(rows)} configurations lowered+compiled, "
          f"{len(failures)} failures")
    for f_ in failures:
        print("  FAIL:", f_)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
