import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape)
cell on the production meshes, record memory/cost/roofline terms.

MUST be the first importer of jax in the process (the XLA_FLAGS line
above precedes every other import, including repro.*).

Usage:
  python -m repro.launch.dryrun --arch gemma2-2b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod-only|--single-pod-only]
  python -m repro.launch.dryrun --all --out results/dryrun
"""
import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402

from repro import configs as config_registry             # noqa: E402
from repro.launch import cells as cell_builder           # noqa: E402
from repro.launch import roofline as rl                  # noqa: E402
from repro.launch.mesh import make_production_mesh       # noqa: E402

HBM_PER_CHIP = 16 * 2**30


def run_cell(arch: str, shape: str, multi_pod: bool, verbose: bool = True):
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    cell = cell_builder.build_cell(arch, shape, mesh)
    t0 = time.perf_counter()
    with mesh:
        jitted = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                         out_shardings=cell.out_shardings,
                         donate_argnums=cell.donate)
        lowered = jitted.lower(*cell.args)
        compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    analytic_cost = None
    if "analytic_hbm" in cell.meta:
        # recsys trains: XLA 'bytes accessed' badly under-counts dense
        # optimizer table streaming; use the documented analytic model
        analytic_cost = dict(flops=cell.meta["model_flops"],
                             hbm_bytes=cell.meta["analytic_hbm"],
                             coll_bytes=cell.meta["analytic_coll"])
    elif cell.kind in ("train", "prefill", "decode"):
        # scan-based programs: HloCostAnalysis counts while bodies once;
        # use the analytic model (launch/analytic.py)
        from repro.launch.analytic import lm_cost
        cfg = config_registry.get(arch).FULL
        analytic_cost = lm_cost(cell.kind, cfg,
                                config_registry.get(arch).SHAPES[shape], mesh)
    roof = rl.analyze(compiled, n_chips,
                      model_flops=cell.meta.get("model_flops", 0.0),
                      analytic=analytic_cost)
    peak = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes - getattr(mem, "alias_size_in_bytes", 0))
    rec = {
        "arch": arch, "shape": shape,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": n_chips,
        "compile_s": round(compile_s, 2),
        "arg_bytes_per_dev": mem.argument_size_in_bytes,
        "temp_bytes_per_dev": mem.temp_size_in_bytes,
        "out_bytes_per_dev": mem.output_size_in_bytes,
        "peak_bytes_per_dev": peak,
        "fits_hbm": bool(peak <= HBM_PER_CHIP),
        "roofline": roof.as_dict(),
        "analytic": analytic_cost,
        "raw_hlo": {
            "flops": float((compiled.cost_analysis()[0]
                            if isinstance(compiled.cost_analysis(), list)
                            else compiled.cost_analysis()).get("flops", 0)),
            "coll_bytes_hlo_text":
                rl.collective_bytes(compiled.as_text()).total_bytes,
        },
        "meta": cell.meta,
    }
    if verbose:
        print(compiled.memory_analysis())
        ca = compiled.cost_analysis()
        ca = ca[0] if isinstance(ca, list) else ca
        print({k: v for k, v in ca.items()
               if k in ("flops", "bytes accessed")})
        print(f"[{arch}/{shape}/{rec['mesh']}] peak/dev="
              f"{peak/2**30:.2f} GiB fits={rec['fits_hbm']} "
              f"bottleneck={roof.bottleneck} "
              f"terms(c/m/coll)={roof.compute_s:.4f}/{roof.memory_s:.4f}/"
              f"{roof.collective_s:.4f}s compile={compile_s:.1f}s")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str)
    ap.add_argument("--shape", type=str)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--multi-pod-only", action="store_true")
    ap.add_argument("--out", type=str, default="results/dryrun")
    ap.add_argument("--archs", type=str, default=None,
                    help="comma-separated subset for --all")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)

    if not args.all:
        assert args.arch and args.shape, "--arch/--shape or --all"
        rec = run_cell(args.arch, args.shape, args.multi_pod)
        _save(args.out, rec)
        return

    arch_list = (args.archs.split(",") if args.archs
                 else config_registry.ARCH_IDS)
    pods = []
    if not args.multi_pod_only:
        pods.append(False)
    if not args.single_pod_only:
        pods.append(True)
    failures = []
    for arch in arch_list:
        mod = config_registry.get(arch)
        for shape in mod.SHAPES:
            for multi in pods:
                tag = f"{config_registry.canon(arch)}__{shape}__" \
                      f"{'multi' if multi else 'single'}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path):
                    print(f"skip cached {tag}")
                    continue
                try:
                    rec = run_cell(arch, shape, multi)
                    _save(args.out, rec)
                except Exception as e:  # noqa: BLE001
                    traceback.print_exc()
                    failures.append((tag, str(e)))
        for shape, reason in mod.SKIP.items():
            print(f"SKIP {arch}/{shape}: {reason}")
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for tag, err in failures:
            print(f"  {tag}: {err[:200]}")
        raise SystemExit(1)
    print("\nall dry-run cells compiled OK")


def _save(out_dir, rec):
    tag = f"{config_registry.canon(rec['arch'])}__{rec['shape']}__" \
          f"{'multi' if rec['mesh'] == '2x16x16' else 'single'}"
    with open(os.path.join(out_dir, tag + ".json"), "w") as f:
        json.dump(rec, f, indent=1)


if __name__ == "__main__":
    main()
