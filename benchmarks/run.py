"""Benchmark harness — one module per paper table/figure.

``python -m benchmarks.run [--only fig5,table6] [--fast]``
prints ``name,us_per_call,derived`` CSV rows (benchmarks/common.emit).
"""
from __future__ import annotations

import argparse
import inspect
import sys
import time
import traceback

from repro.runtime.compile_cache import configure_compile_cache

ALL = [
    "fig5_dataflow_opts",
    "fig6_breakdown",
    "fig7_tier_bandwidth",
    "fig8_kernel_tiers",
    "fig10_placement",
    "fig12_large_batch",
    "table3_accuracy",
    "table4_sampling",
    "table5_memory_model",
    "table6_fullgraph_vs_subgraph",
    "roofline",
    "serving",
    "training",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", type=str, default=None)
    ap.add_argument("--mesh", type=str, default=None,
                    help="mesh shape ('4', '2x2') forwarded to benchmarks "
                         "that take one (fig12); pair with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N on CPU")
    ap.add_argument("--topology", type=str, default=None,
                    help="registered TierTopology preset forwarded to "
                         "benchmarks that take one (fig7, fig8, fig10), "
                         "e.g. dram-optane-appdirect")
    ap.add_argument("--compression", type=str, default=None,
                    help="compression scheme ('int8') forwarded to "
                         "benchmarks that take one (fig7's quantized-"
                         "storage arm; records BENCH_compression.json)")
    args = ap.parse_args()
    configure_compile_cache()
    names = args.only.split(",") if args.only else ALL
    failures = []
    for name in names:
        mod_name = next((m for m in ALL if m.startswith(name)), name)
        print(f"# === {mod_name} ===", flush=True)
        t0 = time.perf_counter()
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
            kw = {}
            params = inspect.signature(mod.run).parameters
            if args.mesh is not None and "mesh" in params:
                kw["mesh"] = args.mesh
            if args.topology is not None and "topology" in params:
                kw["topology"] = args.topology
            if args.compression is not None and "compression" in params:
                kw["compression"] = args.compression
            mod.run(**kw)
        except Exception:  # noqa: BLE001
            traceback.print_exc()
            failures.append(mod_name)
        print(f"# {mod_name} done in {time.perf_counter()-t0:.1f}s",
              flush=True)
    if failures:
        print(f"# FAILED: {failures}")
        sys.exit(1)
    print("# all benchmarks OK")


if __name__ == "__main__":
    main()
