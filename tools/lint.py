#!/usr/bin/env python
"""JAX-aware static lint + HLO invariant audit (``repro.analysis``).

Layer 1 (default; no JAX import): AST rules over ``src/`` and
``benchmarks/`` — tracer-unsafe Python inside jitted/Pallas functions,
PRNG hygiene, f64-promotion hazards, Pallas kernel rules — plus
cross-file registry-completeness rules (kernel oracles, spec sections,
topology snapshot arms).  Findings are compared against the committed
ratchet baseline (``tools/lint_baseline.json``): NEW findings fail,
FIXED findings must be removed from the baseline (``--update``), so the
recorded debt only ever shrinks.

Layer 2 (``--hlo``): lowers the jitted train step and the fused serve
path for representative lightgcn-smoke presets — single device and a
forced-4-device mesh with int8 psum / int8 ring arms — and asserts on
the lowered text: no f64 ops, no host transfers inside the step,
collectives present/absent exactly per MeshCfg/CompressionCfg, one
microbatch chunk shape across the schedule.  Each arm runs in a
subprocess so ``XLA_FLAGS`` device forcing works.

    python tools/lint.py                   # lint vs baseline
    python tools/lint.py --check-baseline  # same, explicit (CI)
    python tools/lint.py --update          # rewrite the ratchet baseline
    python tools/lint.py --hlo             # Layer 2 HLO audit
    python tools/lint.py --rules           # rule catalogue
    python tools/lint.py src/repro/eval    # restrict lint paths
"""
from __future__ import annotations

import os
import subprocess
import sys

import _cli

_cli.ensure_src()

BASELINE_PATH = _cli.tool_file("lint_baseline.json")
LINT_ROOTS = ("src", "benchmarks")

# (arch, mesh, grads, ring): the representative preset points ``make
# audit`` lowers — lightgcn single device, plain 4-way mesh, int8
# gradient psum, int8 quantized ring; ngcf single device (the fused
# Hadamard contract: fusion_audit's cross-arm message-shape check) and
# 4-way mesh ('auto' must resolve to the factored route, which rides
# the symmetric ring)
HLO_ARMS = (("lightgcn", 1, "none"), ("lightgcn", 4, "none"),
            ("lightgcn", 4, "int8"), ("ngcf", 1, "none"), ("ngcf", 4, "none"))


def run_lint(paths: list[str]) -> list:
    from repro.analysis import lint_paths, lint_repo
    root = _cli.repo_root()
    targets = [root / p for p in (paths or LINT_ROOTS)]
    findings = lint_paths([p for p in targets if p.exists()], root=root)
    if not paths:  # registry rules are repo-wide, skip when restricted
        findings += lint_repo(root)
    return findings


def lint_main(args) -> int:
    from repro.analysis import compare, load_baseline, save_baseline
    findings = run_lint(args.paths)
    if args.update:
        n = save_baseline(BASELINE_PATH, findings)
        print(f"wrote {BASELINE_PATH} ({n} baselined finding(s))")
        return 0
    new, stale = compare(findings, load_baseline(BASELINE_PATH))
    failures = [str(f) for f in new]
    failures += [f"stale baseline entry {k!r}: recorded {rec}, "
                 f"now {rem} — shrink the baseline"
                 for k, rec, rem in stale]
    return _cli.report(
        "lint (repro.analysis layer 1)", failures,
        ok=f"lint OK ({len(findings)} finding(s), all baselined; "
           f"baseline {BASELINE_PATH.name})",
        hint="new findings: fix them; fixed findings: rerun with "
             "--update and commit the shrunk baseline")


def hlo_main(args) -> int:
    failures: list[str] = []
    for arch, mesh, grads in HLO_ARMS:
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            [str(_cli.repo_root() / "src"),
             env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
        if mesh > 1:
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                                f" --xla_force_host_platform_device_count"
                                f"={mesh}").strip()
        code = ("import json, sys\n"
                "from repro.analysis import hlo_audit\n"
                f"v = hlo_audit.smoke_audit(mesh={mesh}, "
                f"grads={grads!r}, arch={arch!r})\n"
                "print(json.dumps(v))\n")
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True, env=env)
        arm = f"arch={arch},mesh={mesh},grads={grads}"
        if proc.returncode != 0:
            failures.append(f"[{arm}] audit crashed:\n"
                            + proc.stderr.strip())
            continue
        import json
        violations = json.loads(proc.stdout.strip().splitlines()[-1])
        failures += violations
        print(f"  audited {arm}: "
              f"{'FAIL' if violations else 'ok'}")
    return _cli.report(
        "HLO audit (repro.analysis layer 2)", failures,
        ok=f"HLO audit OK ({len(HLO_ARMS)} preset arms: train halves + "
           "fused serve + recompile hazard)",
        hint="the lowering violated a placement/dtype/collective "
             "invariant — see docs/ARCHITECTURE.md 'Static analysis'")


def rules_main() -> int:
    from repro.analysis import ALL_RULES
    width = max(map(len, ALL_RULES))
    for name in sorted(ALL_RULES):
        print(f"  {name:<{width}}  {ALL_RULES[name]}")
    return 0


def main() -> int:
    ap = _cli.make_parser(__doc__)
    ap.add_argument("paths", nargs="*",
                    help=f"lint roots (default: {', '.join(LINT_ROOTS)})")
    ap.add_argument("--update", action="store_true",
                    help="rewrite the ratchet baseline from current "
                         "findings")
    ap.add_argument("--check-baseline", action="store_true",
                    help="explicit alias of the default compare mode "
                         "(what CI runs)")
    ap.add_argument("--hlo", action="store_true",
                    help="run the Layer 2 HLO invariant audit (slow; "
                         "imports JAX, forces devices in subprocesses)")
    ap.add_argument("--rules", action="store_true",
                    help="print the rule catalogue and exit")
    args = ap.parse_args()
    if args.rules:
        return rules_main()
    if args.hlo:
        return hlo_main(args)
    return lint_main(args)


if __name__ == "__main__":
    sys.exit(main())
