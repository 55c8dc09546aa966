"""Readings that the limits of a cell are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--faults half_batch,no_exchange]

In one process, for each seed: the program's three compared steps
against the float32 reference (the lower readings); on the control
seeds, the reference computed in bfloat16 put in the program's place
(the control); and each planted fault: ``half_batch`` (the reference
with the mean over the first half of each batch) and ``no_exchange``
(the program with its collective-permutes made the identity, so no
chip sees another's rows).  One JSON line per reading goes to standard
output and to ``bench_out/calibrate-<cell>.jsonl``.  The benchmark's
own runs never run this.
"""
import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


@contextlib.contextmanager
def no_exchange():
    """Collective-permutes return the sender's own block."""
    import jax
    real = jax.lax.ppermute
    jax.lax.ppermute = lambda x, axis_name, perm: x
    try:
        yield
    finally:
        jax.lax.ppermute = real


def program_readings(cfg, graph, seed, ref, steps):
    from bench.loops.closed_train import Program
    prog = Program(cfg, graph, seed, ref, {})
    first = prog.first_steps(steps, {})
    del prog
    return first


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="")
    args = ap.parse_args()
    ints = lambda s: [int(x) for x in s.split(",") if x]
    faults = [f for f in args.faults.split(",") if f]

    import jax.numpy as jnp
    import numpy as np
    from bench import check
    from bench.harness import CACHE, devices_for
    from bench.loops.closed_train import reference_readings, run_graph
    from bench.manifest import Manifest
    from repro.runtime.compile_cache import configure_compile_cache

    man = Manifest()
    w = man.cell(args.workload)
    devices_for(w["chips"])
    configure_compile_cache()
    cfg = man.config(w["config"])
    ref = man.reference(cfg["model"])
    steps = man.mix(w["traffic"])["compared_steps"]
    out = os.path.join(ROOT, "bench_out")
    os.makedirs(out, exist_ok=True)
    sink = open(os.path.join(out, f"calibrate-{args.workload}.jsonl"), "a")

    def emit(seed, kind, numbers, t):
        line = json.dumps({"cell": args.workload, "seed": seed, "kind": kind,
                           "seconds": time.perf_counter() - t, **numbers})
        print(line, flush=True)
        sink.write(line + "\n")
        sink.flush()

    for seed in ints(args.seeds):
        t = time.perf_counter()
        graph = run_graph(cfg, seed, CACHE)
        first = program_readings(cfg, graph, seed, ref, steps)
        batches = first["batches"]
        f32 = reference_readings(ref, cfg, graph, seed, batches)
        emit(seed, "program", check.compare(first, f32), t)
        if seed in ints(args.control_seeds):
            t = time.perf_counter()
            bf16 = reference_readings(ref, cfg, graph, seed, batches,
                                      dtype=jnp.bfloat16)
            emit(seed, "control_bf16", check.compare(bf16, f32), t)
            for fault in faults:
                t = time.perf_counter()
                if fault == "half_batch":
                    got = reference_readings(ref, cfg, graph, seed, batches,
                                             half=True)
                elif fault == "no_exchange":
                    with no_exchange():
                        got = program_readings(cfg, graph, seed, ref, steps)
                    if not all(np.array_equal(a[0], b[0]) for a, b in
                               zip(got["batches"], batches, strict=True)):
                        raise RuntimeError("fault run drew other batches")
                else:
                    raise ValueError(f"unknown fault {fault!r}")
                emit(seed, fault, check.compare(got, f32), t)
    sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
