"""Run one cell of the benchmark once: set-up, a measured window, the
comparison with the plain reference, and (``--trace 1``) the per-layer
metrics read from a profiler trace of the window.

The harness knows no mix.  The cell's mix (``bench/mixes/<t>.json``)
names its loop, ``bench/loops/<loop>.py``, which has ``validate(mix)``
(refusing keys it does not implement) and a class ``Loop(mix, cfg,
ref, seed, *, phases, cache_dir, require_tpu, log)`` whose constructor
is the set-up, and which has ``window(seconds, annotate)`` returning
the window's record (each unit of work under a ``bench.step``
annotation when ``annotate``), ``attempted`` and ``failed``,
``release()`` to free the program, ``counts()`` for per-layer readers,
and ``check(log)`` returning the numbers compared and the limits it
fixes itself.  Every metric, end-to-end or per-layer, is read by
``bench/metrics/<name>.py`` from the loop's record and, with
``--trace 1``, from the trace's reduction.
"""
from __future__ import annotations

import gc
import pathlib
import shutil
import sys
import tempfile
import time

from bench import check
from bench.manifest import ROOT, Manifest

CACHE = str(ROOT / ".bench_cache")


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def devices_for(chips: int, require_tpu: bool = True):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"needs {chips} chips, JAX found {len(devs)}")
    return devs


def peak_bytes(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    log(f"peak_bytes_in_use per chip: {peaks}")
    return int(max(peaks))


def reduce_trace(tdir: str, devs) -> dict:
    """The trace of the window, reduced, with the chip's peaks: what
    per-layer readers take besides the loop's counts."""
    from bench import trace_reduce
    from bench.peaks import peaks_for
    red = trace_reduce.reduce_dir(tdir, n_chips=len(devs))
    for i, c in enumerate(red.chips):
        log(f"trace chip {i}: busy {c.busy_s:.6f} s of "
            f"{red.window_s:.6f} s, idle share "
            f"{100 * (1 - c.busy_s / red.window_s):.4f} %")
        if c.collective_ops:
            ms = sorted(1e3 * d for d in c.collective_ops)
            exposed = ("not recorded" if c.exposed_s is None else
                       f"{1e3 * c.exposed_s / red.n_steps:.6f} ms a step")
            log(f"trace chip {i}: collective-permute ops {len(ms)}, "
                f"{1e3 * c.collective_s / red.n_steps:.6f} ms a step; "
                f"over 1 ms: {sum(d > 1 for d in ms)}, longest "
                f"{ms[-1]:.6f} ms, median {ms[len(ms) // 2]:.6f} ms; "
                f"exposed {exposed}")
    return {"reduction": red, "peaks": peaks_for(devs[0].device_kind)}


def read_metrics(man: Manifest, specs: list, ctx: dict, *,
                 required: bool) -> dict:
    """Each metric's reader over ``ctx``; a reader that finds nothing
    returns None, which leaves a per-layer metric out of the line and
    is an error for an end-to-end one."""
    out = {}
    for m in specs:
        value = man.metric_reader(m["name"]).read(ctx)
        if value is None and required:
            raise RuntimeError(f"end-to-end metric {m['name']!r} has no "
                               f"reading in this cell's loop")
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(cell: str, seed: int, seconds: float, trace: bool, *,
             cfg: dict | None = None, require_tpu: bool = True,
             t_start: float | None = None,
             root: pathlib.Path = ROOT) -> dict:
    """One run of one cell; returns the result line's object.  ``cfg``
    replaces the cell's configuration (tests run tiny ones off the chip
    with ``require_tpu=False``); ``root`` is the checkout to read
    ``BENCHMARK.json`` and its files from."""
    t_start = time.perf_counter() if t_start is None else t_start
    man = Manifest(root)
    w = man.cell(cell)
    cfg = cfg or man.config(w["config"])
    mix = man.mix(w["traffic"])
    loop_mod = man.loop(mix["loop"])
    ref = man.reference(cfg["model"])
    phases = {}

    devs = devices_for(w["chips"], require_tpu)
    import jax
    if require_tpu:
        from repro.runtime.compile_cache import configure_compile_cache
        log(f"compile cache: {configure_compile_cache()}")
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    phases["import"] = time.perf_counter() - t_start
    used = devs[:w["chips"]]
    log(f"device {devs[0].device_kind} x{len(devs)}, cell uses "
        f"{len(used)}")

    loop = loop_mod.Loop(mix, cfg, ref, seed, phases=phases,
                         cache_dir=CACHE if require_tpu else None,
                         require_tpu=require_tpu, log=log)
    setup_s = time.perf_counter() - t_start
    log("set-up by phase (s): " + ", ".join(
        f"{k} {v:.3f}" for k, v in phases.items())
        + f"; total {setup_s:.3f}")

    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        if trace:
            jax.profiler.start_trace(tdir)
        try:
            record = loop.window(seconds, annotate=trace)
        finally:
            if trace:
                jax.profiler.stop_trace()
        log("window: " + ", ".join(f"{k} {v}" for k, v in record.items()
                                   if not isinstance(v, list)))
        dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
               "count": len(devs), "memory_peak_bytes": peak_bytes(used)}
        loop.release()
        gc.collect()
        ctx = {"window": record, "setup_s": setup_s, "chips": len(used),
               **loop.counts()}
        if trace:
            ctx.update(reduce_trace(tdir, used))
            metrics = read_metrics(man, man.per_layer(cell), ctx,
                                   required=False)
            red = ctx["reduction"]
            dev.update({"busy_s": red.busy_s, "window_s": red.window_s})
        else:
            metrics = read_metrics(man, man.end_to_end(cell), ctx,
                                   required=True)
    finally:
        if trace:
            shutil.rmtree(tdir, ignore_errors=True)

    numbers, fixed = loop.check(log=log)
    correct, shown = check.verdict(numbers, {**man.limits(cell), **fixed})
    result = {"correct": correct, "attempted": loop.attempted,
              "failed": loop.failed, "metrics": metrics, "device": dev}
    if trace:
        result["breakdown"] = red.breakdown
    result["checks"] = shown
    for k, v in shown.items():
        print(f"{k} {v['value']!r} limit {v['limit']!r}", file=sys.stderr)
    return result
