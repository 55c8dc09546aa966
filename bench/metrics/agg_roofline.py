"""Share of the HBM roofline the aggregation reaches, whatever its
route: the compulsory bytes of a step's SpMM calls (``bench/counts.py``)
at the chip's peak bandwidth, over the device time under the program's
``agg`` scope per step (``agg.ms``)."""
from bench import scope_trace


def read(ctx):
    sc = scope_trace.of_run(ctx)
    ms = None if sc is None else sc.agg_ms()
    if ms is None:
        return None
    least_s = ctx["spmm_step_bytes"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms * 1e-3)
