"""Share of the HBM roofline the aggregation kernels reach: the
compulsory bytes of a step's SpMM calls (``bench/counts.py``) at the
chip's peak bandwidth, over their device time per step.  Bandwidth
bounds these calls (a few FLOPs per byte), so bytes set the roofline."""


def read(ctx):
    ms = ctx["reduction"].ops_ms("spmm_")
    if ms is None:
        return None
    least_s = ctx["spmm_step_bytes"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms * 1e-3)
