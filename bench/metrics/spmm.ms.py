"""Device ms per step of the aggregation kernels: the ``spmm_*`` Pallas
kernel events (``kernels/spmm.py``), mean over chips."""


def read(ctx):
    return ctx["reduction"].ops_ms("spmm_")
