"""Device ms per step of the operations under the program's aggregation
scope (``agg``, ``pipeline/sparse.py``), forward and backward, whatever
route aggregates (Pallas kernels, XLA gathers and segment sums, the
ring and its collective-permutes); mean over chips."""
from bench import scope_trace


def read(ctx):
    sc = scope_trace.of_run(ctx)
    return None if sc is None else sc.agg_ms()
