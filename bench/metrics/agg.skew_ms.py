"""Slowest less fastest chip's device ms per step under the program's
``agg`` scope, collective ops excluded: the aggregation work one chip
has beyond another, which the others wait for at the ring's permutes."""
from bench import scope_trace


def read(ctx):
    sc = scope_trace.of_run(ctx)
    return None if sc is None else sc.agg_skew_ms()
