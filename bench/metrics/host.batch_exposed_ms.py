"""Device idle ms per step inside the program's ``train.batch`` spans,
mean over chips: the part of the batch draw the device waits for."""
from bench import scope_trace


def read(ctx):
    sc = scope_trace.of_run(ctx)
    return None if sc is None else sc.batch_exposed_ms()
