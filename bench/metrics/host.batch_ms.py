"""Host ms per step inside the program's ``train.batch`` spans
(``pipeline/engine.py``): the loader's microbatches and the negatives
drawn for one step."""
from bench import scope_trace


def read(ctx):
    sc = scope_trace.of_run(ctx)
    return None if sc is None else sc.batch_ms()
