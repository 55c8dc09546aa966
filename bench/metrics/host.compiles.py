"""Lowerings of the engine's two step programs beyond the first of each
(``repro.obs`` counter, one a jit cache miss), from the program's
import to the read after the window: 0 unless a step retraced, in
set-up's compared steps or in the window.  None for a program without
the counter, or one whose step programs never lowered."""
PROGRAMS = ("jit(micro_value_and_grad)", "jit(apply_update)")


def read(ctx):
    try:
        from repro import obs
    except ImportError:
        return None
    lowered = obs.counters()["lowerings"]
    if not all(lowered.get(p) for p in PROGRAMS):
        return None
    return sum(lowered[p] - 1 for p in PROGRAMS)
