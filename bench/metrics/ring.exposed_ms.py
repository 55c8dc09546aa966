"""Device ms per step in which a chip waits on the ring: its
collective-permute ops and their in-flight spans, less the time any
other operation runs on that chip.  Only chips whose trace records the
in-flight spans count (the profiler records them for the first chip):
elsewhere the number would be the permute ops' time by construction."""


def read(ctx):
    red = ctx["reduction"]
    seen = [c.exposed_s for c in red.chips if c.exposed_s is not None]
    if not seen or not any(c.collective_s for c in red.chips):
        return None
    return red.per_step(seen)
