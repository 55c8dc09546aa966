"""Device ms per step of the ring's collective-permute ops
(``dist/ring_spmm.py``): the union of their intervals on the ``XLA Ops``
line, mean over chips."""


def read(ctx):
    red = ctx["reduction"]
    per = [c.collective_s for c in red.chips]
    return red.per_step(per) if any(per) else None
