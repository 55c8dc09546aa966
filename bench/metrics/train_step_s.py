"""Seconds per training step: the window's wall time (host clock, ended
by ``block_until_ready``) over the steps completed in it."""


def read(ctx):
    w = ctx["window"]
    if not w.get("steps"):
        return None
    return w["seconds"] / w["steps"]
