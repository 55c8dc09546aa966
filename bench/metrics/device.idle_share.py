"""Share of the traced window in which no operation ran on the device,
mean over the cell's chips (each chip's share is on an earlier line)."""


def read(ctx):
    red = ctx["reduction"]
    if red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
