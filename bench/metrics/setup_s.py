"""Seconds from process start to the window: import, data, build,
weights, warm-up and whatever else the loop does before it measures."""


def read(ctx):
    return ctx["setup_s"]
