"""Model FLOPs of a step (``bench/counts.py``) over the traced step
time times the chips times the chip's peak."""


def read(ctx):
    red = ctx["reduction"]
    step_s = red.window_s / red.n_steps
    peak = ctx["peaks"]["flops_bf16"] * ctx["chips"]
    return 100.0 * ctx["step_flops"] / (step_s * peak)
