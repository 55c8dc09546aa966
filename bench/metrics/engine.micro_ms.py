"""Device ms per step of the engine's forward-and-backward program
(``jit_micro_value_and_grad``, ``pipeline/engine.py``), mean over chips."""


def read(ctx):
    return ctx["reduction"].module_ms("jit_micro_value_and_grad")
