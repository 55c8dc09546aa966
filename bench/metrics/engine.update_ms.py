"""Device ms per step of the engine's optimizer program
(``jit_apply_update``, ``pipeline/engine.py``), mean over chips."""


def read(ctx):
    return ctx["reduction"].module_ms("jit_apply_update")
