"""Backend compiles inside the measured window, from `jax.monitoring`
(lib/common.CompileCounter).  Should be 0: every shape is warmed up in
set-up."""


def compute(ctx):
    return ctx["timings"].get("compiles_in_window")
