"""Scheduler: backend compilations JAX reported inside the measured window
(there should be none: every shape is warmed up in set-up)."""


def read(ctx):
    return ctx.window_compiles
