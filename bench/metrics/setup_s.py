"""Set-up time: process start to the start of the schedule (model and
weights built, every program compiled or loaded, warm-up served)."""


def read(ctx):
    return ctx.setup_s
