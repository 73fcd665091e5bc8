"""Device: share of the traced span in which no operation ran on the chip."""


def read(ctx):
    if ctx.trace is None or ctx.trace["window_s"] <= 0:
        return None
    return 100.0 * ctx.trace["idle_share"]
