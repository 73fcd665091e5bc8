"""Median, over every request due in the window, of the time from its due
instant to the event that carries its first token (host clock)."""
import numpy as np


def read(ctx):
    if not ctx.ttft_s:
        return None
    return float(np.percentile(ctx.ttft_s, 50)) * 1e3
