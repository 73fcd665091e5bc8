"""Median, over every request due in the window, of its time per output
token after the first: (last token's event - first token's event)
/ (tokens - 1), host clock."""
import numpy as np


def read(ctx):
    if not ctx.tpot_s:
        return None
    return float(np.percentile(ctx.tpot_s, 50)) * 1e3
