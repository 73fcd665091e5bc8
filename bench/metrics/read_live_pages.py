"""Read kernel: share of the pages the read's grid walked in the window
that held context of an active query, from the engine's own counters
(``read_pages_live`` over ``read_pages_walked``, one layer, summed over
steps). None where the engine keeps no such counters."""


def read(ctx):
    s = ctx.stats_window
    walked = s.get("read_pages_walked", 0)
    if walked <= 0:
        return None
    return 100.0 * s["read_pages_live"] / walked
