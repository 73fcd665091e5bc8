"""Decision plane: share of the KV rows written in the window that took the
staged path (ring, then drain), of all rows written (direct + staged +
bulk prefill), from the engine's own counters."""


def read(ctx):
    s = ctx.stats_window
    total = s["direct_writes"] + s["staged_writes"] + s["prefill_writes"]
    if total <= 0:
        return None
    return 100.0 * s["staged_writes"] / total
