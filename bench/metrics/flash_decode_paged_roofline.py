"""Read kernel: least time the chip needs for the kernel's logical work in
the traced span (each valid query against its context; K and V of each
slot's context read once, q and out once), the larger of FLOPs over peak
and bytes over bandwidth, over the summed device time of
``flash_decode_paged``."""


def read(ctx):
    if ctx.trace is None or ctx.span_work is None or ctx.peaks is None:
        return None
    t = ctx.trace["kernel_s"].get("flash_decode_paged", 0.0)
    w = ctx.span_work
    if t <= 0 or w.read_bytes <= 0:
        return None
    least = max(w.read_flops / ctx.peaks.flops_bf16,
                w.read_bytes / ctx.peaks.hbm_bw)
    return 100.0 * least / t
