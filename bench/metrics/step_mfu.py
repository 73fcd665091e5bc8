"""Model step: model FLOPs of the tokens the traced span processed
(matrices, attention over each token's context, the head for the rows whose
logits were needed; counted from the configuration and each request's
context) over device busy time times the chip's bf16 peak."""


def read(ctx):
    if ctx.trace is None or ctx.span_work is None or ctx.peaks is None:
        return None
    flops = ctx.span_work.model_flops(ctx.shapes)
    if flops <= 0 or ctx.trace["busy_s"] <= 0:
        return None
    return 100.0 * flops / (ctx.trace["busy_s"] * ctx.peaks.flops_bf16)
