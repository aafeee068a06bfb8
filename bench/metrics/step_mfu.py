"""The whole step's share of the chips' bf16 peak: model FLOPs of the
tokens completed in the traced window (``bench/flops.py``), over the
window's length, the chips and the peak of their ``device_kind``, %."""


def read(ctx):
    s = ctx.summary
    if not s.steps or s.window_s <= 0:
        return None
    work = ctx.flops_per_token * ctx.tokens_per_step * s.steps
    peak = ctx.device["peaks"]["bf16_flops_per_s"] * ctx.device["count"]
    return 100.0 * work / (s.window_s * peak)
