"""Model FLOPs of the real tokens in the traced steps over their device
time x chips x the bf16 peak, in percent."""
from metrics._step_trace import traced_step_work


def read(ctx):
    got = traced_step_work(ctx)
    if got is None:
        return None
    seconds, per_step = got
    flops = sum(f for f, _ in per_step)
    return 100.0 * flops / (seconds * ctx.chips
                            * ctx.peak["bf16_flops_per_s"])
