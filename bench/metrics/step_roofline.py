"""The traced steps' least time (the larger of FLOPs over the bf16 peak
and bytes over HBM bandwidth, per step) over their device time, in
percent."""
import work
from metrics._step_trace import traced_step_work


def read(ctx):
    got = traced_step_work(ctx)
    if got is None:
        return None
    seconds, per_step = got
    least = sum(work.least_seconds(f, b, ctx.peak) for f, b in per_step)
    return 100.0 * least / (seconds * ctx.chips)
