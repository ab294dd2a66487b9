"""The ragged page walk's least time on the chips used, from each traced
step's rows, over the device time per chip of the ragged attention
kernel's events, in percent."""
import work

# the step's only Pallas kernel is the ragged page walk (one call per
# layer); its trace event carries no kernel name, only the call's target
KERNEL = r'_mx_attn_ragged|custom_call_target="tpu_custom_call"'


def read(ctx):
    if ctx.trace is None:
        return None
    seconds = ctx.trace.op_seconds(KERNEL)
    steps = [s for s in ctx.traced if s.dispatched]
    if seconds <= 0 or not steps:
        return None
    least = sum(work.least_seconds(*work.attention_work(ctx.conf, s.rows),
                                   ctx.peak) for s in steps)
    return 100.0 * least / (seconds * ctx.chips)
