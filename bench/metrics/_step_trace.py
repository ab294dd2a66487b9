"""Shared by the step readers: the traced steps' device time and work."""
import work

STEP_MODULE = r"ragged_step|sharded_step"


def traced_step_work(ctx):
    """(device seconds per chip, [(flops, bytes) per step]) of the traced
    steps, or None where the trace holds no step programs to read."""
    if ctx.trace is None:
        return None
    mods = ctx.trace.module_events(STEP_MODULE)
    steps = [s for s in ctx.traced if s.dispatched]
    if not mods or not steps:
        return None
    seconds = sum(e.seconds for e in mods) / ctx.trace.chips
    return seconds, [work.step_work(ctx.conf, s.rows) for s in steps]
