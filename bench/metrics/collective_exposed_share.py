"""Device time of the collectives in the traced step programs that no
other op on the same chip overlaps, over the step programs' device time
on that chip, averaged over the chips, in percent. Nothing to read where
the step programs hold no collective (one chip, or a trace without
them)."""
import re

import trace as bench_trace
from metrics._step_trace import STEP_MODULE


def share(red):
    """The share on reduction ``red``, or None."""
    shares, found = [], False
    for ops, modules in red.per_chip or ():
        steps = [m for m in modules if re.search(STEP_MODULE, m.name)]
        seconds = sum(m.seconds for m in steps)
        if seconds <= 0:
            continue
        total, exposed = bench_trace.collective_seconds(
            bench_trace.inside(ops, steps))
        found = found or total > 0
        shares.append(exposed / seconds)
    return 100.0 * sum(shares) / len(shares) if found else None


def read(ctx):
    return None if ctx.trace is None else share(ctx.trace)
