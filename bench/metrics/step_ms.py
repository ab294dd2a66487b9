"""Mean host-clock milliseconds of the window's engine steps that
dispatched (each ends with the host reading the step's tokens)."""


def read(ctx):
    steps = [s for s in ctx.steps if s.dispatched]
    if not steps:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in steps) / len(steps)
