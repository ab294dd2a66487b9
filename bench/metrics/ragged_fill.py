"""Real tokens per ragged dispatch over the rows it computes: prefill
tokens plus one per decoding row, over max_slots x step width, summed
over the window's dispatching steps."""


def read(ctx):
    steps = [s for s in ctx.steps if s.dispatched and s.width]
    if not steps:
        return None
    real = sum(n for s in steps for _, n, _ in s.rows)
    return real / sum(s.slots * s.width for s in steps)
