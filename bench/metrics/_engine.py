"""Shared by the readers of the program's own counters: the serving
engine that ran the window, its step log, and its step program's scopes.

``harness.Context`` carries no engine, so the engine is found among the
live objects: the ``ContinuousBatchingEngine`` whose step log holds the
window's steps. A program without a step log (or without
``step_program``) yields None, and its readers report nothing.
"""
import bisect
import gc

import program_trace


def serving_engine(ctx):
    """The live engine whose step log holds the window's steps, or None."""
    if not ctx.steps:
        return None
    try:
        from repro.serve.engine import ContinuousBatchingEngine
    except ImportError:
        return None
    lo, hi = ctx.steps[0].t0, ctx.steps[-1].t1
    for obj in gc.get_objects():
        if (isinstance(obj, ContinuousBatchingEngine)
                and any(lo <= r.t0 <= hi
                        for r in getattr(obj, "step_log", ()))):
            return obj
    return None


def window_records(records, steps, traced) -> list:
    """[(record, traced)] of the step-log records that fall inside the
    harness's steps of the window, each flagged if its step was traced."""
    steps = sorted(steps, key=lambda s: s.t0)
    starts = [s.t0 for s in steps]
    traced_t0 = {s.t0 for s in traced}
    out = []
    for r in records:
        i = bisect.bisect_right(starts, r.t0) - 1
        if i >= 0 and r.t1 <= steps[i].t1:
            out.append((r, steps[i].t0 in traced_t0))
    return out


def engine_records(ctx):
    """The window's step-log records as ``window_records`` gives them, or
    None where the program keeps no step log."""
    engine = serving_engine(ctx)
    if engine is None:
        return None
    return window_records(engine.step_log, ctx.steps, ctx.traced)


def step_scopes(ctx):
    """{instruction: named scope} of the step program the engine ran, or
    None; read after the window, in traced runs only."""
    engine = serving_engine(ctx)
    if engine is None or not hasattr(engine, "step_program"):
        return None
    program = engine.step_program()
    if program is None:
        return None
    return program_trace.scope_map(program.as_text())
