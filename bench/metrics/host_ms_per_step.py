"""Mean host milliseconds of the window's untraced engine steps that
dispatched: each step's wall time less its wait for the step's tokens
(``serve.sync``), from the engine's step log."""
from metrics._engine import engine_records


def host_ms(window_records):
    """The mean over ``[(record, traced)]``, or None."""
    recs = [r for r, traced in window_records if r.dispatched and not traced]
    if not recs:
        return None
    return 1e3 * sum(r.host_s for r in recs) / len(recs)


def read(ctx):
    records = engine_records(ctx)
    return None if records is None else host_ms(records)
