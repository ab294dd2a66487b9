"""Device time of the ops in the ``weight_quant`` named scope (the
per-step fake-quantization of the weights) over the device time of the
traced step programs, in percent."""
import program_trace
from metrics._engine import step_scopes
from metrics._step_trace import STEP_MODULE

SCOPE = "weight_quant"


def share(red, scopes):
    """The share on reduction ``red`` with scope map ``scopes``, or None
    where the trace has no step programs or the map no such scope."""
    mods = red.module_events(STEP_MODULE)
    if not mods or SCOPE not in scopes.values():
        return None
    seconds = program_trace.scope_seconds(red, scopes, STEP_MODULE)[SCOPE]
    return 100.0 * seconds / (sum(e.seconds for e in mods) / red.chips)


def read(ctx):
    if ctx.trace is None or not ctx.trace.module_events(STEP_MODULE):
        return None
    scopes = step_scopes(ctx)
    return None if scopes is None else share(ctx.trace, scopes)
