"""Reduce what the program itself puts into a trace: its ``serve.*``
host spans and the named scopes of the step program's instructions.

    python bench/program_trace.py <file.xplane.pb> [<scopes.json>]

prints the device's idle time by the innermost host span open when each
gap began, ``serve.*`` phases included, and with a scope map (written by
``scope_map``) the device time of the step programs by named scope.

A device op's event in the trace carries its HLO instruction's name and
no metadata, so the scope of an op comes from the compiled step
program's HLO text: each instruction's ``op_name`` metadata holds the
``jax.named_scope`` path it was traced under. XLA keeps one op_name on a
fusion, that of the fusion's root, so a fusion counts to the scope of
its root instruction.
"""
from __future__ import annotations

import collections
import heapq
import json
import re
import sys

import trace as bench_trace

# the program's named scopes, as nn/ and serve/ name them; an op counts
# to the innermost of these on its op_name path
SCOPES = ("embed", "layer_loop", "qkv_proj", "rope", "ragged_attn",
          "o_proj", "ffn", "weight_quant", "lm_head", "sample")
UNSCOPED = "(no scope)"
HOST_SPANS = ("bench.", "serve.")

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = ", re.M)
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPERAND = re.compile(r"\(%([\w.\-]+)")


def scope_of(op_name: str):
    """The innermost named scope on an op_name path, or None."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return None


def scope_map(hlo_text: str) -> dict:
    """{instruction name: scope} of every instruction of a compiled HLO
    module's text whose op_name metadata lies in a named scope. An
    instruction without metadata (a copy or an async start that XLA
    inserted) takes the scope of its first operand, where that has one."""
    starts = [(m.end(), m.group(1)) for m in _INSTRUCTION.finditer(hlo_text)]
    out = {}
    for i, (body, name) in enumerate(starts):
        end = starts[i + 1][0] if i + 1 < len(starts) else len(hlo_text)
        m = _OP_NAME.search(hlo_text, body, end)
        if m:
            scope = scope_of(m.group(1))
        else:
            operand = _OPERAND.search(hlo_text, body, end)
            scope = operand and out.get(operand.group(1))
        if scope:
            out[name] = scope
    return out


def instruction(event_name: str) -> str:
    """The HLO instruction name of a device op's trace event."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def exclusive_seconds(events) -> list:
    """(event, seconds): each instant that some event covers counts to
    the shortest event covering it, so a loop's nested ops come out of
    the loop, and the seconds add up to the union of the events' time
    (an op that ends just after the loop that holds it is counted once;
    ``trace.self_seconds`` counts it in the loop too)."""
    order = sorted(range(len(events)), key=lambda i: events[i].start)
    bounds = sorted({t for e in events for t in (e.start, e.end)})
    own, heap, j = [0.0] * len(events), [], 0
    for a, b in zip(bounds, bounds[1:]):
        while j < len(order) and events[order[j]].start <= a:
            heapq.heappush(heap, (events[order[j]].seconds, order[j]))
            j += 1
        while heap and events[heap[0][1]].end <= a:
            heapq.heappop(heap)
        if heap:
            own[heap[0][1]] += b - a
    return list(zip(events, own))


def scope_seconds(red, scopes: dict, module: str) -> collections.Counter:
    """{scope: device seconds per chip} of the ops inside the programs
    whose ``XLA Modules`` events match ``module``, each op's own time
    (``exclusive_seconds``, on its own chip) counted to its instruction's
    scope."""
    rx = re.compile(module)
    tally = collections.Counter()
    for ops, modules in red.per_chip or [(red.ops, red.modules)]:
        steps = [e for e in modules if rx.search(e.name)]
        for e, seconds in exclusive_seconds(bench_trace.inside(ops, steps)):
            tally[scopes.get(instruction(e.name), UNSCOPED)] += (seconds
                                                                / red.chips)
    return tally


def host_spans(data) -> list:
    """Host events of the trace whose names start with ``HOST_SPANS``."""
    out = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [e for e in bench_trace._events(line)
                        if e.name.startswith(HOST_SPANS)]
    return out


def idle_by_span(data, red=None, n: int = 12) -> list:
    """[host span, idle seconds]: the device's idle time in the window,
    split by the innermost ``bench.*`` or ``serve.*`` span open when each
    gap began, as ``trace.idle_gaps`` splits it by ``bench.*`` spans."""
    red = red or bench_trace.reduce(data)
    return bench_trace.idle_gaps(
        bench_trace.Reduction(red.window, red.chips, red.ops, red.modules,
                              host_spans(data), red.busy_s, red.per_chip),
        n)


def idle_intervals(red) -> list:
    """The device's idle (start, end) intervals in the window, all
    chips' ops merged, as ``trace.idle_gaps`` finds them."""
    gaps, end = [], red.window[0]
    for e in sorted(red.ops, key=lambda e: e.start):
        if e.start > end:
            gaps.append((end, e.start))
        end = max(end, e.end)
    if red.window[1] > end:
        gaps.append((end, red.window[1]))
    return gaps


def idle_split_by_span(data, red=None) -> list:
    """[host span, idle seconds]: each of the device's idle gaps in the
    window split over the innermost ``bench.*`` or ``serve.*`` spans it
    overlaps, by time ("outside bench spans" where none is open)."""
    red = red or bench_trace.reduce(data)
    spans = [e for e in host_spans(data) if e.name != "bench.window"]
    tally = collections.Counter()
    for lo, hi in idle_intervals(red):
        inside = [s for s in spans if s.start < hi and s.end > lo]
        cuts = sorted({lo, hi} | {t for s in inside for t in (s.start, s.end)
                                  if lo < t < hi})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            open_ = [s for s in inside if s.start <= mid < s.end]
            where = (min(open_, key=lambda s: s.seconds).name if open_
                     else "outside bench spans")
            tally[where] += b - a
    return [[k, v] for k, v in tally.most_common()]


def main(argv) -> int:
    data = bench_trace.load(argv[0])
    red = bench_trace.reduce(data)
    print(json.dumps({"idle_by_span": idle_by_span(data, red),
                      "idle_split_by_span": idle_split_by_span(data, red)}))
    if len(argv) > 1:
        with open(argv[1]) as f:
            scopes = json.load(f)
        tally = scope_seconds(red, scopes, r"ragged_step")
        total = sum(tally.values())
        print(json.dumps({k: [v, 100 * v / total]
                          for k, v in tally.most_common()}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
