"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to device time.

    python bench/trace.py <file.xplane.pb>    # print what the trace holds

Device planes are ``/device:TPU:<n>``; each has an ``XLA Ops`` line (one
event per executed op) and an ``XLA Modules`` line (one event per
executed program). The window is the host span the harness names
(``bench.window``). Busy time is the union of the op intervals inside
the window, averaged over the chips.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import re
import sys

DEVICE_PLANE = re.compile(r"/device:TPU:\d+")
OPS, MODULES = "XLA Ops", "XLA Modules"


@dataclasses.dataclass
class Event:
    name: str
    start: float  # seconds on the trace's clock
    end: float
    stats: dict

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def text(self) -> str:
        """Name and every stat value, for matching by pattern."""
        return " ".join([self.name, *map(str, self.stats.values())])


@dataclasses.dataclass
class Reduction:
    window: tuple           # (start, end) seconds
    chips: int
    ops: list               # Events of every chip's ops line, in window
    modules: list           # Events of every chip's modules line
    host: list              # host Events named bench.*
    busy_s: float           # mean over chips of the union of op time
    per_chip: list = None   # (ops, modules) of each chip, as above

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def op_seconds(self, pattern: str) -> float:
        """Summed device time of ops whose name or stats match, per chip."""
        rx = re.compile(pattern)
        return sum(e.seconds for e in self.ops
                   if rx.search(e.text())) / self.chips

    def module_events(self, pattern: str) -> list:
        rx = re.compile(pattern)
        return [e for e in self.modules if rx.search(e.name)]


def _events(line, lo=None, hi=None):
    out = []
    for e in line.events:
        s = e.start_ns * 1e-9
        t = s + e.duration_ns * 1e-9
        if lo is not None and (t <= lo or s >= hi):
            continue
        out.append(Event(e.name, s if lo is None else max(s, lo),
                         t if hi is None else min(t, hi),
                         {k: v for k, v in e.stats}))
    return out


def _union(events) -> float:
    total, end = 0.0, float("-inf")
    for e in sorted(events, key=lambda e: e.start):
        if e.end > end:
            total += e.end - max(e.start, end)
            end = e.end
    return total


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(str(path))


def reduce(data, window_span: str = "bench.window") -> Reduction:
    """Reduce a trace (loaded by ``load``, or its path) to the window's
    device time."""
    if isinstance(data, (str, bytes)) or hasattr(data, "__fspath__"):
        data = load(data)
    host, window = [], None
    devices = []
    for plane in data.planes:
        if DEVICE_PLANE.fullmatch(plane.name):
            devices.append(plane)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in _events(line):
                if e.name == window_span and window is None:
                    window = (e.start, e.end)
                if e.name.startswith("bench."):
                    host.append(e)
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    if window is None:
        raise ValueError(f"no host span {window_span!r} in the trace")
    ops, modules, busy, per_chip = [], [], [], []
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        if OPS not in lines:
            raise ValueError(f"{plane.name} has no {OPS!r} line")
        dev_ops = _events(lines[OPS], *window)
        dev_modules = (_events(lines[MODULES], *window) if MODULES in lines
                       else [])
        ops += dev_ops
        modules += dev_modules
        busy.append(_union(dev_ops))
        per_chip.append((dev_ops, dev_modules))
    return Reduction(window, len(devices), ops, modules, host,
                     sum(busy) / len(busy), per_chip)


def self_seconds(events) -> list:
    """(event, seconds) with the time of the events nested inside each
    (a loop's body inside the loop) taken out of it."""
    own, stack = {}, []
    for i, e in sorted(enumerate(events), key=lambda x: (x[1].start,
                                                        -x[1].end)):
        while stack and events[stack[-1]].end <= e.start:
            stack.pop()
        own[i] = e.seconds
        if stack and e.end <= events[stack[-1]].end:
            own[stack[-1]] -= e.seconds
        stack.append(i)
    return [(e, own[i]) for i, e in enumerate(events)]


# collectives: an op event reads "%<instruction> = <type> <opcode>(...";
# the instruction's name (all_gather.3, all-gather-start.1) or its opcode
# (all-gather, or all-gather-start and -done of an async pair) names one
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
                "collective-permute", "all-to-all")
COLLECTIVE_NAME = re.compile(
    "^%?(?:" + "|".join(k.replace("-", "[-_]") for k in _COLLECTIVES) + ")")
COLLECTIVE_OPCODE = re.compile(
    " (?:" + "|".join(_COLLECTIVES) + r")(?:-start|-done)?\(")


def is_collective(e: Event) -> bool:
    name, _, text = e.name.partition(" = ")
    return bool(COLLECTIVE_NAME.match(name)
                or COLLECTIVE_OPCODE.search(text))


def inside(ops, spans) -> list:
    """The ops that lie within one of the (module) events ``spans``."""
    spans = sorted((s.start, s.end) for s in spans)
    starts = [s for s, _ in spans]
    out = []
    for e in ops:
        i = bisect.bisect_right(starts, e.start) - 1
        if i >= 0 and e.end <= spans[i][1]:
            out.append(e)
    return out


def collective_seconds(ops) -> tuple:
    """(collective seconds, exposed seconds) of one chip's ops: the union
    of its collectives' time, and the part of it that no other op
    overlaps. An op that spans a whole collective holds it (a loop
    around it) and is no overlap; one that covers only part of it, or
    lies inside it, runs beside it."""
    bounds = sorted({t for e in ops for t in (e.start, e.end)})
    order = sorted(ops, key=lambda e: e.start)
    total = exposed = 0.0
    live, j = [], 0
    for a, b in zip(bounds, bounds[1:]):
        while j < len(order) and order[j].start <= a:
            live.append(order[j])
            j += 1
        live = [e for e in live if e.end > a]
        coll = [e for e in live if is_collective(e)]
        if not coll:
            continue
        total += b - a
        if not any(not is_collective(o) and not any(
                o.start <= c.start and c.end <= o.end for c in coll)
                for o in live):
            exposed += b - a
    return total, exposed


def top_ops(red: Reduction, label, n: int = 10) -> list:
    """[label, seconds per chip] of the ops that took most device time of
    their own (nested ops taken out on their own chip)."""
    tally = collections.Counter()
    for ops, _ in red.per_chip or [(red.ops, None)]:
        for e, seconds in self_seconds(ops):
            tally[label(e)] += seconds / red.chips
    return [[k, v] for k, v in tally.most_common(n)]


def idle_gaps(red: Reduction, n: int = 10) -> list:
    """[what the host was doing, idle seconds]: the device's idle time in
    the window (all chips' ops merged), split by the innermost host span
    open when each gap began."""
    gaps, end = [], red.window[0]
    for e in sorted(red.ops, key=lambda e: e.start):
        if e.start > end:
            gaps.append((end, e.start))
        end = max(end, e.end)
    if red.window[1] > end:
        gaps.append((end, red.window[1]))
    spans = sorted((e for e in red.host if e.name != "bench.window"),
                   key=lambda e: e.seconds)
    tally = collections.Counter()
    for lo, hi in gaps:
        where = next((s.name for s in spans if s.start <= lo < s.end),
                     "outside bench spans")
        tally[where] += hi - lo
    return [[k, v] for k, v in tally.most_common(n)]


def main(argv) -> int:
    data = load(argv[0])
    for plane in data.planes:
        lines = list(plane.lines)
        print(plane.name, [(ln.name, len(list(ln.events))) for ln in lines])
        for ln in lines:
            for e in list(ln.events)[:int(argv[1]) if len(argv) > 1 else 3]:
                print("   ", ln.name, "|", e.name, e.start_ns, e.duration_ns,
                      dict(e.stats))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
