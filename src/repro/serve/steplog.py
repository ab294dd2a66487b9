"""Host time of each engine step, on the profiler's clock and in memory.

Every ``step()`` of the engine runs under a ``serve.step`` profiler span
(``jax.profiler.StepTraceAnnotation``, numbered by the engine's tick),
and each of its phases under a span of its own:

  ``serve.admit``         admission (swap restores, monolithic prefill)
  ``serve.repack``        the tiered pool's background repack pass
  ``serve.ensure_pages``  page growth, copy-on-write ``copy_page``
                          dispatches, preemption snapshots
  ``serve.assemble``      the step's host arrays, drafts, sampling
                          vectors and their host-to-device copies
  ``serve.dispatch``      the jitted step call, until it returns
  ``serve.sync``          the blocking read of the step's tokens
  ``serve.record``        advance, ``record_token`` and its callbacks

The spans share the device trace's clock, so a profile shows what the
host was doing while the device idled. The same boundaries, read with
``time.perf_counter``, go into a bounded in-memory log of the last
``STEP_LOG_LEN`` steps (``StepRecord``), with the backend compiles that
began during each step: what an operator reads without a profiler.
A step of the per-layer ragged kernel also records, from the rows the
host assembled, how much of that kernel's page walk does work
(``walk_live`` of ``walk_grid`` grid steps a layer).
Phases run one after another and never nest, so a step's phase times
add up to its wall time, less the few statements between them.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional, Tuple

import jax

STEP_LOG_LEN = 4096  # steps the engine's step log keeps

_PHASES = ("admit", "repack", "ensure_pages", "assemble", "dispatch", "sync",
           "record")
_SPAN_NAMES = {p: f"serve.{p}" for p in _PHASES}

_compiles = None  # one-element list, once the listener is registered


def backend_compiles() -> int:
    """Backend compiles begun in this process since the first call (the
    listener is registered then, once)."""
    global _compiles
    if _compiles is None:
        _compiles = [0]

        def listener(event, secs, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                _compiles[0] += 1

        jax.monitoring.register_event_duration_secs_listener(listener)
    return _compiles[0]


@dataclasses.dataclass(frozen=True)
class StepRecord:
    """One engine step: its tick, ``perf_counter`` seconds at its start
    and end and at the start and end of each phase, whether it
    dispatched device work, and the backend compiles during it.

    ``walk_live`` and ``walk_grid`` describe the ragged page walk of a
    step of the per-layer ragged kernel (0 on other steps): the page
    bodies it executes, summed over its rows and averaged over the
    attention layers (each row's ``ceil(seq_len / page_size)`` pages
    less those below its sliding window), and its grid steps, rows x
    page-table width, each a layer."""

    tick: int
    t0: float
    t1: float
    phases: Tuple[Tuple[str, float, float], ...]  # (phase, start, end)
    dispatched: bool
    compiles: int
    walk_live: float = 0.0
    walk_grid: int = 0

    @property
    def wall_s(self) -> float:
        return self.t1 - self.t0

    def seconds(self, phase: str) -> float:
        """Time spent in ``phase`` (a phase may run more than once)."""
        return sum(b - a for p, a, b in self.phases if p == phase)

    @property
    def host_s(self) -> float:
        """Wall time less the wait for the step's tokens."""
        return self.wall_s - self.seconds("sync")


class _Phase:
    """Context manager of one phase: its profiler span and its
    boundaries in the open step's list."""

    __slots__ = ("step", "name", "span", "t0")

    def __init__(self, step: "StepTimer", name: str):
        self.step, self.name = step, name
        self.span = jax.profiler.TraceAnnotation(_SPAN_NAMES[name])

    def __enter__(self):
        self.span.__enter__()
        self.t0 = time.perf_counter()
        return self.span

    def __exit__(self, *exc):
        self.step.phases.append((self.name, self.t0, time.perf_counter()))
        self.span.__exit__(*exc)
        return False


class StepTimer:
    """The engine step in progress: its ``serve.step`` span and phases."""

    def __init__(self, tick: int):
        self.tick = tick
        self.phases = []
        self.walk_live, self.walk_grid = 0.0, 0  # set by a ragged step
        self.compiles0 = backend_compiles()
        self.span = jax.profiler.StepTraceAnnotation("serve.step",
                                                     step_num=tick)
        self.span.__enter__()
        self.t0 = time.perf_counter()

    def phase(self, name: str) -> _Phase:
        return _Phase(self, name)

    def close(self, dispatched: bool) -> StepRecord:
        t1 = time.perf_counter()
        self.span.__exit__(None, None, None)
        return StepRecord(self.tick, self.t0, t1, tuple(self.phases),
                          dispatched, backend_compiles() - self.compiles0,
                          self.walk_live, self.walk_grid)


def phase_means_ms(records) -> Optional[dict]:
    """Mean milliseconds per step of each phase, of the host time (wall
    less ``sync``) and of the wall time, over the ``records`` of steps
    that dispatched; None if none did."""
    recs = [r for r in records if r.dispatched]
    if not recs:
        return None
    out = {p: 1e3 * sum(r.seconds(p) for r in recs) / len(recs)
           for p in _PHASES}
    out["host"] = 1e3 * sum(r.host_s for r in recs) / len(recs)
    out["wall"] = 1e3 * sum(r.wall_s for r in recs) / len(recs)
    return out
