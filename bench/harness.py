"""One run of one cell: set up, drive the window, score, report.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by the name that
``BENCHMARK.json`` gives it:

  configs/<config>.json        the configuration as it is run; its
                               ``family`` names models/<family>.py (the
                               program side) and models/<family>_ref.py
                               (the plain reference)
  traffic/<mix>.json           parameters that ``loadgen`` reads
  metrics/<metric>.py          ``read(ctx) -> float | None``

The window drives ``ServeEngine.submit`` and ``ServeEngine.step`` as
``repro.launch.serve.build_engine`` builds them, and times every token
on the benchmark's clock through ``scheduler.on_token``.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import json
import math
import re
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (str(BENCH), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import loadgen  # noqa: E402
import work  # noqa: E402
from metrics._step_trace import STEP_MODULE  # noqa: E402

TRACE_STEPS = 8        # engine steps traced in a --trace 1 run
TTFT_WAIT_S = 60.0     # past the window, for first tokens still due
CHECK_TOKENS = 512     # served tokens the check compares, at least
CHECK_REQUESTS = 8     # ... from at most this many requests


def log(msg: str) -> None:
    print(msg, flush=True)


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def load_config(name: str) -> dict:
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def cell_spec(name: str, bench: dict = None) -> dict:
    """The cell's workload entry with its config, mix and metric lists."""
    bench = bench or load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return {"cell": cell, "conf": load_config(cell["config"]),
            "mix": loadgen.load_mix(cell["traffic"]),
            "end_to_end": e2e, "per_layer": per_layer}


def metric_reader(name: str):
    return importlib.import_module(f"metrics.{name}").read


def family(conf: dict):
    """(program adapter, plain reference) modules of the config."""
    return (importlib.import_module(f"models.{conf['family']}"),
            importlib.import_module(f"models.{conf['family']}_ref"))


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    dispatched: bool
    rows: list          # (start, n_new, logit rows) of each active row
    width: int          # token columns per row
    slots: int


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader may read."""
    conf: dict
    peak: dict
    chips: int
    counters: dict      # program counters, deltas over the window
    steps: list         # Steps in the window
    traced: list        # Steps inside the traced span
    trace: object       # trace.Reduction, or None in a --trace 0 run


class Recorder:
    """Token times on the benchmark's clock, and the rows of each step,
    hooked onto the engine from outside."""

    def __init__(self, engine):
        self.times = {}       # request id -> [token times]
        self.tokens = {}      # request id -> [tokens], as streamed
        self.finished = []    # request ids, in finishing order
        self.last_rows = None
        engine.scheduler.on_token = self.on_token
        assemble = engine.scheduler.assemble_ragged

        def recorded(width, extra_tokens=0):
            out = assemble(width, extra_tokens=extra_tokens)
            row_start, seq_lens, modes = out[1], out[2], out[5]
            finals = {seq.slot for seq, _, _, final in out[7] if final}
            self.last_rows = (width, [
                (int(row_start[s]), int(seq_lens[s] - row_start[s]),
                 int(modes[s] == 1 or s in finals))
                for s in range(len(modes)) if modes[s]])
            return out

        engine.scheduler.assemble_ragged = recorded

    def on_token(self, req, token, finished):
        self.times.setdefault(req.id, []).append(time.perf_counter())
        self.tokens.setdefault(req.id, []).append(int(token))
        if finished:
            self.finished.append(req.id)


def serve_kwargs(conf: dict, seed: int) -> dict:
    """The ``ServeConfig`` fields that the configuration's ``serving``
    sets: ``max_seq``, ``max_slots``, and where given ``mesh`` (a
    ``[data, model]`` pair, as ``mesh_shape``)."""
    srv = conf["serving"]
    kw = dict(max_seq=srv["max_seq"], max_slots=srv["max_slots"],
              seed=int(seed) & 0x7FFFFFFF)
    if "mesh" in srv:
        kw["mesh_shape"] = tuple(srv["mesh"])
    return kw


def mesh_size(serve_kw: dict) -> int:
    return math.prod(serve_kw.get("mesh_shape", (1, 1)))


def _check_chips(cell: dict, serve_kw: dict) -> None:
    if mesh_size(serve_kw) != cell["chips"]:
        raise SystemExit(
            f"bench: the cell asks for {cell['chips']} chips but its "
            f"configuration's mesh {serve_kw.get('mesh_shape', (1, 1))} "
            f"spans {mesh_size(serve_kw)}")


def _serve_mesh(serve_kw: dict, devices):
    """The (data, model) mesh the sharded engine builds over ``devices``,
    or None for one device."""
    if mesh_size(serve_kw) == 1:
        return None
    from repro.launch.mesh import _make_mesh

    return _make_mesh(serve_kw["mesh_shape"], ("data", "model"), devices)


def _device_check(chips: int, require_tpu: bool):
    import jax

    devices = jax.devices()
    if require_tpu and jax.default_backend() != "tpu":
        raise SystemExit(f"bench: needs a TPU, JAX found "
                         f"{jax.default_backend()!r}")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found "
                         f"{len(devices)}")
    return devices[:chips]


POOL_MARGIN_BYTES = 512 * 2**20  # HBM left free beside the pool and the step
POOL_PAGES_MULTIPLE = 64         # the pool rounds down to this many pages, so
                                 # that small changes in free HBM keep one shape
PROBE_PAGES = 320                # the smaller pool the step is compiled at to
                                 # size the pool (twice that is the other),
                                 # unless one sequence of max_seq needs more


class _Captured(Exception):
    pass


def _step_args(engine) -> tuple:
    """The ragged step's arguments as the engine's first step passes
    them, captured before anything is dispatched."""
    got = []

    def capture(*args):
        got.append(args)
        raise _Captured

    engine.pallas_calls_per_step = -1   # skips the dispatch audit's trace
    engine._ragged_fn = capture
    engine.submit(np.zeros(8, np.int32), 1)
    try:
        engine.step()
    except _Captured:
        pass
    return got[0]


def probe_pages(max_seq: int, page_size: int) -> int:
    """The smaller probe pool: PROBE_PAGES, or one whole sequence."""
    return max(PROBE_PAGES, -(-max_seq // page_size))


def step_memory(step_fn, args, cfg, slots: int, pages: int,
                page_size: int, mesh=None, pool_specs=None) -> dict:
    """The compiler's memory analysis of the ragged step with a pool of
    ``pages`` pages (trash page included): argument bytes, and the bytes
    the step needs beyond its arguments while it runs, on each device
    (the pool laid out by ``pool_specs`` on ``mesh``, where given)."""
    import jax

    from repro.nn import model

    cache = jax.eval_shape(
        lambda: model.init_paged_cache(cfg, slots, pages, page_size))
    if mesh is not None:
        from jax.sharding import NamedSharding

        flat, treedef = jax.tree_util.tree_flatten(cache)
        cache = treedef.unflatten([
            jax.ShapeDtypeStruct(x.shape, x.dtype,
                                 sharding=NamedSharding(mesh, spec))
            for x, spec in zip(flat, treedef.flatten_up_to(pool_specs))])
    ma = step_fn.lower(args[0], cache, *args[2:]).compile().memory_analysis()
    return {"pages": pages, "argument": ma.argument_size_in_bytes,
            "extra": (ma.temp_size_in_bytes + ma.output_size_in_bytes
                      - ma.alias_size_in_bytes)}


def fit_pool(probes: list, free: int, trash: int) -> int:
    """Pages that ``free`` bytes (the least over the devices) hold beside
    the step, less the margin: the pool's bytes and the step's extra
    bytes on a device are linear in the pages, read from two compiles of
    the step (``step_memory``)."""
    lo, hi = probes
    span = hi["pages"] - lo["pages"]
    per_page = (hi["argument"] - lo["argument"]) / span
    extra_per_page = (hi["extra"] - lo["extra"]) / span
    extra_fixed = lo["extra"] - extra_per_page * lo["pages"]
    total = (free - POOL_MARGIN_BYTES - extra_fixed) / (per_page
                                                         + extra_per_page)
    pages = (int(total) - trash) // POOL_PAGES_MULTIPLE * POOL_PAGES_MULTIPLE
    log(f"pool sizing: {per_page:.0f} bytes a page as compiled; the step "
        f"needs {extra_fixed:.0f} bytes plus {extra_per_page:.1f} a page "
        f"beyond its arguments; {free} bytes free after the weights, "
        f"margin {POOL_MARGIN_BYTES}")
    if pages < POOL_PAGES_MULTIPLE:
        raise RuntimeError(f"no room for a page pool: {pages} pages")
    return pages


def _built(engine, serve_kw: dict):
    """``engine``, unless it fell back from the sharded ragged step."""
    if not engine.ragged:
        raise RuntimeError("the engine fell back from the ragged step")
    if mesh_size(serve_kw) > 1 and engine.mesh is None:
        raise RuntimeError(f"the engine fell back to one device from the "
                           f"mesh {serve_kw['mesh_shape']}")
    return engine


def _pool_pages(conf: dict, cfg, params, serve_kw: dict, devices) -> int:
    """The pool a deployment would give these chips: the pages that the
    HBM left free after the weights holds beside the compiled step's own
    needs, on the fullest chip, with a margin. The step is compiled at
    two probe pool sizes from an engine with a small pool, and its memory
    analysis read."""
    srv = conf["serving"]
    if "num_pages" in srv:
        return srv["num_pages"]
    from repro.launch.serve import build_engine
    from repro.serve import ServeConfig

    page_size = ServeConfig.page_size
    small = probe_pages(serve_kw["max_seq"], page_size)
    probe = _built(build_engine(cfg, ServeConfig(num_pages=small, **serve_kw),
                                params, "continuous"), serve_kw)
    step_fn, trash = probe._ragged_fn, probe._trash_pages
    layout = ({} if probe.mesh is None else
              {"mesh": probe.mesh, "pool_specs": probe._pool_specs})
    args = _step_args(probe)
    del probe
    probes = [step_memory(step_fn, args, cfg, serve_kw["max_slots"], n,
                          page_size, **layout)
              for n in (small, 2 * small)]
    del args, step_fn, layout
    gc.collect()
    free = [s["bytes_limit"] - s["bytes_in_use"]
            for s in (d.memory_stats() for d in devices)]
    return fit_pool(probes, min(free), trash)


def build_cell_engine(conf: dict, seed: int, devices):
    """The serving engine of a cell's configuration over ``devices``:
    the seed's weights (drawn in the mesh's layout where the
    configuration gives one), the pool that ``_pool_pages`` sizes, and
    the engine built on them, refused if it fell back."""
    from repro.launch.serve import build_engine
    from repro.serve import ServeConfig

    program, _ = family(conf)
    serve_kw = serve_kwargs(conf, seed)
    cfg = program.program_config(conf)
    mesh = _serve_mesh(serve_kw, devices)
    params = program.program_params(conf, seed, mesh)
    program.check_layout(conf, cfg, params)
    num_pages = _pool_pages(conf, cfg, params, serve_kw, devices)
    return _built(build_engine(cfg, ServeConfig(num_pages=num_pages,
                                                 **serve_kw),
                               params, "continuous"), serve_kw)


_COMPILES = []


def _compile_counter():
    """A one-element list counting backend compiles in this process."""
    if not _COMPILES:
        import jax

        _COMPILES.append(0)

        def listener(event, secs, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                _COMPILES[0] += 1

        jax.monitoring.register_event_duration_secs_listener(listener)
    return _COMPILES


def _step(engine, rec, steps, annotate):
    n0 = sum(engine.dispatch_counts.values())
    rec.last_rows = None
    t0 = time.perf_counter()
    if annotate:
        import jax

        with jax.profiler.TraceAnnotation("bench.step"):
            engine.step()
    else:
        engine.step()
    t1 = time.perf_counter()
    width, rows = rec.last_rows or (0, [])
    steps.append(Step(t0, t1, sum(engine.dispatch_counts.values()) > n0,
                      rows, width, engine.serve_cfg.max_slots))


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float = None, require_tpu: bool = True, spec=None,
             patch=None, trace_dir: str = None, control: bool = False,
             check: bool = True) -> dict:
    """One run. ``spec`` replaces the cell's files (CPU rehearsals);
    ``patch(engine)`` breaks the program for the fault tests;
    ``control`` puts the control in the program's place for the check,
    which then judges the tokens that the control puts first (for
    setting the limit; the benchmark's own runs never do); ``check``
    False skips the check (the knee sweep)."""
    t_start = time.perf_counter() if t_start is None else t_start
    spec = spec or cell_spec(name)
    cell, conf, mix = spec["cell"], spec["conf"], spec["mix"]
    serve_kw = serve_kwargs(conf, seed)
    _check_chips(cell, serve_kw)
    devices = _device_check(cell["chips"], require_tpu)
    import jax

    from repro.launch.compile_cache import setup_compile_cache

    cache_dir = setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev = devices[0]
    log(f"device: platform {dev.platform}, device_kind {dev.device_kind}, "
        f"count {len(devices)}; compile cache {cache_dir}")
    peak = work.peaks(dev.device_kind) if require_tpu else None
    compiles = _compile_counter()

    # -- set-up ---------------------------------------------------------
    engine = build_cell_engine(conf, seed, devices)
    num_pages, page_size = engine.num_pages, engine.serve_cfg.page_size
    stats = engine.cache_stats()
    log(f"pool: {num_pages} pages of {page_size} tokens, "
        f"{stats['page_bytes']} bytes a page, "
        f"{stats['allocated_bytes']} bytes allocated")
    if engine.mesh is not None:
        log(f"mesh: {dict(engine.mesh.shape)} over "
            f"{[d.id for d in engine.mesh.devices.flat]}, KV-head shards "
            f"{engine.tp}, step width {engine.serve_cfg.prefill_chunk}")
    rec = Recorder(engine)
    if patch is not None:
        patch(engine)
    rng = np.random.default_rng([int(seed), 1])
    engine.submit(rng.integers(0, conf["vocab_size"], 64).astype(np.int32),
                  2)
    steps = []
    while engine.scheduler.has_work:
        _step(engine, rec, steps, False)
    rec.times.clear()
    rec.tokens.clear()
    rec.finished.clear()
    reqs = loadgen.generate(mix, seed, seconds, conf["vocab_size"])
    log(f"traffic: {cell['traffic']}, {len(reqs)} requests scheduled, "
        f"{sum(len(r.prompt) for r in reqs)} prompt tokens, "
        f"{sum(r.max_new for r in reqs)} output tokens at most")

    # -- preroll and window ---------------------------------------------
    closed = mix["loop"] == "closed"
    ids, due, late = {}, {}, []
    queue = list(reqs)

    def submit(r, due_at):
        rid = engine.submit(r.prompt, r.max_new)
        ids[rid] = r
        due[rid] = due_at
        if not closed:
            late.append(time.perf_counter() - due_at)

    steps = []
    if closed:
        for r in queue[:mix["concurrency"]]:
            submit(r, time.perf_counter())
        queue = queue[mix["concurrency"]:]
        while engine.scheduler.queue or engine.scheduler.prefilling():
            _step(engine, rec, steps, False)
        w0 = time.perf_counter()
    else:
        w0 = time.perf_counter() + mix["preroll_s"]
    w1 = w0 + seconds
    c0, compiles_before = None, 0
    steps, traced, tracing, window_span = [], [], None, None
    while True:
        now = time.perf_counter()
        if now >= w1:
            break
        if c0 is None and now >= w0:
            c0, compiles_before = engine.cache_stats(), compiles[0]
        if not closed:
            while queue and w0 + queue[0].due <= now:
                r = queue.pop(0)
                submit(r, w0 + r.due)
        elif rec.finished:
            for _ in range(len(rec.finished)):
                rec.finished.pop()
                if queue:
                    submit(queue.pop(0), now)
        if trace and tracing is None and now >= w0 + seconds / 2:
            tracing = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(tracing)
            window_span = jax.profiler.TraceAnnotation("bench.window")
            window_span.__enter__()
        if engine.scheduler.has_work:
            _step(engine, rec, steps, window_span is not None)
            if window_span is not None:
                traced.append(steps[-1])
                if len(traced) == TRACE_STEPS:
                    window_span.__exit__(None, None, None)
                    window_span = None
                    jax.profiler.stop_trace()
        elif queue and not closed:
            time.sleep(max(0.0, min(w0 + queue[0].due, w1)
                           - time.perf_counter()))
        else:
            time.sleep(0.001)
    if window_span is not None:
        window_span.__exit__(None, None, None)
        jax.profiler.stop_trace()
    # the window closes with the step that was running at w1, so that
    # rates count whole steps; it is at most one step longer than asked
    w_end = max([w1] + [s.t1 for s in steps])
    c1 = engine.cache_stats()
    queued = len(engine.scheduler.queue)
    in_window = [s for s in steps if s.t0 >= w0 and s.t1 <= w_end]
    compiles_in_window = compiles[0] - compiles_before

    # -- first tokens still due -----------------------------------------
    due_in = {rid for rid, t in due.items() if w0 <= t < w1}
    t_wait = time.perf_counter()
    while (any(rid not in rec.times for rid in due_in)
           and engine.scheduler.has_work
           and time.perf_counter() - t_wait < TTFT_WAIT_S):
        _step(engine, rec, [], False)
    mem_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)

    # -- end-to-end metrics ---------------------------------------------
    ttft = [(rec.times[rid][0] - due[rid]) * 1e3 for rid in due_in
            if rid in rec.times]
    failed = len(due_in) - len(ttft)
    gaps, out_tokens = [], 0
    for times in rec.times.values():
        t = np.asarray(times)
        inside = (t >= w0) & (t <= w_end)
        out_tokens += int(inside.sum())
        pair = inside[1:] & inside[:-1]
        gaps += list((np.diff(t)[pair]) * 1e3)
    attempted = len(due_in) if not closed else len(
        [r for r, t in rec.times.items() if any(w0 <= x <= w_end for x in t)])
    values = {
        "ttft_p90_ms": percentile(ttft, 90) if ttft else None,
        "itl_p95_ms": percentile(gaps, 95) if gaps else None,
        "output_tok_s": out_tokens / (w_end - w0),
        "setup_s": w0 - t_start,
    }
    log(f"window: {w_end - w0:.6f} s, {len(in_window)} steps "
        f"({sum(s.dispatched for s in in_window)} dispatched), "
        f"{out_tokens} tokens, {len(gaps)} token gaps; {len(due_in)} "
        f"requests due in the window, {len(ttft)} with a first token, "
        f"{failed} without; {queued} queued at the close; compiles in the "
        f"window {compiles_in_window}")
    if late:
        log(f"generator: submitted {len(late)} requests, late by median "
            f"{percentile(late, 50) * 1e3:.3f} ms, max "
            f"{max(late) * 1e3:.3f} ms (one engine step at most)")
    log(f"memory: peak {mem_peak} bytes in use on the fullest of "
        f"{len(devices)} {dev.device_kind}")

    # -- per-layer metrics ----------------------------------------------
    metrics = {}
    breakdown = None
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(mem_peak)}
    if trace:
        import trace as trace_mod

        red = None
        if tracing is not None:
            path = next(Path(tracing).rglob("*.xplane.pb"))
            if trace_dir:
                out = Path(trace_dir)
                out.mkdir(parents=True, exist_ok=True)
                shutil.copy(path, out / f"{name}.xplane.pb")
                (out / f"{name}.steps.json").write_text(json.dumps({
                    "window": [dataclasses.asdict(s) for s in in_window],
                    "traced": [dataclasses.asdict(s) for s in traced],
                    "counters": {k: c1[k] - c0[k] for k in (
                        "prompt_tokens", "prefill_tokens_computed")}}))
            try:
                red = trace_mod.reduce(trace_mod.load(path))
            except ValueError:
                if require_tpu:
                    raise
                red = None  # a CPU rehearsal's trace has no TPU plane
            shutil.rmtree(tracing, ignore_errors=True)
        if red is not None:
            device["busy_s"] = red.busy_s
            device["window_s"] = red.window_s
            breakdown = {
                "device_ops": trace_mod.top_ops(red, op_label),
                "idle_gaps": trace_mod.idle_gaps(red)}
            log(f"trace: {len(traced)} steps, window {red.window_s:.6f} s, "
                f"busy {red.busy_s:.6f} s, "
                f"{len(red.module_events(STEP_MODULE))} step programs, "
                f"{len(red.ops)} device ops")
        ctx = Context(conf, peak, len(devices), {
            k: c1[k] - c0[k] for k in ("prompt_tokens",
                                       "prefill_tokens_computed")},
            in_window, traced, red)
        for m in spec["per_layer"]:
            v = metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        for m in spec["end_to_end"]:
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": float(values[m["name"]]),
                                      "unit": m["unit"]}

    durations = [s.t1 - s.t0 for s in in_window if s.dispatched]
    host_gaps = [b.t0 - a.t1 for a, b in zip(in_window, in_window[1:])]
    if durations:
        slowest = sorted(in_window, key=lambda s: s.t1 - s.t0)[-3:]
        log(f"steps: median {percentile(durations, 50):.6f} s; slowest "
            f"(seconds, at window second) "
            f"{[(s.t1 - s.t0, round(s.t0 - w0, 3)) for s in slowest]}; "
            f"widest host gap between steps {max(host_gaps, default=0):.6f} s")
    window = {"steps": len(in_window), "tokens": out_tokens,
              "step_s": {"median": percentile(durations, 50),
                         "max": max(durations)} if durations else None,
              "host_gap_max_s": max(host_gaps, default=0.0),
              "due": len(due_in), "queued_at_close": queued,
              "compiles": compiles_in_window, "preemptions":
              c1["preemptions"] - c0["preemptions"],
              "ttft_p50_ms": percentile(ttft, 50) if ttft else None,
              "values": values}

    # -- correct ---------------------------------------------------------
    served = {rid: ids[rid] for rid in rec.tokens if rid in ids}
    outputs = {rid: np.asarray(rec.tokens[rid], np.int32) for rid in served}
    del engine, rec
    gc.collect()
    if not check:
        return {"window": window, "metrics": metrics}
    t_check = time.perf_counter()
    check = check_outputs(conf, seed, served, outputs, control)
    t_check = time.perf_counter() - t_check
    correct = check["value"] <= check["limit"] and failed == 0
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["window"] = window
    result["readings"] = check["readings"]
    result["check"] = {"mean_logit_gap": {"value": check["value"],
                                          "limit": check["limit"]},
                       "requests_without_first_token": {
                           "value": failed, "limit": 0}}

    log(f"check: {check['requests']} requests, {check['tokens']} served "
        f"tokens against the float32 reference; mean gap "
        f"{check['value']:.6f} (limit {check['limit']}), mean per request "
        f"{[round(g, 5) for g in check['per_request']]}; "
        f"{check['argmax_share']:.4f} of served tokens are its argmax; "
        f"readings {check['readings']}; {t_check:.1f} s")
    return result


def op_label(e) -> str:
    """A device op's kind without its instance number (``%fusion``), and
    a custom call's target (``%closed_call tpu_custom_call``)."""
    kind = e.name.split(" = ")[0].rstrip("0123456789").rstrip(".")
    target = re.search(r'custom_call_target="([^"]+)"', e.name)
    return f"{kind} {target.group(1)}" if target else kind


def sample_requests(served: dict, outputs: dict, seed: int) -> list:
    """The longest served request and others drawn from the seed, until
    CHECK_TOKENS served tokens or CHECK_REQUESTS requests."""
    rids = sorted(rid for rid in served if len(outputs[rid]))
    if not rids:
        return []
    longest = max(rids, key=lambda r: (len(served[r].prompt)
                                       + len(outputs[r]), -r))
    rng = np.random.default_rng([int(seed), 2])
    rest = [r for r in rng.permutation(rids) if r != longest]
    pick, n = [longest], len(outputs[longest])
    for r in rest:
        if n >= CHECK_TOKENS or len(pick) >= CHECK_REQUESTS:
            break
        pick.append(int(r))
        n += len(outputs[r])
    return pick


def reference_gaps(conf: dict, seed: int, prompts: list, outputs: list,
                   control: bool = False):
    """Per request, the gaps ``best - logit[token]`` of the reference at
    each served position, for the served tokens; with ``control`` also
    the gaps of the tokens that the fp8-activation control puts first."""
    import jax.numpy as jnp

    _, ref = family(conf)
    params = ref.make_params(conf, seed)
    # the reference is causal: padding past the longest request changes
    # no logit that is read
    length = max(len(p) + len(o) for p, o in zip(prompts, outputs))
    length += -length % ref.QUERY_CHUNK
    rows = max(len(o) for o in outputs)
    rows += -rows % 64
    served, ctrl, hits = [], [], 0
    for p, out in zip(prompts, outputs):
        toks = np.zeros((length,), np.int32)
        toks[:len(p)] = p
        toks[len(p):len(p) + len(out)] = out
        first = len(p) - 1
        lg = ref.logits_at(params, conf, toks, first, rows)
        best = jnp.max(lg, axis=-1)
        pick = jnp.asarray(np.pad(out, (0, rows - len(out))))
        g = best - jnp.take_along_axis(lg, pick[:, None], axis=1)[:, 0]
        served.append(np.asarray(g)[:len(out)])
        hits += int(jnp.sum((jnp.argmax(lg, -1) == pick)[:len(out)]))
        if control:
            lc = ref.logits_at(params, conf, toks, first, rows, act_fp8=True)
            c = jnp.argmax(lc, axis=-1)
            gc_ = best - jnp.take_along_axis(lg, c[:, None], axis=1)[:, 0]
            ctrl.append(np.asarray(gc_)[:len(out)])
    del params
    return served, ctrl, hits


def check_outputs(conf: dict, seed: int, served: dict, outputs: dict,
                  control: bool = False) -> dict:
    """Judge the sampled requests' served tokens, or with ``control`` the
    tokens that the control puts first at the same positions, by their
    mean gap below the reference's best logit."""
    pick = sample_requests(served, outputs, seed)
    limit = conf["check"]["mean_logit_gap"]
    if not pick:
        return {"value": math.inf, "limit": limit, "requests": 0,
                "tokens": 0, "per_request": [], "argmax_share": 0.0,
                "readings": {}}
    prompts = [served[r].prompt for r in pick]
    outs = [outputs[r] for r in pick]
    gaps, ctrl, hits = reference_gaps(conf, seed, prompts, outs, control)
    readings = {"program_mean_gap": float(np.concatenate(gaps).mean()),
                "program_max_gap": max(float(g.max()) for g in gaps)}
    if control:
        readings.update(
            control_mean_gap=float(np.concatenate(ctrl).mean()),
            control_max_gap=max(float(c.max()) for c in ctrl))
    judged = ctrl if control else gaps
    return {"value": float(np.concatenate(judged).mean()), "limit": limit,
            "requests": len(pick), "tokens": sum(len(o) for o in outs),
            "per_request": [float(g.mean()) for g in judged],
            "argmax_share": hits / sum(len(o) for o in outs),
            "readings": readings}
