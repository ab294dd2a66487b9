"""The reductions of the program's own spans and named scopes, and the
readers that use them (``host_ms_per_step``, ``weight_quant_share``), on
a trace and an HLO text built by hand with known times, on traces
recorded on the chip, and in a CPU rehearsal through the real engine."""
import gzip
import json
from pathlib import Path

import _bench_path  # noqa: F401
import pytest
from _bench_small import rehearse
from test_bench_trace import _plane

import program_trace
import trace as bench_trace
from metrics import _engine, host_ms_per_step, weight_quant_share

DATA = Path(__file__).resolve().parent / "data"
SEED = 2**32 + 101


def synthetic():
    """Window 100-1100 us, two engine steps of 500 us, one chip. The
    device runs the step program 180-540 and 730-1050 and idles 100-180
    (begun in ``serve.assemble``), 540-730 and 1050-1100 (begun in
    ``serve.sync``)."""
    from jax.profiler import ProfileData

    host = _plane(1, "/host:CPU", [("python", [
        ("bench.window", 100, 1000), ("bench.step", 100, 500),
        ("bench.step", 600, 500),
        ("serve.step", 100, 500), ("serve.assemble", 100, 50),
        ("serve.dispatch", 150, 50), ("serve.sync", 200, 360),
        ("serve.record", 560, 40),
        ("serve.step", 600, 500), ("serve.admit", 600, 20),
        ("serve.assemble", 620, 80), ("serve.dispatch", 700, 20),
        ("serve.sync", 720, 360), ("serve.record", 1080, 20)])])
    tpu = _plane(2, "/device:TPU:0", [
        ("XLA Modules", [("jit__ragged_step_fn(1)", 180, 360),
                         ("jit__ragged_step_fn(1)", 730, 320)]),
        ("XLA Ops", [("%fusion.1 = bf16[8]{0} fusion(%p)", 180, 120),
                     ("%mx_attn_ragged.2 = f32[8]{0} custom-call(%fusion.1)",
                      300, 200),
                     ("%copy.3 = f32[8]{0} copy(%p)", 500, 40),
                     ("%fusion.1 = bf16[8]{0} fusion(%p)", 730, 120),
                     ("%mx_attn_ragged.2 = f32[8]{0} custom-call(%fusion.1)",
                      850, 150),
                     ("%fusion.4 = bf16[8]{0} fusion(%p)", 1000, 50)])])
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(" ".join([host, tpu])))


SCOPES = {"fusion.1": "weight_quant", "mx_attn_ragged.2": "ragged_attn",
          "fusion.4": "ffn"}

HLO = '''HloModule jit__ragged_step_fn, is_scheduled=true

%fused_computation (param_0: f32[8]) -> bf16[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %convert.1 = bf16[8]{0} convert(%param_0), metadata={op_name="jit(_ragged_step_fn)/layer_loop/while/body/closed_call/ffn/weight_quant/convert_element_type" stack_frame_id=3}
}

%body (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg = (s32[], f32[8]{0}) parameter(0)
  %gte = f32[8]{0} get-tuple-element(%arg), index=1
  %fusion.1 = bf16[8]{0} fusion(%gte), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(_ragged_step_fn)/layer_loop/while/body/closed_call/ffn/weight_quant/convert_element_type" stack_frame_id=3}
  %mx_attn_ragged.2 = f32[8]{0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={
"kernel":"mx_attn_ragged"
}}, metadata={op_name="jit(_ragged_step_fn)/layer_loop/while/body/closed_call/ragged_attn/mx_attn_ragged/pallas_call" stack_frame_id=5}
  %copy.3 = f32[8]{0} copy(%mx_attn_ragged.2)
  %copy-start.5 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%gte)
  ROOT %add.4 = f32[8]{0} add(%copy.3, %gte), metadata={op_name="jit(_ragged_step_fn)/layer_loop/while/body/add"}
}

ENTRY %main.9 (p: f32[8]) -> s32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %iota.9 = s32[8]{0} iota(), iota_dimension=0, metadata={op_name="jit(_ragged_step_fn)/sample/iota"}
}
'''


def test_scope_of_takes_the_innermost_named_scope():
    assert program_trace.scope_of(
        "jit(f)/layer_loop/while/body/closed_call/qkv_proj/weight_quant/"
        "jit(frexp)/and") == "weight_quant"
    assert program_trace.scope_of(
        "jit(f)/layer_loop/while/body/closed_call/ragged_attn/"
        "mx_attn_ragged/pallas_call") == "ragged_attn"
    assert program_trace.scope_of("jit(f)/while/body/add") is None


def test_scope_map_of_hand_written_hlo():
    """Each instruction's op_name metadata names its scope, read past a
    multi-line attribute; one without metadata takes its first operand's
    scope; parameters and unscoped ops stay out."""
    assert program_trace.scope_map(HLO) == {
        "convert.1": "weight_quant", "fusion.1": "weight_quant",
        "mx_attn_ragged.2": "ragged_attn", "copy.3": "ragged_attn",
        "add.4": "layer_loop", "iota.9": "sample"}


def test_idle_by_span_names_program_phases_on_a_synthetic_trace():
    data = synthetic()
    red = bench_trace.reduce(data)
    assert bench_trace.idle_gaps(red) == [["bench.step",
                                           pytest.approx(320e-6)]]
    got = dict(program_trace.idle_by_span(data, red))
    assert got == {"serve.sync": pytest.approx(240e-6),
                   "serve.assemble": pytest.approx(80e-6)}
    split = dict(program_trace.idle_split_by_span(data, red))
    assert split == pytest.approx({
        "serve.assemble": 130e-6, "serve.dispatch": 50e-6,
        "serve.sync": 60e-6, "serve.record": 60e-6, "serve.admit": 20e-6})
    assert sum(split.values()) == pytest.approx(
        red.window_s - red.busy_s)


def test_scope_seconds_and_weight_quant_share_on_a_synthetic_trace():
    red = bench_trace.reduce(synthetic())
    tally = program_trace.scope_seconds(red, SCOPES, r"ragged_step")
    assert dict(tally) == pytest.approx({
        "weight_quant": 240e-6, "ragged_attn": 350e-6, "ffn": 50e-6,
        program_trace.UNSCOPED: 40e-6})
    assert sum(tally.values()) == pytest.approx(red.busy_s)
    assert weight_quant_share.share(red, SCOPES) == pytest.approx(
        100 * 240 / 680)
    # a program without the scope reads nothing
    assert weight_quant_share.share(
        red, {"mx_attn_ragged.2": "ragged_attn"}) is None


def test_exclusive_seconds_counts_each_instant_once():
    """A loop 0-100 us holds ops 10-30 and 40-60 and an op 90-105 that
    ends past it; each instant counts to the shortest op covering it."""
    ev = [bench_trace.Event(n, a * 1e-6, b * 1e-6, {}) for n, a, b in [
        ("while", 0, 100), ("a", 10, 30), ("b", 40, 60), ("c", 90, 105)]]
    own = {e.name: s for e, s in program_trace.exclusive_seconds(ev)}
    assert own == pytest.approx({"while": 50e-6, "a": 20e-6, "b": 20e-6,
                                 "c": 15e-6})
    loop = dict((e.name, s) for e, s in bench_trace.self_seconds(ev))
    assert loop["while"] == pytest.approx(60e-6)


class _Rec:
    def __init__(self, t0, t1, sync, dispatched=True):
        self.t0, self.t1, self.dispatched = t0, t1, dispatched
        self.host_s = (t1 - t0) - sync


class _Step:
    def __init__(self, t0, t1):
        self.t0, self.t1 = t0, t1


def test_host_ms_per_step_reads_untraced_dispatching_steps():
    """Records match the harness's steps by time; traced steps, steps
    that dispatched nothing and records outside the window are left
    out."""
    steps = [_Step(10.0 * i, 10.0 * i + 9) for i in range(5)]
    traced = steps[3:]
    recs = [_Rec(0.5, 8.5, 6.0), _Rec(10.5, 18.5, 7.0),
            _Rec(20.5, 21.0, 0.0, dispatched=False),
            _Rec(30.5, 38.5, 0.5), _Rec(40.5, 48.5, 0.5),
            _Rec(50.5, 58.5, 1.0)]
    window = _engine.window_records(recs, steps, traced)
    assert [r for r, _ in window] == recs[:5]
    assert [t for _, t in window] == [False, False, False, True, True]
    assert host_ms_per_step.host_ms(window) == pytest.approx(1e3 * 1.5)
    assert host_ms_per_step.host_ms(window[2:]) is None


def test_a_chip_trace_without_program_spans_splits_idle_as_before():
    """Without program spans the split by span is the split by bench
    span that ``trace.idle_gaps`` gives."""
    import shutil
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        path = Path(d) / "t.xplane.pb"
        with gzip.open(DATA / "mistral-decode.xplane.pb.gz", "rb") as f, \
                open(path, "wb") as g:
            shutil.copyfileobj(f, g)
        data = bench_trace.load(path)
    red = bench_trace.reduce(data)
    assert program_trace.idle_by_span(data, red) == \
        bench_trace.idle_gaps(red)
    assert program_trace.host_spans(data) == red.host


def test_a_program_without_a_step_log_reads_nothing():
    """Where no live engine's step log holds the window's steps (as in a
    program that keeps none), both readers report nothing."""
    import harness

    ctx = harness.Context({}, None, 1, {}, [_Step(-2.0, -1.0)], [], None)
    assert _engine.engine_records(ctx) is None
    assert host_ms_per_step.read(ctx) is None
    assert weight_quant_share.read(ctx) is None


def test_traced_rehearsal_reads_the_engine_step_log():
    """A traced CPU rehearsal through the real engine reports the host
    time per step, under the step's wall time; the trace holds no TPU,
    so the share of the weight fake-quant stays out."""
    r = rehearse("mistral-7b-l16", "decode", SEED, 1.5, trace=True)
    got = r["metrics"]
    assert "weight_quant_share" not in got
    host = got["host_ms_per_step"]["value"]
    assert 0 < host < got["step_ms"]["value"]


def _chip_trace(tmp_path):
    """Eight mistral-decode steps traced on one TPU v5e ("TPU v5 lite")
    by ``bench/run.py --trace 1`` with the program's spans, the rows the
    harness recorded, the step program's scope map and the engine's step
    log of the window."""
    import harness
    from repro.serve.steplog import StepRecord

    path = tmp_path / "mistral-decode.spans.xplane.pb"
    path.write_bytes(gzip.decompress(
        (DATA / "mistral-decode.spans.xplane.pb.gz").read_bytes()))
    recorded = json.loads((DATA / "mistral-decode.spans.steps.json")
                          .read_text())
    steps = {k: [harness.Step(**dict(s, rows=[tuple(r) for r in s["rows"]]))
                 for s in recorded[k]] for k in ("window", "traced")}
    scopes = json.loads(gzip.decompress(
        (DATA / "mistral-decode.spans.scopes.json.gz").read_bytes()))
    log = [StepRecord(r["tick"], r["t0"], r["t1"],
                      tuple(map(tuple, r["phases"])), r["dispatched"],
                      r["compiles"])
           for r in json.loads(gzip.decompress(
               (DATA / "mistral-decode.spans.steplog.json.gz").read_bytes()))]
    data = bench_trace.load(path)
    return data, bench_trace.reduce(data), steps, scopes, log


def test_chip_trace_with_program_spans(tmp_path):
    """The readers on the chip trace give what they gave on the chip, the
    in-step idle all begins in ``serve.sync`` (the device has finished
    the step program), and split by overlap it lies mostly in
    ``serve.assemble``; ops without a named scope take under 0.01% of the
    step programs' device time."""
    import harness
    import work
    from metrics import ragged_attn_roofline

    data, red, steps, scopes, log = _chip_trace(tmp_path)
    assert len(steps["traced"]) == 8 and len(log) == len(steps["window"])
    assert bench_trace.idle_gaps(red) == [
        ["bench.step", pytest.approx(0.049376563)],
        ["outside bench spans", pytest.approx(0.003363833)]]
    by_start = dict(program_trace.idle_by_span(data, red))
    assert by_start["serve.sync"] == pytest.approx(0.049376496)
    split = dict(program_trace.idle_split_by_span(data, red))
    assert split["serve.assemble"] == pytest.approx(0.026770468)
    assert split["serve.sync"] == pytest.approx(0.021075258)
    assert sum(split.values()) == pytest.approx(red.window_s - red.busy_s)
    tally = program_trace.scope_seconds(red, scopes, r"ragged_step")
    modules = sum(e.seconds for e in red.module_events(r"ragged_step"))
    assert sum(tally.values()) == pytest.approx(modules, rel=1e-4)
    assert tally.most_common(4) == [
        ("ragged_attn", pytest.approx(5.460259849)),
        ("ffn", pytest.approx(0.766976819)),
        ("weight_quant", pytest.approx(0.511313167)),
        ("layer_loop", pytest.approx(0.391470080))]
    # trace.self_seconds counts the ops that end just past their loop in
    # the loop as well
    self_total = sum(s for _, s in bench_trace.self_seconds(red.ops))
    assert self_total - modules == pytest.approx(0.2164737, rel=1e-3)
    assert tally[program_trace.UNSCOPED] < 1e-4 * modules
    assert weight_quant_share.share(red, scopes) == pytest.approx(
        6.7782342918)
    window = _engine.window_records(log, steps["window"], steps["traced"])
    assert sum(traced for _, traced in window) == 8
    assert host_ms_per_step.host_ms(window) == pytest.approx(4.0447485778)
    # the named kernel: its events name it, and the roofline reads them
    top = bench_trace.top_ops(red, harness.op_label)
    kernel = red.op_seconds(ragged_attn_roofline.KERNEL)
    assert top[0] == ["%mx_attn_ragged tpu_custom_call",
                      pytest.approx(kernel)]
    ctx = harness.Context(harness.load_config("mistral-7b-l16"),
                          work.peaks("TPU v5 lite"), 1, {}, steps["window"],
                          steps["traced"], red)
    assert ragged_attn_roofline.read(ctx) == pytest.approx(0.0667483989)
