"""The trace reduction and the readers that use it, on a trace built by
hand with known times, and on traces recorded on the chip."""
import gzip
import json
from pathlib import Path

import _bench_path  # noqa: F401
import pytest

import harness
import trace as bench_trace
import work

assert Path(bench_trace.__file__).parent == harness.BENCH

DATA = Path(__file__).resolve().parent / "data"
US = 1_000_000  # picoseconds in a microsecond


def _events(spec, names):
    out = []
    for name, start_us, dur_us in spec:
        out.append(f"events {{ metadata_id: {names[name]} "
                   f"offset_ps: {start_us * US} duration_ps: {dur_us * US} }}")
    return " ".join(out)


def _plane(pid, name, lines):
    names = {}
    for _, evs in lines:
        for ev in evs:
            names.setdefault(ev[0], len(names) + 1)
    body = " ".join(f'lines {{ id: {i + 1} name: "{ln}" timestamp_ns: 0 '
                    f'{_events(evs, names)} }}'
                    for i, (ln, evs) in enumerate(lines))
    meta = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{n}" }} }}' for n, i in names.items())
    return f'planes {{ id: {pid} name: "{name}" {body} {meta} }}'


def synthetic():
    """Window 100-1100 us. Chip 0 runs ops 50-150 (clipped to 100-150),
    140-400 and 600-700: busy 400 us. Chip 1 runs 200-450: busy 250 us.
    Merged, the device idles 450-600 (inside a bench.step span) and
    700-1100 (outside any step)."""
    from jax.profiler import ProfileData

    host = _plane(1, "/host:CPU", [("python", [
        ("bench.window", 100, 1000), ("bench.step", 120, 580)])])
    tpu0 = _plane(2, "/device:TPU:0", [
        ("XLA Modules", [("jit__ragged_step_fn(1)", 50, 650)]),
        ("XLA Ops", [("fusion.1", 50, 100), ("_mx_attn_ragged_kernel", 140, 260),
                     ("fusion.2", 600, 100)])])
    tpu1 = _plane(3, "/device:TPU:1", [
        ("XLA Ops", [("_mx_attn_ragged_kernel", 200, 250)])])
    text = " ".join([host, tpu0, tpu1])
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))


def test_reduction_by_hand():
    red = bench_trace.reduce(synthetic())
    assert red.window == pytest.approx((100e-6, 1100e-6))
    assert red.chips == 2
    assert red.busy_s == pytest.approx((400e-6 + 250e-6) / 2)
    assert red.op_seconds(r"_mx_attn_ragged") == pytest.approx((260e-6 + 250e-6) / 2)
    assert [e.seconds for e in red.module_events("ragged_step")] == \
        pytest.approx([600e-6])
    top = dict(bench_trace.top_ops(red, lambda e: e.name))
    assert top["_mx_attn_ragged_kernel"] == pytest.approx(255e-6)
    gaps = dict(bench_trace.idle_gaps(red))
    assert gaps == pytest.approx({"bench.step": 150e-6,
                                  "outside bench spans": 400e-6})


def test_reduction_keeps_each_chips_events():
    red = bench_trace.reduce(synthetic())
    assert [len(ops) for ops, _ in red.per_chip] == [3, 1]
    assert [[m.name for m in mods] for _, mods in red.per_chip] == [
        ["jit__ragged_step_fn(1)"], []]
    assert sum(len(ops) for ops, _ in red.per_chip) == len(red.ops)


def test_trace_readers_by_hand():
    from metrics import device_idle_share, ragged_attn_roofline, step_mfu

    conf = harness.load_config("phi4-mini-3.8b")
    peak = work.peaks("TPU v5 lite")
    step = harness.Step(0.0, 1.0, True, [(100, 1, 1), (0, 64, 0)], 64, 32)
    ctx = harness.Context(conf, peak, 2, {}, [step], [step],
                          bench_trace.reduce(synthetic()))
    assert device_idle_share.read(ctx) == pytest.approx(
        100 * (1 - 325e-6 / 1000e-6))
    flops, _ = work.step_work(conf, step.rows)
    assert step_mfu.read(ctx) == pytest.approx(
        100 * flops / (300e-6 * 2 * peak["bf16_flops_per_s"]))
    least = work.least_seconds(*work.attention_work(conf, step.rows), peak)
    assert ragged_attn_roofline.read(ctx) == pytest.approx(
        100 * least / (255e-6 * 2))
    silent = harness.Context(conf, peak, 1, {}, [step], [step], None)
    assert step_mfu.read(silent) is None
    assert device_idle_share.read(silent) is None


def test_no_device_plane_is_an_error():
    from jax.profiler import ProfileData

    text = _plane(1, "/host:CPU", [("python", [("bench.window", 0, 10)])])
    data = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))
    with pytest.raises(ValueError, match="no TPU device plane"):
        bench_trace.reduce(data)


def test_chip_trace(tmp_path):
    """Eight mistral-decode steps traced on one TPU v5e ("TPU v5 lite")
    by ``bench/run.py --trace 1``, with the rows the harness recorded."""
    from metrics import (device_idle_share, ragged_attn_roofline, step_mfu,
                         step_roofline)
    from metrics._step_trace import STEP_MODULE

    path = tmp_path / "mistral-decode.xplane.pb"
    path.write_bytes(gzip.decompress(
        (DATA / "mistral-decode.xplane.pb.gz").read_bytes()))
    recorded = json.loads((DATA / "mistral-decode.steps.json").read_text())
    steps = {k: [harness.Step(**dict(s, rows=[tuple(r) for r in s["rows"]]))
                 for s in recorded[k]] for k in ("window", "traced")}
    red = bench_trace.reduce(path)
    assert red.chips == 1
    assert 0 < red.busy_s <= red.window_s
    modules = red.module_events(STEP_MODULE)
    assert len(modules) == len(steps["traced"]) == 8
    kernel = red.op_seconds(ragged_attn_roofline.KERNEL)
    assert 0 < kernel < sum(e.seconds for e in modules) <= red.window_s
    top = bench_trace.top_ops(red, harness.op_label)
    assert top[0] == ["%closed_call tpu_custom_call", pytest.approx(kernel)]
    assert [k for k, _ in bench_trace.idle_gaps(red)] == [
        "bench.step", "outside bench spans"]
    ctx = harness.Context(harness.load_config("mistral-7b-l16"),
                          work.peaks("TPU v5 lite"), 1, {}, steps["window"],
                          steps["traced"], red)
    for reader in (step_mfu, step_roofline, ragged_attn_roofline,
                   device_idle_share):
        assert 0 < reader.read(ctx) < 100
    assert ragged_attn_roofline.read(ctx) < step_roofline.read(ctx)
