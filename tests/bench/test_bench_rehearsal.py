"""CPU rehearsals of whole runs at a small size through the real engine.
They print no device metric: the trace's readers find no TPU and stay
silent."""
import _bench_path  # noqa: F401
import pytest
from _bench_small import rehearse

import harness

SEED = 2**32 + 99


def test_chat_run_is_correct():
    r = rehearse("phi4-mini-3.8b", "chat", SEED, 2.0)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {
        m["name"] for m in harness.load_benchmark()["end_to_end"]}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["device"]["platform"] == "cpu"
    gap = r["check"]["mean_logit_gap"]
    assert gap["value"] <= gap["limit"]
    assert list(r)[-1] == "check"


def test_traced_decode_run_reads_counters_and_no_device_metric():
    r = rehearse("mistral-7b-l16", "decode", SEED, 1.5, trace=True)
    assert r["correct"]
    got = set(r["metrics"])
    assert {"ragged_fill", "step_ms"} <= got
    assert not got & {"step_mfu", "step_roofline", "ragged_attn_roofline",
                      "device_idle_share"}
    assert "busy_s" not in r["device"]
    assert 0 < r["metrics"]["ragged_fill"]["value"] <= 1


def test_the_harness_refuses_a_cpu():
    with pytest.raises(SystemExit, match="needs a TPU"):
        harness.run_cell("mistral-decode", SEED, 1.0, False)
