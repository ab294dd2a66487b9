"""The control of the check: the reference computed one precision below
the configuration's (fp8 activations for bf16) fails the limit that the
program's served tokens pass, on the same prompts and tokens. Served to
completion, untimed, so the readings repeat exactly."""
import _bench_path  # noqa: F401
import pytest
from _bench_small import rehearse, serve_to_completion

import harness


@pytest.mark.parametrize("seed", [2, 3, 4])
def test_control_fails_where_the_program_passes(seed):
    conf, served, outputs = serve_to_completion("mistral-7b-l16", "decode",
                                                  seed)
    c = harness.check_outputs(conf, seed, served, outputs, control=True)
    assert c["tokens"] >= 80
    assert c["readings"]["program_mean_gap"] <= c["limit"] < c["value"]


def test_control_in_the_programs_place_fails_the_run():
    r = rehearse("mistral-7b-l16", "decode", 2**35 + 5, 1.5, control=True)
    assert r["correct"] is False
    gap = r["check"]["mean_logit_gap"]
    assert r["readings"]["program_mean_gap"] <= gap["limit"] < gap["value"]
