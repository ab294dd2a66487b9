"""Each fault that a served cell can have, planted under a rehearsal of
a whole run, makes ``correct`` come out false."""
import _bench_path  # noqa: F401
import pytest
from _bench_small import rehearse


def stale_state(engine):
    """The step returns the KV pool it was given, unchanged."""
    step = engine._ragged_fn

    def stale(*args):
        return (*step(*args)[:-1], args[1])

    engine._ragged_fn = stale


def altered_token(engine):
    """Every fifth token is changed where the scheduler records it."""
    record = engine.scheduler.record_token
    count = [0]

    def altered(seq, token, eos_id=None):
        count[0] += 1
        if count[0] % 5 == 0:
            token = (int(token) + 1) % 512
        return record(seq, token, eos_id=eos_id)

    engine.scheduler.record_token = altered


@pytest.mark.parametrize("fault", [stale_state, altered_token])
def test_fault_fails_the_check(fault):
    r = rehearse("mistral-7b-l16", "decode", 2**40 + 3, 1.5, patch=fault)
    assert not r["correct"]
    gap = r["check"]["mean_logit_gap"]
    assert gap["value"] > gap["limit"]
