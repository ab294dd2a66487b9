"""``collective_exposed_share`` on reductions built by hand: each chip's
collectives inside the step programs, less what other ops on that chip
overlap, over the step programs' time, averaged over the chips."""
import _bench_path  # noqa: F401
import pytest

import trace as bench_trace
from metrics import collective_exposed_share

STEP = "jit__sharded_step(7)"


def ev(name, start, end):
    return bench_trace.Event(name, start, end, {})


def chip(*ops, step=(0.0, 100.0)):
    """One chip's (ops, modules): a step program over ``step`` holding a
    layer loop and ``ops``."""
    loop = ev("%while.3 = (s32[]) while(%t), body=%body", *step)
    return [loop, *ops], [ev(STEP, *step)]


def gather(start, end):
    return ev("%all-gather-done.1 = bf16[8,8,256,3,128] "
              "all-gather-done(%all-gather-start.1)", start, end)


def fusion(start, end):
    return ev("%fusion.12 = bf16[2048,3072] fusion(%p), kind=kOutput",
              start, end)


def reduction(*chips):
    ops = [e for o, _ in chips for e in o]
    mods = [m for _, m in chips for m in m]
    return bench_trace.Reduction((0.0, 100.0), len(chips), ops, mods, [],
                                 1.0, list(chips))


def test_no_collective_reads_nothing():
    red = reduction(chip(fusion(10, 40)), chip(fusion(10, 40)))
    assert collective_exposed_share.share(red) is None
    # a reduction without per-chip lists (a one-chip trace read before
    # they existed) has nothing to read either
    assert collective_exposed_share.share(
        bench_trace.Reduction((0, 1), 1, [], [], [], 0.5)) is None


def test_fully_overlapped_collectives_are_not_exposed():
    # 40-60 covered by two ops that each overlap part of it; the loop
    # that holds the gather overlaps nothing
    red = reduction(chip(gather(40, 60), fusion(35, 50), fusion(50, 65)),
                    chip(gather(40, 60), fusion(30, 45), fusion(45, 70)))
    assert collective_exposed_share.share(red) == 0.0


def test_half_exposed_on_one_of_two_chips():
    # chip 0: a 20-unit gather, 40-50 beside a fusion, 50-60 alone: 10 of
    # its 100-unit step; chip 1: fully overlapped. Mean 5%.
    red = reduction(chip(gather(40, 60), fusion(20, 50)),
                    chip(gather(40, 60), fusion(30, 50), fusion(50, 70)))
    assert collective_exposed_share.share(red) == pytest.approx(5.0)


def test_an_op_spanning_the_whole_collective_holds_it():
    # as the layer loop does: no overlap, all 20 units exposed
    red = reduction(chip(gather(40, 60), fusion(35, 65)))
    assert collective_exposed_share.share(red) == pytest.approx(20.0)


def test_ops_outside_the_step_programs_count_for_nothing():
    ops, mods = chip(gather(40, 60), step=(0.0, 80.0))
    ops.append(ev("%all_gather.2 = f32[4] all-gather(%x)", 85, 95))
    red = reduction((ops, mods))
    assert collective_exposed_share.share(red) == pytest.approx(25.0)


@pytest.mark.parametrize("name, want", [
    ("%all_gather.10 = f32[4,8]{1,0} all-gather(%copy.1), channel_id=1",
     True),
    ("%all-gather-start.2 = (bf16[2], bf16[8]) all-gather-start(%x)", True),
    ("%all-reduce.5 = f32[] all-reduce(%y), to_apply=%add", True),
    ("%collective-permute-done = f32[8] collective-permute-done(%s)", True),
    ("%fusion.3 = bf16[2] fusion(%all_gather.1), kind=kLoop", False),
    ("%copy.4 = f32[2] copy(%x)", False),
])
def test_collectives_by_name(name, want):
    assert bench_trace.is_collective(ev(name, 0, 1)) is want


def test_scopes_and_top_ops_count_each_chip_on_its_own():
    """Two chips run the same step 5 units apart. Each chip's loop holds
    its own fusion; taken together, one chip's ops would fall inside the
    other's loop and be counted once."""
    import program_trace

    def shifted(d):
        wq = ev("%fusion.1 = bf16[8] fusion(%p), kind=kLoop", 10 + d, 40 + d)
        return chip(wq, step=(0.0 + d, 100.0 + d))

    red = reduction(shifted(0), shifted(5))
    scopes = {"while.3": "layer_loop", "fusion.1": "weight_quant"}
    tally = program_trace.scope_seconds(red, scopes, "sharded_step")
    assert dict(tally) == pytest.approx({"layer_loop": 70.0,
                                         "weight_quant": 30.0})
    top = dict(bench_trace.top_ops(red, lambda e: e.name.split(" = ")[0]))
    assert top == pytest.approx({"%while.3": 70.0, "%fusion.1": 30.0})
