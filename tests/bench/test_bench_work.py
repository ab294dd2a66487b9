"""The work functions against hand counts at a small configuration."""
import _bench_path  # noqa: F401
import pytest

import work

CONF = {
    "hidden_size": 8, "intermediate_size": 16, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 2,
    "vocab_size": 10,
    "serving": {"weights": "mxfp8", "weight_block": 4,
                "embedding": "bfloat16", "kv_cache": "mxfp8",
                "kv_block": 2},
}


def test_params_and_bytes_by_hand():
    # q 8x8 + k,v 2 x 8x4 + o 8x8 + ffn 3 x 8x16 = 64+64+64+384
    assert work.layer_matmul_params(CONF) == 576
    assert work.matmul_params(CONF) == 1152
    # fp8 byte + one scale byte per 4; head 8 x 10 in bf16
    assert work.weight_bytes(CONF) == 1152 * 1.25 + 160
    # K and V, 2 heads x 2 dims x (1 + 1/2) bytes, 2 layers
    assert work.kv_bytes_per_token(CONF) == 2 * 2 * 3 * 2


def test_attention_work_by_hand():
    # a decode row at 5 (attends 6 keys) and a 3-token chunk from 4
    # (attends 5, 6, 7 keys): 24 keys in all
    rows = [(5, 1, 1), (4, 3, 0)]
    flops, nbytes = work.attention_work(CONF, rows)
    assert flops == 4 * 4 * 2 * 24 * 2
    kv = (5 + 4 + 1 + 3) * 24
    io = 4 * (4 * 2 * 6 + 2 * 2 * 2 * 2) * 2
    assert nbytes == kv + io


def test_step_work_by_hand():
    rows = [(5, 1, 1), (4, 3, 0)]
    flops, nbytes = work.step_work(CONF, rows)
    attn, _ = work.attention_work(CONF, rows)
    assert flops == 2 * 1152 * 4 + attn + 2 * 8 * 10 * 1
    assert nbytes == work.weight_bytes(CONF) + (5 + 4 + 4) * 24


def test_padding_counts_for_nothing():
    assert work.step_work(CONF, []) == (0.0, work.weight_bytes(CONF))


def test_least_seconds_takes_the_larger_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_seconds(500.0, 20.0, peak) == 5.0
    assert work.least_seconds(100.0, 20.0, peak) == 2.0


def test_known_and_unknown_device_kind():
    assert work.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("TPU v99")


def test_pool_fit_by_hand():
    import harness

    # 1000 bytes a page in the arguments; the step needs 5e6 bytes plus
    # 2000 a page beyond them: (free - margin - 5e6) / 3000 pages, less
    # the trash page, rounded down to the multiple
    probes = [{"pages": 320, "argument": 7e6 + 320e3,
               "extra": 5e6 + 640e3},
              {"pages": 640, "argument": 7e6 + 640e3,
               "extra": 5e6 + 1280e3}]
    free = harness.POOL_MARGIN_BYTES + 5e6 + 3000 * 1000
    assert harness.fit_pool(probes, free, 1) == 960
    with pytest.raises(RuntimeError, match="no room"):
        harness.fit_pool(probes, harness.POOL_MARGIN_BYTES, 1)
