"""The work a step needs, counted from the configuration and each row's
metadata: what the algorithm requires, not what an implementation does.
Padding rows and columns, weights re-quantized per step, and logits of
rows nobody reads count for nothing, so removing them raises a share
and can never push it past 100%.

A row is ``(start, n, logits)``: ``n`` new tokens at positions
``start .. start + n - 1``, each attending to every earlier position and
itself, and ``logits`` of them needing the LM head (a decode row, or the
last chunk of a prompt).
"""
from __future__ import annotations

import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ELEMENT_BYTES = {"mxfp8": 1.0, "bfloat16": 2.0}


def peaks(device_kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def layer_matmul_params(conf: dict) -> int:
    d, f = conf["hidden_size"], conf["intermediate_size"]
    q = conf["num_attention_heads"] * conf["head_dim"]
    kv = conf["num_key_value_heads"] * conf["head_dim"]
    return d * q + 2 * d * kv + q * d + 3 * d * f


def matmul_params(conf: dict) -> int:
    """Parameters of the layers' projections (no embedding, no head)."""
    return conf["num_hidden_layers"] * layer_matmul_params(conf)


def weight_bytes(conf: dict) -> float:
    """Bytes of one pass over the weights a step reads, as served: the
    projections in their MX format with one scale byte per block, and
    the LM head in its own format (the embedding gather is negligible)."""
    srv = conf["serving"]
    mx = matmul_params(conf) * (ELEMENT_BYTES[srv["weights"]]
                                + 1.0 / srv["weight_block"])
    head = conf["hidden_size"] * conf["vocab_size"] \
        * ELEMENT_BYTES[srv["embedding"]]
    return mx + head


def kv_bytes_per_token(conf: dict) -> float:
    """Cached K and V bytes of one token over all layers."""
    srv = conf["serving"]
    per_head = conf["head_dim"] * (ELEMENT_BYTES[srv["kv_cache"]]
                                   + 1.0 / srv["kv_block"])
    return 2 * conf["num_key_value_heads"] * per_head \
        * conf["num_hidden_layers"]


def _context_sum(start: int, n: int) -> int:
    """Sum over the row's new tokens of the keys each attends to."""
    return n * start + n * (n + 1) // 2


def attention_work(conf: dict, rows) -> tuple:
    """(flops, bytes) of the ragged page walk over all layers: QK and PV
    per new token over its context; each row reads its cached context
    once, reads its new Q (bf16) and wide new K/V (bf16), writes its
    output (f32) and its new K/V in the cache format."""
    h, kvh, hd = (conf["num_attention_heads"], conf["num_key_value_heads"],
                  conf["head_dim"])
    layers = conf["num_hidden_layers"]
    ctx = sum(_context_sum(s, n) for s, n, _ in rows)
    new = sum(n for _, n, _ in rows)
    cached = sum(s for s, _, _ in rows)
    flops = 4 * h * hd * ctx * layers
    per_layer_io = new * (h * hd * (2 + 4) + 2 * kvh * hd * 2)
    nbytes = (cached + new) * kv_bytes_per_token(conf) + per_layer_io * layers
    return float(flops), float(nbytes)


def step_work(conf: dict, rows) -> tuple:
    """(flops, bytes) of one model step: projections for every new token,
    attention over each token's context, the LM head on the rows whose
    logits are read; the weights once, the cached KV context once and
    the new tokens' KV written once."""
    new = sum(n for _, n, _ in rows)
    heads = sum(k for _, _, k in rows)
    attn_flops, _ = attention_work(conf, rows)
    flops = (2 * matmul_params(conf) * new + attn_flops
             + 2 * conf["hidden_size"] * conf["vocab_size"] * heads)
    cached = sum(s for s, _, _ in rows)
    nbytes = weight_bytes(conf) + (cached + new) * kv_bytes_per_token(conf)
    return float(flops), float(nbytes)


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
