"""Plain float32 reference of a dense GQA decoder, as its configuration
file states it: RMSNorm, RoPE on every head dim (rotate-half pairs),
grouped-query causal attention, a SwiGLU FFN, tied or untied LM head.

It imports nothing of the program. Weights come from ``weights.make``
with the seed, in bf16 as served; the projections are quantized as the
configuration states (MXFP8 E4M3, one E8M0 scale per 32 inputs, OCP MX
rounding: nearest even, saturating), and so are the cached keys (after
RoPE) and values, per head along the head dim. Everything else runs in
float32 at HIGHEST matmul precision.

``act_fp8=True`` is the control: it also rounds the input of every
projection to MXFP8 E4M3 (blocks of 32 along the contraction), the next
precision below bf16 activations.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import weights

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_EMAX, E4M3_MAX, E4M3_MANT, E4M3_EMIN = 8, 448.0, 3, -6
QUERY_CHUNK = 512


def mx_fp8(x, axis: int, block: int = 32):
    """Quantize-dequantize ``x`` to MXFP8 E4M3 in blocks along ``axis``."""
    x = jnp.moveaxis(x.astype(jnp.float32), axis, -1)
    shape = x.shape
    xb = x.reshape(*shape[:-1], shape[-1] // block, block)
    amax = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
    _, e = jnp.frexp(jnp.where(amax > 0, amax, 1.0))
    shared = jnp.clip(e - 1 - E4M3_EMAX, -127, 127)
    scale = jnp.ldexp(jnp.ones_like(amax), shared)
    y = jnp.clip(xb / scale, -E4M3_MAX, E4M3_MAX)
    _, ey = jnp.frexp(jnp.where(y != 0, jnp.abs(y), 1.0))
    quantum = jnp.ldexp(jnp.ones_like(y),
                        jnp.maximum(ey - 1, E4M3_EMIN) - E4M3_MANT)
    y = jnp.round(y / quantum) * quantum
    return jnp.moveaxis((y * scale).reshape(shape), -1, axis)


def make_params(conf: dict, seed: int) -> dict:
    return weights.make(weights.dense_gqa_specs(conf), seed)


def _rms(x, g, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + g.astype(jnp.float32))


def _rope(x, pos, theta):
    d = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(d // 2, dtype=jnp.float32) * 2 / d)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, g):
    """Causal GQA attention; q (T, H, D), k/v (T, KVH, D), query-chunked."""
    t, h, d = q.shape
    kvh = k.shape[1]
    qg = q.reshape(t // QUERY_CHUNK, QUERY_CHUNK, kvh, g, d)
    kpos = jnp.arange(t)

    def chunk(args):
        qc, c = args
        s = jnp.einsum("skgd,tkd->kgst", qc, k, precision=HIGHEST) * d ** -0.5
        qpos = c * QUERY_CHUNK + jnp.arange(QUERY_CHUNK)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgst,tkd->skgd", p, v, precision=HIGHEST)

    out = jax.lax.map(chunk, (qg, jnp.arange(t // QUERY_CHUNK)))
    return out.reshape(t, h * d)


@functools.partial(jax.jit, static_argnames=("conf_items", "rows",
                                             "act_fp8"))
def _logits(params, tokens, first, conf_items, rows, act_fp8):
    conf = dict(conf_items)
    h, kvh, hd = (conf["num_attention_heads"], conf["num_key_value_heads"],
                  conf["head_dim"])
    wb, kb, eps = conf["weight_block"], conf["kv_block"], conf["rms_norm_eps"]
    t = tokens.shape[0]
    pos = jnp.arange(t)

    def proj(x, w):
        if act_fp8:
            x = mx_fp8(x, -1, wb)
        return jnp.dot(x, mx_fp8(w, 0, wb), precision=HIGHEST)

    def layer(x, lp):
        a = _rms(x, lp["attn_norm"], eps)
        q = _rope(proj(a, lp["wq"]).reshape(t, h, hd), pos, conf["rope_theta"])
        k = _rope(proj(a, lp["wk"]).reshape(t, kvh, hd), pos,
                  conf["rope_theta"])
        v = proj(a, lp["wv"]).reshape(t, kvh, hd)
        k, v = mx_fp8(k, -1, kb), mx_fp8(v, -1, kb)
        x = x + proj(_attend(q, k, v, h // kvh), lp["wo"])
        a = _rms(x, lp["ffn_norm"], eps)
        f = jax.nn.silu(proj(a, lp["w_gate"])) * proj(a, lp["w_up"])
        return x + proj(f, lp["w_down"]), None

    names = ("attn_norm", "ffn_norm", "wq", "wk", "wv", "wo", "w_gate",
             "w_up", "w_down")
    x = params["embed"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(layer, x, {n: params[n] for n in names})
    x = jax.lax.dynamic_slice_in_dim(x, first, rows)
    x = _rms(x, params["final_norm"], eps)
    head = params["head"] if "head" in params else params["embed"].T
    return jnp.dot(x, head.astype(jnp.float32), precision=HIGHEST)


def logits_at(params, conf: dict, tokens, first: int, rows: int,
              act_fp8: bool = False):
    """Logits (rows, V) at positions ``first .. first + rows - 1`` of the
    causal forward over ``tokens`` (whose length is a multiple of 512)."""
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta")
    items = tuple((k, conf[k]) for k in keys) + (
        ("weight_block", conf["serving"]["weight_block"]),
        ("kv_block", conf["serving"]["kv_block"]))
    return _logits(params, jnp.asarray(tokens, jnp.int32),
                   jnp.int32(first), items, rows, act_fp8)
