"""Token embedding + LM head (tied or untied), logit softcapping."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common as C


def init(key, vocab: int, d_model: int, tied: bool = True):
    k1, k2 = jax.random.split(key)
    params = {"embed": jax.random.normal(k1, (vocab, d_model)) * 0.01}
    axes = {"embed": (C.VOCAB, C.D_MODEL)}
    if not tied:
        params["head"] = C.truncated_normal_init(k2, (d_model, vocab), 1.0)
        axes["head"] = (C.D_MODEL, C.VOCAB)
    return params, axes


@jax.named_scope("embed")
def embed(params, tokens, scale_by_sqrt_dim: bool, compute_dtype=jnp.bfloat16):
    d = params["embed"].shape[-1]
    x = params["embed"].astype(compute_dtype)[tokens]
    if scale_by_sqrt_dim:
        x = x * jnp.asarray(d, jnp.float32).astype(compute_dtype) ** 0.5
    return x


@jax.named_scope("lm_head")
def logits(params, x, softcap=None, compute_dtype=jnp.bfloat16):
    """Project hidden states to vocab logits (tied embedding transpose)."""
    if "head" in params:
        w = params["head"].astype(compute_dtype)
    else:
        w = params["embed"].astype(compute_dtype).T
    out = jnp.einsum("...d,dv->...v", x.astype(compute_dtype), w)
    if softcap:
        out = jnp.tanh(out.astype(jnp.float32) / softcap) * softcap
        return out  # f32 for the loss
    return out.astype(jnp.float32)
