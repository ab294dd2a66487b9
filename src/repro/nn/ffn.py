"""Gated feed-forward blocks (SwiGLU / GeGLU / GELU) over MX linears."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import QuantConfig

from . import common as C
from . import linear


def init(key, d_model: int, d_ff: int, kind: str = "swiglu"):
    ks = C.split_keys(key, 3)
    gate, ga = linear.init(ks[0], d_model, d_ff, (C.D_MODEL, C.D_FF))
    up, ua = linear.init(ks[1], d_model, d_ff, (C.D_MODEL, C.D_FF))
    down, da = linear.init(ks[2], d_ff, d_model, (C.D_FF, C.D_MODEL))
    params = {"gate": gate, "up": up, "down": down}
    axes = {"gate": ga, "up": ua, "down": da}
    if kind == "gelu":  # no gate branch
        params.pop("gate")
        axes.pop("gate")
    return params, axes


@jax.named_scope("ffn")
def apply(params, x, quant: QuantConfig, kind: str = "swiglu",
          compute_dtype=jnp.bfloat16):
    up = linear.apply(params["up"], x, quant, compute_dtype)
    # activation narrowings go through C.round_to, not bare astype: these
    # casts sit between elementwise ops, where XLA's excess-precision
    # fusion may skip the rounding — which would make the layer-fused
    # megakernel (one fused kernel jaxpr) round differently from the
    # per-layer step and break their bit-identity
    if kind == "gelu":
        h = C.round_to(jax.nn.gelu(up.astype(jnp.float32)), compute_dtype)
    else:
        gate = linear.apply(params["gate"], x, quant, compute_dtype)
        g32 = gate.astype(jnp.float32)
        act = jax.nn.silu(g32) if kind == "swiglu" else jax.nn.gelu(g32, approximate=True)
        # product of two compute-dtype values is exact in f32, so one
        # explicit rounding == true narrow-multiply semantics
        h = C.round_to(
            C.round_to(act, compute_dtype).astype(jnp.float32)
            * up.astype(jnp.float32),
            compute_dtype,
        )
    return linear.apply(params["down"], h, quant, compute_dtype, tp_on="in")
