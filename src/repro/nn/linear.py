"""MX-aware linear layers — the framework integration of the paper's技ique.

Every dense projection in the model zoo goes through ``mx_linear``:

  * quant disabled      -> plain bf16 matmul (the FP32/BF16 baselines of §III)
  * weight-only         -> wide activations x MX weights (vector-scalar
                           variant; serving-style weight compression)
  * weight+activation   -> both operands block-quantized per step via the
                           custom-vjp ``qat_matmul`` (vector-vector variant)

Execution mode (emulated | fused | pallas) comes from ``QuantConfig.mode``.
Master weights stay wide; quantization happens at use, so the same params
train with or without MX.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import QuantConfig, fake_quant, mx_dot, qat_matmul, quantize
from repro.core.mx_tensor import MXTensor

from . import common as C


def init(key, d_in: int, d_out: int, axes=(C.D_MODEL, C.D_FF), scale=1.0):
    return C.dense_init(key, d_in, d_out, axes, scale)


def apply(params, x, quant: QuantConfig, compute_dtype=jnp.bfloat16,
          tp_on: str = "out"):
    """Apply ``x @ w`` under the quantization policy.

    ``tp_on`` marks which w dim is tensor-parallel (see qat_matmul): "in"
    for output projections (wo/down/out_proj), "out" otherwise.
    """
    w = params["w"]
    if isinstance(w, MXTensor):  # pre-quantized weights (serving path)
        y = mx_dot(
            x.astype(compute_dtype) if not quant.enabled else _maybe_q_act(x, quant),
            w,
            mode=quant.mode if quant.mode != "pallas" or _pallas_ok() else "fused",
            acc_dtype=quant.acc_dtype,
        )
        return y.astype(compute_dtype)
    if not quant.enabled:
        xw = x.astype(compute_dtype)
        return _dot_rounded(xw, w.astype(compute_dtype), compute_dtype)
    if quant.quantize_acts:
        # activations enter in compute dtype (bf16): the QAT path is
        # dtype-preserving end to end (§Perf iteration 2)
        y = qat_matmul(
            x.astype(compute_dtype),
            w.astype(jnp.float32),
            quant.fmt,
            quant.block_size,
            True,
            quant.mode if quant.mode != "pallas" else "fused",
            quant.acc_dtype,
            tp_on if quant.mx_weight_gather else "off",
        )
    else:
        # weight-only: straight-through fake-quantized weights, wide acts
        with jax.named_scope("weight_quant"):
            wq = fake_quant(w.astype(jnp.float32), quant.fmt,
                            quant.block_size, 0).astype(compute_dtype)
        y = _dot_rounded(x.astype(compute_dtype), wq, compute_dtype)
    return y.astype(compute_dtype)


def _dot_rounded(x, w, compute_dtype):
    """``x @ w`` with the output narrowing made explicit.

    A bf16-output dot accumulates in f32 and rounds at the output — but
    when the dot's consumer is an elementwise op inside one fused
    computation (the layer-fused megakernel body), XLA's excess-precision
    rules may hand the consumer the f32 accumulator instead. Accumulating
    in f32 and narrowing through ``reduce_precision`` pins the rounding
    point into the program so the per-layer step and the megakernel see
    bit-identical values. Numerically this is exactly what the plain
    bf16-output dot does when the rounding is *not* elided.
    """
    x = C.round_to(x, x.dtype)  # snap operand: its producer chain may
    w = C.round_to(w, w.dtype)  # carry excess precision into the f32 dot
    y = jnp.matmul(x, w, preferred_element_type=jnp.float32)
    return C.round_to(y, compute_dtype)


def _maybe_q_act(x, quant: QuantConfig):
    if quant.enabled and quant.quantize_acts:
        return quantize(
            x.astype(jnp.float32), quant.activation_format, quant.block_size
        )
    return x.astype(jnp.bfloat16)


def _pallas_ok() -> bool:
    import jax

    return jax.default_backend() == "tpu"


def quantize_weights(params, quant: QuantConfig):
    """Convert wide weight leaves to MXTensors (serving weight compression)."""
    if not quant.enabled:
        return params
    return {"w": quantize(params["w"].astype(jnp.float32), quant.fmt,
                          quant.block_size, axis=0)}
