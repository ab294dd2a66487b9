"""MX dot products: the software execution modes of the VMXDOTP study.

Three execution modes mirror the paper's three hardware tiers:

  * ``emulated`` — the RVV-baseline analogue (paper §III): MX is treated as a
    storage-only format. Elements are decoded to f32 in one step, scales are
    expanded and applied in a second step, and a plain f32 dot follows. Wide
    intermediates materialize in HBM; on a vector core the same structure
    costs conversion + scale instructions.
  * ``fused`` — the Spatz-baseline analogue (MiniFloat-NN-style): a single
    fused dequantize expression produces bf16 operands directly consumed by a
    dot with f32 accumulation. Fewer steps, narrower intermediates, but wide
    operands still materialize.
  * ``pallas`` — the VMXDOTP analogue: the fused TPU kernel in
    ``repro.kernels`` streams compact MX data HBM→VMEM and applies scales
    in-register; no wide tensor touches HBM. (Validated in interpret mode on
    CPU; selected automatically only when explicitly requested.)

``mx_dot`` contracts ``a @ b`` where the blocked axis is the contraction
axis on both sides. ``qat_matmul`` is the custom-vjp training primitive
(straight-through estimator through quantization).
"""
from __future__ import annotations

import functools
from typing import Optional, Union

import jax
import jax.numpy as jnp

from . import formats as F
from .mx_tensor import MXTensor
from .quantize import quantize, quantize_value

Array = jnp.ndarray
MODES = ("emulated", "fused", "pallas")


def _dequant_two_step(t: MXTensor) -> Array:
    """Paper §III emulated path: decode, then expand + apply scales (f32)."""
    vals = F.decode_elements(t.elements, t.fmt, jnp.float32)
    blocked = vals.reshape(*vals.shape[:-1], t.num_blocks, t.block_size)
    scales = F.e8m0_to_scale(t.scales)  # separate expansion step
    wide = (blocked * scales[..., None]).reshape(vals.shape)
    if t.axis not in (-1, wide.ndim - 1):
        wide = jnp.moveaxis(wide, -1, t.axis)
    return wide


def _dequant_fused(t: MXTensor, dtype=jnp.bfloat16) -> Array:
    """Single-expression dequant in a narrow dtype (XLA fuses to one kernel)."""
    return t.dequantize(dtype)


def _as_wide(x: Union[Array, MXTensor], mode: str, dtype) -> Array:
    if isinstance(x, MXTensor):
        if mode == "emulated":
            return _dequant_two_step(x)
        return _dequant_fused(x, dtype)
    return x.astype(dtype) if mode != "emulated" else x.astype(jnp.float32)


def mx_dot(
    a: Union[Array, MXTensor],
    b: Union[Array, MXTensor],
    *,
    mode: str = "fused",
    acc_dtype=jnp.float32,
    out_dtype=None,
) -> Array:
    """Contract ``a (..., K) @ b (K, N)`` with MX semantics.

    Either operand may be an :class:`MXTensor` (blocked along the contraction
    axis) or a plain array — the latter matches the paper's vector-scalar
    variants (``vmxdotp.*f``) where one side is wide.
    """
    if mode not in MODES:
        raise ValueError(f"unknown mode {mode!r}")
    if mode == "pallas":
        from repro.kernels import ops as kops  # lazy: avoid import cycle

        return kops.mx_matmul(a, b, acc_dtype=acc_dtype, out_dtype=out_dtype)

    operand_dtype = jnp.float32 if mode == "emulated" else jnp.bfloat16
    aw = _as_wide(a, mode, operand_dtype)
    bw = _as_wide(b, mode, operand_dtype)
    out = jax.lax.dot_general(
        aw,
        bw,
        (((aw.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=acc_dtype,
    )
    return out.astype(out_dtype or acc_dtype)


# ---------------------------------------------------------------------------
# Quantization-aware training primitive
# ---------------------------------------------------------------------------


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7)
)
def qat_matmul(
    x: Array,
    w: Array,
    fmt: str = "fp8_e4m3",
    block_size: int = 32,
    quantize_acts: bool = True,
    mode: str = "fused",
    acc_dtype=jnp.float32,
    tp_on: str = "out",
) -> Array:
    """``x @ w`` through MX quantization with a straight-through backward.

    Master weights stay wide; both operands are freshly block-quantized along
    the contraction axis each call (per-step quantization, as in MX training
    recipes). The backward pass uses the *quantized* values (consistent
    gradients) but flows straight through the quantizer.

    ``tp_on`` ("out" | "in") says which w dim carries tensor parallelism —
    used to pin the quantized representation's sharding so the FSDP weight
    all-gather moves MX bytes (~1.06 B/param), not f32 masters (MX-FSDP,
    §Perf iteration 5).
    """
    y, _ = _qat_fwd(x, w, fmt, block_size, quantize_acts, mode, acc_dtype,
                    tp_on)
    return y


def _mx_fsdp_quantize(w, fmt, block_size, tp_on):
    """MX-FSDP: quantize on the FSDP shard, all-gather the MX bytes.

    GSPMD left to itself gathers the f32 master and quantizes replicated
    (measured: f32 weight all-gathers, §Perf iteration 5a — refuted).
    shard_map makes the intended dataflow explicit: each device quantizes
    its local weight shard (MX blocks are shard-local), then the FSDP
    all-gather moves fp8 elements + u8 scales (~1.06 B/param) instead of
    f32 (4 B/param) — a 3.8x cut of weight-gather traffic. TP-dim sharding
    is preserved; any divisibility failure falls back to the plain path.
    """
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.parallel.ctx import current_mesh

    mesh = current_mesh()
    fmt_i = F.get_format(fmt)
    if mesh is None or fmt_i.packed:  # fp4 path keeps the plain quantizer
        return quantize(w, fmt, block_size, axis=0)
    fsdp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    tp = "model" if "model" in mesh.axis_names else None
    if not fsdp:
        return quantize(w, fmt, block_size, axis=0)
    d_in, d_out = w.shape
    fsdp_size = int(np.prod([mesh.shape[a] for a in fsdp]))
    tp_size = mesh.shape[tp] if tp else 1

    if tp_on == "out":
        ok = (d_in % fsdp_size == 0 and (d_in // fsdp_size) % block_size == 0)
        tp_ok = tp is not None and d_out % tp_size == 0
        if not ok:
            return quantize(w, fmt, block_size, axis=0)
        w_spec = P(fsdp, tp if tp_ok else None)
        out_specs = (P(tp if tp_ok else None, None),
                     P(tp if tp_ok else None, None))
        gather_dim = 1  # elements (d_out_shard, d_in_shard): gather d_in
    else:
        ok = (tp is not None and d_in % tp_size == 0
              and (d_in // tp_size) % block_size == 0)
        fsdp_ok = d_out % fsdp_size == 0
        if not ok or not fsdp_ok:
            return quantize(w, fmt, block_size, axis=0)
        w_spec = P(tp, fsdp)
        out_specs = (P(None, tp), P(None, tp))
        gather_dim = 0  # elements (d_out_shard, d_in_shard): gather d_out

    def body(w_shard):
        t = quantize(w_shard, fmt, block_size, axis=0)
        elems = jax.lax.all_gather(t.elements, fsdp, axis=gather_dim,
                                   tiled=True)
        scales = jax.lax.all_gather(t.scales, fsdp, axis=gather_dim,
                                    tiled=True)
        return elems, scales

    elems, scales = jax.shard_map(body, mesh=mesh, in_specs=(w_spec,),
                                  out_specs=out_specs, check_vma=False)(w)
    return MXTensor(elements=elems, scales=scales, fmt_name=fmt_i.name,
                    block_size=block_size, axis=0, shape=w.shape)


def _qat_fwd(x, w, fmt, block_size, quantize_acts, mode, acc_dtype,
             tp_on="out"):
    # Residuals and dot operands stay bf16 (fp8/fp4 values are exactly
    # representable; power-of-two scales are exact): no f32 activation
    # copies materialize in the training graph (§Perf iteration 2).
    res_dtype = x.dtype if x.dtype == jnp.bfloat16 else jnp.float32
    if tp_on != "off":
        w_mx = _mx_fsdp_quantize(w, fmt, block_size, tp_on)
    else:
        w_mx = quantize(w, fmt, block_size, axis=0)
    if quantize_acts:
        x_mx = quantize(x, fmt, block_size, axis=-1)
        y = mx_dot(x_mx, w_mx, mode=mode, acc_dtype=acc_dtype)
        xq = x_mx.dequantize(res_dtype)
    else:
        y = mx_dot(x, w_mx, mode=mode, acc_dtype=acc_dtype)
        xq = x
    wq = w_mx.dequantize(res_dtype)
    return y.astype(x.dtype), (xq, wq)


def _qat_bwd(fmt, block_size, quantize_acts, mode, acc_dtype, tp_on, res, dy):
    xq, wq = res
    op_dtype = xq.dtype  # bf16 in training graphs, f32 in exact tests
    dy = dy.astype(op_dtype)
    # dx in operand dtype: the TP all-reduce of activation grads then moves
    # bf16 instead of f32 — halves the dominant train-step collective
    # (§Perf iteration 3). dw stays f32 into the optimizer.
    dx = jax.lax.dot_general(
        dy,
        wq,
        (((dy.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=op_dtype,
    )
    x2 = xq.reshape(-1, xq.shape[-1])
    dy2 = dy.reshape(-1, dy.shape[-1])
    dw = jax.lax.dot_general(
        x2, dy2, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    return dx.astype(xq.dtype), dw.astype(jnp.float32)


qat_matmul.defvjp(_qat_fwd, _qat_bwd)


def fake_quant(x: Array, fmt: str, block_size: int, axis: int = -1) -> Array:
    """Straight-through fake quantization of a single tensor (for QAT)."""

    @jax.custom_vjp
    def _fq(v):
        return quantize_value(v, fmt, block_size, axis)

    _fq.defvjp(lambda v: (_fq(v), None), lambda _, g: (g,))
    return _fq(x)
