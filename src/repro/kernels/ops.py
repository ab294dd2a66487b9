"""Public jit'd wrappers around the Pallas MX kernels.

``mx_matmul`` accepts MXTensor / wide-array operands with arbitrary leading
batch dims and dispatches to the vector-vector or weight-only kernel;
``quantize_pallas`` produces an MXTensor via the fused quantization kernel.
On CPU backends (this container) kernels run in interpret mode; on TPU they
compile to Mosaic.
"""
from __future__ import annotations

import functools
from typing import Optional, Union

import jax
import jax.numpy as jnp

from repro.core import formats as F
from repro.core.mx_tensor import MXTensor

from . import mx_matmul as _mm
from . import mx_quantize as _mq

Array = jnp.ndarray


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pick(v, default):
    return default if v is None else v


def _tile(dim: int, pref: int) -> int:
    """Largest divisor of ``dim`` that is <= pref (tries hw-aligned first)."""
    for cand in (pref, 512, 256, 128, 64, 32, 16, 8):
        if cand <= pref and dim % cand == 0:
            return cand
    return dim


def mx_matmul(
    a: Union[Array, MXTensor],
    b: MXTensor,
    *,
    acc_dtype=jnp.float32,
    out_dtype=None,
    bm: Optional[int] = None,
    bn: Optional[int] = None,
    bk: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Array:
    """``a (..., K) @ b (K, N)`` with MX semantics via the Pallas kernel.

    ``b`` must be an MXTensor blocked along K (axis=0 — stored (N, K),
    the paper's column-major layout). ``a`` is either an MXTensor blocked
    along its last axis (vector-vector) or a wide array (weight-only /
    vector-scalar variant).
    """
    interpret = _pick(interpret, _default_interpret())
    if not isinstance(b, MXTensor) or b.axis != 0:
        raise ValueError("b must be an MXTensor blocked along axis 0 (K)")
    k, n = b.shape
    block_size = b.block_size

    if isinstance(a, MXTensor):
        if a.axis not in (-1, len(a.shape) - 1):
            raise ValueError("a must be blocked along its last axis")
        if a.block_size != block_size or a.fmt_name != b.fmt_name:
            raise ValueError("operand quantization configs differ")
        lead = a.shape[:-1]
        m = 1
        for d in lead:
            m *= d
        ae = a.elements.reshape(m, -1)
        asc = a.scales.reshape(m, -1)
        bm_ = _tile(m, _pick(bm, 128))
        bn_ = _tile(n, _pick(bn, 128))
        out = _mm.mx_matmul_vv(
            ae,
            asc,
            b.elements,
            b.scales,
            fmt_name=b.fmt_name,
            block_size=block_size,
            acc_dtype=acc_dtype,
            bm=bm_,
            bn=bn_,
            bk=_pick(bk, 4096),
            interpret=interpret,
        )
    else:
        lead = a.shape[:-1]
        m = 1
        for d in lead:
            m *= d
        a2 = a.reshape(m, k)
        bm_ = _tile(m, _pick(bm, 128))
        bn_ = _tile(n, _pick(bn, 128))
        out = _mm.mx_matmul_wo(
            a2,
            b.elements,
            b.scales,
            fmt_name=b.fmt_name,
            block_size=block_size,
            acc_dtype=acc_dtype,
            bm=bm_,
            bn=bn_,
            bk=_pick(bk, 4096),
            interpret=interpret,
        )
    out = out.reshape(*lead, n)
    return out.astype(out_dtype or acc_dtype)


def quantize_pallas(
    x: Array,
    fmt_name: str = "fp8_e4m3",
    block_size: int = 32,
    *,
    interpret: Optional[bool] = None,
) -> MXTensor:
    """Fused block quantization of ``x (..., K)`` along the last axis."""
    interpret = _pick(interpret, _default_interpret())
    lead = x.shape[:-1]
    k = x.shape[-1]
    m = 1
    for d in lead:
        m *= d
    bm = _tile(m, 256)
    bk = max(_tile(k, 2048), block_size)
    elems, scales = _mq.mx_quantize(
        x.reshape(m, k),
        fmt_name=fmt_name,
        block_size=block_size,
        bm=bm,
        bk=bk,
        interpret=interpret,
    )
    ek = elems.shape[-1]
    return MXTensor(
        elements=elems.reshape(*lead, ek),
        scales=scales.reshape(*lead, k // block_size),
        fmt_name=fmt_name,
        block_size=block_size,
        axis=len(lead),
        shape=x.shape,
    )


# ---------------------------------------------------------------------------
# Trainable entry point: Pallas forward, straight-through wide backward
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def mx_matmul_trainable(x: Array, w_mx: MXTensor, fmt, block_size, acc_dtype):
    """Weight-only Pallas matmul with a differentiable wide backward."""
    return mx_matmul(x, w_mx, acc_dtype=acc_dtype)


def _fwd(x, w_mx, fmt, block_size, acc_dtype):
    y = mx_matmul(x, w_mx, acc_dtype=acc_dtype)
    return y, (x, w_mx)


def _bwd(fmt, block_size, acc_dtype, res, dy):
    x, w_mx = res
    dy32 = dy.astype(jnp.float32)
    # dx through the native dgrad kernel (the stored MX layout is already
    # W^T; scales fold in-register — no wide weight copy materializes)
    lead = dy32.shape[:-1]
    m = 1
    for d in lead:
        m *= d
    n = dy32.shape[-1]
    k = w_mx.shape[0]
    dx = _mm.mx_matmul_dgrad(
        dy32.reshape(m, n), w_mx.elements, w_mx.scales,
        fmt_name=w_mx.fmt_name, block_size=w_mx.block_size,
        bm=_tile(m, 128), bn=_tile(n, 128),
        interpret=_default_interpret(),
    ).reshape(*lead, k).astype(x.dtype)
    x2 = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    dy2 = dy32.reshape(-1, dy32.shape[-1])
    dw = jax.lax.dot_general(
        x2, dy2, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    # Gradient w.r.t. the quantized weight flows to the master copy via the
    # straight-through estimator at the layer level; MXTensor itself is not
    # a differentiable leaf, so return a zero cotangent structure.
    zero_w = jax.tree_util.tree_map(jnp.zeros_like, w_mx)
    del dw  # layer-level QAT uses qat_matmul for weight grads
    return dx, zero_w


mx_matmul_trainable.defvjp(_fwd, _bwd)
