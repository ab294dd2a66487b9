"""Pallas kernel for fused MX block quantization.

Computes, per block of ``block_size`` elements along the last axis: the
block amax, the E8M0 shared exponent (floor(log2(amax)) - emax via FP32
exponent-field extraction — no transcendentals), and the RNE+saturate cast
of the scaled elements to the target format. One pass over the data: the
wide input is read once, compact elements + scales are written.

This is the producer side of the VMXDOTP story: on-the-fly activation
quantization feeding the vector-vector MX matmul.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import formats as F

from .mx_matmul import (_block_amax, _broadcast_blocks, _decode_e8m0,
                        _deinterleave, _interleave)


def _floor_log2(x: jnp.ndarray) -> jnp.ndarray:
    """floor(log2(x)) for normal positive f32 via exponent-field extraction."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    return (jnp.right_shift(bits, 23) & 0xFF).astype(jnp.int32) - 127


def _encode_fp4_codes(v: jnp.ndarray) -> jnp.ndarray:
    """Arithmetic RNE+saturate encode of f32 to E2M1 codes (int32, no gather).

    jnp.round implements round-half-to-even, so each regime below inherits
    correct tie behaviour; regime boundaries coincide with grid points.
    """
    sign = jnp.signbit(v)
    mag = jnp.clip(jnp.abs(v), 0.0, 6.0)
    r1 = jnp.round(mag * 2.0) * 0.5  # grid {0, .5, 1, 1.5, 2}
    r2 = jnp.round(mag)  # grid {2, 3, 4}
    r3 = jnp.round(mag * 0.5) * 2.0  # grid {4, 6}
    val = jnp.where(mag <= 1.75, r1, jnp.where(mag <= 3.5, r2, r3))
    code = jnp.where(val < 2.0, val * 2.0, jnp.where(val < 4.0, val + 2.0, val * 0.5 + 4.0))
    code = code.astype(jnp.int32)
    return jnp.where(sign, code | 0x8, code)


def _pack_fp4(codes: jnp.ndarray) -> jnp.ndarray:
    lo, hi = _deinterleave(codes, 2)
    return (lo | (hi << 4)).astype(jnp.uint8)


def _encode_fp6_codes(v: jnp.ndarray, fmt: F.ElementFormat) -> jnp.ndarray:
    """Arithmetic RNE+saturate encode of f32 to FP6 codes (int32, no gather).

    Same construction as :func:`repro.core.formats.fp6_encode` (grid snap
    via the exponent-field quantum, then exact field recovery) — pure
    bitcast/shift/round arithmetic, so it is Pallas-safe. Kept in one
    place with the fp4 encoder so every in-kernel quantizer shares it.
    """
    sign = jnp.signbit(v)
    mag = jnp.clip(jnp.abs(v), 0.0, fmt.max)
    snapped = jnp.abs(F.snap_to_fp8_grid(mag, fmt))
    bits = jax.lax.bitcast_convert_type(snapped, jnp.uint32)
    e = (jnp.right_shift(bits, 23) & 0xFF).astype(jnp.int32) - 127
    is_norm = snapped >= 2.0 ** (1 - fmt.bias)
    e_field = jnp.where(is_norm, e + fmt.bias, 0)
    q_bits = ((e - fmt.mantissa_bits + 127) << 23).astype(jnp.uint32)
    quantum = jnp.where(
        is_norm, jax.lax.bitcast_convert_type(q_bits, jnp.float32),
        jnp.float32(fmt.min_subnormal))
    p_bits = ((e + 127) << 23).astype(jnp.uint32)
    frac = snapped - jnp.where(
        is_norm, jax.lax.bitcast_convert_type(p_bits, jnp.float32), 0.0)
    m = jnp.round(frac / quantum).astype(jnp.int32)
    code = (e_field << fmt.mantissa_bits) | m
    return jnp.where(sign, code | 0x20, code)


def _pack_fp6(codes: jnp.ndarray) -> jnp.ndarray:
    """Pack quads of 6-bit codes into 3 bytes (low bits first)."""
    c0, c1, c2, c3 = _deinterleave(codes, 4)
    b0 = (c0 | (c1 << 6)) & 0xFF
    b1 = ((c1 >> 2) | (c2 << 4)) & 0xFF
    b2 = ((c2 >> 4) | (c3 << 2)) & 0xFF
    return _interleave([b0, b1, b2]).astype(jnp.int32).astype(jnp.uint8)


def quantize_tile(x: jnp.ndarray, fmt: F.ElementFormat, block_size: int):
    """(T, D) f32 -> (elements (T, ED) storage, E8M0 scales (T, D//k) uint8).

    The exact math of ``core.quantize`` (f32 work dtype): block amax ->
    E8M0 shared exponent (exponent-field floor-log2, no transcendentals
    and no lookup tables — Pallas rejects captured constant arrays) ->
    RNE saturating element cast. Dividing by a power of two is exact, so
    multiplying by its reciprocal (the E8M0 code ``254 - e``) gives the
    same bits as the host's division.
    """
    amax = _block_amax(x, block_size)  # (T, nb)
    e_unb = _floor_log2(amax) - fmt.emax + F.E8M0_BIAS
    e = jnp.clip(jnp.where(amax > 0, e_unb, 0), 0, 254)
    inv = _broadcast_blocks(_decode_e8m0(254 - e), block_size)
    ratio = jnp.clip(x * inv, -fmt.max, fmt.max)
    if fmt.name == "fp4_e2m1":
        elems = _pack_fp4(_encode_fp4_codes(ratio))
    elif fmt.bits == 6:
        elems = _pack_fp6(_encode_fp6_codes(ratio, fmt))
    else:
        # exact RNE snap before the storage cast: XLA's direct fp8 cast
        # double-rounds via bf16 on some backends (see formats.py)
        elems = F.snap_to_fp8_grid(ratio, fmt).astype(fmt.storage_dtype)
    return elems, e.astype(jnp.uint8)


def _mx_quantize_kernel(x_ref, q_ref, e_ref, *, fmt: F.ElementFormat, block_size: int):
    q_ref[...], e_ref[...] = quantize_tile(
        x_ref[...].astype(jnp.float32), fmt, block_size)


def mx_quantize(
    x,
    *,
    fmt_name: str = "fp8_e4m3",
    block_size: int = 32,
    bm: int = 256,
    bk: int = 2048,
    interpret: bool = False,
):
    """Quantize ``x (M, K)`` along K. Returns (elements, e8m0_scales)."""
    fmt = F.get_format(fmt_name)
    m, k = x.shape
    bm, bk = min(bm, m), min(bk, k)
    if m % bm or k % bk or bk % block_size:
        raise ValueError(f"tiling mismatch: {(m, k)} vs {(bm, bk)}/{block_size}")
    ebk = fmt.storage_len(bk)
    ek = fmt.storage_len(k)
    nb = bk // block_size
    grid = (m // bm, k // bk)
    kernel = functools.partial(_mx_quantize_kernel, fmt=fmt, block_size=block_size)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bk), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((bm, ebk), lambda i, j: (i, j)),
            pl.BlockSpec((bm, nb), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, ek), fmt.storage_dtype),
            jax.ShapeDtypeStruct((m, k // block_size), jnp.uint8),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x)
