"""Pallas TPU kernel for fused MX matmul — the VMXDOTP analogue.

The paper's VMXDOTP instruction computes, per accumulator element,
``vd[i] += X(A) * X(B) * sum_j A[j] * B[ki+j]`` with scales applied in
hardware and no wide intermediate leaving the datapath. The TPU-native
reading (DESIGN.md §2) is a tiled matmul kernel where:

  * MX elements and E8M0 scales stream HBM -> VMEM in *compact* form
    (fp8 bytes, fp4 packed nibbles, uint8 scales) — this is the bandwidth
    win; no dequantized tensor ever exists in HBM;
  * decode + scale application happen in-register (VREG) on VMEM tiles:
    scales are folded into the operand tiles per MX block (exact — scales
    are powers of two), which is the kernel form of the paper's insight
    that an MX dot decomposes into sub-dot-products reusing block scales;
  * the MXU then runs a full-depth (bk >= 128) contraction at full systolic
    utilization — unlike a literal port of the 8-wide RVV instruction,
    which would starve a 128x128 systolic array (see DESIGN.md assumption
    deltas);
  * accumulation is f32 (spec) or bf16 (compact option) in the output tile,
    revisited across the K grid dimension.

Layouts (blocked/contraction axis last — the paper's column-major B):
  a_elems (M, K) fp8 | (M, K//2) packed fp4      a_scales (M, K/k) uint8
  b_elems (N, K) fp8 | (N, K//2) packed fp4      b_scales (N, K/k) uint8
  out     (M, N) acc_dtype

Software-defined block size: any k with k | bk (bk = K-tile). Validated
against ``ref.py`` in interpret mode; targets TPU MXU when compiled.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import formats as F

# ---------------------------------------------------------------------------
# In-kernel decode helpers (pure jnp: lower on TPU and in interpret mode)
#
# Mosaic lowers no reshape that splits or merges the lane (last) axis and
# no direct cast between f32 and uint8, so the helpers below never do
# either: per-block work broadcasts or reduces along lanes, and lane
# (de)interleaving of packed sub-byte codes is a one-hot matmul. Those
# matmuls are exact in one bf16 pass: every output sums exactly one
# nonzero product of a 0/1 entry with a byte, an fp4/fp6 value or a
# power-of-two scale, all of which bf16 holds exactly, into an f32
# accumulator. The one exception is the E8M0 code 0, 2^-127, which is
# subnormal: a backend that flushes it reads that block as zeros, an
# error below 1e-33 in the decoded values. The E8M0 NaN code (255), which
# no encoder here emits, poisons its whole row, not only its block.
#
# Each one-hot works on one aligned 128-lane chunk of its input whenever
# the input is a whole number of chunks, so its size (and the MXU work per
# lane) stays fixed however wide the K tile is.
# ---------------------------------------------------------------------------

_LANES = 128


def _lane_dot(x: jnp.ndarray, m: jnp.ndarray) -> jnp.ndarray:
    """``x (..., a) @ m (a, b)`` in f32, exact for one-hot ``m`` and
    bf16-exact ``x``."""
    return jax.lax.dot_general(
        x.astype(jnp.float32).astype(jnp.bfloat16), m,
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _onehot(rows: int, cols: int, pred) -> jnp.ndarray:
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    return pred(r, c).astype(jnp.bfloat16)


def _chunk(n: int, width: int) -> int:
    """``width`` if an ``n``-lane input is a whole number of such chunks,
    else ``n`` (one chunk)."""
    return width if n % width == 0 else n


def _over_chunks(n: int, c: int, fn) -> jnp.ndarray:
    """``fn(start)`` for each ``c``-lane chunk of ``n`` lanes, concatenated
    along lanes."""
    outs = [fn(s) for s in range(0, n, c)]
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=-1)


def _interleave(parts) -> jnp.ndarray:
    """k arrays (..., n) -> (..., k*n) f32 with out[..., k*i + j] = parts[j][..., i]."""
    k, n = len(parts), parts[0].shape[-1]
    c = _chunk(n, _LANES)
    hots = [_onehot(c, k * c, lambda r, q, j=j: q == k * r + j)
            for j in range(k)]

    def chunk(s):
        out = _lane_dot(parts[0][..., s:s + c], hots[0])
        for p, hot in zip(parts[1:], hots[1:]):
            out = out + _lane_dot(p[..., s:s + c], hot)
        return out

    return _over_chunks(n, c, chunk)


def _deinterleave(x: jnp.ndarray, k: int):
    """(..., k*n) -> k int32 arrays (..., n) with parts[j][..., i] = x[..., k*i + j]."""
    kn = x.shape[-1]
    c = _chunk(kn, k * _LANES)
    xi = x.astype(jnp.int32)
    parts = []
    for j in range(k):
        hot = _onehot(c, c // k, lambda r, q, j=j: r == k * q + j)
        parts.append(_over_chunks(
            kn, c, lambda s, hot=hot: _lane_dot(xi[..., s:s + c], hot)
        ).astype(jnp.int32))
    return parts


def _broadcast_blocks(s: jnp.ndarray, block_size: int) -> jnp.ndarray:
    """(..., nb) per-block values -> (..., nb * block_size), each value
    repeated over its block's lanes."""
    nb = s.shape[-1]
    c = _chunk(nb, _LANES)
    hot = _onehot(c, c * block_size, lambda r, q: q // block_size == r)
    return _over_chunks(nb, c, lambda i: _lane_dot(s[..., i:i + c], hot))


def _block_amax(x: jnp.ndarray, block_size: int) -> jnp.ndarray:
    """(T, D) f32 -> (T, D // block_size) per-block max of ``|x|``."""
    t, d = x.shape
    blk = jax.lax.broadcasted_iota(jnp.int32, (t, d), 1) // block_size
    a = jnp.abs(x)
    return jnp.concatenate(
        [jnp.max(jnp.where(blk == b, a, 0.0), axis=1, keepdims=True)
         for b in range(d // block_size)], axis=1)


def _decode_e8m0(e: jnp.ndarray) -> jnp.ndarray:
    """E8M0 -> f32 scale via exponent-field bitcast (paper's shift trick)."""
    e32 = e.astype(jnp.uint32)
    bits = jnp.where(e32 > 0, e32 << 23, jnp.uint32(0x00400000))
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _decode_fp4_codes(codes: jnp.ndarray) -> jnp.ndarray:
    """Arithmetic E2M1 decode of 4-bit codes (no gather/table lookup)."""
    c = codes.astype(jnp.int32)
    sign = jnp.where((c & 0x8) != 0, -1.0, 1.0).astype(jnp.float32)
    e = (c >> 1) & 0x3
    m = (c & 0x1).astype(jnp.float32)
    pow2 = jnp.left_shift(1, jnp.maximum(e - 1, 0)).astype(jnp.float32)
    mag = jnp.where(e == 0, 0.5 * m, pow2 * (1.0 + 0.5 * m))
    return sign * mag


def _unpack_fp4(packed: jnp.ndarray) -> jnp.ndarray:
    """(..., n) packed bytes -> (..., 2n) f32 values (low nibble first)."""
    b = packed.astype(jnp.int32)
    return _interleave([_decode_fp4_codes(b & 0xF),
                        _decode_fp4_codes((b >> 4) & 0xF)])


def _decode_fp6_codes(codes: jnp.ndarray, fmt_name: str) -> jnp.ndarray:
    """Arithmetic FP6 E3M2/E2M3 decode of 6-bit codes (no gather/table).

    Subnormals (exponent field 0) decode as m * 2^(1 - bias - mant); the
    normal-path power of two is built by integer shift, exact and
    Pallas-safe like :func:`_decode_fp4_codes`.
    """
    mant = 2 if fmt_name == "fp6_e3m2" else 3
    ebits = 3 if fmt_name == "fp6_e3m2" else 2
    bias = 2 ** (ebits - 1) - 1
    eps = 2.0 ** -mant
    min_sub = 2.0 ** (1 - bias - mant)
    c = codes.astype(jnp.int32)
    sign = jnp.where((c & 0x20) != 0, -1.0, 1.0).astype(jnp.float32)
    e = (c >> mant) & ((1 << ebits) - 1)
    m = (c & ((1 << mant) - 1)).astype(jnp.float32)
    # 2^(e - bias) for normals: shift against the worst negative exponent
    # (e3m2 min normal exp is -2) so the shift count stays non-negative
    pow2 = jnp.left_shift(1, jnp.maximum(e - 1, 0)).astype(jnp.float32) * (
        2.0 ** (1 - bias))
    mag = jnp.where(e == 0, min_sub * m, pow2 * (1.0 + eps * m))
    return sign * mag


def _unpack_fp6(packed: jnp.ndarray, fmt_name: str) -> jnp.ndarray:
    """(..., 3n) packed bytes -> (..., 4n) f32 values (low bits first)."""
    b0, b1, b2 = _deinterleave(packed, 3)
    codes = [b0 & 0x3F,
             ((b0 >> 6) | (b1 << 2)) & 0x3F,
             ((b1 >> 4) | (b2 << 4)) & 0x3F,
             (b2 >> 2) & 0x3F]
    return _interleave([_decode_fp6_codes(c, fmt_name) for c in codes])


def _decode_tile(tile: jnp.ndarray, fmt_name: str) -> jnp.ndarray:
    """Decode a VMEM tile of stored elements to f32 (in-register upcast)."""
    if fmt_name == "fp4_e2m1":
        return _unpack_fp4(tile)
    if fmt_name in ("fp6_e3m2", "fp6_e2m3"):
        return _unpack_fp6(tile, fmt_name)
    return tile.astype(jnp.float32)


def _fold_scales(vals: jnp.ndarray, scales_e8m0: jnp.ndarray, block_size: int):
    """Fold per-block power-of-two scales into decoded element rows (exact)."""
    return vals * _broadcast_blocks(_decode_e8m0(scales_e8m0), block_size)


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------


def _mx_matmul_kernel(
    a_ref, as_ref, b_ref, bs_ref, o_ref, *, fmt_name: str, block_size: int
):
    """Vector-vector variant: both operands MX (paper Eq. (2))."""
    kk = pl.program_id(2)
    a = _fold_scales(_decode_tile(a_ref[...], fmt_name), as_ref[...], block_size)
    b = _fold_scales(_decode_tile(b_ref[...], fmt_name), bs_ref[...], block_size)
    partial = jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(kk == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += partial.astype(o_ref.dtype)


def _mx_matmul_wo_kernel(
    a_ref, b_ref, bs_ref, o_ref, *, fmt_name: str, block_size: int
):
    """Vector-scalar variant (`vmxdotp.*f`): wide A x MX B (weight-only)."""
    kk = pl.program_id(2)
    a = a_ref[...].astype(jnp.float32)
    b = _fold_scales(_decode_tile(b_ref[...], fmt_name), bs_ref[...], block_size)
    partial = jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(kk == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += partial.astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call builders
# ---------------------------------------------------------------------------


def _elem_tile(bk: int, fmt_name: str) -> int:
    return F.get_format(fmt_name).storage_len(bk)


def _k_tile(k: int, block_size: int, pref: int) -> int:
    """Tile of the blocked K axis whose scale block is a legal TPU tile.

    The scale block is (rows, bk // block_size), and the TPU tiles a
    block's last dimension in 128 lanes unless the block spans the whole
    array: so bk // block_size is a multiple of 128, or bk is all of K.
    Returns the largest such divisor of K up to ``pref``, else K.
    """
    step = 128 * block_size
    for bk in range(pref - pref % step, 0, -step):
        if k % bk == 0:
            return bk
    return k


def mx_matmul_vv(
    a_elems,
    a_scales,
    b_elems,
    b_scales,
    *,
    fmt_name: str = "fp8_e4m3",
    block_size: int = 32,
    acc_dtype=jnp.float32,
    bm: int = 128,
    bn: int = 128,
    bk: int = 4096,
    interpret: bool = False,
):
    """Tiled MX x MX matmul. Shapes per module docstring; returns (M, N)."""
    m = a_scales.shape[0]
    n = b_scales.shape[0]
    kb = a_scales.shape[1]
    k = kb * block_size
    bm, bn, bk = min(bm, m), min(bn, n), _k_tile(k, block_size, bk)
    if m % bm or n % bn or k % block_size:
        raise ValueError(f"tiling mismatch: {(m, n, k)} vs {(bm, bn, bk)}/{block_size}")
    ebk = _elem_tile(bk, fmt_name)
    nb = bk // block_size
    grid = (m // bm, n // bn, k // bk)
    kernel = functools.partial(
        _mx_matmul_kernel, fmt_name=fmt_name, block_size=block_size
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, ebk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bm, nb), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bn, ebk), lambda i, j, kk: (j, kk)),
            pl.BlockSpec((bn, nb), lambda i, j, kk: (j, kk)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), acc_dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a_elems, a_scales, b_elems, b_scales)


def mx_matmul_wo(
    a,
    b_elems,
    b_scales,
    *,
    fmt_name: str = "fp8_e4m3",
    block_size: int = 32,
    acc_dtype=jnp.float32,
    bm: int = 128,
    bn: int = 128,
    bk: int = 4096,
    interpret: bool = False,
):
    """Tiled wide-A x MX-B matmul (weight-only). Returns (M, N)."""
    m, k = a.shape
    n = b_scales.shape[0]
    bm, bn, bk = min(bm, m), min(bn, n), _k_tile(k, block_size, bk)
    if m % bm or n % bn or k % block_size:
        raise ValueError(f"tiling mismatch: {(m, n, k)} vs {(bm, bn, bk)}/{block_size}")
    ebk = _elem_tile(bk, fmt_name)
    nb = bk // block_size
    grid = (m // bm, n // bn, k // bk)
    kernel = functools.partial(
        _mx_matmul_wo_kernel, fmt_name=fmt_name, block_size=block_size
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bn, ebk), lambda i, j, kk: (j, kk)),
            pl.BlockSpec((bn, nb), lambda i, j, kk: (j, kk)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), acc_dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b_elems, b_scales)


# ---------------------------------------------------------------------------
# dgrad: dx = dy @ W^T with MX weights (training backward, weight-only path)
# ---------------------------------------------------------------------------


def _mx_dgrad_kernel(dy_ref, b_ref, bs_ref, o_ref, *, fmt_name: str,
                     block_size: int):
    """dx tile = dy (bm, bn) @ dequant(stored (bn, bk)). Accumulate over n."""
    nn = pl.program_id(2)
    dy = dy_ref[...].astype(jnp.float32)
    s = _fold_scales(_decode_tile(b_ref[...], fmt_name), bs_ref[...],
                     block_size)  # (bn, bk) dequantized W^T tile
    partial = jax.lax.dot_general(
        dy, s, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(nn == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += partial.astype(o_ref.dtype)


def mx_matmul_dgrad(
    dy,
    b_elems,
    b_scales,
    *,
    fmt_name: str = "fp8_e4m3",
    block_size: int = 32,
    out_dtype=jnp.float32,
    bm: int = 128,
    bn: int = 128,
    bk: int = 4096,
    interpret: bool = False,
):
    """dx (M, K) = dy (M, N) @ dequant(W)^T for W stored (N, K) MX-blocked
    along K (the forward weight layout — no transposition needed: the
    stored layout IS W^T)."""
    m, n = dy.shape
    kb = b_scales.shape[1]
    k = kb * block_size
    bm, bn, bk = min(bm, m), min(bn, n), _k_tile(k, block_size, bk)
    if m % bm or n % bn or k % block_size:
        raise ValueError(f"tiling mismatch: {(m, n, k)} vs {(bm, bn, bk)}")
    ebk = _elem_tile(bk, fmt_name)
    nb = bk // block_size
    grid = (m // bm, k // bk, n // bn)
    kernel = functools.partial(_mx_dgrad_kernel, fmt_name=fmt_name,
                               block_size=block_size)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, nn: (i, nn)),
            pl.BlockSpec((bn, ebk), lambda i, j, nn: (nn, j)),
            pl.BlockSpec((bn, nb), lambda i, j, nn: (nn, j)),
        ],
        out_specs=pl.BlockSpec((bm, bk), lambda i, j, nn: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, k), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(dy, b_elems, b_scales)
