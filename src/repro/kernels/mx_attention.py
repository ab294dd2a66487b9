"""Pallas decode-attention kernels over an MX-quantized KV cache.

The serving-side application of VMXDOTP's insight: decode attention is
HBM-bandwidth-bound on the KV cache, so the cache is stored block-scaled
(fp8 elements + E8M0 scales along head_dim) and decoded **in-register** —
the wide K/V never exist in HBM. This is the vector-scalar instruction
family (`vmxdotp.*f`): one wide query operand against compact MX operands.

Three entry points, two cache layouts:

  * **contiguous** (`mx_attention_decode`): one (T, D) tile per (batch,
    kv-head), the fixed-slot serving layout. ``kpos``/``pos`` may be shared
    across the batch or per-sequence (continuous batching decodes requests
    at different positions in the same step).
  * **paged, two-pass** (`mx_attention_decode_paged`): the cache lives in a
    global page pool (num_pages, KVH, page_size, D) and each sequence owns
    a list of pages (its page-table row). `gather_kv_pages` is a Pallas
    kernel whose BlockSpec index maps read the scalar-prefetched page
    table — the DMA engine walks the page list directly, and the gathered
    operands stay **compact** (fp8/fp4 + E8M0). Decode then reuses the
    contiguous kernel bit-for-bit, which is what makes paged-vs-contiguous
    equivalence exact rather than approximate. Kept as the bit-exactness
    oracle; the engine no longer runs it.
  * **paged, single-pass fused** (`mx_attention_decode_fused` /
    `mx_attention_verify_fused`): the serve engine's hot path. One
    kernel, grid (B, KVH, num_kv_pages) with the page dimension
    innermost: the BlockSpec index maps read the scalar-prefetched page
    table, so each grid step DMAs one *compact* pool page tile straight
    into VMEM, dequantizes it in-register, and folds it into a
    flash-style online softmax (running max / rescaled partial sums in
    VMEM scratch). The gathered cache never exists — not wide, not even
    compact — and ``pl.when`` skips every page tile past
    ``ceil(seq_len / page_size)`` (the index map also re-points skipped
    steps at the last valid page, so the pipeline's DMA is elided by the
    revisit rule). Per-step work is proportional to *resident* tokens,
    not the padded table width. The verify variant runs Tq > 1 query
    tokens (speculative decoding's batched multi-token verify) through
    the *same* page walk with per-row causal intra-chunk masking — one
    tile DMA + dequant now feeds K+1 tokens of attention, the serving
    analogue of the paper's keep-the-MX-dataflow-dense argument; decode
    is its Tq == 1 case.

Per grid cell (batch b, kv-head h): load the query group (G, D) wide, the
K/V cache tiles compact, fold scales in VREGs, run the (G, ·) logits
matmul + masked f32 softmax + (G, D) output matmul.

Layouts:
  q        (B, KVH, G, D)    bf16/f32 (G = query heads per kv head)
  k_elems  (B, KVH, T, D)    fp8   k_scales (B, KVH, T, D//k) u8
  v_elems  (B, KVH, T, D)    fp8   v_scales (B, KVH, T, D//k) u8
  kpos     (T,) or (B, T)    i32 (absolute positions; -1 = empty slot)
  pos      scalar or (B,)    i32 (last valid position per sequence)
  out      (B, KVH, G, D)    f32
Paged pools: (NP, KVH, PS, D[/2]) elems, (NP, KVH, PS, D//k) scales,
page_table (B, P) i32 (entries < 0 = unallocated; rows are masked out via
seq_lens so garbage pages never contribute). The pools are KV-head major
so one (page, kv-head) tile is a contiguous (PS, width) slab: the TPU
compiler tiles the last two dimensions of every block, and a KV-head axis
blocked at 1 in second-to-last place is an illegal tiling.

Every paged kernel also returns a page-visit counter, one int32 per
(row, kv-head) cell in SMEM (scalar stores to VMEM do not lower), which
the wrappers hand back only under ``debug_visits``.

Element formats are threaded explicitly (``fmt_name``, as ``mx_matmul``
does) — fp4 packs two nibbles per stored byte, so the storage dtype alone
cannot name the format once more than one byte-backed format exists.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import formats as F

from .mx_matmul import _decode_tile, _fold_scales, _unpack_fp4, _unpack_fp6
from .mx_quantize import quantize_tile

NEG_INF = -2.0e38


def _check_fmt(elems, fmt_name: str, mixed: bool = False):
    """Fail loudly when ``fmt_name`` contradicts the storage dtype.

    fp4/fp6 pack sub-byte codes into uint8 bytes, so decoding them as fp8
    (or vice versa) produces shape garbage deep inside the kernel; catching
    the mismatch at the wrapper names the actual mistake. Mixed-format
    (tiered) pools are always raw uint8 bytes regardless of ``fmt_name``
    (which then names the hot/write format).
    """
    if mixed:
        if elems.dtype != jnp.uint8:
            raise ValueError(
                "mixed-format (tiered) pools must store raw uint8 bytes, "
                f"got {elems.dtype}")
        return
    stored_u8 = elems.dtype == jnp.uint8
    if stored_u8 != F.get_format(fmt_name).sub_byte:
        raise ValueError(
            f"fmt_name {fmt_name!r} does not match the cache storage dtype "
            f"{elems.dtype} (packed fp4/fp6 pools need a sub-byte fmt_name, "
            "fp8 pools an fp8 format)")


def _dequant_rows(elems, scales, fmt_name: str, block_size: int):
    """(T, D) stored elements + (T, D//k) scales -> (T, D) f32.

    ``fmt_name`` is threaded explicitly from the caller (never sniffed from
    the storage dtype): fp8 variants share decode-by-astype but fp4 stores
    two packed nibbles per byte, and any future byte-backed format would
    make dtype sniffing silently wrong.
    """
    return _fold_scales(_decode_tile(elems, fmt_name), scales, block_size)


# ---------------------------------------------------------------------------
# mixed-format (tiered) pools: full-width uint8 rows, per-page format id
# ---------------------------------------------------------------------------

# the repack ladder (hot -> cold); also the default candidate set the mixed
# kernels compile decode branches for
MIXED_FMTS_DEFAULT = ("fp8_e4m3", "fp6_e3m2", "fp4_e2m1")


def _decode_u8_codes(codes, ebits: int, mant: int) -> jnp.ndarray:
    """Arithmetic decode of byte-stored fp8 codes (sign/exp/mant fields).

    Used only on mixed pools, where fp8 elements live as raw bytes rather
    than an fp8 dtype. Exact: the normal-path power of two comes from an
    f32 exponent-field bitcast and ``(1 + m * 2^-mant)`` is exact in f32,
    so the result is bit-identical to ``astype(f32)`` on the fp8 view
    (our encoders never emit inf/NaN codes — saturating RNE).
    """
    bias = 2 ** (ebits - 1) - 1
    c = codes.astype(jnp.int32)
    sign = jnp.where((c & 0x80) != 0, -1.0, 1.0).astype(jnp.float32)
    e = (c >> mant) & ((1 << ebits) - 1)
    m = (c & ((1 << mant) - 1)).astype(jnp.float32)
    eps = 2.0 ** -mant
    min_sub = 2.0 ** (1 - bias - mant)
    scale_bits = ((e - bias + 127) << 23).astype(jnp.uint32)
    scale = jax.lax.bitcast_convert_type(scale_bits, jnp.float32)
    mag = jnp.where(e == 0, min_sub * m, scale * (1.0 + eps * m))
    return sign * mag


def _decode_bytes_as(bytes_tile, fmt_name: str) -> jnp.ndarray:
    """Decode a (T, D) full-width uint8 row tile as ``fmt_name``.

    Tiered pool rows are D bytes wide regardless of element format; a
    narrower format's codes occupy the row *prefix* (fp8 = D bytes,
    fp6 = 3D/4, fp4 = D/2) and the tail bytes are dead. Always returns
    (T, D) f32 — one decoded value per logical element.
    """
    fmt = F.get_format(fmt_name)
    d = bytes_tile.shape[-1]
    w = fmt.storage_len(d)
    prefix = bytes_tile[..., :w]
    if fmt.name == "fp4_e2m1":
        return _unpack_fp4(prefix)
    if fmt.bits == 6:
        return _unpack_fp6(prefix, fmt.name)
    return _decode_u8_codes(prefix, fmt.exp_bits, fmt.mantissa_bits)


def _dequant_rows_mixed(bytes_tile, scales, fmt_id, mixed_fmts,
                        block_size: int):
    """(T, D) uint8 rows + scales + scalar page format id -> (T, D) f32.

    ``fmt_id`` is a traced scalar (the page's entry in the prefetched
    per-page format array); ``mixed_fmts`` is the *static* tuple of
    formats this kernel was compiled for. Every candidate decode runs and
    a scalar-predicate select picks the live one — branchless, the same
    shape every grid step, which is what keeps the page walk a single
    trace. The E8M0 scale fold is format-independent (scales are
    recomputed at repack time because emax differs per format).
    """
    out = None
    for name in mixed_fmts:
        vals = _decode_bytes_as(bytes_tile, name)
        sel = fmt_id == F.FORMAT_IDS[name]
        out = vals if out is None else jnp.where(sel, vals, out)
    return _fold_scales(out, scales, block_size)


def _mx_attn_kernel(q_ref, ke_ref, ks_ref, ve_ref, vs_ref, kpos_ref,
                    pos_ref, o_ref, *, fmt_name: str, block_size: int,
                    softcap):
    """One (batch, kv_head) cell: full-T attention with masked f32 softmax."""
    q = q_ref[0, 0].astype(jnp.float32)  # (G, D)
    k = _dequant_rows(ke_ref[0, 0], ks_ref[0, 0], fmt_name, block_size)
    v = _dequant_rows(ve_ref[0, 0], vs_ref[0, 0], fmt_name, block_size)
    d = q.shape[-1]
    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * (d ** -0.5)  # (G, T)
    if softcap:
        logits = jnp.tanh(logits / softcap) * softcap
    kpos = kpos_ref[0]
    pos = pos_ref[0]
    mask = (kpos <= pos) & (kpos >= 0)
    logits = jnp.where(mask[None, :], logits, NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    out = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    o_ref[0, 0] = (out / denom).astype(o_ref.dtype)


def mx_attention_decode(q, k_elems, k_scales, v_elems, v_scales, kpos, pos,
                        *, fmt_name: str = "fp8_e4m3", block_size: int = 32,
                        softcap=None, interpret: bool | None = None):
    """Decode attention against an MX-quantized cache. Returns (B,KVH,G,D).

    ``kpos`` may be (T,) shared or (B, T) per-sequence; ``pos`` a scalar or
    (B,) per-sequence — the ragged-batch form continuous batching needs.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    _check_fmt(k_elems, fmt_name)
    b, kvh, g, d = q.shape
    t = k_elems.shape[2]
    nb = k_scales.shape[-1]
    kpos = jnp.asarray(kpos, jnp.int32)
    if kpos.ndim == 1:
        kpos = jnp.broadcast_to(kpos[None], (b, t))
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos[None], (b,))
    kernel = functools.partial(_mx_attn_kernel, fmt_name=fmt_name,
                               block_size=block_size, softcap=softcap)
    ed = k_elems.shape[-1]
    return pl.pallas_call(
        kernel,
        grid=(b, kvh),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, t, ed), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, t, nb), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, t, ed), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, t, nb), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, t), lambda i, j: (i, 0)),
            pl.BlockSpec((1,), lambda i, j: (i,)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(q, k_elems, k_scales, v_elems, v_scales, kpos, pos)


# ---------------------------------------------------------------------------
# paged cache: page-table gather kernel + decode wrapper
# ---------------------------------------------------------------------------


def _gather_pages_kernel(pt_ref, ke_ref, ks_ref, ve_ref, vs_ref,
                         oke_ref, oks_ref, ove_ref, ovs_ref):
    """Copy one pool page tile into its contiguous slot (pure DMA shuffle).

    The interesting part is outside the body: the *input* BlockSpec index
    maps read the scalar-prefetched page table, so block (b, h, p) is DMA'd
    straight from pool page ``page_table[b, p]`` — the kernel never touches
    a wide value and never materializes an indirection on the compute units.
    """
    oke_ref[0, 0] = ke_ref[0, 0]
    oks_ref[0, 0] = ks_ref[0, 0]
    ove_ref[0, 0] = ve_ref[0, 0]
    ovs_ref[0, 0] = vs_ref[0, 0]


def gather_kv_pages(ke_pool, ks_pool, ve_pool, vs_pool, page_table,
                    *, interpret: bool | None = None):
    """Gather per-sequence K/V pages into contiguous compact caches.

    Pools: (NP, KVH, PS, ED) elems + (NP, KVH, PS, NB) scales.
    page_table: (B, P) int32, entries < 0 = unallocated (clamped to page 0;
    callers mask those rows via seq_lens).
    Returns (k_elems, k_scales, v_elems, v_scales) shaped (B, KVH, P*PS, ·).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    npages, kvh, ps, ed = ke_pool.shape
    nb = ks_pool.shape[-1]
    b, pmax = page_table.shape
    t = pmax * ps
    table = jnp.clip(jnp.asarray(page_table, jnp.int32), 0, npages - 1)

    def pool_spec(width):
        return pl.BlockSpec((1, 1, ps, width),
                            lambda i, j, p, pt: (pt[i, p], j, 0, 0))

    def out_spec(width):
        return pl.BlockSpec((1, 1, ps, width),
                            lambda i, j, p, pt: (i, j, p, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kvh, pmax),
        in_specs=[pool_spec(ed), pool_spec(nb), pool_spec(ed), pool_spec(nb)],
        out_specs=[out_spec(ed), out_spec(nb), out_spec(ed), out_spec(nb)],
    )
    return pl.pallas_call(
        _gather_pages_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, kvh, t, ed), ke_pool.dtype),
            jax.ShapeDtypeStruct((b, kvh, t, nb), ks_pool.dtype),
            jax.ShapeDtypeStruct((b, kvh, t, ed), ve_pool.dtype),
            jax.ShapeDtypeStruct((b, kvh, t, nb), vs_pool.dtype),
        ],
        interpret=interpret,
    )(table, ke_pool, ks_pool, ve_pool, vs_pool)


def mx_attention_decode_paged(q, ke_pool, ks_pool, ve_pool, vs_pool,
                              page_table, seq_lens, *,
                              fmt_name: str = "fp8_e4m3",
                              block_size: int = 32, softcap=None,
                              interpret: bool | None = None):
    """Two-pass decode attention through a page table over an MX page pool.

    q: (B, KVH, G, D); pools per :func:`gather_kv_pages`; seq_lens (B,) =
    number of valid cache rows per sequence (query sits at seq_len - 1).
    Returns (B, KVH, G, D) f32, bit-identical to `mx_attention_decode` on
    the equivalent contiguous cache (same gather order, same kernel).

    This materializes the gathered *compact* cache (pass 1) before
    attending over the full padded table (pass 2) — kept as the exactness
    oracle for :func:`mx_attention_decode_fused`, which does both in one
    kernel and never materializes the gather.
    """
    ke, ks, ve, vs = gather_kv_pages(ke_pool, ks_pool, ve_pool, vs_pool,
                                     page_table, interpret=interpret)
    t = ke.shape[2]
    seq_lens = jnp.asarray(seq_lens, jnp.int32)
    kpos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None],
                            (q.shape[0], t))
    return mx_attention_decode(q, ke, ks, ve, vs, kpos, seq_lens - 1,
                               fmt_name=fmt_name, block_size=block_size,
                               softcap=softcap, interpret=interpret)


# ---------------------------------------------------------------------------
# single-pass fused paged decode: page-table walk + dequant + online softmax
# ---------------------------------------------------------------------------


def _quantize_rows(x, fmt_name: str, block_size: int):
    """(T, D) f32 -> (elements (T, ED) storage, scales (T, D//k) uint8).

    Bit-identical to the host cache-write path
    (``attention._quantize_kv_token``, i.e. ``core.quantize``), which is
    what lets the fused kernels' in-kernel page writes substitute for the
    host ``jnp.at[].set`` install without perturbing a single cache byte.
    Shares ``mx_quantize``'s tile quantizer, the repo's other in-kernel
    quantizer.
    """
    return quantize_tile(x, F.get_format(fmt_name), block_size)


#: row-tile budget for one flash-update step, in f32 elements of the
#: (rows, D) partial-output slab. Verify windows and prefill/ragged
#: chunks put ``num_q * G`` query rows in one cell; at large G*D (e.g.
#: head_dim 128 x G 8 x a multi-token window) the full (rows, D) slab
#: outgrows a comfortable VREG/VMEM working set, so the update walks
#: static row tiles instead. Tiling is exact: the online-softmax state
#: (m, l, acc) is per *query row*, so splitting rows changes no
#: accumulation order within any row.
_FLASH_ROW_TILE_ELEMS = 4096


def _flash_update(m_ref, l_ref, acc_ref, q, k, v, mask, softcap, row0=0):
    """One online-softmax accumulation step over a (PS, D) key/value tile.

    Shared by the decode/verify, prefill, and ragged kernels so the
    accumulation order (and therefore the f32 rounding) of every fused
    path is identical by construction. ``q`` (R, D) f32, ``mask``
    (R, PS) bool; the state of its rows is rows ``[row0, row0 + R)`` of
    ``m_ref``, ``l_ref`` and ``acc_ref`` — the ragged kernel keeps every
    KV head's rows in one scratch, and passes a traced ``row0`` that is a
    multiple of R. When R * D exceeds :data:`_FLASH_ROW_TILE_ELEMS` the
    update runs over static row tiles (see there) — bit-identical to the
    untiled form because every row's state is independent.
    """
    rows, d = q.shape
    tile = max(1, _FLASH_ROW_TILE_ELEMS // max(d, 1))
    for lo in range(0, rows, tile):
        hi = min(lo + tile, rows)
        sl = slice(lo, hi)  # rows of the query tile
        # the same rows of the state; a traced start is a multiple of
        # gcd(R, tile), which is what lets the TPU slice the scratch there
        start = (row0 + lo if isinstance(row0, int)
                 else pl.multiple_of(row0 + lo, math.gcd(rows, tile)))
        st = pl.ds(start, hi - lo)
        s = jax.lax.dot_general(
            q[sl], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * (d ** -0.5)  # (r, PS)
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        mrows = mask[sl]
        s = jnp.where(mrows, s, NEG_INF)
        m_prev = m_ref[st]  # (r, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # the explicit mask (not just exp(NEG_INF - m)) guards the
        # all-masked tile: there m_new == NEG_INF and the difference is 0
        probs = jnp.where(mrows, jnp.exp(s - m_new), 0.0)  # (r, PS)
        l_ref[st] = l_ref[st] * alpha + jnp.sum(probs, axis=-1,
                                                keepdims=True)
        acc_ref[st] = acc_ref[st] * alpha + jax.lax.dot_general(
            probs, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[st] = m_new


def _cell(i):
    """Flat page-visit counter slot of grid cell (row ``i``, kv-head
    ``program_id(1)``) in a ``(R, KVH, P)`` page walk. Read grid indices
    at the kernel's top level: interpret mode does not substitute them
    inside ``pl.when`` bodies."""
    return i * pl.num_programs(1) + pl.program_id(1)


#: out spec of the page-visit counter: one int32 per (row, kv-head) cell,
#: whole-array in SMEM (a scalar store to a VMEM block does not lower)
_VISITS_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def _first_window_page(qpos_min, window, page_size: int):
    """Index of the first page any query can see under a sliding window.

    The earliest key row any of the chunk's queries attends is
    ``qpos_min - window + 1`` (the *oldest* query bounds it); pages wholly
    below that hold only masked keys, so both the kernel body and the
    BlockSpec index maps can skip them — the head-page analogue of the
    past-``seq_len`` tail skip. ``window is None`` disables the clamp.
    """
    if window is None:
        return 0
    return jnp.maximum((qpos_min - window + 1) // page_size, 0)


def _mx_attn_fused_kernel(*refs, page_size: int, fmt_name: str,
                          block_size: int, softcap, window, num_q: int,
                          group: int, mixed_fmts=None):
    """One page tile of one (batch, kv-head) cell, flash-style.

    Grid is (B, KVH, P) with P innermost ("arbitrary"), so the VMEM
    scratch — running max ``m``, running denominator ``l``, rescaled
    partial output ``acc`` — persists across the page walk of a cell and
    is re-initialized at page 0. ``pl.when`` skips tiles past
    ``ceil(seq_len / page_size)`` entirely: masked-out pages cost neither
    dequant nor MXU work, and their DMA is elided because the index map
    re-points them at the last valid page (unchanged block index = no
    refetch). The wide K/V tile exists only in VREGs.

    ``num_q`` query tokens per sequence share the page walk (speculative
    verify): the query tile holds ``num_q * group`` rows, rows
    ``[i*group, (i+1)*group)`` belonging to the query at absolute
    position ``seq_len - num_q + i``, and the causal mask is per-row —
    query ``i`` sees keys ``kpos <= seq_len - num_q + i`` (intra-chunk
    causality), so drafted tokens never attend to their own successors.
    ``num_q == 1`` is exactly the decode kernel this generalizes.

    Sliding-window head skip: pages wholly below the oldest query's
    window (``p < _first_window_page``) are skipped exactly like tail
    pages past ``seq_len`` — their keys are fully masked, so the body is
    predicated away (``visits`` counts only pages actually inside the
    window) and the index maps re-point them at the first in-window page
    so their DMA is elided by the revisit rule.

    Mixed-format (tiered) pools: when ``mixed_fmts`` is set, a third
    scalar-prefetch operand carries one format id per *pool page*, and
    the page's id — read through the same page-table walk the BlockSpec
    index maps use (``fmts[tbl[i, p]]``) — selects the dequant path for
    that grid step (branchless select over the static candidate set, so
    the walk stays one trace).
    """
    if mixed_fmts is None:
        (tbl_ref, lens_ref, q_ref, ke_ref, ks_ref, ve_ref, vs_ref,
         o_ref, visits_ref, m_ref, l_ref, acc_ref) = refs
        fmts_ref = None
    else:
        (tbl_ref, lens_ref, fmts_ref, q_ref, ke_ref, ks_ref, ve_ref, vs_ref,
         o_ref, visits_ref, m_ref, l_ref, acc_ref) = refs
    i = pl.program_id(0)
    p = pl.program_id(2)
    cell = _cell(i)
    last = pl.num_programs(2) - 1

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        visits_ref[cell] = 0

    seq_len = lens_ref[i]  # wrapper-clamped to >= num_q
    valid_pages = pl.cdiv(seq_len, page_size)
    first_page = _first_window_page(seq_len - num_q, window, page_size)

    @pl.when((p >= first_page) & (p < valid_pages))
    def _page():
        # the skip predicate's audit trail: counts page bodies actually
        # executed, so tests/benchmarks can assert work == resident pages
        # inside the window
        visits_ref[cell] += 1
        q = q_ref[0, 0].astype(jnp.float32)  # (num_q * G, D)
        if mixed_fmts is None:
            k = _dequant_rows(ke_ref[0, 0], ks_ref[0, 0],
                              fmt_name, block_size)  # (PS, D)
            v = _dequant_rows(ve_ref[0, 0], vs_ref[0, 0],
                              fmt_name, block_size)
        else:
            fid = fmts_ref[tbl_ref[i, p]]
            k = _dequant_rows_mixed(ke_ref[0, 0], ks_ref[0, 0],
                                    fid, mixed_fmts, block_size)
            v = _dequant_rows_mixed(ve_ref[0, 0], vs_ref[0, 0],
                                    fid, mixed_fmts, block_size)
        kpos = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        rows = num_q * group
        # row r belongs to query index r // group; query i sits at
        # absolute position seq_len - num_q + i
        qpos = seq_len - num_q + jax.lax.broadcasted_iota(
            jnp.int32, (rows, 1), 0) // group
        mask = kpos <= qpos  # (R, PS)
        if window is not None:
            mask &= kpos > qpos - window
        _flash_update(m_ref, l_ref, acc_ref, q, k, v, mask, softcap)

    @pl.when(p == last)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def mx_attention_verify_fused(q, ke_pool, ks_pool, ve_pool, vs_pool,
                              page_table, seq_lens, *,
                              fmt_name: str = "fp8_e4m3",
                              block_size: int = 32, softcap=None,
                              window=None, page_fmts=None, mixed_fmts=None,
                              debug_visits: bool = False,
                              interpret: bool | None = None):
    """Single-pass fused paged attention for ``Tq >= 1`` query tokens.

    The speculative-decoding verify kernel: the draft tokens' K/V have
    already been written into the sequence's pages, and all ``Tq``
    queries — the last accepted token plus the drafts, at absolute
    positions ``seq_len - Tq .. seq_len - 1`` — share one page walk.
    One Pallas kernel with grid (B, KVH, P): the BlockSpec index maps
    read the scalar-prefetched page table, each grid step dequantizes one
    compact fp8/fp4 + E8M0 pool page tile in-register exactly once for
    the whole chunk (this is the amortization speculative decoding buys:
    K+1 tokens of attention per page-tile DMA + dequant instead of one),
    and the softmax is accumulated online per query row in VMEM scratch.
    Causal intra-chunk masking is per row: query ``i`` attends keys
    ``kpos <= seq_len - Tq + i``, so a draft never sees its successors
    and row ``i``'s output is exactly what a one-token decode at position
    ``seq_len - Tq + i`` would compute.

    q: (B, KVH, Tq, G, D); pools (NP, KVH, PS, ED/NB); page_table (B, P)
    i32 (entries < 0 = unallocated, clamped); seq_lens (B,) valid cache
    rows per sequence *including* the chunk's own tokens (inactive rows
    may pass 0, clamped to Tq so every query position stays valid —
    garbage rows whose logits the host ignores). ``window`` masks keys
    at ``kpos <= qpos - window`` per query row. Returns
    (B, KVH, Tq, G, D) f32.

    ``debug_visits=True`` additionally returns a (B, KVH, 1) i32 count of
    page bodies actually executed per cell — the kernel always maintains
    it (one SMEM scalar store per visited tile), and tests/benchmarks assert
    it equals ``ceil(seq_lens / PS)`` exactly (minus, under a sliding
    window, the head pages wholly below the oldest query's window, which
    are skipped like tail pages — visits is then exactly the page count
    actually *inside* the window), making the page-skip predicate
    falsifiable on every backend (off-TPU, interpret-mode wall-clock
    cannot see the skip: the grid loop visits every cell and only the
    body is predicated away).

    ``page_fmts`` switches the kernel to mixed-format (tiered) pools:
    a (NP,) i32 array of per-*pool-page* format ids
    (:data:`repro.core.formats.FORMAT_IDS`), prefetched alongside the
    page table; the pools must then be full-width uint8 byte rows
    (narrower formats occupy the row prefix). ``mixed_fmts`` is the
    static candidate-format tuple compiled into the dequant select
    (default :data:`MIXED_FMTS_DEFAULT`).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    mixed = page_fmts is not None
    _check_fmt(ke_pool, fmt_name, mixed=mixed)
    if mixed and mixed_fmts is None:
        mixed_fmts = MIXED_FMTS_DEFAULT
    mixed_fmts = tuple(mixed_fmts) if mixed else None
    b, kvh, tq, g, d = q.shape
    rows = tq * g
    npages, ps = ke_pool.shape[0], ke_pool.shape[2]
    ed = ke_pool.shape[-1]
    nb = ks_pool.shape[-1]
    pmax = page_table.shape[1]
    table = jnp.clip(jnp.asarray(page_table, jnp.int32), 0, npages - 1)
    lens = jnp.maximum(jnp.asarray(seq_lens, jnp.int32), tq)
    qr = q.reshape(b, kvh, rows, d)

    def pool_spec(width):
        def imap(i, j, p, tbl, ln, *_fmts):
            # clamp skipped steps into the live page range: tail steps
            # (p >= valid) re-point at the last valid page, head steps
            # wholly below the sliding window at the first in-window
            # page (ln is wrapper-clamped >= Tq >= 1, so valid >= 1).
            # An unchanged block index means the pipeline elides the
            # DMA entirely, so skipped pages cost no HBM traffic.
            valid = pl.cdiv(ln[i], ps)
            first = _first_window_page(ln[i] - tq, window, ps)
            return (tbl[i, jnp.clip(p, first, valid - 1)], j, 0, 0)
        return pl.BlockSpec((1, 1, ps, width), imap)

    scalar_ops = [table, lens]
    if mixed:
        scalar_ops.append(jnp.asarray(page_fmts, jnp.int32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalar_ops),
        grid=(b, kvh, pmax),
        in_specs=[
            pl.BlockSpec((1, 1, rows, d),
                         lambda i, j, p, *_: (i, j, 0, 0)),
            pool_spec(ed), pool_spec(nb), pool_spec(ed), pool_spec(nb),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, rows, d),
                         lambda i, j, p, *_: (i, j, 0, 0)),
            _VISITS_SPEC,
        ],
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),  # running max m
            pltpu.VMEM((rows, 1), jnp.float32),  # running denominator l
            pltpu.VMEM((rows, d), jnp.float32),  # rescaled partial output
        ],
    )
    kernel = functools.partial(
        _mx_attn_fused_kernel, page_size=ps, fmt_name=fmt_name,
        block_size=block_size, softcap=softcap, window=window,
        num_q=tq, group=g, mixed_fmts=mixed_fmts)
    out, visits = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, kvh, rows, d), jnp.float32),
            jax.ShapeDtypeStruct((b * kvh,), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*scalar_ops, qr, ke_pool, ks_pool, ve_pool, vs_pool)
    out = out.reshape(b, kvh, tq, g, d)
    return (out, visits.reshape(b, kvh, 1)) if debug_visits else out


def mx_attention_decode_fused(q, ke_pool, ks_pool, ve_pool, vs_pool,
                              page_table, seq_lens, *,
                              fmt_name: str = "fp8_e4m3",
                              block_size: int = 32, softcap=None,
                              window=None, page_fmts=None, mixed_fmts=None,
                              debug_visits: bool = False,
                              interpret: bool | None = None):
    """Single-pass fused paged decode attention (the serve-engine hot path).

    The ``Tq == 1`` case of :func:`mx_attention_verify_fused` (one kernel
    serves both paths — decode is just a verify chunk of one): the
    BlockSpec index maps read the scalar-prefetched page table, each grid
    step dequantizes one compact fp8/fp4 + E8M0 pool page tile
    in-register, and the softmax is accumulated online (flash-decoding)
    in VMEM scratch — no gathered cache, wide or compact, ever exists in
    HBM, and page tiles at or past ``ceil(seq_len / page_size)`` are
    skipped, so per-step work scales with resident tokens rather than
    the padded table.

    q: (B, KVH, G, D); pools (NP, KVH, PS, ED/NB); page_table (B, P) i32
    (entries < 0 = unallocated, clamped — rows past ``seq_lens`` never
    contribute); seq_lens (B,) valid cache rows per sequence (the query
    sits at seq_len - 1; inactive rows may pass 0, clamped to 1 so the
    denominator stays finite, matching the einsum path's pos=0 garbage
    rows whose logits the host ignores). ``window`` masks keys at
    ``kpos <= pos - window`` (sliding-window layers). Returns
    (B, KVH, G, D) f32; matches the two-pass/einsum f32 reference to
    online-softmax rounding (~1e-7, well inside 1e-5). ``debug_visits``
    as in :func:`mx_attention_verify_fused`.
    """
    res = mx_attention_verify_fused(
        q[:, :, None], ke_pool, ks_pool, ve_pool, vs_pool, page_table,
        seq_lens, fmt_name=fmt_name, block_size=block_size,
        softcap=softcap, window=window, page_fmts=page_fmts,
        mixed_fmts=mixed_fmts, debug_visits=debug_visits,
        interpret=interpret)
    if debug_visits:
        out, visits = res
        return out[:, :, 0], visits
    return res[:, :, 0]


# ---------------------------------------------------------------------------
# single-pass fused chunked prefill: page walk + quantize-write + attention
# ---------------------------------------------------------------------------


def _mx_attn_prefill_kernel(*refs, page_size: int, fmt_name: str,
                            block_size: int, softcap, window, chunk: int,
                            group: int, mixed_fmts=None):
    """One page tile of one (batch, kv-head) prefill cell.

    The page walk splits into three regions per cell:

      * ``p < c0`` (resident pages, written by earlier chunks / a shared
        prefix): read the compact pool tile, dequantize in-register, fold
        into the online softmax — exactly the verify kernel's body.
      * ``c0 <= p < valid`` (this chunk's own pages): quantize the
        chunk's wide K/V page slice in-register (``_quantize_rows``, the
        exact ``core.quantize`` math), store the compact tile to the
        sequence's pool page through the *output* index map, and attend
        over the in-register dequantized snap — the same bytes any later
        reader will load, so prefill, decode and verify agree
        bit-for-bit. The wide K/V rows never touch HBM beyond the
        one-chunk projection output.
      * ``p >= valid`` / ``p < first`` (past the resident rows / wholly
        below the sliding window): body predicated away, DMA elided by
        index-map clamping.

    Chunk alignment contract (enforced by the nn wrapper): chunk starts
    are page-aligned and the chunk covers whole pages, so every visited
    page is *either* fully resident *or* fully owned by this chunk —
    never a blend. The last chunk of a prompt is padded up to the fixed
    chunk length; ``seq_len`` counts only the real rows, so wholly-padded
    pages are never written and the partial last page's padding rows are
    dead by position masking (exactly like rejected speculative drafts).

    Mixed-format (tiered) pools (``mixed_fmts`` set): resident pages
    dequantize through the per-page format id (fourth scalar-prefetch
    operand, indexed via the page table exactly like the verify kernel);
    chunk pages are always written in the hot format ``fmt_name`` (an
    fp8 — the engine marks freshly written pages hot) with the fp8 bytes
    bitcast into the full-width uint8 rows.
    """
    if mixed_fmts is None:
        (tbl_ref, start_ref, lens_ref, q_ref, kc_ref, vc_ref,
         ke_ref, ks_ref, ve_ref, vs_ref, o_ref,
         oke_ref, oks_ref, ove_ref, ovs_ref, visits_ref,
         m_ref, l_ref, acc_ref) = refs
        fmts_ref = None
    else:
        (tbl_ref, start_ref, lens_ref, fmts_ref, q_ref, kc_ref, vc_ref,
         ke_ref, ks_ref, ve_ref, vs_ref, o_ref,
         oke_ref, oks_ref, ove_ref, ovs_ref, visits_ref,
         m_ref, l_ref, acc_ref) = refs
    i = pl.program_id(0)
    p = pl.program_id(2)
    cell = _cell(i)
    last = pl.num_programs(2) - 1

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        visits_ref[cell] = 0

    start = start_ref[i]  # chunk start row, page-aligned
    seq_len = lens_ref[i]  # resident rows incl. this chunk's real tokens
    c0 = start // page_size
    valid_pages = pl.cdiv(seq_len, page_size)
    first_page = _first_window_page(start, window, page_size)

    def _attend_tile(k, v):
        q = q_ref[0, 0].astype(jnp.float32)  # (chunk * G, D)
        kpos = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        rows = chunk * group
        # row r belongs to chunk query r // group at absolute position
        # start + r // group (intra-chunk causality per row)
        qpos = start + jax.lax.broadcasted_iota(
            jnp.int32, (rows, 1), 0) // group
        mask = kpos <= qpos  # (R, PS)
        if window is not None:
            mask &= kpos > qpos - window
        _flash_update(m_ref, l_ref, acc_ref, q, k, v, mask, softcap)

    @pl.when((p >= first_page) & (p < c0))
    def _resident_page():
        visits_ref[cell] += 1
        if mixed_fmts is None:
            k = _dequant_rows(ke_ref[0, 0], ks_ref[0, 0],
                              fmt_name, block_size)  # (PS, D)
            v = _dequant_rows(ve_ref[0, 0], vs_ref[0, 0],
                              fmt_name, block_size)
        else:
            fid = fmts_ref[tbl_ref[i, p]]
            k = _dequant_rows_mixed(ke_ref[0, 0], ks_ref[0, 0],
                                    fid, mixed_fmts, block_size)
            v = _dequant_rows_mixed(ve_ref[0, 0], vs_ref[0, 0],
                                    fid, mixed_fmts, block_size)
        _attend_tile(k, v)

    @pl.when((p >= c0) & (p < valid_pages))
    def _chunk_page():
        visits_ref[cell] += 1
        kw = kc_ref[0, 0].astype(jnp.float32)  # (PS, D) wide
        vw = vc_ref[0, 0].astype(jnp.float32)
        kq_e, kq_s = _quantize_rows(kw, fmt_name, block_size)
        vq_e, vq_s = _quantize_rows(vw, fmt_name, block_size)
        if mixed_fmts is None:
            oke_ref[0, 0] = kq_e
            ove_ref[0, 0] = vq_e
        else:
            # hot-format fp8 bytes into the full-width uint8 rows
            oke_ref[0, 0] = jax.lax.bitcast_convert_type(
                kq_e, jnp.uint8)
            ove_ref[0, 0] = jax.lax.bitcast_convert_type(
                vq_e, jnp.uint8)
        oks_ref[0, 0] = kq_s
        ovs_ref[0, 0] = vq_s
        # attend over the in-register dequantized snap — identical bytes
        # (and therefore identical f32 values) to what a later page read
        # would produce, without a round trip through HBM
        _attend_tile(_dequant_rows(kq_e, kq_s, fmt_name, block_size),
                     _dequant_rows(vq_e, vq_s, fmt_name, block_size))

    @pl.when(p == last)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def mx_attention_prefill_fused(q, k_chunk, v_chunk, ke_pool, ks_pool,
                               ve_pool, vs_pool, page_table, chunk_start,
                               seq_lens, *, fmt_name: str = "fp8_e4m3",
                               block_size: int = 32, softcap=None,
                               window=None, page_fmts=None, mixed_fmts=None,
                               debug_visits: bool = False,
                               interpret: bool | None = None):
    """Single-pass fused chunked paged prefill (quantize-into-pages).

    One prompt chunk of ``C`` tokens runs against the MX page pool in a
    single Pallas kernel: the chunk's queries attend over every page
    written so far *plus* the chunk itself (per-row causal masking, the
    prefill generalization of :func:`mx_attention_verify_fused`'s draft
    chunk), and the chunk's own K/V is quantized in-register and written
    straight into its pool pages through aliased outputs whose index maps
    walk the scalar-prefetched page table. No wide prefill cache is ever
    materialized and no separate install pass runs: per-chunk work scales
    with the tokens resident so far, and the serve engine's jitted trace
    population for prefill is O(1) fixed chunk shapes.

    Layouts::

      q          (B, KVH, C, G, D)  wide chunk queries (RoPE'd)
      k_chunk    (B, KVH, C, D)     wide chunk keys (RoPE'd)
      v_chunk    (B, KVH, C, D)     wide chunk values
      pools      (NP, KVH, PS, ED/NB) as the decode/verify kernels
      page_table (B, P) i32         entries < 0 = unallocated (clamped)
      chunk_start (B,) i32          chunk's first absolute row; must be
                                    page-aligned (see alignment contract)
      seq_lens   (B,) i32           resident rows *including* the chunk's
                                    real tokens, i.e. chunk_start + the
                                    number of non-padding chunk rows

    Alignment contract (the nn layer enforces it statically): ``C`` is a
    page multiple and ``chunk_start`` is page-aligned, so every page is
    either fully resident or fully this chunk's — the kernel never blends
    pool rows and chunk rows inside one tile. The last chunk of a prompt
    is padded up to ``C``; ``seq_lens`` counts only real rows, so pages
    wholly past ``seq_lens`` are neither written nor read, and padding
    rows sharing the final partial page are written as garbage that every
    reader masks by position (the same dead-row contract as rejected
    speculative drafts). Padding queries produce garbage output rows the
    caller ignores.

    Returns ``(out (B, KVH, C, G, D) f32, (ke, ks, ve, vs) updated
    pools)`` — the pool outputs alias the inputs (in-place page writes
    under jit donation). With ``debug_visits=True`` additionally returns
    the (B, KVH, 1) executed-page counter; it must equal
    ``ceil(seq_lens / PS)`` minus the pages wholly below the sliding
    window, exactly as in the decode/verify kernels.

    When ``B > 1``, rows must not share pages between one row's chunk
    range and another row's read range (the serve engine prefills one
    sequence per call; batched calls are for tests/benchmarks with
    disjoint tables).

    When ``B > 1`` every row's chunk pages must be freshly allocated
    (never shared), which the engine guarantees — chunk pages are new
    allocations by construction. Same-shape chunks from *different*
    concurrently-prefilling sequences may therefore batch into one
    dispatch (each row reads only its own table row; resident pages may
    be COW-shared across rows since they are read-only here).

    ``page_fmts``/``mixed_fmts`` switch to mixed-format (tiered) pools
    exactly as in :func:`mx_attention_verify_fused`; ``fmt_name`` must
    then be an fp8 (the hot format freshly written pages get).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    mixed = page_fmts is not None
    _check_fmt(ke_pool, fmt_name, mixed=mixed)
    if mixed:
        if mixed_fmts is None:
            mixed_fmts = MIXED_FMTS_DEFAULT
        mixed_fmts = tuple(mixed_fmts)
        if F.get_format(fmt_name).bits != 8:
            raise ValueError(
                "tiered prefill writes chunk pages in the hot format, "
                f"which must be an fp8; got {fmt_name!r}")
    else:
        mixed_fmts = None
    b, kvh, c, g, d = q.shape
    rows = c * g
    npages, ps = ke_pool.shape[0], ke_pool.shape[2]
    ed = ke_pool.shape[-1]
    nb = ks_pool.shape[-1]
    pmax = page_table.shape[1]
    if c % ps != 0:
        raise ValueError(
            f"chunk length {c} must be a whole number of pages "
            f"(page_size={ps}): a partial chunk page would blend resident "
            "and chunk rows inside one tile")
    cps = c // ps  # chunk pages (static)
    table = jnp.clip(jnp.asarray(page_table, jnp.int32), 0, npages - 1)
    start = jnp.asarray(chunk_start, jnp.int32)
    # at least one real token per chunk, at most the whole chunk
    lens = jnp.clip(jnp.asarray(seq_lens, jnp.int32), start + 1, start + c)
    qr = q.reshape(b, kvh, rows, d)

    def pool_in_spec(width):
        def imap(i, j, p, tbl, st, ln, *_fmts):
            # resident pages map to themselves; chunk pages (whose pool
            # bytes are stale — the kernel writes them this pass) and
            # below-window head pages re-point at the nearest live
            # resident page so their DMA is elided by the revisit rule.
            # A chunk starting at row 0 has no resident pages at all;
            # the clamp then parks every read on the first chunk page's
            # pool slot, whose bytes the body never uses.
            c0 = st[i] // ps
            first = _first_window_page(st[i], window, ps)
            hi = jnp.maximum(c0 - 1, first)
            return (tbl[i, jnp.clip(p, first, hi)], j, 0, 0)
        return pl.BlockSpec((1, 1, ps, width), imap)

    def chunk_in_spec():
        def imap(i, j, p, tbl, st, ln, *_fmts):
            # page p of the walk is chunk page p - c0; steps outside the
            # chunk range clamp to its ends (same-index revisit = no DMA)
            return (i, j, jnp.clip(p - st[i] // ps, 0, cps - 1), 0)
        return pl.BlockSpec((1, 1, ps, d), imap)

    def pool_out_spec(width):
        def imap(i, j, p, tbl, st, ln, *_fmts):
            # steps below the chunk park on the first chunk page (it is
            # written before the index ever changes), steps past the
            # last written page park on it (flushed once at cell end)
            c0 = st[i] // ps
            valid = pl.cdiv(ln[i], ps)
            return (tbl[i, jnp.clip(p, c0, valid - 1)], j, 0, 0)
        return pl.BlockSpec((1, 1, ps, width), imap)

    scalar_ops = [table, start, lens]
    if mixed:
        scalar_ops.append(jnp.asarray(page_fmts, jnp.int32))
    ns = len(scalar_ops)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=ns,
        grid=(b, kvh, pmax),
        in_specs=[
            pl.BlockSpec((1, 1, rows, d),
                         lambda i, j, p, *_: (i, j, 0, 0)),
            chunk_in_spec(), chunk_in_spec(),
            pool_in_spec(ed), pool_in_spec(nb),
            pool_in_spec(ed), pool_in_spec(nb),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, rows, d),
                         lambda i, j, p, *_: (i, j, 0, 0)),
            pool_out_spec(ed), pool_out_spec(nb),
            pool_out_spec(ed), pool_out_spec(nb),
            _VISITS_SPEC,
        ],
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),  # running max m
            pltpu.VMEM((rows, 1), jnp.float32),  # running denominator l
            pltpu.VMEM((rows, d), jnp.float32),  # rescaled partial output
        ],
    )
    kernel = functools.partial(
        _mx_attn_prefill_kernel, page_size=ps, fmt_name=fmt_name,
        block_size=block_size, softcap=softcap, window=window,
        chunk=c, group=g, mixed_fmts=mixed_fmts)
    out, oke, oks, ove, ovs, visits = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, kvh, rows, d), jnp.float32),
            jax.ShapeDtypeStruct(ke_pool.shape, ke_pool.dtype),
            jax.ShapeDtypeStruct(ks_pool.shape, ks_pool.dtype),
            jax.ShapeDtypeStruct(ve_pool.shape, ve_pool.dtype),
            jax.ShapeDtypeStruct(vs_pool.shape, vs_pool.dtype),
            jax.ShapeDtypeStruct((b * kvh,), jnp.int32),
        ],
        # pools update in place (operand indices count the scalar-prefetch
        # operands, then q, k_chunk, v_chunk, then the four pools)
        input_output_aliases={ns + 3 + k: 1 + k for k in range(4)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*scalar_ops, qr, k_chunk, v_chunk,
      ke_pool, ks_pool, ve_pool, vs_pool)
    out = out.reshape(b, kvh, c, g, d)
    pools = (oke, oks, ove, ovs)
    return ((out, pools, visits.reshape(b, kvh, 1)) if debug_visits
            else (out, pools))


# ---------------------------------------------------------------------------
# single-pass fused ragged engine step: decode + verify + prefill-chunk rows
# in one page walk, with the write window quantized in-kernel
# ---------------------------------------------------------------------------


def _mx_attn_ragged_kernel(*refs, page_size: int, fmt_name: str,
                           block_size: int, softcap, window, width: int,
                           group: int, mixed_fmts=None):
    """One page of one ragged-step row, for every KV head of the row.

    The grid is ``(R, P)``: each step loads the page's ``(KVH, PS, ·)``
    block — one contiguous slab of the KV-head-major pool — and runs the
    per-head work below once per KV head, head after head, so a row pays
    one pipelined grid step per page rather than one per (head, page).
    Each head's math, row tiling and page order are those of a
    one-head-per-step walk, so outputs and written bytes do not depend
    on how many heads share a step.

    The generalization that lets decode rows (1 new token), speculative
    verify windows (1 + K new tokens), and prefill chunks (up to W new
    tokens) coexist in ONE grid: each row carries only ``(row_start,
    seq_len)`` scalars — ``row_start`` is where this step's new tokens
    begin and ``seq_len = row_start + n_new`` where they end — and the
    page walk splits into three regions per row:

      * ``first <= p < w0`` (resident pages, ``w0 = row_start // PS``):
        read the compact pool tile, dequantize in-register, fold into the
        online softmax — exactly the verify kernel's body.
      * ``w0 <= p < valid`` (the row's *write window*): the step's wide
        new K/V rows are scattered onto page-row positions by an exact
        one-hot (PS, W) f32 matmul (each product is 1.0 * x or 0.0 * x,
        so the gather is bit-exact), quantized in-register
        (``_quantize_rows``, the same math as the host install path),
        merged with the page's existing codes row-by-row in the *code*
        domain (``where(row_start <= kpos < seq_len, new, old)`` — rows
        outside the window keep their stored bytes untouched), written
        back through the aliased pool outputs, and attended over the
        merged dequantized tile. This is what removes the split path's
        per-token host ``.at[].set`` HBM round-trip: unlike the prefill
        kernel, the window need NOT be page-aligned — a decode token in
        the middle of a half-full page merges into it in-register.
      * ``p < first`` / ``p >= valid``: body predicated away, DMA elided
        by index-map clamping (the decode/verify kernels' skip rule).

    Query rows: each head holds ``W * G`` query rows; row r belongs to
    query ``t = r // G`` at absolute position ``row_start + min(t,
    n_new - 1)`` — padding queries (t >= n_new: decode rows in a W > 1
    batch, the tail of a final partial chunk) clamp onto the last real
    position, producing duplicate garbage output rows the host ignores,
    while real rows see exactly the mask the split kernels apply.

    Inactive slots pass ``row_start = 0, seq_len = 1`` with an
    all-negative table row: the wrapper maps negative entries onto the
    pool's LAST page, which callers must reserve as a scratch ("trash")
    page — inactive rows then read and write only that page and no live
    page is ever touched by a dead row.

    Mixed-format (tiered) pools: one format id per pool page, shared by
    its heads and read once per step; resident pages dequantize through
    it; write-window pages are guaranteed base-fp8 by the engine
    (freshly written pages are hot), so old and new codes merge in one
    format and the fp8 bytes bitcast into the full-width uint8 rows
    exactly as in the prefill kernel.
    """
    if mixed_fmts is None:
        (tbl_ref, start_ref, lens_ref, q_ref, kn_ref, vn_ref,
         ke_ref, ks_ref, ve_ref, vs_ref, o_ref,
         oke_ref, oks_ref, ove_ref, ovs_ref, visits_ref,
         m_ref, l_ref, acc_ref) = refs
        fmts_ref = None
    else:
        (tbl_ref, start_ref, lens_ref, fmts_ref, q_ref, kn_ref, vn_ref,
         ke_ref, ks_ref, ve_ref, vs_ref, o_ref,
         oke_ref, oks_ref, ove_ref, ovs_ref, visits_ref,
         m_ref, l_ref, acc_ref) = refs
    i = pl.program_id(0)
    p = pl.program_id(1)
    kvh = q_ref.shape[1]
    rows = width * group
    last = pl.num_programs(1) - 1

    # the skip predicate's audit trail: the row's page-visit counter
    # slots, one per kv-head, each counting every executed page body
    slots = [i * kvh + h for h in range(kvh)]

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        for s in slots:
            visits_ref[s] = 0

    start = start_ref[i]  # first new-token row of this step
    seq_len = lens_ref[i]  # resident rows incl. this step's new tokens
    n_new = seq_len - start
    w0 = start // page_size
    valid_pages = pl.cdiv(seq_len, page_size)
    first_page = _first_window_page(start, window, page_size)

    def _page_mask():
        kpos = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        # row r belongs to query t = r // G at absolute position
        # start + min(t, n_new - 1): real queries get exactly the split
        # kernels' positions, padding queries clamp onto the last real one
        t = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // group
        qpos = start + jnp.minimum(t, n_new - 1)
        mask = kpos <= qpos  # (W * G, PS)
        if window is not None:
            mask &= kpos > qpos - window
        return mask

    def _attend_tile(h, k, v, mask):
        q = q_ref[0, h].astype(jnp.float32)  # (W * G, D)
        # head h's softmax state is rows [h * W * G, (h + 1) * W * G) of
        # the 2-D scratch (a head axis in front of m's and l's one-lane
        # minor dim does not tile)
        _flash_update(m_ref, l_ref, acc_ref, q, k, v, mask, softcap,
                      row0=h * rows)

    def _dequant(elems, scales, fid):
        if mixed_fmts is None:
            return _dequant_rows(elems, scales, fmt_name, block_size)
        return _dequant_rows_mixed(elems, scales, fid, mixed_fmts,
                                   block_size)

    def _each_head(body):
        # a loop, not a static unroll: the same code for every head keeps
        # the kernel's compile time and code size those of one head
        def step(h, carry):
            body(h)
            return carry
        jax.lax.fori_loop(0, kvh, step, 0)

    @pl.when((p >= first_page) & (p < w0))
    def _resident_page():
        for s in slots:
            visits_ref[s] += 1
        fid = None if mixed_fmts is None else fmts_ref[tbl_ref[i, p]]
        mask = _page_mask()

        def head(h):
            k = _dequant(ke_ref[0, h], ks_ref[0, h], fid)  # (PS, D)
            v = _dequant(ve_ref[0, h], vs_ref[0, h], fid)
            _attend_tile(h, k, v, mask)
        _each_head(head)

    @pl.when((p >= w0) & (p < valid_pages))
    def _write_page():
        for s in slots:
            visits_ref[s] += 1
        fid = None if mixed_fmts is None else fmts_ref[tbl_ref[i, p]]
        mask = _page_mask()
        # scatter new row t onto page row j where start + t == p*PS + j:
        # a one-hot f32 matmul (products are 1.0*x or 0.0*x — exact), so
        # page rows outside [start, seq_len) gather exact zeros that the
        # merge below discards anyway
        jrow = jax.lax.broadcasted_iota(
            jnp.int32, (page_size, width), 0)  # page row
        tcol = jax.lax.broadcasted_iota(
            jnp.int32, (page_size, width), 1)  # new-row index
        kpos_rows = p * page_size + jrow[:, :1]  # (PS, 1)
        onehot = ((start + tcol) == (p * page_size + jrow)
                  ).astype(jnp.float32)  # (PS, W)
        in_w = (kpos_rows >= start) & (kpos_rows < seq_len)  # (PS, 1)

        def head(h):
            kw = kn_ref[0, h].astype(jnp.float32)  # (W, D) wide new rows
            vw = vn_ref[0, h].astype(jnp.float32)
            k_page = jax.lax.dot_general(
                onehot, kw, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)  # (PS, D)
            v_page = jax.lax.dot_general(
                onehot, vw, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            kq_e, kq_s = _quantize_rows(k_page, fmt_name, block_size)
            vq_e, vq_s = _quantize_rows(v_page, fmt_name, block_size)
            if mixed_fmts is not None:
                kq_e = jax.lax.bitcast_convert_type(kq_e, jnp.uint8)
                vq_e = jax.lax.bitcast_convert_type(vq_e, jnp.uint8)
            # merge in the CODE domain: in-window page rows take this
            # step's freshly quantized codes, the rest keep their stored
            # bytes — then write the whole tile back through the aliased
            # output
            k_codes = jnp.where(in_w, kq_e, ke_ref[0, h])
            v_codes = jnp.where(in_w, vq_e, ve_ref[0, h])
            k_scales = jnp.where(in_w, kq_s, ks_ref[0, h])
            v_scales = jnp.where(in_w, vq_s, vs_ref[0, h])
            oke_ref[0, h] = k_codes
            ove_ref[0, h] = v_codes
            oks_ref[0, h] = k_scales
            ovs_ref[0, h] = v_scales
            # attend over the merged tile — identical bytes (and
            # therefore identical f32 values) to what the split path's
            # separate host install + page re-read would produce
            _attend_tile(h, _dequant(k_codes, k_scales, fid),
                         _dequant(v_codes, v_scales, fid), mask)
        _each_head(head)

    @pl.when(p == last)
    def _finish():
        for h in range(kvh):
            hrows = pl.ds(h * rows, rows)
            o_ref[0, h] = (acc_ref[hrows] / l_ref[hrows]).astype(o_ref.dtype)


def mx_attention_ragged_fused(q, k_new, v_new, ke_pool, ks_pool, ve_pool,
                              vs_pool, page_table, row_start, seq_lens, *,
                              fmt_name: str = "fp8_e4m3",
                              block_size: int = 32, softcap=None,
                              window=None, page_fmts=None, mixed_fmts=None,
                              debug_visits: bool = False,
                              interpret: bool | None = None):
    """One-dispatch ragged engine step over the MX page pool.

    The single kernel behind ``ServeConfig.step_mode="ragged"``: every
    engine-step row — a plain decode token, a speculative verify window,
    or an in-flight prefill chunk — is one grid row of the SAME
    ``(R, P)`` scalar-prefetch page walk, distinguished only by its
    ``(row_start, seq_len)`` metadata. A grid step loads one page for
    all of the row's KV heads (the ``KVH`` the pools carry: the local
    head slice under the serve engine's KV-head ``shard_map``) and
    attends head after head, so a row pays one pipelined step per page
    of its table, not one per (head, page). Each row's new K/V rows are
    quantized and merged into its pages *inside* the kernel through
    aliased pool outputs (see :func:`_mx_attn_ragged_kernel`), so a
    steady-state mixed batch costs exactly one device dispatch and the
    decode/verify paths stop paying a separate 1-row ``.at[].set`` HBM
    round-trip per token.

    Layouts::

      q          (R, KVH, W, G, D)  wide step queries (RoPE'd); W is the
                                    static row width = max over modes of
                                    the per-row new-token count
      k_new      (R, KVH, W, D)     wide new keys (RoPE'd)
      v_new      (R, KVH, W, D)     wide new values
      pools      (NP, KVH, PS, ED/NB) as the decode/verify kernels
      page_table (R, P) i32         entries < 0 map to pool page NP - 1
      row_start  (R,) i32           first absolute row this step writes
      seq_lens   (R,) i32           row_start + n_new (n_new in [1, W])

    Unlike the prefill kernel, ``row_start`` need NOT be page-aligned —
    the write window merges into partially filled pages row-by-row in
    the code domain. Rows only ever write pages in ``[row_start // PS,
    ceil(seq_len / PS))`` and the engine guarantees those pages are
    exclusively owned (COW for decode/verify windows, fresh allocations
    for chunk pages), so concurrent rows never write the same page.

    Trash-page contract: negative table entries (inactive slots, table
    tails) are mapped to the pool's **last** page, which the caller must
    reserve as scratch — the ragged engine allocates ``num_pages + 1``
    physical pages and never hands out the last one. Inactive rows
    (``row_start = 0, seq_len = 1``) then write their garbage there.

    Returns ``(out (R, KVH, W, G, D) f32, (ke, ks, ve, vs) updated
    pools)`` — pool outputs alias the inputs. ``debug_visits=True``
    additionally returns the (R, KVH, 1) executed-page counter, exactly
    ``ceil(seq_lens / PS)`` minus sliding-window head pages as in the
    other fused kernels. ``page_fmts``/``mixed_fmts`` switch to
    mixed-format (tiered) pools; ``fmt_name`` must then be an fp8 (the
    hot format) and every write-window page must already be base-fp8
    (the engine's hot-write invariant).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    mixed = page_fmts is not None
    _check_fmt(ke_pool, fmt_name, mixed=mixed)
    if mixed:
        if mixed_fmts is None:
            mixed_fmts = MIXED_FMTS_DEFAULT
        mixed_fmts = tuple(mixed_fmts)
        if F.get_format(fmt_name).bits != 8:
            raise ValueError(
                "tiered ragged steps write the window in the hot format, "
                f"which must be an fp8; got {fmt_name!r}")
    else:
        mixed_fmts = None
    r, kvh, w, g, d = q.shape
    rows = w * g
    npages, ps = ke_pool.shape[0], ke_pool.shape[2]
    ed = ke_pool.shape[-1]
    nb = ks_pool.shape[-1]
    pmax = page_table.shape[1]
    table = jnp.asarray(page_table, jnp.int32)
    # negative entries -> the reserved trash page (see docstring); live
    # entries clamp defensively into the pool
    table = jnp.where(table < 0, npages - 1,
                      jnp.clip(table, 0, npages - 1))
    start = jnp.asarray(row_start, jnp.int32)
    # at least one new token per row, at most the whole width
    lens = jnp.clip(jnp.asarray(seq_lens, jnp.int32), start + 1, start + w)
    qr = q.reshape(r, kvh, rows, d)

    # pool blocks hold one page for all of the row's KV heads: the pool
    # is KV-head major within a page, so (1, KVH, PS, width) is one
    # contiguous slab
    def pool_in_spec(width_):
        def imap(i, p, tbl, st, ln, *_fmts):
            # every page in [first, valid) is read — resident pages to
            # attend, write-window pages to merge with; skipped steps
            # clamp into that range so their DMA is elided
            valid = pl.cdiv(ln[i], ps)
            first = _first_window_page(st[i], window, ps)
            return (tbl[i, jnp.clip(p, first, valid - 1)], 0, 0, 0)
        return pl.BlockSpec((1, kvh, ps, width_), imap)

    def row_spec(rows_):
        # per-row operands (queries, output, the step's wide new K/V
        # rows): constant across the page walk, fetched once per row
        return pl.BlockSpec((1, kvh, rows_, d),
                            lambda i, p, *_: (i, 0, 0, 0))

    def pool_out_spec(width_):
        def imap(i, p, tbl, st, ln, *_fmts):
            # steps below the write window park on its first page (it is
            # written before the index ever changes), steps past the
            # last written page park on it (flushed once at row end)
            w0 = st[i] // ps
            valid = pl.cdiv(ln[i], ps)
            return (tbl[i, jnp.clip(p, w0, valid - 1)], 0, 0, 0)
        return pl.BlockSpec((1, kvh, ps, width_), imap)

    scalar_ops = [table, start, lens]
    if mixed:
        scalar_ops.append(jnp.asarray(page_fmts, jnp.int32))
    ns = len(scalar_ops)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=ns,
        grid=(r, pmax),
        in_specs=[
            row_spec(rows), row_spec(w), row_spec(w),
            pool_in_spec(ed), pool_in_spec(nb),
            pool_in_spec(ed), pool_in_spec(nb),
        ],
        out_specs=[
            row_spec(rows),
            pool_out_spec(ed), pool_out_spec(nb),
            pool_out_spec(ed), pool_out_spec(nb),
            _VISITS_SPEC,
        ],
        scratch_shapes=[
            pltpu.VMEM((kvh * rows, 1), jnp.float32),  # running max m
            pltpu.VMEM((kvh * rows, 1), jnp.float32),  # running denominator
            pltpu.VMEM((kvh * rows, d), jnp.float32),  # rescaled output
        ],
    )
    kernel = functools.partial(
        _mx_attn_ragged_kernel, page_size=ps, fmt_name=fmt_name,
        block_size=block_size, softcap=softcap, window=window,
        width=w, group=g, mixed_fmts=mixed_fmts)
    out, oke, oks, ove, ovs, visits = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((r, kvh, rows, d), jnp.float32),
            jax.ShapeDtypeStruct(ke_pool.shape, ke_pool.dtype),
            jax.ShapeDtypeStruct(ks_pool.shape, ks_pool.dtype),
            jax.ShapeDtypeStruct(ve_pool.shape, ve_pool.dtype),
            jax.ShapeDtypeStruct(vs_pool.shape, vs_pool.dtype),
            jax.ShapeDtypeStruct((r * kvh,), jnp.int32),
        ],
        # pools update in place (operand indices count the scalar-prefetch
        # operands, then q, k_new, v_new, then the four pools)
        input_output_aliases={ns + 3 + k: 1 + k for k in range(4)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="mx_attn_ragged",  # the HLO instruction's and trace's name
    )(*scalar_ops, qr, k_new, v_new,
      ke_pool, ks_pool, ve_pool, vs_pool)
    out = out.reshape(r, kvh, w, g, d)
    pools = (oke, oks, ove, ovs)
    return ((out, pools, visits.reshape(r, kvh, 1)) if debug_visits
            else (out, pools))
