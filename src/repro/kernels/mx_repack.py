"""Pallas repack kernel: requantize KV pool pages down the format ladder.

The tiering engine's workhorse: a batch of cold pages is re-encoded from
their current element format (fp8 hot tier) to a narrower one (fp6 mid /
fp4 cold) **in place**, inside the mixed-format uint8 page pool that the
fused attention kernels read. Per page the kernel

  1. dequantizes the stored rows exactly (the same per-page-format decode
     select the attention kernels use — see
     :func:`repro.kernels.mx_attention._dequant_rows_mixed`),
  2. requantizes to the target format with the exact ``core.quantize``
     math (:func:`repro.kernels.mx_attention._quantize_rows` — block amax
     -> E8M0 shared exponent -> RNE saturating cast). Scales are
     **recomputed**, not copied: emax differs per format, so the old
     shared exponents are wrong for the new element grid.
  3. writes the packed codes into the row *prefix* (fp8 = D bytes,
     fp6 = 3D/4, fp4 = D/2) and zeroes the dead tail bytes, so repacked
     pages are bit-deterministic end to end — tests assert the prefix is
     bit-identical to a host ``core.quantize`` re-encode of the decoded
     values and the tail is zero.

The page list rides scalar prefetch, like the attention kernels' page
tables: the BlockSpec index maps send each grid step's DMA straight at
pool page ``page_ids[n]``. The list is a fixed-size operand so the
engine's jitted repack call is one trace regardless of how many pages
this step actually repacks: ``count`` names the live prefix, and padding
entries must **repeat the last live id (and its source format)** — their
bodies are predicated off, so the parked input/output blocks keep the
already-correct bytes of a page this call just wrote (safe under both
the revisit-elision rule on TPU and per-step copies in interpret mode).
Callers must not invoke the kernel with ``count == 0`` (skip at host
level instead — the pad contract needs at least one live entry).

COW safety is the caller's contract: the engine repacks a shared page
once (pages are keyed physically, not per sequence) and flips the
per-page format id *after* the kernel completes, between engine steps,
so no attention call ever sees bytes and format id out of sync.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import formats as F

from .mx_attention import (MIXED_FMTS_DEFAULT, _dequant_rows_mixed,
                           _quantize_rows)


def _repack_kernel(ids_ref, fmts_ref, cnt_ref, ke_ref, ks_ref, ve_ref,
                   vs_ref, oke_ref, oks_ref, ove_ref, ovs_ref, *,
                   dst_fmt_name: str, mixed_fmts, block_size: int):
    n = pl.program_id(0)
    dst = F.get_format(dst_fmt_name)

    @pl.when(n < cnt_ref[0])
    def _do():
        fid = fmts_ref[n]  # source format id of this page
        for e_in, s_in, e_out, s_out in (
                (ke_ref, ks_ref, oke_ref, oks_ref),
                (ve_ref, vs_ref, ove_ref, ovs_ref)):
            rows = e_in[0, 0]  # (PS, D) uint8
            ps, d = rows.shape
            wide = _dequant_rows_mixed(rows, s_in[0, 0], fid,
                                       mixed_fmts, block_size)
            q_e, q_s = _quantize_rows(wide, dst_fmt_name, block_size)
            if dst.bits == 8:
                qb = jax.lax.bitcast_convert_type(q_e, jnp.uint8)
            else:
                w = dst.storage_len(d)
                qb = jnp.concatenate(
                    [q_e, jnp.zeros((ps, d - w), jnp.uint8)], axis=-1)
            e_out[0, 0] = qb
            s_out[0, 0] = q_s


def mx_repack_pages(ke_pool, ks_pool, ve_pool, vs_pool, page_ids, src_fmts,
                    count, *, dst_fmt_name: str, mixed_fmts=None,
                    block_size: int = 32, interpret: bool | None = None):
    """Repack ``count`` pool pages to ``dst_fmt_name`` in place.

    Pools are the tiered layout: (NP, KVH, PS, D) uint8 elements +
    (NP, KVH, PS, D//k) uint8 E8M0 scales. ``page_ids``/``src_fmts`` are
    fixed-size (N,) i32 arrays — the live prefix of length ``count``
    names the pages to repack and their *current* format ids
    (:data:`repro.core.formats.FORMAT_IDS`); padding entries repeat the
    last live entry (see module docstring for why). ``count`` may be a
    traced scalar; it must be >= 1.

    Returns the four updated pools (inputs are aliased: in-place under
    jit donation). Works per page, so one call can mix target-distinct
    batches only by issuing one call per target format — the ladder
    steps (fp8 -> fp6, fp6 -> fp4) are separate calls anyway since the
    engine ages tiers independently.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if ke_pool.dtype != jnp.uint8:
        raise ValueError(
            "mx_repack_pages operates on mixed-format (tiered) pools, "
            f"which store raw uint8 bytes; got {ke_pool.dtype}")
    if mixed_fmts is None:
        mixed_fmts = MIXED_FMTS_DEFAULT
    mixed_fmts = tuple(mixed_fmts)
    if dst_fmt_name not in F.FORMAT_IDS:
        raise ValueError(f"unknown target format {dst_fmt_name!r}")
    npages, kvh, ps, d = ke_pool.shape
    nb = ks_pool.shape[-1]
    nlist = page_ids.shape[0]
    ids = jnp.clip(jnp.asarray(page_ids, jnp.int32), 0, npages - 1)
    fmts = jnp.asarray(src_fmts, jnp.int32)
    cnt = jnp.asarray(count, jnp.int32).reshape(1)

    def spec(width):
        return pl.BlockSpec((1, 1, ps, width),
                            lambda n, j, ids, fmts, cnt: (ids[n], j, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nlist, kvh),
        in_specs=[spec(d), spec(nb), spec(d), spec(nb)],
        out_specs=[spec(d), spec(nb), spec(d), spec(nb)],
    )
    kernel = functools.partial(
        _repack_kernel, dst_fmt_name=dst_fmt_name, mixed_fmts=mixed_fmts,
        block_size=block_size)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(ke_pool.shape, jnp.uint8),
            jax.ShapeDtypeStruct(ks_pool.shape, jnp.uint8),
            jax.ShapeDtypeStruct(ve_pool.shape, jnp.uint8),
            jax.ShapeDtypeStruct(vs_pool.shape, jnp.uint8),
        ],
        # pools update in place (operands: ids=0, fmts=1, cnt=2, pools 3-6)
        input_output_aliases={3: 0, 4: 1, 5: 2, 6: 3},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(ids, fmts, cnt, ke_pool, ks_pool, ve_pool, vs_pool)
