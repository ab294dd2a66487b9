"""Grouped-query attention with sliding windows, softcap, and KV caches.

Features used across the assigned archs:
  * GQA / MQA / MHA via ``num_kv_heads`` (no materialized head repeat —
    grouped einsum keeps HLO bytes honest for the roofline),
  * sliding-window masking (mixtral SWA, gemma2 local, recurrentgemma local),
  * attention logit softcapping (gemma2),
  * query-chunked computation for long prefill (bounds the live logits
    buffer; flash-style full kernels are a TPU-runtime concern, the chunk
    loop gives the same asymptotic memory on the dry-run),
  * ring-buffer KV cache bounded by the window for local layers — this is
    what makes 500k-token decode feasible for SWA archs,
  * optional MX-quantized KV cache (beyond-paper: block-scaled cache storage
    cuts decode HBM traffic, the dominant roofline term at long context).

Projections go through ``linear.apply`` and therefore inherit the MX policy.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import QuantConfig, quantize
from repro.core import formats as F

from . import common as C
from . import linear
from .rotary import apply_rope

NEG_INF = -2.0e38


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    window: Optional[int] = None  # sliding window (None = full causal)
    softcap: Optional[float] = None
    query_chunk: int = 1024
    cache_dtype: object = jnp.bfloat16
    # paged serving: don't clamp the cache to the window (no ring wraparound;
    # decode slot == absolute position, so caches map 1:1 onto page pools)
    no_ring: bool = False
    # paged decode path: "einsum" gathers + dequantizes the padded table in
    # HBM (reference oracle); "fused" runs the single-pass Pallas
    # flash-decode kernel over the page table (MX pools; wide bf16 pools
    # fall back to the einsum gather — there is nothing to dequantize)
    decode_kernel: str = "einsum"


def init(key, cfg: AttnConfig):
    ks = C.split_keys(key, 4)
    h, kvh, d, dm = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.d_model
    wq, aq = linear.init(ks[0], dm, h * d, (C.D_MODEL, C.HEADS))
    wk, ak = linear.init(ks[1], dm, kvh * d, (C.D_MODEL, C.KV_HEADS))
    wv, av = linear.init(ks[2], dm, kvh * d, (C.D_MODEL, C.KV_HEADS))
    wo, ao = linear.init(ks[3], h * d, dm, (C.HEADS, C.D_MODEL))
    return (
        {"wq": wq, "wk": wk, "wv": wv, "wo": wo},
        {"wq": aq, "wk": ak, "wv": av, "wo": ao},
    )


def _mask(qpos, kpos, window):
    """Causal + window + validity mask: (..., S_q, S_k) boolean."""
    m = kpos[..., None, :] <= qpos[..., :, None]
    if window is not None:
        m &= kpos[..., None, :] > (qpos[..., :, None] - window)
    m &= kpos[..., None, :] >= 0
    return m


def _attend(q, k, v, qpos, kpos, cfg: AttnConfig):
    """Grouped attention core. q: (B,S,H,D), k/v: (B,T,KVH,D). f32 softmax.

    Under a mesh, query rows are sequence-sharded over the TP axis
    (``seq_model``) so the (S, T) logits temp shards 16-way regardless of
    head count — GQA head counts (8, 10) often don't divide the TP axis,
    so head-sharding alone cannot bound this buffer.
    """
    from repro.parallel.ctx import maybe_constrain

    b, s, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, s, kvh, g, d)
    qg = maybe_constrain(qg, "batch", "seq_model", None, None, None)
    logits = jnp.einsum(
        "bskgd,btkd->bkgst", qg, k, preferred_element_type=jnp.float32
    )
    logits = maybe_constrain(logits, "batch", None, None, "seq_model", None)
    logits = logits * (d**-0.5)
    if cfg.softcap:
        logits = jnp.tanh(logits / cfg.softcap) * cfg.softcap
    mask = _mask(qpos, kpos, cfg.window)  # (B, S, T) or (S, T)
    while mask.ndim < logits.ndim:
        mask = mask[..., None, :, :] if mask.ndim >= 3 else mask[None]
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    # Constrain the output like the query: without this, the BACKWARD of
    # this einsum sees inconsistent shardings and SPMD falls back to full
    # rematerialization (an all-gather of the f32 logits over the batch
    # axis — measured 1.2e13 B/device on phi4 train_4k; §Perf iteration 1).
    out = maybe_constrain(out, "batch", "seq_model", None, None, None)
    return out.reshape(b, s, h, d)


def _attend_chunked(q, k, v, qpos, kpos, cfg: AttnConfig):
    """Query-chunked attention: bounds live logits to (B,H,chunk,T)."""
    b, s, h, d = q.shape
    cs = cfg.query_chunk
    if s <= cs or s % cs != 0:
        return _attend(q, k, v, qpos, kpos, cfg)
    nc = s // cs
    qc = q.reshape(b, nc, cs, h, d).swapaxes(0, 1)  # (nc, B, cs, H, D)
    pc = qpos.reshape(b, nc, cs).swapaxes(0, 1) if qpos.ndim == 2 else qpos.reshape(nc, cs)

    def body(args):
        qi, pi = args
        return _attend(qi, k, v, pi, kpos, cfg)

    out = jax.lax.map(body, (qc, pc))  # (nc, B, cs, H, D)
    return out.swapaxes(0, 1).reshape(b, s, h, d)


def apply_train(params, x, positions, cfg: AttnConfig, quant: QuantConfig,
                compute_dtype=jnp.bfloat16):
    """Full-sequence causal self-attention (training / prefill compute)."""
    b, s, _ = x.shape
    h, kvh, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = linear.apply(params["wq"], x, quant, compute_dtype).reshape(b, s, h, d)
    k = linear.apply(params["wk"], x, quant, compute_dtype).reshape(b, s, kvh, d)
    v = linear.apply(params["wv"], x, quant, compute_dtype).reshape(b, s, kvh, d)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    out = _attend_chunked(q, k, v, positions, positions, cfg)
    return linear.apply(params["wo"], out.reshape(b, s, h * d), quant,
                        compute_dtype, tp_on="in")


# ---------------------------------------------------------------------------
# KV cache (ring buffer, optionally MX-quantized)
# ---------------------------------------------------------------------------


def cache_len(cfg: AttnConfig, max_seq: int) -> int:
    if cfg.no_ring:
        return max_seq
    return min(cfg.window, max_seq) if cfg.window else max_seq


def _cache_arrays(lead, cfg: AttnConfig, quant: QuantConfig,
                  paged: bool = False):
    """Zero cache leaves: ``(B, T, KVH, ·)`` for ``lead = (B, T)``, or the
    KV-head-major page pool ``(NP, KVH, PS, ·)`` for ``lead = (NP, PS)``
    with ``paged``.

    Single source of truth for the MX-vs-wide storage leaves: the
    contiguous per-slot caches and the paged pools must agree exactly,
    since prefill caches reshape (and transpose) 1:1 into pool pages.
    """
    kvh, d = cfg.num_kv_heads, cfg.head_dim
    dims = (lead[0], kvh, lead[1]) if paged else (*lead, kvh)
    if quant.quantize_kv_cache and quant.enabled:
        bs = min(quant.block_size, d)
        fmt = F.get_format(quant.fmt)
        ed = d // 2 if fmt.packed else d
        zeros_e = jnp.zeros((*dims, ed), fmt.storage_dtype)
        zeros_s = jnp.zeros((*dims, d // bs), jnp.uint8)
        return {
            "k_elems": zeros_e, "k_scales": zeros_s,
            "v_elems": zeros_e, "v_scales": zeros_s,
        }
    z = jnp.zeros((*dims, d), cfg.cache_dtype)
    return {"k": z, "v": z}


def _quantize_kv_token(k_new, v_new, cfg: AttnConfig, quant: QuantConfig):
    """The MX cache-write quantization, shared by every write path."""
    bs = min(quant.block_size, cfg.head_dim)
    return (quantize(k_new.astype(jnp.float32), quant.fmt, bs),
            quantize(v_new.astype(jnp.float32), quant.fmt, bs))


def init_cache(batch: int, max_seq: int, cfg: AttnConfig,
               quant: QuantConfig):
    """Allocate an empty ring-buffer cache. ``kpos`` tracks absolute key
    positions (-1 = empty slot) so windowed wraparound masking is exact."""
    t = cache_len(cfg, max_seq)
    cache = _cache_arrays((batch, t), cfg, quant)
    cache["kpos"] = jnp.full((t,), -1, jnp.int32)
    return cache


def _write_cache(cache, k_new, v_new, slot, pos, quant: QuantConfig, cfg):
    """Write one token's k/v at ring slot (dynamic_update_slice)."""
    if "k" in cache:
        cache = dict(cache)
        cache["k"] = jax.lax.dynamic_update_slice(
            cache["k"], k_new.astype(cache["k"].dtype), (0, slot, 0, 0)
        )
        cache["v"] = jax.lax.dynamic_update_slice(
            cache["v"], v_new.astype(cache["v"].dtype), (0, slot, 0, 0)
        )
    else:
        kq, vq = _quantize_kv_token(k_new, v_new, cfg, quant)
        cache = dict(cache)
        cache["k_elems"] = jax.lax.dynamic_update_slice(
            cache["k_elems"], kq.elements, (0, slot, 0, 0))
        cache["k_scales"] = jax.lax.dynamic_update_slice(
            cache["k_scales"], kq.scales, (0, slot, 0, 0))
        cache["v_elems"] = jax.lax.dynamic_update_slice(
            cache["v_elems"], vq.elements, (0, slot, 0, 0))
        cache["v_scales"] = jax.lax.dynamic_update_slice(
            cache["v_scales"], vq.scales, (0, slot, 0, 0))
    cache["kpos"] = jax.lax.dynamic_update_slice(
        cache["kpos"], pos[None].astype(jnp.int32), (slot,)
    )
    return cache


def _read_cache(cache, quant: QuantConfig, cfg, dtype):
    if "k" in cache:
        return cache["k"].astype(dtype), cache["v"].astype(dtype)
    bs = min(quant.block_size, cfg.head_dim)
    fmt = F.get_format(quant.fmt)

    def deq(elems, scales):
        vals = F.decode_elements(elems, fmt, jnp.float32)
        blocked = vals.reshape(*vals.shape[:-1], scales.shape[-1], bs)
        wide = blocked * F.e8m0_to_scale(scales)[..., None]
        return wide.reshape(vals.shape).astype(dtype)

    return (deq(cache["k_elems"], cache["k_scales"]),
            deq(cache["v_elems"], cache["v_scales"]))


def cache_kv_view(k, v, cfg: AttnConfig, quant: QuantConfig):
    """K/V exactly as the cache will hold them.

    bf16 caches store K/V verbatim, so this is the identity. MX caches
    store quantized elements+scales, so prefill attention must see the
    quantize->dequantize snap — the same values decode reads back and the
    same values a prefix-cache tail prefill gathers from shared pages.
    Routing through ``_quantize_kv_token`` + ``_read_cache`` (the cache's
    own write/read pair) is what makes full prefill, tail prefill over
    cached pages, and decode agree bit-for-bit.
    """
    if not (quant.quantize_kv_cache and quant.enabled):
        return k, v
    kq, vq = _quantize_kv_token(k, v, cfg, quant)
    view = {"k_elems": kq.elements, "k_scales": kq.scales,
            "v_elems": vq.elements, "v_scales": vq.scales}
    return _read_cache(view, quant, cfg, k.dtype)


def gather_page_kv(pool, page_ids, cfg: AttnConfig, quant: QuantConfig,
                   dtype=jnp.bfloat16):
    """Dequantized K/V of ``page_ids`` pool pages, as (1, n*PS, KVH, D).

    The prefix-cache read path for tail prefill: pages are gathered in
    page-table order, so row ``t`` is absolute position ``t`` of the
    cached prefix.
    """
    return _read_cache(_pages_view(pool, page_ids[None]), quant, cfg, dtype)


def _pages_view(pool, page_rows):
    """Gather (B, P) page rows of a (NP, KVH, PS, ·) pool into the
    contiguous cache layout (B, P*PS, KVH, ·)."""
    b, pmax = page_rows.shape

    def gather(leaf):
        pages = jnp.swapaxes(leaf[page_rows], 2, 3)  # (B, P, PS, KVH, ·)
        return pages.reshape(b, pmax * leaf.shape[2], *pages.shape[3:])

    return {key: gather(leaf) for key, leaf in pool.items()}


def _project_decode_qkv(params, x, posv, cfg: AttnConfig,
                        quant: QuantConfig, compute_dtype):
    """Decode prologue shared by the fixed-slot, paged, and speculative
    verify paths: QKV projection + RoPE at per-token positions posv
    (B, S) for x (B, S, d_model) — S == 1 for one-token decode, S == Tq
    for a verify chunk. Every op is token-row independent, and keeping
    this (and ``_quantize_kv_token`` / ``_read_cache``) single-sourced is
    what makes continuous-batching and speculative outputs
    token-identical to the fixed-slot path.

    Head counts are inferred from the projection widths, not the config:
    inside the sharded serve step (``parallel.ctx.serve_tp_axis``) the
    wq/wk/wv shards carry only the device's KV-head slice, so the
    reshape must follow the local width."""
    b, s = x.shape[:2]
    d = cfg.head_dim
    q = linear.apply(params["wq"], x, quant, compute_dtype).reshape(b, s, -1, d)
    k = linear.apply(params["wk"], x, quant, compute_dtype).reshape(b, s, -1, d)
    v = linear.apply(params["wv"], x, quant, compute_dtype).reshape(b, s, -1, d)
    q = apply_rope(q, posv, cfg.rope_theta)
    k = apply_rope(k, posv, cfg.rope_theta)
    return q, k, v


def apply_decode(params, x, cache, pos, cfg: AttnConfig, quant: QuantConfig,
                 compute_dtype=jnp.bfloat16):
    """Single-token decode: x (B, 1, d_model), pos scalar int32."""
    b = x.shape[0]
    h, d = cfg.num_heads, cfg.head_dim
    posv = jnp.full((b, 1), pos, jnp.int32)
    q, k, v = _project_decode_qkv(params, x, posv, cfg, quant, compute_dtype)
    t = cache["kpos"].shape[0]
    slot = jnp.asarray(pos % t, jnp.int32)
    cache = _write_cache(cache, k, v, slot, jnp.asarray(pos, jnp.int32), quant, cfg)
    kc, vc = _read_cache(cache, quant, cfg, compute_dtype)
    out = _attend(q, kc, vc, posv, cache["kpos"][None], cfg)
    y = linear.apply(params["wo"], out.reshape(b, 1, h * d), quant,
                     compute_dtype, tp_on="in")
    return y, cache


# ---------------------------------------------------------------------------
# paged KV cache (continuous batching: global page pool + per-slot tables)
# ---------------------------------------------------------------------------


def init_paged_pool(num_pages: int, page_size: int, cfg: AttnConfig,
                    quant: QuantConfig, tiered: bool = False):
    """Allocate a layer's global KV page pool (no per-sequence dimension).

    Layout matches the paged Pallas kernels: KV-head major
    (NP, KVH, PS, ·), with the same storage leaves as the contiguous
    cache (``_cache_arrays``).
    Ownership (which page belongs to which sequence at which position)
    lives in the host-side page table, not in the arrays.

    ``tiered=True`` allocates the mixed-format layout instead: element
    leaves are raw uint8 rows of the *full* head_dim width regardless of
    element format — a narrower format's codes occupy the row prefix
    (fp8 = D bytes, fp6 = 3D/4, fp4 = D/2) and which format a page
    currently holds lives in the engine's per-page format array, not in
    the pool. Requires an MX-quantized cache with an 8-bit hot format
    (fresh writes are always fp8; the repack ladder narrows them later).
    """
    if not tiered:
        return _cache_arrays((num_pages, page_size), cfg, quant, paged=True)
    if not (quant.quantize_kv_cache and quant.enabled):
        raise ValueError("tiered KV pools require an MX-quantized cache")
    if F.get_format(quant.fmt).bits != 8:
        raise ValueError(
            "tiered KV pools write new pages in the hot format, which "
            f"must be an fp8; got {quant.fmt!r}")
    kvh, d = cfg.num_kv_heads, cfg.head_dim
    bs = min(quant.block_size, d)
    zeros_e = jnp.zeros((num_pages, kvh, page_size, d), jnp.uint8)
    zeros_s = jnp.zeros((num_pages, kvh, page_size, d // bs), jnp.uint8)
    return {"k_elems": zeros_e, "k_scales": zeros_s,
            "v_elems": zeros_e, "v_scales": zeros_s}


def apply_decode_paged(params, x, pool, page_rows, pos, cfg: AttnConfig,
                       quant: QuantConfig, compute_dtype=jnp.bfloat16,
                       page_fmts=None, mixed_fmts=None):
    """Per-slot decode through a page table: x (B, 1, d_model), pos (B,).

    ``page_rows`` (B, P) holds each slot's page ids (-1 = unallocated).
    Each slot writes its new token's K/V at page ``pos // PS`` slot
    ``pos % PS`` (inactive slots route to an out-of-bounds page and are
    dropped), then attends over its pages. Write-then-read order,
    quantization, and dequantization are shared with the fixed-slot path,
    which is what keeps continuous-batching outputs token-identical.

    Two attention paths, selected by ``cfg.decode_kernel``:

      * ``"einsum"`` — gather the *entire padded* table out of the pool,
        dequantize it to wide ``compute_dtype`` in HBM, and run the masked
        einsum attention. Cost scales with the table width (max_pages),
        not the tokens actually resident; kept as the reference oracle.
      * ``"fused"`` — single Pallas kernel (`mx_attention_decode_fused`):
        walk the page table via scalar prefetch, dequantize each compact
        page tile in-register, accumulate the softmax online. No gathered
        copy (wide or compact) is ever materialized and pages past
        ``ceil(seq_len / page_size)`` are skipped. Wide bf16 pools fall
        back to the einsum gather (there is nothing to dequantize).

    Implemented as the Tq == 1 case of :func:`apply_verify_paged` (one
    shared body, exactly as the kernel layer delegates decode to the
    verify kernel) — a fix to either path cannot miss the other, which
    the spec-vs-plain token-identity guarantee depends on.
    """
    return apply_verify_paged(params, x, pool, page_rows, pos, cfg, quant,
                              compute_dtype, page_fmts=page_fmts,
                              mixed_fmts=mixed_fmts)


def apply_verify_paged(params, x, pool, page_rows, pos, cfg: AttnConfig,
                       quant: QuantConfig, compute_dtype=jnp.bfloat16,
                       page_fmts=None, mixed_fmts=None):
    """Multi-token paged verify: x (B, Tq, d_model), pos (B,).

    The speculative-decoding verify step: each slot feeds ``Tq`` tokens —
    the pending sampled token plus ``Tq - 1`` drafts — at absolute
    positions ``pos .. pos + Tq - 1``. All Tq tokens' K/V are quantized
    and written into their pages first (page ``p // PS``, slot
    ``p % PS``; inactive slots route out-of-bounds and are dropped), then
    every query attends over the pages with *per-row causal masking*:
    query ``i`` sees keys at positions ``<= pos + i`` only, so a draft
    token's attention — and therefore its logits and its K/V, should it
    be accepted — is bit-for-bit what a one-token decode at that position
    would have produced. Rejected drafts leave K/V rows beyond the
    accepted point; those rows are dead by masking (the host truncates
    the sequence's position, nothing is zeroed) and the next write at
    that position overwrites them.

    Tq == 1 degenerates to :func:`apply_decode_paged`'s dataflow: the
    projection/RoPE/cache-write path is literally shared
    (``_project_decode_qkv`` / ``_quantize_kv_token``), and every op in
    it is token-row independent — which is what keeps speculative output
    token-identical to non-speculative decode.

    Two attention paths, selected by ``cfg.decode_kernel`` exactly as in
    :func:`apply_decode_paged`: the fused ``mx_attention_verify_fused``
    kernel (one page walk feeds all Tq queries) or the einsum gather
    reference (also the wide-bf16-pool fallback).

    ``page_fmts`` (a (NP,) i32 device array of per-page format ids)
    switches to the mixed-format tiered pool layout: the pool stores raw
    uint8 byte rows, writes land in the hot fp8 format (bitcast into the
    byte rows — the engine marks written pages hot), and the fused kernel
    selects each page's dequant path from its format id. Tiered pools
    require the fused kernel path (the einsum gather has no per-page
    format select).
    """
    if cfg.decode_kernel not in ("einsum", "fused"):
        raise ValueError(f"unknown decode_kernel {cfg.decode_kernel!r}")
    if page_fmts is not None and (cfg.decode_kernel != "fused"
                                  or "k_elems" not in pool):
        raise ValueError("tiered (mixed-format) KV pools require the fused "
                         "MX decode kernel path")
    b, tq, _ = x.shape
    h, kvh, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    pos = jnp.asarray(pos, jnp.int32)
    posv = pos[:, None] + jnp.arange(tq, dtype=jnp.int32)[None]  # (B, Tq)
    q, k, v = _project_decode_qkv(params, x, posv, cfg, quant, compute_dtype)

    lead = pool["k" if "k" in pool else "k_elems"]
    npages, ps = lead.shape[0], lead.shape[2]
    pmax = page_rows.shape[1]
    widx = posv // ps  # (B, Tq) page-table columns
    page = jnp.take_along_axis(page_rows, jnp.clip(widx, 0, pmax - 1),
                               axis=1)
    # OOB: dropped by mode="drop". Unallocated entries are -1, and a
    # position past the table's extent must drop too, not clamp into the
    # last column — a padded final prefill chunk can reach past the
    # table while the sequence legitimately owns its last page, and a
    # clamped write would scatter garbage over live cache rows there.
    page = jnp.where((page < 0) | (widx > pmax - 1), npages, page)
    slot = posv % ps

    # pools are (NP, KVH, PS, ·): indexing [page, :, slot] with (B, Tq)
    # page/slot arrays addresses (B, Tq, KVH, ·) rows, the K/V layout
    pool = dict(pool)
    if "k" in pool:
        pool["k"] = pool["k"].at[page, :, slot].set(
            k.astype(pool["k"].dtype), mode="drop")
        pool["v"] = pool["v"].at[page, :, slot].set(
            v.astype(pool["v"].dtype), mode="drop")
    else:
        kq, vq = _quantize_kv_token(k, v, cfg, quant)
        k_el, v_el = kq.elements, vq.elements
        if page_fmts is not None:
            # tiered pool: hot-format fp8 bytes into the uint8 byte rows
            k_el = jax.lax.bitcast_convert_type(k_el, jnp.uint8)
            v_el = jax.lax.bitcast_convert_type(v_el, jnp.uint8)
        pool["k_elems"] = pool["k_elems"].at[page, :, slot].set(
            k_el, mode="drop")
        pool["k_scales"] = pool["k_scales"].at[page, :, slot].set(
            kq.scales, mode="drop")
        pool["v_elems"] = pool["v_elems"].at[page, :, slot].set(
            v_el, mode="drop")
        pool["v_scales"] = pool["v_scales"].at[page, :, slot].set(
            vq.scales, mode="drop")

    if cfg.decode_kernel == "fused" and "k_elems" in pool:
        from repro.kernels import mx_attention_verify_fused

        # heads split (KVH major, G minor) as the decode path does
        qk = q.reshape(b, tq, kvh, h // kvh, d).transpose(0, 2, 1, 3, 4)
        out = mx_attention_verify_fused(
            qk, pool["k_elems"], pool["k_scales"], pool["v_elems"],
            pool["v_scales"], page_rows, pos + tq,
            fmt_name=quant.fmt, block_size=min(quant.block_size, d),
            softcap=cfg.softcap, window=cfg.window,
            page_fmts=page_fmts, mixed_fmts=mixed_fmts)
        out = out.transpose(0, 2, 1, 3, 4).reshape(
            b, tq, h, d).astype(compute_dtype)
    else:
        idx = jnp.clip(page_rows, 0, npages - 1)  # (B, P); garbage masked
        kc, vc = _read_cache(_pages_view(pool, idx), quant, cfg,
                             compute_dtype)
        t = kc.shape[1]
        kpos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))
        out = _attend(q, kc, vc, posv, kpos, cfg)
    y = linear.apply(params["wo"], out.reshape(b, tq, h * d), quant,
                     compute_dtype, tp_on="in")
    return y, pool


def apply_prefill_chunked(params, x, pool, page_rows, pos, num_valid,
                          cfg: AttnConfig, quant: QuantConfig,
                          compute_dtype=jnp.bfloat16, page_fmts=None,
                          mixed_fmts=None):
    """One chunk of paged prefill: x (B, C, d_model), pos (B,), num_valid
    (B,).

    The chunked-prefill generalization of :func:`apply_verify_paged`:
    ``C`` prompt tokens at absolute positions ``pos .. pos + C - 1``
    (``pos`` page-aligned, ``C`` a page multiple — the engine enforces
    both) attend over every page written so far plus themselves
    intra-causally, and the chunk's K/V lands in the sequence's pages.
    ``num_valid`` is how many chunk rows are real prompt tokens (the last
    chunk of a prompt is padded up to the fixed ``C``; padding rows write
    only dead-by-masking garbage and their outputs are ignored).

    Two paths, selected by ``cfg.decode_kernel`` exactly as decode/verify:

      * ``"fused"`` (MX pools) — :func:`mx_attention_prefill_fused`: one
        Pallas kernel walks the page table, quantizes the chunk's K/V
        in-register and writes it straight into its pages (aliased
        outputs — no host-side install), and folds both resident pages
        and the chunk's own quantized snap into one online softmax. No
        wide K/V beyond the chunk's own (B, C, KVH, D) projection output
        ever exists, and per-chunk work scales with resident tokens.
      * ``"einsum"`` (reference oracle, and wide bf16 pools) — delegate
        to :func:`apply_verify_paged` with Tq == C: host-side quantized
        page writes, then the gather-and-dequantize masked attention.

    Both share ``_project_decode_qkv`` / the ``core.quantize`` math with
    decode and verify, so the cache bytes a chunk writes are bit-for-bit
    what one-token decode at those positions would have written — the
    invariant chunked-vs-monolithic token identity rests on.

    ``page_fmts``/``mixed_fmts`` switch to the mixed-format tiered pool
    exactly as in :func:`apply_verify_paged` (fused path only): resident
    pages dequantize per their format id, the chunk's pages are written
    in the hot fp8 format.
    """
    if cfg.decode_kernel not in ("einsum", "fused"):
        raise ValueError(f"unknown decode_kernel {cfg.decode_kernel!r}")
    if page_fmts is not None and (cfg.decode_kernel != "fused"
                                  or "k_elems" not in pool):
        raise ValueError("tiered (mixed-format) KV pools require the fused "
                         "MX prefill kernel path")
    if cfg.decode_kernel == "fused" and "k_elems" in pool:
        from repro.kernels import mx_attention_prefill_fused

        b, c, _ = x.shape
        h, kvh, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        pos = jnp.asarray(pos, jnp.int32)
        posv = pos[:, None] + jnp.arange(c, dtype=jnp.int32)[None]
        q, k, v = _project_decode_qkv(params, x, posv, cfg, quant,
                                      compute_dtype)
        qk = q.reshape(b, c, kvh, h // kvh, d).transpose(0, 2, 1, 3, 4)
        out, (ke, ks, ve, vs) = mx_attention_prefill_fused(
            qk, k.swapaxes(1, 2), v.swapaxes(1, 2), pool["k_elems"],
            pool["k_scales"], pool["v_elems"],
            pool["v_scales"], page_rows, pos,
            pos + jnp.asarray(num_valid, jnp.int32),
            fmt_name=quant.fmt, block_size=min(quant.block_size, d),
            softcap=cfg.softcap, window=cfg.window,
            page_fmts=page_fmts, mixed_fmts=mixed_fmts)
        pool = dict(pool, k_elems=ke, k_scales=ks, v_elems=ve, v_scales=vs)
        out = out.transpose(0, 2, 1, 3, 4).reshape(
            b, c, h, d).astype(compute_dtype)
        y = linear.apply(params["wo"], out.reshape(b, c, h * d), quant,
                         compute_dtype, tp_on="in")
        return y, pool
    return apply_verify_paged(params, x, pool, page_rows, pos, cfg, quant,
                              compute_dtype)


def apply_ragged(params, x, pool, page_rows, row_start, seq_lens,
                 cfg: AttnConfig, quant: QuantConfig,
                 compute_dtype=jnp.bfloat16, page_fmts=None,
                 mixed_fmts=None):
    """One ragged engine step: x (R, W, d_model), row_start/seq_lens (R,).

    The one-dispatch generalization of decode, verify, AND chunked
    prefill: every row feeds ``W`` token columns at absolute positions
    ``row_start .. row_start + W - 1``, of which ``seq_lens - row_start``
    are real this step — 1 for a plain decode row, 1 + K for a
    speculative verify window, up to W for an in-flight prefill chunk.
    Unlike :func:`apply_verify_paged` there is NO host-side ``.at[].set``
    cache write: the new rows' K/V ride into
    :func:`~repro.kernels.mx_attention_ragged_fused` wide and are
    quantized + merged into the row's pages inside the kernel (aliased
    pool outputs), so the whole step is one device dispatch and the
    per-token write stops round-tripping through HBM.

    Padding columns (past ``seq_lens``) project garbage the kernel
    clamps onto the last real position; their outputs are ignored and
    their K/V rows are excluded from the page merge, so real rows are
    bit-identical to the split decode/verify/prefill paths (shared
    ``_project_decode_qkv`` / ``_quantize_rows`` math, same page-walk
    accumulation order).

    Fused-MX-only: the ragged step exists to fuse the kernel page walk
    with the in-kernel write, so there is no einsum/wide-pool fallback —
    the engine falls back to ``step_mode="split"`` for those configs.
    ``page_rows`` may contain negative entries; the kernel routes them
    to the pool's reserved trash page (see the kernel's contract).

    Inside the engine's KV-head-sharded serve step
    (``parallel.ctx.serve_tp_axis`` set, i.e. traced under the engine's
    ``shard_map``) the pool leaves and the wq/wk/wv projections carry
    only this device's ``KVH / M`` head slice, so the kernel's grid —
    already ``(R, KVH, P)`` — shards along its KV-head dimension for
    free. The ONE collective of the whole step happens here: the kernel
    output is all-gathered over the mesh axis (tiled along the KV-head
    dim, device order == head order) before the output projection, whose
    replicated ``wo`` then sees bit-identical full-width operands on
    every device — which is what keeps the sharded engine
    token-identical to the single-device one (a sharded-``wo`` psum
    would split the f32 reduction instead and drift).

    MIRROR CONTRACT: the layer-fused megakernel
    (``kernels.mx_megakernel_step``) re-implements this row math —
    norm, QKV projection + RoPE, the fused page walk, the in-kernel
    quantized write — inside its own kernel body, and its acceptance
    bar is bit-identity with this path (logits AND written pool bytes).
    Any numeric change here (rounding points, projection order, RoPE
    variant, quantize math) must land in ``kernels/mx_megakernel.py``
    in the same PR or ``tests/test_megakernel.py`` will catch the
    drift.
    """
    if cfg.decode_kernel != "fused" or "k_elems" not in pool:
        raise ValueError(
            "apply_ragged requires the fused MX decode kernel over an "
            "MX-quantized page pool (use step_mode='split' otherwise)")
    from repro.kernels import mx_attention_ragged_fused
    from repro.parallel.ctx import serve_tp_axis

    r, w, _ = x.shape
    d = cfg.head_dim
    row_start = jnp.asarray(row_start, jnp.int32)
    posv = row_start[:, None] + jnp.arange(w, dtype=jnp.int32)[None]
    q, k, v = _project_decode_qkv(params, x, posv, cfg, quant,
                                  compute_dtype)
    # local head counts (== cfg's when unsharded; the device's slice
    # under serve TP — heads are laid out KV-major, so contiguous q-head
    # shards align with contiguous KV-head shards)
    kvh = k.shape[2]
    g = q.shape[2] // kvh
    qk = q.reshape(r, w, kvh, g, d).transpose(0, 2, 1, 3, 4)
    out, (ke, ks, ve, vs) = mx_attention_ragged_fused(
        qk, k.swapaxes(1, 2), v.swapaxes(1, 2), pool["k_elems"],
        pool["k_scales"], pool["v_elems"],
        pool["v_scales"], page_rows, row_start,
        jnp.asarray(seq_lens, jnp.int32),
        fmt_name=quant.fmt, block_size=min(quant.block_size, d),
        softcap=cfg.softcap, window=cfg.window,
        page_fmts=page_fmts, mixed_fmts=mixed_fmts)
    pool = dict(pool, k_elems=ke, k_scales=ks, v_elems=ve, v_scales=vs)
    axis = serve_tp_axis()
    if axis is not None:
        # (R, KVH/M, W, G, D) -> (R, KVH, W, G, D): the step's one
        # collective; per-(row, kv-head) online softmax is independent,
        # so the gathered tensor is exactly the unsharded kernel output
        out = jax.lax.all_gather(out, axis, axis=1, tiled=True)
    out = out.transpose(0, 2, 1, 3, 4)
    out = out.reshape(r, w, -1).astype(compute_dtype)
    y = linear.apply(params["wo"], out, quant,
                     compute_dtype, tp_on="in")
    return y, pool


def prefill_cache(params, x, positions, cfg: AttnConfig, quant: QuantConfig,
                  k, v, max_seq: int):
    """Populate a fresh cache from full-sequence K/V (last window if ring)."""
    b, s = positions.shape
    t = cache_len(cfg, max_seq)
    cache = init_cache(b, max_seq, cfg, quant)
    take = min(s, t)
    k_tail = k[:, s - take:s]
    v_tail = v[:, s - take:s]
    pos_tail = positions[0, s - take:s]
    # Decode writes token p at ring slot p % t, so prefill must too. The
    # tail positions are contiguous, so slot assignment is a roll by p0 % t
    # (p0 = first tail position; p0 == 0 whenever take < t).
    def place(buf2d):
        # buf2d: (..., take, ...) written at slots [(p0 + i) % t]
        return jnp.roll(buf2d, pos_tail[0] % t, axis=1) if take == t else buf2d

    if "k" in cache:
        cache["k"] = place(cache["k"].at[:, :take].set(k_tail.astype(cache["k"].dtype)))
        cache["v"] = place(cache["v"].at[:, :take].set(v_tail.astype(cache["v"].dtype)))
    else:
        kq, vq = _quantize_kv_token(k_tail, v_tail, cfg, quant)
        cache["k_elems"] = place(cache["k_elems"].at[:, :take].set(kq.elements))
        cache["k_scales"] = place(cache["k_scales"].at[:, :take].set(kq.scales))
        cache["v_elems"] = place(cache["v_elems"].at[:, :take].set(vq.elements))
        cache["v_scales"] = place(cache["v_scales"].at[:, :take].set(vq.scales))
    kpos = cache["kpos"].at[:take].set(pos_tail)
    cache["kpos"] = jnp.roll(kpos, pos_tail[0] % t, axis=0) if take == t else kpos
    return cache
