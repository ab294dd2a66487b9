"""LM assembly: embedding -> scanned block groups -> head; train & serve.

The repeated ``pattern`` runs under ``jax.lax.scan`` with rematerialization,
so compile time and HLO size are O(|pattern|) regardless of depth, and
activation memory is O(1 group) — both required for the 512-device dry-runs
of 56-layer models. Prologue/epilogue blocks (e.g. deepseek's first dense
layer) run unscanned.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from . import blocks, common as C, embedding
from .config import BlockDef, ModelConfig
from .norms import rmsnorm_apply, rmsnorm_init


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init(key, cfg: ModelConfig):
    ks = C.split_keys(key, 4 + len(cfg.prologue) + len(cfg.epilogue))
    params, axes = {}, {}
    p, a = embedding.init(ks[0], cfg.vocab_size * cfg.num_codebooks
                          if cfg.num_codebooks > 1 else cfg.vocab_size,
                          cfg.d_model, cfg.tied_embeddings)
    params["embedding"], axes["embedding"] = p, a

    def group_init(k):
        gp, ga = {}, {}
        for i, bd in enumerate(cfg.pattern):
            bp, ba = blocks.init(jax.random.fold_in(k, i), bd, cfg)
            gp[f"block{i}"] = bp
            ga[f"block{i}"] = ba
        return gp, ga

    stacked, gaxes = C.stack_inits(group_init, ks[1], cfg.num_groups)
    params["groups"], axes["groups"] = stacked, gaxes

    for j, bd in enumerate(cfg.prologue):
        p, a = blocks.init(ks[4 + j], bd, cfg)
        params[f"prologue{j}"], axes[f"prologue{j}"] = p, a
    for j, bd in enumerate(cfg.epilogue):
        p, a = blocks.init(ks[4 + len(cfg.prologue) + j], bd, cfg)
        params[f"epilogue{j}"], axes[f"epilogue{j}"] = p, a

    p, a = rmsnorm_init(ks[2], cfg.d_model)
    params["final_norm"], axes["final_norm"] = p, a
    return params, axes


def init_params(key, cfg: ModelConfig):
    """Parameters only, made on the device by one jitted program and
    stored as bf16 (serving holds bf16 masters, the dtype published
    checkpoints ship in). Each leaf is drawn and cast inside one fusion,
    so no f32 copy of the whole model ever exists: at 3.8B parameters
    that copy alone would fill a 16 GB chip."""
    return jax.jit(lambda k: jax.tree_util.tree_map(
        lambda leaf: leaf.astype(jnp.bfloat16), init(k, cfg)[0]))(key)


# ---------------------------------------------------------------------------
# layer enumeration (shared by every per-layer walk and the megakernel)
# ---------------------------------------------------------------------------


def iter_layer_blocks(cfg: ModelConfig):
    """Yield ``(param_key, group_index, bd)`` for every decoder block in
    execution order: prologue, then ``num_groups`` repetitions of the
    pattern, then epilogue (``group_index`` is None for unscanned blocks).

    This is THE layer enumeration: the per-layer step functions walk it
    through :func:`_walk_blocks`, and the megakernel's stacked-weight
    packing (:func:`pack_megakernel_params`) and stacked-pool cache
    (:func:`init_megakernel_cache`) consume the same order — so layer
    ``l`` of the megakernel grid and step ``l`` of the per-layer oracle
    can never disagree about which weights they mean.
    """
    for j, bd in enumerate(cfg.prologue):
        yield f"prologue{j}", None, bd
    for g in range(cfg.num_groups):
        for i, bd in enumerate(cfg.pattern):
            yield f"block{i}", g, bd
    for j, bd in enumerate(cfg.epilogue):
        yield f"epilogue{j}", None, bd


def layer_params(params, key: str, group_index):
    """One layer's parameter subtree for an :func:`iter_layer_blocks` entry."""
    if group_index is None:
        return params[key]
    return jax.tree_util.tree_map(lambda leaf: leaf[group_index],
                                  params["groups"][key])


def _walk_blocks(apply_fn, params, cfg: ModelConfig, x, cache):
    """Shared prologue -> ``lax.scan`` (groups) -> epilogue traversal.

    ``apply_fn(block_params, x, block_cache, bd) -> (x, new_block_cache)``
    is applied to every block in :func:`iter_layer_blocks` order; the
    repeated pattern runs under ``jax.lax.scan`` exactly as before (one
    trace of the pattern regardless of depth). Factoring the six
    near-identical per-step walks here keeps the residual threading — and
    therefore the layer order the megakernel must reproduce — defined in
    one place.
    """
    cache = dict(cache)
    for j, bd in enumerate(cfg.prologue):
        key = f"prologue{j}"
        x, cache[key] = apply_fn(params[key], x, cache[key], bd)

    def scan_fn(x, inputs):
        gparams, gcache = inputs
        new = []
        for i, bd in enumerate(cfg.pattern):
            x, c = apply_fn(gparams[f"block{i}"], x, gcache[i], bd)
            new.append(c)
        return x, tuple(new)

    x, gcaches = jax.lax.scan(scan_fn, x, (params["groups"], cache["groups"]))
    cache["groups"] = gcaches
    for j, bd in enumerate(cfg.epilogue):
        key = f"epilogue{j}"
        x, cache[key] = apply_fn(params[key], x, cache[key], bd)
    return x, cache


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _embed_inputs(params, cfg: ModelConfig, tokens=None, embeds=None):
    if embeds is not None:  # vlm/audio stub: precomputed frontend embeddings
        return embeds.astype(cfg.compute_dtype)
    if cfg.num_codebooks > 1:
        # musicgen: tokens (B, S, CB); codebook c uses vocab slice c
        offsets = jnp.arange(cfg.num_codebooks, dtype=tokens.dtype) * cfg.vocab_size
        x = embedding.embed(params["embedding"], tokens + offsets,
                            cfg.scale_embeds_by_sqrt_dim, cfg.compute_dtype)
        return x.sum(axis=2)
    return embedding.embed(params["embedding"], tokens,
                           cfg.scale_embeds_by_sqrt_dim, cfg.compute_dtype)


def _group_fwd(cfg: ModelConfig, gparams, x, positions):
    from repro.parallel.ctx import maybe_constrain

    aux = jnp.zeros((), jnp.float32)
    for i, bd in enumerate(cfg.pattern):
        # Sequence-parallel residual stream (Megatron-SP): the TP-boundary
        # all-reduce of each block's output becomes reduce-scatter (+ a
        # bf16 all-gather at the next matmul) — 25% less collective
        # traffic and 1/TP the norm HBM traffic (§Perf iteration 4).
        x = maybe_constrain(x, "batch", "seq_model", None)
        x, a = blocks.apply_train(gparams[f"block{i}"], x, positions, bd, cfg)
        aux = aux + a
    return x, aux


def forward(params, cfg: ModelConfig, tokens=None, embeds=None,
            positions=None):
    """Full-sequence forward. Returns (logits, aux_loss)."""
    x = _embed_inputs(params, cfg, tokens, embeds)
    b, s = x.shape[:2]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    aux = jnp.zeros((), jnp.float32)
    for j, bd in enumerate(cfg.prologue):
        x, a = blocks.apply_train(params[f"prologue{j}"], x, positions, bd, cfg)
        aux = aux + a

    body = functools.partial(_group_fwd, cfg)
    if cfg.remat == "full":
        body = jax.checkpoint(body,
                              policy=jax.checkpoint_policies.nothing_saveable)

    def scan_fn(carry, gparams):
        x, aux = carry
        x, a = body(gparams, x, positions)
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(scan_fn, (x, aux), params["groups"])

    for j, bd in enumerate(cfg.epilogue):
        x, a = blocks.apply_train(params[f"epilogue{j}"], x, positions, bd, cfg)
        aux = aux + a
    x = rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    logits = embedding.logits(params["embedding"], x, cfg.logit_softcap,
                              cfg.compute_dtype)
    if cfg.num_codebooks > 1:
        logits = logits.reshape(b, s, cfg.num_codebooks, cfg.vocab_size)
    return logits, aux


def loss_fn(params, cfg: ModelConfig, batch):
    """Cross-entropy LM loss (+ MoE aux). batch: {tokens|embeds, labels}."""
    logits, aux = forward(params, cfg, batch.get("tokens"), batch.get("embeds"))
    labels = batch["labels"]
    mask = (labels >= 0).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, jnp.maximum(labels, 0)[..., None],
                             axis=-1)[..., 0]
    denom = jnp.maximum(mask.sum(), 1.0)
    ce = -(ll * mask).sum() / denom
    # z-loss keeps softmax normalizers bounded (large-scale stability)
    z = jax.scipy.special.logsumexp(logits.astype(jnp.float32), axis=-1)
    zloss = 1e-4 * ((z**2) * mask).sum() / denom
    total = ce + zloss + cfg.aux_loss_weight * aux
    return total, {"ce": ce, "zloss": zloss, "aux": aux}


# ---------------------------------------------------------------------------
# serving: prefill + decode
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_seq: int):
    cache = {}
    for j, bd in enumerate(cfg.prologue):
        cache[f"prologue{j}"] = blocks.init_cache(batch, max_seq, bd, cfg)
    group = tuple(
        blocks.init_cache(batch, max_seq, bd, cfg) for bd in cfg.pattern
    )
    cache["groups"] = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (cfg.num_groups, *x.shape)).copy(), group
    )
    for j, bd in enumerate(cfg.epilogue):
        cache[f"epilogue{j}"] = blocks.init_cache(batch, max_seq, bd, cfg)
    return cache


def init_paged_cache(cfg: ModelConfig, num_slots: int, num_pages: int,
                     page_size: int, tiered: bool = False):
    """Paged serving cache: per-layer page pools (attention) + per-slot
    state rows (recurrent mixers). The page table that assigns pool pages
    to sequences is host-side scheduler state (``serve/kv_cache.py``) and
    is shared by every layer — same allocation for all of them.

    ``tiered`` allocates the mixed-format uint8 pool layout instead of
    the single-format one: full-width byte rows that narrower formats
    occupy as a prefix, so the tiering engine can repack pages down the
    format ladder in place (see ``attention.init_paged_pool``)."""
    cache = {}
    for j, bd in enumerate(cfg.prologue):
        cache[f"prologue{j}"] = blocks.init_paged_cache(
            num_slots, num_pages, page_size, bd, cfg, tiered=tiered)
    group = tuple(
        blocks.init_paged_cache(num_slots, num_pages, page_size, bd, cfg,
                                tiered=tiered)
        for bd in cfg.pattern
    )
    cache["groups"] = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (cfg.num_groups, *x.shape)).copy(), group
    )
    for j, bd in enumerate(cfg.epilogue):
        cache[f"epilogue{j}"] = blocks.init_paged_cache(
            num_slots, num_pages, page_size, bd, cfg, tiered=tiered)
    return cache


def decode_step_paged(params, cfg: ModelConfig, cache, tokens, page_rows,
                      pos, page_fmts=None, mixed_fmts=None):
    """Continuous-batching decode: tokens (B, 1), page_rows (B, P) int32
    page ids per slot (-1 = unallocated), pos (B,) per-slot positions.

    Returns (logits (B, 1, V), new_cache). Inactive slots (page_rows all
    -1) compute garbage that never lands: their KV writes are dropped and
    the host ignores their logits. Attention runs the path named by
    ``cfg.decode_kernel`` ("einsum" reference gather, or the single-pass
    "fused" Pallas flash-decode kernel the serve engine defaults to).

    ``page_fmts`` (NP,) i32 per-page format ids enables the tiered
    mixed-format pool path (fused kernel only); all layers share the one
    array, like the page table. ``mixed_fmts`` optionally restricts the
    candidate-format set compiled into the kernel.
    """
    x = _embed_inputs(params, cfg, tokens)
    b = x.shape[0]
    x, cache = _walk_blocks(
        lambda bp, x, bc, bd: blocks.apply_decode_paged(
            bp, x, bc, page_rows, pos, bd, cfg, page_fmts=page_fmts,
            mixed_fmts=mixed_fmts),
        params, cfg, x, cache)
    x = rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    logits = embedding.logits(params["embedding"], x, cfg.logit_softcap,
                              cfg.compute_dtype)
    if cfg.num_codebooks > 1:
        logits = logits.reshape(b, 1, cfg.num_codebooks, cfg.vocab_size)
    return logits, cache


def verify_step_paged(params, cfg: ModelConfig, cache, tokens, page_rows,
                      pos, page_fmts=None, mixed_fmts=None):
    """Speculative-decoding verify: tokens (B, Tq), page_rows (B, P),
    pos (B,) per-slot position of each row's *first* token.

    Feeds each slot's pending sampled token plus its Tq - 1 drafts in
    one batched pass: K/V for all Tq tokens land in the slot's pages
    (positions pos .. pos + Tq - 1 — the host guarantees those pages
    exist and are exclusively owned), and per-row causal masking keeps
    every token's logits exactly what one-at-a-time decode would
    produce. Returns (logits (B, Tq, V), new_cache); the host accepts a
    prefix of the drafts by comparing greedy argmaxes and rolls back the
    rest by simply not advancing the sequence position (rejected rows
    are dead by masking — nothing is zeroed or copied).

    Tq == 1 is :func:`decode_step_paged`'s dataflow; attention-only
    models only (see ``blocks.apply_verify_paged``).
    """
    x = _embed_inputs(params, cfg, tokens)
    b = x.shape[0]
    x, cache = _walk_blocks(
        lambda bp, x, bc, bd: blocks.apply_verify_paged(
            bp, x, bc, page_rows, pos, bd, cfg, page_fmts=page_fmts,
            mixed_fmts=mixed_fmts),
        params, cfg, x, cache)
    x = rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    logits = embedding.logits(params["embedding"], x, cfg.logit_softcap,
                              cfg.compute_dtype)
    if cfg.num_codebooks > 1:
        logits = logits.reshape(b, x.shape[1], cfg.num_codebooks,
                                cfg.vocab_size)
    return logits, cache


def prefill_chunk_paged(params, cfg: ModelConfig, cache, tokens, page_rows,
                        pos, num_valid, logit_idx, page_fmts=None,
                        mixed_fmts=None):
    """One fixed-size chunk of paged prefill: tokens (B, C), page_rows
    (B, P), pos (B,) chunk start positions, num_valid (B,) real tokens in
    the chunk, logit_idx (B,) which chunk row's logits to return.

    The chunked-prefill analogue of :func:`verify_step_paged`: each slot
    feeds ``C`` prompt tokens at absolute positions ``pos .. pos + C - 1``
    straight against the paged MX cache — the chunk's K/V is quantized
    into its pages (inside the fused kernel on the default path) and
    every chunk query attends over the pages written so far plus the
    chunk itself under per-row causal masking. Because ``C``, ``P`` and
    the scalar shapes are fixed, a serve engine needs exactly ONE jitted
    trace of this function for every prompt length and prefix-hit
    combination — admission latency is O(chunk) and the trace population
    is O(1), versus the monolithic path's O(distinct prompt lengths x
    prefix pages).

    Returns (logits (B, 1, V) of row ``logit_idx`` per slot, new cache).
    Mid-prompt chunks pass a throwaway index (their logits are unused);
    the final chunk passes its last real token's row, whose logits sample
    the first generated token. Attention-only models (see
    ``blocks.apply_prefill_chunked``).
    """
    x = _embed_inputs(params, cfg, tokens)
    b = x.shape[0]
    x, cache = _walk_blocks(
        lambda bp, x, bc, bd: blocks.apply_prefill_chunked(
            bp, x, bc, page_rows, pos, num_valid, bd, cfg,
            page_fmts=page_fmts, mixed_fmts=mixed_fmts),
        params, cfg, x, cache)
    # slice the requested row BEFORE the final norm + lm head: every op is
    # row-independent, so this matches the monolithic prefill's last-token
    # logits bit-for-bit while paying the vocab matmul for one row only
    idx = jnp.asarray(logit_idx, jnp.int32)[:, None, None]
    x = jnp.take_along_axis(
        x, jnp.broadcast_to(idx, (b, 1, x.shape[-1])), axis=1)
    x = rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    logits = embedding.logits(params["embedding"], x, cfg.logit_softcap,
                              cfg.compute_dtype)
    if cfg.num_codebooks > 1:
        logits = logits.reshape(b, 1, cfg.num_codebooks, cfg.vocab_size)
    return logits, cache


def ragged_step_paged(params, cfg: ModelConfig, cache, tokens, page_rows,
                      row_start, seq_lens, logit_idx, num_logits: int = 1,
                      page_fmts=None, mixed_fmts=None):
    """One-dispatch ragged engine step: tokens (R, W), page_rows (R, P),
    row_start (R,) first new-token position per row, seq_lens (R,) =
    row_start + n_new, logit_idx (R,) first row whose logits to return,
    num_logits static count of logit rows gathered per row.

    The single entry point behind ``ServeConfig.step_mode="ragged"``:
    decode rows (n_new == 1), verify windows (n_new == 1 + K) and
    prefill chunks (n_new up to W) coexist in one batch, so a steady
    mixed step issues ONE device dispatch per layer-stack traversal
    instead of decode + verify + prefill + K/V-write calls. Each row's
    new K/V is quantize-written into its pages inside the fused kernel
    (``kernels.mx_attention_ragged_fused``) — no ``.at[].set`` HBM
    round-trip anywhere on this path. Rows shorter than W clamp their
    padding queries onto the last real position; their outputs are
    garbage duplicates the host never reads. Inactive rows
    (row_start 0, seq_len 1, page_rows all -1) write only the pool's
    reserved trash page.

    Returns (logits (R, num_logits, V), new_cache). Logit rows are
    gathered pre-final-norm at ``logit_idx .. logit_idx + num_logits - 1``
    clamped to the last real row — decode/prefill-final rows use row 0 /
    the last prompt row, verify rows all 1 + K draft rows. Shapes are
    fixed by (R, W, P, num_logits), so one jitted trace covers every
    batch composition. Attention-only models (see
    ``blocks.apply_ragged_step``).
    """
    x = _embed_inputs(params, cfg, tokens)
    r = x.shape[0]
    x, cache = _walk_blocks(
        lambda bp, x, bc, bd: blocks.apply_ragged_step(
            bp, x, bc, page_rows, row_start, seq_lens, bd, cfg,
            page_fmts=page_fmts, mixed_fmts=mixed_fmts),
        params, cfg, x, cache)
    # gather the requested rows BEFORE the final norm + lm head (both are
    # row-independent, so this is bit-identical to slicing afterwards);
    # out-of-range gather rows clamp onto the row's last real token, whose
    # duplicate logits the host ignores
    last = jnp.maximum(seq_lens - row_start - 1, 0)[:, None]
    idx = jnp.clip(jnp.asarray(logit_idx, jnp.int32)[:, None]
                   + jnp.arange(num_logits, dtype=jnp.int32)[None, :],
                   0, last)
    x = jnp.take_along_axis(
        x, jnp.broadcast_to(idx[:, :, None], (r, num_logits, x.shape[-1])),
        axis=1)
    x = rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    logits = embedding.logits(params["embedding"], x, cfg.logit_softcap,
                              cfg.compute_dtype)
    if cfg.num_codebooks > 1:
        logits = logits.reshape(r, num_logits, cfg.num_codebooks,
                                cfg.vocab_size)
    return logits, cache


# ---------------------------------------------------------------------------
# megakernel step: the whole layer stack as ONE pallas_call
# ---------------------------------------------------------------------------


def init_megakernel_cache(cfg: ModelConfig, num_slots: int, num_pages: int,
                          page_size: int, tiered: bool = False):
    """Stacked-layer paged cache for the megakernel step.

    ONE grouped pool whose leaves carry a leading ``L = cfg.num_layers``
    axis (layer order = :func:`iter_layer_blocks`), wrapped as
    ``{"groups": (pool,)}`` so every ``serve.kv_cache`` structural walk —
    copy_page, extract/restore, ``pool_specs`` (KV heads stay at
    ``ndim - 3``), repack — treats the layer axis exactly like the
    per-layer cache's group axis. For an attention-only config with
    ``pattern == (bd,)`` and ``num_groups == L`` this is bit-for-bit the
    same pytree layout as :func:`init_paged_cache`, which is what lets
    the megakernel tests compare written pool bytes directly against the
    per-layer ragged oracle.
    """
    bd0 = cfg.all_blocks()[0]
    pool = blocks.init_paged_cache(num_slots, num_pages, page_size, bd0,
                                   cfg, tiered=tiered)
    layers = cfg.num_layers
    return {"groups": (jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (layers, *x.shape)).copy(), pool),)}


def pack_megakernel_params(params, cfg: ModelConfig):
    """Stack per-layer weights along a leading L axis for the megakernel.

    Consumes the SAME layer enumeration as the per-layer oracle
    (:func:`iter_layer_blocks`), so megakernel grid coordinate ``l``
    indexes exactly the weights the oracle's step ``l`` applies. The
    packed dict keeps the ``wq/wk/wv/wo`` key structure, so
    ``parallel.sharding.serve_param_specs`` still finds the attention
    projection group and shards the head columns (the KV-head slice)
    exactly as on the per-layer path. Embedding and final norm stay
    unstacked — they run outside the kernel.
    """
    layers = [layer_params(params, key, g)
              for key, g, _ in iter_layer_blocks(cfg)]

    def stack(pick):
        return jnp.stack([pick(bp) for bp in layers], axis=0)

    packed = {
        "norm_mixer": {"scale": stack(lambda bp: bp["norm_mixer"]["scale"])},
        "wq": {"w": stack(lambda bp: bp["mixer"]["wq"]["w"])},
        "wk": {"w": stack(lambda bp: bp["mixer"]["wk"]["w"])},
        "wv": {"w": stack(lambda bp: bp["mixer"]["wv"]["w"])},
        "wo": {"w": stack(lambda bp: bp["mixer"]["wo"]["w"])},
        "norm_ffn": {"scale": stack(lambda bp: bp["norm_ffn"]["scale"])},
        "up": {"w": stack(lambda bp: bp["ffn"]["up"]["w"])},
        "down": {"w": stack(lambda bp: bp["ffn"]["down"]["w"])},
    }
    if cfg.ffn_kind != "gelu":
        packed["gate"] = {"w": stack(lambda bp: bp["ffn"]["gate"]["w"])}
    return {"embedding": params["embedding"],
            "final_norm": params["final_norm"], "layers": packed}


def megakernel_step_paged(params, cfg: ModelConfig, cache, tokens, page_rows,
                          row_start, seq_lens, logit_idx, num_logits: int = 1,
                          page_fmts=None, mixed_fmts=None):
    """:func:`ragged_step_paged` with the whole layer stack fused into ONE
    ``pallas_call`` (``kernels.mx_megakernel_step``).

    ``params`` is a :func:`pack_megakernel_params` dict and ``cache`` an
    :func:`init_megakernel_cache` stacked pool; everything else —
    ragged row metadata, trash-page contract, tiered ``page_fmts``,
    logit-row gather — matches the per-layer oracle argument-for-argument.
    Embedding, the pre-head logit-row gather, final norm, and the LM head
    run outside the kernel exactly as written in :func:`ragged_step_paged`,
    so the returned logits are bit-identical to the oracle's whenever the
    kernel's per-layer math is (which the megakernel guarantees by reusing
    the oracle's own jnp helpers and fused-kernel primitives).

    Only configs accepted by ``blocks.megakernel_reject_reason`` may come
    here; the serve engine enforces that and falls back to
    ``step_mode="ragged"`` otherwise.
    """
    from repro.kernels import mx_megakernel_step

    x = _embed_inputs(params, cfg, tokens)
    r = x.shape[0]
    lay = params["layers"]
    pool = cache["groups"][0]
    bd0 = cfg.all_blocks()[0]
    d = cfg.head_dim
    x, pools = mx_megakernel_step(
        x, lay["norm_mixer"]["scale"], lay["wq"]["w"], lay["wk"]["w"],
        lay["wv"]["w"], lay["wo"]["w"], lay["norm_ffn"]["scale"],
        lay["gate"]["w"] if "gate" in lay else None,
        lay["up"]["w"], lay["down"]["w"],
        pool["k_elems"], pool["k_scales"], pool["v_elems"],
        pool["v_scales"], page_rows, row_start, seq_lens,
        head_dim=d, rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps,
        ffn_kind=cfg.ffn_kind, quant=cfg.quant, fmt_name=cfg.quant.fmt,
        block_size=min(cfg.quant.block_size, d), softcap=cfg.attn_softcap,
        window=bd0.window, compute_dtype=cfg.compute_dtype,
        page_fmts=page_fmts, mixed_fmts=mixed_fmts)
    ke, ks, ve, vs = pools
    cache = {"groups": (dict(pool, k_elems=ke, k_scales=ks, v_elems=ve,
                             v_scales=vs),)}
    # logit-row gather + head: verbatim the per-layer oracle's tail
    seq_lens = jnp.asarray(seq_lens, jnp.int32)
    row_start = jnp.asarray(row_start, jnp.int32)
    last = jnp.maximum(seq_lens - row_start - 1, 0)[:, None]
    idx = jnp.clip(jnp.asarray(logit_idx, jnp.int32)[:, None]
                   + jnp.arange(num_logits, dtype=jnp.int32)[None, :],
                   0, last)
    x = jnp.take_along_axis(
        x, jnp.broadcast_to(idx[:, :, None], (r, num_logits, x.shape[-1])),
        axis=1)
    x = rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    logits = embedding.logits(params["embedding"], x, cfg.logit_softcap,
                              cfg.compute_dtype)
    if cfg.num_codebooks > 1:
        logits = logits.reshape(r, num_logits, cfg.num_codebooks,
                                cfg.vocab_size)
    return logits, cache


def prefill(params, cfg: ModelConfig, tokens=None, embeds=None,
            max_seq: Optional[int] = None):
    """Process the prompt, build caches. Returns (last-token logits, cache)."""
    x = _embed_inputs(params, cfg, tokens, embeds)
    b, s = x.shape[:2]
    max_seq = max_seq or s
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    cache = {}
    for j, bd in enumerate(cfg.prologue):
        x, cache[f"prologue{j}"] = blocks.prefill_block(
            params[f"prologue{j}"], x, positions, bd, cfg, max_seq)

    def scan_fn(x, gparams):
        from repro.parallel.ctx import maybe_constrain

        caches = []
        for i, bd in enumerate(cfg.pattern):
            x = maybe_constrain(x, "batch", "seq_model", None)
            x, c = blocks.prefill_block(gparams[f"block{i}"], x, positions,
                                        bd, cfg, max_seq)
            caches.append(c)
        return x, tuple(caches)

    x, gcaches = jax.lax.scan(scan_fn, x, params["groups"])
    cache["groups"] = gcaches
    for j, bd in enumerate(cfg.epilogue):
        x, cache[f"epilogue{j}"] = blocks.prefill_block(
            params[f"epilogue{j}"], x, positions, bd, cfg, max_seq)
    x = rmsnorm_apply(params["final_norm"], x[:, -1:], cfg.norm_eps)
    logits = embedding.logits(params["embedding"], x, cfg.logit_softcap,
                              cfg.compute_dtype)
    if cfg.num_codebooks > 1:
        logits = logits.reshape(b, 1, cfg.num_codebooks, cfg.vocab_size)
    return logits, cache


def prefill_with_prefix(params, cfg: ModelConfig, cache, tokens,
                        prefix_pages, pos0: int, max_seq: int):
    """Prefill the uncached tail of a prompt against shared prefix pages.

    The prefix-cache fast path: a request whose prompt head is already
    resident in the paged cache prefills only ``tokens`` (1, S_tail), its
    uncached tail. ``prefix_pages`` (P0,) are the page ids holding the
    cached head's ``pos0`` tokens (``P0 == ceil(pos0 / page_size)`` —
    ``pos0`` need not be a page multiple: a partial-page hit ends
    mid-page and the last page's rows past ``pos0`` are masked out of
    the attend), gathered read-only from ``cache``; positions are offset
    by ``pos0`` so RoPE stays absolute. Requires an attention-only model (recurrent mixers would
    need per-prefix state snapshots — see ROADMAP).

    Returns (last-token logits, tail cache): the tail cache covers only
    the new tokens at relative slots 0.. and installs into the sequence's
    tail pages with ``kv_cache.install_prefill``, exactly like a full
    prefill cache.
    """
    x = _embed_inputs(params, cfg, tokens)
    b, s = x.shape[:2]
    positions = jnp.broadcast_to(
        pos0 + jnp.arange(s, dtype=jnp.int32), (b, s))
    # the walk threads the read-only prefix pool in and the tail cache out:
    # every block's returned cache entry replaces its input entry, so the
    # result dict holds exactly the new-token tail caches
    x, out_cache = _walk_blocks(
        lambda bp, x, bc, bd: blocks.prefill_block_tail(
            bp, x, positions, bc, prefix_pages, bd, cfg, max_seq),
        params, cfg, x, cache)
    x = rmsnorm_apply(params["final_norm"], x[:, -1:], cfg.norm_eps)
    logits = embedding.logits(params["embedding"], x, cfg.logit_softcap,
                              cfg.compute_dtype)
    if cfg.num_codebooks > 1:
        logits = logits.reshape(b, 1, cfg.num_codebooks, cfg.vocab_size)
    return logits, out_cache


def decode_step(params, cfg: ModelConfig, cache, tokens=None, embeds=None,
                pos=None):
    """One-token decode. tokens: (B, 1) (or (B,1,CB)); pos: scalar int32.

    Returns (logits (B, 1, V), new_cache).
    """
    x = _embed_inputs(params, cfg, tokens, embeds)
    b = x.shape[0]
    x, cache = _walk_blocks(
        lambda bp, x, bc, bd: blocks.apply_decode(bp, x, bc, pos, bd, cfg),
        params, cfg, x, cache)
    x = rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    logits = embedding.logits(params["embedding"], x, cfg.logit_softcap,
                              cfg.compute_dtype)
    if cfg.num_codebooks > 1:
        logits = logits.reshape(b, 1, cfg.num_codebooks, cfg.vocab_size)
    return logits, cache
