"""Serving engines: MX-compressed weights + (paged) MX KV cache.

Two engines share one numerics contract:

  * ``FixedSlotEngine`` — the original continuous-batching-lite loop: a
    fixed batch of slots, one shared position counter, ring-buffer caches
    sized batch x max_seq. Kept as the golden reference: its greedy
    outputs define correctness for the paged path.
  * ``ContinuousBatchingEngine`` (exported as ``ServeEngine``) — requests
    enter and leave mid-stream. Admission prefills one request into pages
    drawn from a global MX page pool (``kv_cache``), the jitted decode
    step runs at fixed shapes (max_slots rows, padding rows masked by
    dropped writes), and EOS/max_new recycles the slot and pages the same
    step (``scheduler``). Per-request greedy outputs are token-identical
    to the fixed-slot engine because every op on the path — projection,
    RoPE, cache quantize/dequantize, masked softmax — is batch-row
    independent and shared between the two paths.

Why this is the paper's serving payoff at production shape: the decode
step's HBM traffic is dominated by the KV cache; MX storage cuts it ~2x
(fp8+E8M0 vs bf16) and paging cuts the *allocated* footprint to what is
actually resident, so ragged, churning traffic stops paying for max_seq
rectangles. ``benchmarks/serve_throughput.py`` measures both.

The decode step runs the single-pass fused Pallas flash-decode kernel by
default (``ServeConfig.decode_kernel="fused"``): attention walks the page
table in-kernel, dequantizes compact MX tiles in-register, and skips
unallocated pages, so per-step attention *work* also scales with resident
tokens — not just the footprint.

Speculative decoding (``ServeConfig.spec_decode``) feeds that kernel
properly: instead of one token per step, each sequence drafts K cheap
candidates (prompt-lookup n-gram by default — no second model) and one
batched multi-token verify pass (``model.verify_step_paged`` over the
Tq > 1 fused kernel) checks them all, amortizing the page walk and
in-register dequant across the chunk. Greedy acceptance + page-exact
rollback keep the output token stream identical to non-speculative
decode for any drafter (see ``spec_decode``).

Prefill is chunked by default (``ServeConfig.prefill_mode="chunked"``):
instead of one monolithic dense prefill per prompt — which materializes
wide bf16 K/V for the whole prompt, installs it into pages afterwards,
retraces per prompt length, and blocks every resident decoder for the
full prompt duration — each prompt streams through fixed-size
page-aligned chunks that run straight against the MX page pool
(``model.prefill_chunk_paged`` over ``mx_attention_prefill_fused``: the
chunk's K/V is quantized and written into its pages *inside* the kernel,
and the chunk attends over everything resident plus itself). Chunks are
interleaved with decode steps under a per-step token budget
(Sarathi-style), so admission latency is O(chunk), head-of-line blocking
disappears, and the engine needs exactly ONE jitted prefill trace.
``prefill_mode="monolithic"`` keeps the dense path as the validated
reference oracle (its per-length trace caches now LRU-bounded); both
modes produce token-identical greedy streams because prefill, decode and
verify share one projection/RoPE/quantize path.

``decode_kernel="einsum"`` is the escape
hatch back to the gather-and-dequantize reference path (what wide bf16
pools fall back to, and what ``benchmarks/decode_attention.py`` compares
against). Numerics caveat: the fused kernel keeps the softmax in f32
while the einsum path rounds probabilities to bf16 before the value
matmul, so across-path logits differ at bf16-rounding level and a greedy
step whose top-2 gap sits inside that band can flip (README §Serving);
within a path, determinism and the paging machinery's exactness
(snapshot/restore, COW, prefix sharing) are unchanged.
"""
from __future__ import annotations

import dataclasses
import json
import logging
import time
from collections import OrderedDict, deque
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.formats import FORMAT_BY_ID, FORMAT_IDS
from repro.core.mx_tensor import MXTensor
from repro.kernels import mx_repack_pages
from repro.nn import blocks, model
from repro.nn.config import ModelConfig

from . import kv_cache, sampling, spec_decode
from .kv_cache import PAGE_UNITS_FULL, UNITS_BY_BITS
from .overload import OverloadConfig, OverloadController
from .sampling import SamplingParams
from .scheduler import Scheduler
from .steplog import STEP_LOG_LEN, StepTimer

log = logging.getLogger("repro.serve")

_PAGED_MIXERS = {"attn", "rglru", "ssd"}

#: element bit width per MX format name (drives quarter-page unit costs)
_FMT_BITS = {"fp8_e4m3": 8, "fp8_e5m2": 8, "fp6_e3m2": 6, "fp6_e2m3": 6,
             "fp4_e2m1": 4}


@dataclasses.dataclass
class TierPolicy:
    """Hot/cold tiering knobs for the mixed-format KV page pool.

    A page is *hot* while it was written within the last ``hot_steps``
    engine steps; past that it is repacked down the format ladder
    (base fp8 -> ``mid_fmt`` -> ``cold_fmt``) by a background budget of
    ``repack_pages_per_step`` pages per step. Repacking requantizes the
    page's elements+scales in place via the exact ``core.quantize`` math
    (``kernels/mx_repack.py``) and credits quarter-page units back to
    the pool's HBM budget, so colder residency buys capacity: more
    resident tokens per byte at a bounded accuracy cost.
    """

    mid_fmt: str = "fp6_e3m2"  # first demotion step (3/4 of a page)
    cold_fmt: str = "fp4_e2m1"  # final demotion step (1/2 of a page)
    hot_steps: int = 8  # steps since last write before base -> mid
    cold_steps: int = 32  # steps since last write before mid -> cold
    repack_pages_per_step: int = 4  # background repack budget per step
    # fixed kernel page-list length: repack dispatches pad to this, so
    # the jitted trace population stays O(1) regardless of batch shape
    repack_list_len: int = 8


@dataclasses.dataclass
class ServeConfig:
    max_seq: int = 1024
    # default sampling for requests that don't carry their own
    # SamplingParams: temperature 0 => exact greedy; top_k 0 => disabled;
    # ``seed`` is the engine's base seed, mixed with each request id into
    # that request's own RNG stream (see serve.sampling.resolve_seed)
    temperature: float = 0.0  # 0 => greedy
    top_p: float = 1.0
    top_k: int = 0
    seed: int = 0
    eos_id: Optional[int] = None
    # overload control (serve.overload): shed submissions (ShedError /
    # HTTP 429) once the predicted first-token latency exceeds slo_ms,
    # and unconditionally once the queue reaches max_queue. None = admit
    # everything (the pre-overload-control behavior).
    slo_ms: Optional[float] = None
    max_queue: Optional[int] = None
    # continuous batching (ignored by FixedSlotEngine)
    max_slots: int = 8
    page_size: int = 16
    num_pages: Optional[int] = None  # default: max_slots * pages_per_slot
    # prefix caching: share page-aligned prompt heads across requests via
    # the radix tree (attention-only models; auto-disabled otherwise)
    prefix_cache: bool = True
    # admission: how far past a stuck queue head to scan for a request
    # that fits (1 = strict FCFS)
    admit_window: int = 4
    # paged decode attention: "fused" (default) runs the single-pass Pallas
    # flash-decode kernel over the page table — per-step work scales with
    # resident tokens; "einsum" is the escape hatch back to the reference
    # gather-and-dequantize path (also what wide bf16 pools fall back to)
    decode_kernel: str = "fused"
    # speculative decoding: draft num_draft_tokens per sequence per step
    # and verify them all in one batched multi-token pass over the paged
    # MX cache. At temperature 0 acceptance is exact greedy prefix
    # matching (token-identical to non-speculative decode for ANY
    # drafter); at temperature > 0 it is rejection sampling against the
    # filtered target distribution (serve.sampling.verify_rejection), so
    # emitted tokens keep exactly the distribution plain sampling would
    # produce — a good drafter only raises tokens/step, never changes
    # what is sampled.
    # ``drafter`` is "ngram" (prompt-lookup, no second model needed) or a
    # spec_decode.Drafter instance.
    spec_decode: bool = False
    num_draft_tokens: int = 4
    drafter: object = "ngram"
    # prefill path: "chunked" (default) streams each prompt through
    # fixed-size page-aligned chunks straight against the MX page pool
    # (fused quantize-into-pages kernel, O(1) jitted traces, admission
    # interleaved with decode under a per-step token budget);
    # "monolithic" is the validated reference oracle — one dense prefill
    # per prompt + page install, retracing per prompt length. Models with
    # recurrent mixers fall back to monolithic automatically (their state
    # is per-slot, not paged — chunks have nothing to resume from).
    prefill_mode: str = "chunked"
    # chunk length in tokens; must be a multiple of page_size so chunk
    # starts stay page-aligned (no page ever blends two chunks)
    prefill_chunk: int = 64
    # max prefill tokens processed per engine step (Sarathi-style budget;
    # default = one chunk). The budget is spent round-robin across
    # admitted-but-prefilling sequences, so a short prompt's first token
    # never waits for a long neighbour's full prompt.
    prefill_token_budget: Optional[int] = None
    # ragged-aware prefill budgeting: how many chunks one prefilling
    # sequence may advance in a single ragged step WHEN the row budget is
    # undersubscribed (fewer active sequences than slots). The ragged
    # trace width grows to prefill_chunk * prefill_max_chunks, and the
    # starvation bound is built in: the moment every slot is occupied,
    # rows fall back to one chunk per step so resident decoders' per-step
    # latency is not taxed by wide prefill rows. 1 (default) = the
    # original one-chunk-per-step behavior.
    prefill_max_chunks: int = 1
    # LRU bound on the monolithic path's per-(length, prefix) jitted
    # prefill traces — a long-running server on the fallback path must
    # not grow trace memory without limit (the chunked path's trace
    # population is bounded by max_slots: one compiled shape per
    # distinct prefill batch size)
    prefill_trace_cache: int = 32
    # tiered mixed-format KV cache: new writes land in the base (fp8)
    # format; pages not written for a while are background-repacked down
    # the ladder (fp8 -> fp6 -> fp4) under ``tier_policy``, and the page
    # pool is metered in quarter-page units so narrower pages genuinely
    # buy capacity (num_pages is then the *fp8-equivalent* byte budget;
    # the physical pool over-provisions 2x). Requires the fused decode
    # kernel, chunked prefill, attention-only mixers, and an 8-bit
    # quantized base KV format.
    tiered: bool = False
    tier_policy: Optional[TierPolicy] = None
    # chunked admission: bound on how many times a request may be
    # deferred waiting for a still-prefilling shared-prefix leader
    # before it gives up on sharing and prefills independently (a
    # preempted or budget-starved leader must not starve followers)
    max_deferrals: int = 8
    # engine step assembly: "ragged" (default) packs every decode-ready
    # sequence's pending token (+ drafts under speculative decoding) and
    # one prompt chunk per prefilling sequence into ONE fused Pallas
    # dispatch per step — attention, the in-kernel quantize-write of each
    # row's new K/V, sampling and draft verification all ride the single
    # call, so a steady mixed batch costs exactly one device dispatch.
    # "split" keeps the separate decode / verify / prefill-chunk / K/V
    # write dispatches as the validated oracle. Ragged requires the fused
    # decode kernel, a quantized (MX) KV cache and attention-only mixers;
    # unsupported configs fall back to split automatically.
    # "megakernel" goes one rung further: the ENTIRE layer stack of the
    # ragged step runs as ONE pallas_call per engine step
    # (kernels.mx_megakernel_step) — per-layer weights stacked along a
    # leading layer axis, the residual stream carried across layer grid
    # steps in VMEM — collapsing device dispatches per mixed step from
    # O(num_layers) to exactly 1. Ragged assembly, the scheduler,
    # speculative rollback, tiering and prefix sharing are unchanged;
    # configs the megakernel cannot serve (nn.blocks.
    # megakernel_reject_reason, plus the runtime conditions: ragged
    # prerequisites, unsharded mesh, wide weight masters) fall back to
    # the per-layer ragged path with a logged reason.
    step_mode: str = "ragged"
    # sharded serving: (data, model) device-mesh shape, e.g. (1, 8). The
    # ragged step then runs KV-head-parallel under shard_map: the page
    # pool's K/V (+ per-page scale) leaves and the wq/wk/wv projections
    # are partitioned along the KV-head axis over the "model" axis, page
    # tables / row metadata / sampling vectors are replicated, and the
    # ONE collective per step is an all-gather of the attention output
    # before the (replicated) output projection — so per-device HBM
    # holds only KVH/M of the pool while token streams stay identical to
    # the single-device engine. Requires the ragged step (falls back to
    # unsharded otherwise) and num_kv_heads divisible by the model dim.
    # None (default) = single-device, no mesh.
    mesh_shape: Optional[tuple] = None


def _sample(logits, key, temperature: float):
    logits = logits[:, -1].astype(jnp.float32)
    if temperature <= 0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(
        key, logits / temperature, axis=-1).astype(jnp.int32)


def _sub_jaxprs(params):
    """Inner jaxprs held by one equation's params (jit/scan/cond/...)."""
    import jax.extend.core as jex

    for v in params.values():
        if isinstance(v, jex.ClosedJaxpr):
            yield v.jaxpr
        elif hasattr(v, "eqns"):
            yield v
        elif isinstance(v, (tuple, list)):
            for x in v:
                if isinstance(x, jex.ClosedJaxpr):
                    yield x.jaxpr
                elif hasattr(x, "eqns"):
                    yield x


def _walk_live(row_start, seq_lens, page_size: int, windows) -> float:
    """Page bodies the ragged kernel executes over these rows, a layer:
    each row's ``ceil(seq_len / page_size)`` pages less those wholly
    below its sliding window (the kernel's ``_first_window_page``),
    averaged over the attention layers' ``windows``."""
    pages = -(-seq_lens // page_size)
    total = 0
    for w in windows:
        first = 0 if w is None else np.maximum(
            (row_start - w + 1) // page_size, 0)
        total += int((pages - first).sum())
    return total / len(windows)


def _pallas_calls_in(jaxpr) -> int:
    """Device-kernel launches one execution of ``jaxpr`` performs.

    Counts ``pallas_call`` equations, multiplying through ``scan`` trip
    counts — the per-layer ragged step scans its pattern over
    ``num_groups``, so its ONE lexical pallas_call runs L times, while
    the layer-fused megakernel's single call runs once. This is the
    measured (not asserted) form of the step's dispatch claim.
    """
    n = 0
    for eqn in jaxpr.eqns:
        inner = sum(_pallas_calls_in(s) for s in _sub_jaxprs(eqn.params))
        if eqn.primitive.name == "pallas_call":
            n += 1
        elif eqn.primitive.name == "scan":
            n += inner * int(eqn.params.get("length", 1))
        else:
            n += inner
    return n


class FixedSlotEngine:
    """Fixed batch of slots, one shared position (the golden reference)."""

    def __init__(self, params, cfg: ModelConfig, serve_cfg: ServeConfig):
        self.params = params
        self.cfg = cfg
        self.serve_cfg = serve_cfg
        self._prefill = jax.jit(
            lambda p, toks: model.prefill(p, cfg, tokens=toks,
                                          max_seq=serve_cfg.max_seq))
        self._decode = jax.jit(
            lambda p, cache, tok, pos: model.decode_step(
                p, cfg, cache, tokens=tok, pos=pos))

    def generate(self, prompts: np.ndarray, max_new_tokens: int,
                 key=None) -> np.ndarray:
        """prompts: (B, S0) int32. Returns (B, S0 + max_new_tokens)."""
        key = key if key is not None else jax.random.PRNGKey(0)
        prompts = jnp.asarray(prompts, jnp.int32)
        b, s0 = prompts.shape
        logits, cache = self._prefill(self.params, prompts)
        out = [prompts]
        tok = _sample(logits, key, self.serve_cfg.temperature)
        for i in range(max_new_tokens):
            out.append(tok[:, None])
            if i == max_new_tokens - 1:
                break
            pos = jnp.asarray(s0 + i, jnp.int32)
            key, sub = jax.random.split(key)
            logits, cache = self._decode(self.params, cache, tok[:, None], pos)
            tok = _sample(logits, sub, self.serve_cfg.temperature)
        return np.asarray(jnp.concatenate(out, axis=1))


class ContinuousBatchingEngine:
    """Continuous batching over a paged MX KV cache."""

    def __init__(self, params, cfg: ModelConfig, serve_cfg: ServeConfig):
        unsupported = {bd.mixer for bd in
                       (*cfg.prologue, *cfg.pattern, *cfg.epilogue)
                       } - _PAGED_MIXERS
        if unsupported:
            raise NotImplementedError(
                f"continuous batching does not support mixers {unsupported} "
                "— use FixedSlotEngine (launch/serve.py --engine fixed)")
        if cfg.num_codebooks > 1:
            raise NotImplementedError(
                "continuous batching with codebook heads is a follow-on")
        if serve_cfg.decode_kernel not in ("einsum", "fused"):
            raise ValueError(
                f"unknown decode_kernel {serve_cfg.decode_kernel!r} "
                "(expected 'fused' or 'einsum')")
        mixers = {bd.mixer for bd in (*cfg.prologue, *cfg.pattern,
                                      *cfg.epilogue)}
        self.spec_enabled = bool(serve_cfg.spec_decode)
        if self.spec_enabled:
            if serve_cfg.num_draft_tokens < 1:
                raise ValueError("spec_decode needs num_draft_tokens >= 1")
            if mixers - {"attn"}:
                raise NotImplementedError(
                    f"speculative decoding requires attention-only models, "
                    f"got mixers {sorted(mixers - {'attn'})}: recurrent "
                    "state has no position axis to roll rejected drafts "
                    "back through")
            self.drafter = spec_decode.resolve_drafter(
                serve_cfg.drafter, cfg.vocab_size)
        self.params = params
        self.cfg = cfg
        # full-length (non-ring) prefill caches: slot == absolute position,
        # so a prompt cache reshapes exactly into its pages
        self.cfg_prefill = cfg.replace(serve_full_cache=True)
        # the decode step runs the fused flash-decode kernel by default;
        # ServeConfig.decode_kernel="einsum" is the escape hatch back to
        # the gather-and-dequantize reference path
        self.cfg_decode = cfg.replace(decode_kernel=serve_cfg.decode_kernel)
        self.serve_cfg = serve_cfg
        ps = serve_cfg.page_size
        pages_per_slot = kv_cache.pages_for(serve_cfg.max_seq, ps)
        self.num_pages = (serve_cfg.num_pages
                          or serve_cfg.max_slots * pages_per_slot)
        # prefix sharing needs every mixer to be attention: K/V pages are a
        # pure function of the token prefix, but recurrent state is not
        # paged (per-prefix snapshots are a follow-on — see ROADMAP)
        self.prefix_enabled = bool(serve_cfg.prefix_cache
                                   and mixers <= {"attn"})
        if serve_cfg.prefix_cache and not self.prefix_enabled:
            log.info("prefix cache disabled: mixers %s are not attention-only",
                     sorted(mixers - {"attn"}))
        if serve_cfg.prefill_mode not in ("chunked", "monolithic"):
            raise ValueError(
                f"unknown prefill_mode {serve_cfg.prefill_mode!r} "
                "(expected 'chunked' or 'monolithic')")
        # chunked prefill streams prompts through the paged attention
        # pools, so it needs every mixer paged — recurrent state is
        # per-slot and has no chunk to resume from; fall back like the
        # prefix cache does rather than failing the whole engine
        self.chunked = (serve_cfg.prefill_mode == "chunked"
                        and mixers <= {"attn"})
        if serve_cfg.prefill_mode == "chunked" and not self.chunked:
            log.info("chunked prefill disabled: mixers %s are not "
                     "attention-only; using monolithic prefill",
                     sorted(mixers - {"attn"}))
        if self.chunked:
            if serve_cfg.prefill_chunk <= 0:
                raise ValueError("prefill_chunk must be >= 1")
            budget = serve_cfg.prefill_token_budget
            if budget is not None and budget <= 0:
                raise ValueError("prefill_token_budget must be >= 1")
            # budget in whole chunks; anything below one chunk still
            # makes progress (one chunk per step)
            self._chunks_per_step = max(
                1, (budget or serve_cfg.prefill_chunk)
                // serve_cfg.prefill_chunk)
        if serve_cfg.prefill_trace_cache < 1:
            raise ValueError("prefill_trace_cache must be >= 1")
        if serve_cfg.step_mode not in ("ragged", "split", "megakernel"):
            raise ValueError(
                f"unknown step_mode {serve_cfg.step_mode!r} "
                "(expected 'ragged', 'split' or 'megakernel')")
        if serve_cfg.prefill_max_chunks < 1:
            raise ValueError("prefill_max_chunks must be >= 1")
        # the one-dispatch ragged step needs every row to run the fused
        # quantize-into-pages attention path: attention-only mixers, the
        # fused decode kernel, an MX-quantized KV pool, and chunked
        # prefill (monolithic admission would dispatch outside the step)
        ragged_ok = (mixers <= {"attn"}
                     and serve_cfg.decode_kernel == "fused"
                     and cfg.quant.quantize_kv_cache
                     and self.chunked)
        # "megakernel" is ragged assembly with a fused layer stack, so it
        # inherits every ragged prerequisite (and falls all the way back
        # to split dispatches when those are unmet)
        ragged_like = serve_cfg.step_mode in ("ragged", "megakernel")
        self.ragged = ragged_like and ragged_ok
        if ragged_like and not self.ragged:
            log.info("ragged step disabled: needs attention-only mixers, "
                     "decode_kernel='fused', a quantized KV cache and "
                     "chunked prefill; using split dispatches")
        # the ragged kernel routes inactive rows' writes to a reserved
        # trash page (page-table entries of -1 map to the pool's last
        # physical page in-kernel), so the physical pool carries one page
        # the scheduler never hands out
        self._trash_pages = 1 if self.ragged else 0
        # sharded serving: KV-head-parallel ragged step over a
        # (data, model) mesh (see ServeConfig.mesh_shape). Fallback
        # ladder: a 1x1 mesh or a non-ragged config runs unsharded; an
        # indivisible KV-head count or missing devices is a hard error
        # (silent replication there would just waste the machine).
        self.mesh = None
        self._tp_axis: Optional[str] = None
        self.tp = 1
        if serve_cfg.mesh_shape is not None:
            shape = tuple(int(s) for s in serve_cfg.mesh_shape)
            if len(shape) != 2 or any(s < 1 for s in shape):
                raise ValueError(
                    f"mesh_shape must be a (data, model) pair of positive "
                    f"ints, got {serve_cfg.mesh_shape!r}")
            if shape[0] != 1:
                raise ValueError(
                    "sharded serving is KV-head (model) parallel only: "
                    f"mesh_shape[0] (data) must be 1, got {shape[0]} — "
                    "data-parallel replicas are a router-level follow-on")
            ndev = shape[0] * shape[1]
            if ndev == 1:
                log.info("mesh_shape %s is a single device; running "
                         "unsharded", shape)
            elif not self.ragged:
                log.info("sharded serving disabled: it requires the ragged "
                         "step (attention-only mixers, decode_kernel="
                         "'fused', a quantized KV cache, chunked prefill); "
                         "running unsharded")
            else:
                if cfg.num_kv_heads % shape[1] != 0:
                    raise ValueError(
                        f"sharded serving splits KV heads over the model "
                        f"axis: num_kv_heads={cfg.num_kv_heads} is not "
                        f"divisible by mesh model dim {shape[1]}")
                from repro.launch.mesh import _make_mesh, require_devices
                self.mesh = _make_mesh(
                    shape, ("data", "model"),
                    require_devices(ndev, f"mesh_shape {shape}"))
                self._tp_axis = "model"
                self.tp = shape[1]
        # layer-fused megakernel: the whole attention-only decoder step —
        # every layer's norm/QKV/RoPE/page-walk/output-proj/FFN plus the
        # in-kernel quantized K/V writes — as ONE pallas_call, with the
        # per-layer ragged step kept as the validated oracle. The ladder
        # is static (config + params), decided once at init; any rung
        # that fails drops to the per-layer ragged step with a log line.
        self.megakernel = False
        self._megakernel_fallback_reason = None
        if serve_cfg.step_mode == "megakernel":
            if not self.ragged:
                reason = ("ragged prerequisites unmet (the megakernel is "
                          "the ragged step fused over layers)")
            elif self.tp > 1:
                reason = ("sharded mesh — megakernel under shard_map is a "
                          "follow-on (see ROADMAP)")
            elif any(isinstance(leaf, MXTensor)
                     for leaf in jax.tree_util.tree_leaves(
                         self.params,
                         is_leaf=lambda x: isinstance(x, MXTensor))):
                reason = ("MXTensor (pre-quantized) weights — the "
                          "megakernel pre-quantizes wide masters itself")
            else:
                reason = blocks.megakernel_reject_reason(self.cfg_decode)
            if reason is None:
                self.megakernel = True
            else:
                self._megakernel_fallback_reason = reason
                log.info("megakernel step disabled: %s; falling back to "
                         "the %s step", reason,
                         "per-layer ragged" if self.ragged
                         else "split-dispatch")
        # tiered mixed-format pool: num_pages is reinterpreted as the
        # fp8-equivalent byte budget (unit-metered); the physical pool
        # over-provisions 2x so repacked (narrower) pages buy residency
        self.tiered = bool(serve_cfg.tiered)
        unit_budget = None
        if self.tiered:
            self.tier = serve_cfg.tier_policy or TierPolicy()
            self._validate_tiering(cfg, mixers)
            unit_budget = self.num_pages * PAGE_UNITS_FULL
            self.num_pages *= 2
        else:
            self.tier = None
        self.scheduler = Scheduler(
            max_slots=serve_cfg.max_slots, num_pages=self.num_pages,
            page_size=ps, max_seq=serve_cfg.max_seq,
            prefix_cache=self.prefix_enabled,
            admit_window=serve_cfg.admit_window,
            num_draft_tokens=(serve_cfg.num_draft_tokens
                              if self.spec_enabled else 0),
            prefill_chunk=(serve_cfg.prefill_chunk if self.chunked else 0),
            prefill_max_chunks=serve_cfg.prefill_max_chunks,
            max_deferrals=serve_cfg.max_deferrals,
            unit_budget=unit_budget, track_allocs=self.tiered)
        self.cache = model.init_paged_cache(
            cfg, serve_cfg.max_slots, self.num_pages + self._trash_pages,
            ps, tiered=self.tiered)
        # donate the cache pytree: without donation every decode step /
        # install / restore copies the whole multi-layer page pool, which
        # would cancel the paged-cache footprint win. CPU has no donation
        # (it only warns), so gate on backend. _extract must NOT donate —
        # the cache lives on after a snapshot. Only the cache is donated:
        # a snapshot or prefill cache has no output to alias into.
        cpu = jax.default_backend() == "cpu"
        if self.tiered:
            # every step function threads the shared per-page format-id
            # array (one array for all layers, like the page table); the
            # candidate-format tuple is static, baked into the kernels
            mf = self._mixed_fmts = tuple(dict.fromkeys(
                (cfg.quant.fmt, self.tier.mid_fmt, self.tier.cold_fmt)))
        else:
            mf = None

        # sharded placement: the pool's KV-head axis and the attention
        # projections' head columns land on their mesh shards ONCE, at
        # init — every step then runs shard-local, no per-step reshards.
        # wo and everything outside attention stay replicated (see
        # parallel.sharding.serve_param_specs for why that — not a
        # sharded-wo psum — is what keeps tokens bit-identical).
        if self.mesh is not None:
            from repro.parallel.sharding import serve_param_specs
            self._param_specs = serve_param_specs(self.params)
            self._pool_specs = kv_cache.pool_specs(self.cache,
                                                   self._tp_axis)
            self.params = self._shard_put(self.params, self._param_specs)
            self.cache = self._shard_put(self.cache, self._pool_specs)

        # sampling happens INSIDE the jitted step, fed per-slot parameter
        # vectors (temperature / top-p / top-k / seed / stream counter):
        # a batch mixing greedy and stochastic requests at different
        # temperatures still costs one dispatch, and greedy rows take the
        # exact f32 argmax the pre-sampling engine took. The verify step
        # likewise runs rejection-sampling acceptance in-dispatch and
        # returns (num_emitted, emitted) instead of raw logits.
        def _decode_step(p, c, tok, rows, pos, temps, tps, tks, seeds,
                         ctrs, fmts=None):
            kw = ({"page_fmts": fmts, "mixed_fmts": mf}
                  if fmts is not None else {})
            logits, c = model.decode_step_paged(
                p, self.cfg_decode, c, tok, rows, pos, **kw)
            with jax.named_scope("sample"):
                toks = sampling.sample(logits[:, -1], temps, tps, tks,
                                       seeds, ctrs)
            return toks, c

        def _verify_step(p, c, tok, rows, pos, temps, tps, tks, seeds,
                         ctrs, fmts=None):
            kw = ({"page_fmts": fmts, "mixed_fmts": mf}
                  if fmts is not None else {})
            logits, c = model.verify_step_paged(
                p, self.cfg_decode, c, tok, rows, pos, **kw)
            with jax.named_scope("sample"):
                n_emit, emitted = sampling.verify_rejection(
                    logits, tok[:, 1:], temps, tps, tks, seeds, ctrs)
            return n_emit, emitted, c

        self._decode = jax.jit(_decode_step,
                               donate_argnums=() if cpu else (1,))
        self._verify = jax.jit(_verify_step,
                               donate_argnums=() if cpu else (1,))
        # prefill-logits sampler (first token of each admitted request);
        # one compiled shape per batch size, bounded by max_slots
        self._sample_fn = jax.jit(sampling.sample)
        self._install = jax.jit(
            lambda c, pf, slot, ids: kv_cache.install_prefill(
                c, pf, slot, ids, ps),
            donate_argnums=() if cpu else (0,))
        self._extract = jax.jit(kv_cache.extract_seq)
        self._restore = jax.jit(kv_cache.restore_seq,
                                donate_argnums=() if cpu else (0,))
        self._copy_page = jax.jit(kv_cache.copy_page,
                                  donate_argnums=() if cpu else (0,))
        # monolithic-path trace caches, LRU-bounded (satellite of the
        # chunked-prefill work: a long-running server on the fallback
        # path must not grow trace memory with every novel length)
        self._prefill_fns = OrderedDict()  # prompt length -> jitted
        self._prefill_tail_fns = OrderedDict()  # (tail, prefix, pos0) ->
        # partial-page prefix hits: offset-install traces, LRU-cached per
        # (tail pages, offset, rows)
        self._install_offset_fns = OrderedDict()
        # the chunked path's jitted trace: fixed (B, C) tokens, full
        # page-table rows, dynamic scalars — every prompt length and
        # prefix hit reuses it, and concurrently-prefilling sequences'
        # same-shape chunks batch into ONE dispatch (B rows). Compiled
        # shapes are keyed by B only, so the trace population is bounded
        # by max_slots — constant per deployment, independent of the
        # workload's prompt lengths.
        if self.tiered:
            self._prefill_chunk = jax.jit(
                lambda p, c, toks, rows, pos, nv, idx, fmts:
                model.prefill_chunk_paged(
                    p, self.cfg_decode, c, toks, rows, pos, nv, idx,
                    page_fmts=fmts, mixed_fmts=self._mixed_fmts),
                donate_argnums=() if cpu else (1,))
        else:
            self._prefill_chunk = jax.jit(
                lambda p, c, toks, rows, pos, nv, idx:
                model.prefill_chunk_paged(
                    p, self.cfg_decode, c, toks, rows, pos, nv, idx),
                donate_argnums=() if cpu else (1,))
        # the ragged step's single jitted trace: fixed (max_slots, W)
        # tokens — W wide enough for one prefill chunk and one verify
        # window — with per-row (row_start, seq_lens, logit_idx) scalars,
        # so EVERY batch composition (decode-only, decode+verify,
        # decode+prefill, all three) reuses the one compiled executable.
        # Sampling always runs on each row's first gathered logits row
        # (decode's next token / a prompt-final chunk's first token);
        # draft verification additionally runs when speculative decoding
        # is on. The host picks per row by mode; unused lanes are
        # discarded exactly like inactive slots' logits always were.
        if self.ragged:
            self._ragged_k = (serve_cfg.num_draft_tokens
                              if self.spec_enabled else 0)
            self._ragged_width = max(
                1 + self._ragged_k,
                (serve_cfg.prefill_chunk * serve_cfg.prefill_max_chunks)
                if self.chunked else 1)
            nl = 1 + self._ragged_k
            rk = self._ragged_k
            # the megakernel step is call-compatible with the per-layer
            # ragged step; it takes the layer-stacked params instead
            step_model = (model.megakernel_step_paged if self.megakernel
                          else model.ragged_step_paged)
            self._step_params = (
                model.pack_megakernel_params(self.params, self.cfg_decode)
                if self.megakernel else self.params)

            def _ragged_step_fn(p, c, tok, rows, start, lens, lidx, temps,
                                tps, tks, seeds, ctrs, fmts=None):
                kw = ({"page_fmts": fmts, "mixed_fmts": mf}
                      if fmts is not None else {})
                logits, c = step_model(
                    p, self.cfg_decode, c, tok, rows, start, lens, lidx,
                    num_logits=nl, **kw)
                with jax.named_scope("sample"):
                    toks = sampling.sample(logits[:, 0], temps, tps, tks,
                                           seeds, ctrs)
                    if rk:
                        n_emit, emitted = sampling.verify_rejection(
                            logits, tok[:, 1:1 + rk], temps, tps, tks,
                            seeds, ctrs)
                        return toks, n_emit, emitted, c
                return toks, c

            if self.mesh is not None:
                from jax.sharding import PartitionSpec as P

                from repro.parallel.ctx import use_serve_tp
                axis = self._tp_axis

                def _sharded_step(p, c, *rest):
                    # trace-time signal: attention.apply_ragged reads it
                    # to size reshapes by the local head slice and to
                    # insert the step's one all-gather
                    with use_serve_tp(axis):
                        return _ragged_step_fn(p, c, *rest)

                # page tables, row metadata and sampling vectors are
                # replicated (every device runs the same host schedule
                # in lockstep); only params' head columns and the pool's
                # KV-head axis are sharded. Outputs: sampled tokens /
                # verify results are factually replicated — each device
                # computed them from the identical post-gather tensor.
                n_meta = 10 + (1 if self.tiered else 0)
                out_specs = ((P(), P(), P(), self._pool_specs) if rk
                             else (P(), self._pool_specs))
                fn = jax.shard_map(
                    _sharded_step, mesh=self.mesh,
                    in_specs=(self._param_specs, self._pool_specs)
                    + (P(),) * n_meta,
                    out_specs=out_specs, check_vma=False)
                self._ragged_fn = jax.jit(
                    fn, donate_argnums=() if cpu else (1,))
            else:
                self._ragged_fn = jax.jit(
                    _ragged_step_fn, donate_argnums=() if cpu else (1,))
            # unjitted handle for the dispatch audit (jaxpr pallas_call
            # count, measured lazily at the first ragged step)
            self._ragged_fn_raw = _ragged_step_fn
        self.pallas_calls_per_step = None
        self._step_shapes = None  # the ragged step's argument shapes
        self._key = jax.random.PRNGKey(0)
        # requests that don't carry SamplingParams sample with these
        self._default_sampling = SamplingParams(
            temperature=serve_cfg.temperature, top_p=serve_cfg.top_p,
            top_k=serve_cfg.top_k).validate()
        # admission gate: sheds submissions (ShedError) once the predicted
        # first-token latency misses slo_ms or the queue hits max_queue;
        # with neither knob set it only keeps stats
        self.overload = OverloadController(OverloadConfig(
            slo_ms=serve_cfg.slo_ms, max_queue=serve_cfg.max_queue))
        self.steps = 0
        # device-dispatch accounting: every jitted call an engine step
        # issues lands in one bucket, so the ragged step's whole claim —
        # dispatches_per_mixed_step == 1 — is measured, never asserted
        self.dispatch_counts = {"decode": 0, "verify": 0, "prefill": 0,
                                "ragged": 0, "write": 0, "repack": 0}
        # the per-layer ragged kernel's page walk over the engine's life:
        # page bodies executed and grid steps, a layer (steplog.StepRecord)
        self.walk_live = 0.0
        self.walk_grid = 0
        self._walk_windows = tuple(
            bd.window for _, _, bd in model.iter_layer_blocks(self.cfg_decode)
            if bd.mixer == "attn")
        self._step_dispatches = 0
        self.mixed_steps = 0  # steps doing decode AND prefill work
        self.mixed_step_dispatches = 0
        self._step_had_prefill = False
        self._step_had_decode = False
        self.prompt_tokens = 0  # total prompt tokens admitted
        self.prefill_tokens = 0  # prompt tokens actually computed
        self.prefill_chunks = 0  # per-sequence chunks processed
        self.prefill_dispatches = 0  # chunked-prefill kernel invocations
        self._rr_clock = 0  # cross-step round-robin cursor over prefills
        # admission latency: seconds from a request's arrival (its
        # caller's receipt time, else submit()) to its first sampled
        # token (the serving-side tail-latency metric chunked prefill
        # exists to improve). Bounded sliding window so a long-running
        # server's stats stay O(1) memory — the same unbounded-growth
        # class the LRU trace cap closes.
        self.admission_latencies: deque = deque(maxlen=4096)
        # host time of each of the last STEP_LOG_LEN steps, by phase
        # (serve.steplog); self._timer is the step in progress
        self.step_log: deque = deque(maxlen=STEP_LOG_LEN)
        self._timer: Optional[StepTimer] = None
        # speculative decoding stats
        self.spec_steps = 0  # verify steps run
        self.spec_seq_steps = 0  # (sequence, verify step) participations
        self.drafted_tokens = 0  # k per active sequence per verify step
        self.accepted_tokens = 0  # drafts that matched the greedy target
        self.emitted_tokens = 0  # tokens recorded by verify steps
        # tiered mixed-format pool state (host-authoritative, mirrored to
        # device on change): one format id + last-write tick per physical
        # page, shared by every layer like the page table
        self._tick = 0  # advances every step(); drives page ages
        if self.tiered:
            self._base_fmt_id = FORMAT_IDS[cfg.quant.fmt]
            self.page_fmts = np.full(
                (self.num_pages + self._trash_pages,), self._base_fmt_id,
                np.int32)
            self._page_fmts_dev = jnp.asarray(self.page_fmts)
            self._fmts_dirty = False
            self._last_write = np.zeros(
                (self.num_pages + self._trash_pages,), np.int64)
            # swap snapshots preserve raw page bytes, so the pages'
            # format ids must survive the free/realloc cycle with them
            self._swap_fmts: Dict[int, list] = {}
            self._repack_fns: Dict[str, object] = {}  # dst fmt -> jitted
            self.repacked_pages = 0
            self.repack_dispatches = 0
            self.max_repacked_in_step = 0
            self._repacked_this_step = 0

    def _validate_tiering(self, cfg: ModelConfig, mixers) -> None:
        tp = self.tier
        scfg = self.serve_cfg
        if scfg.decode_kernel != "fused":
            raise ValueError(
                "tiered KV cache requires decode_kernel='fused': the "
                "einsum gather path dequantizes without per-page formats")
        if not self.chunked:
            raise ValueError(
                "tiered KV cache requires chunked prefill on an "
                "attention-only model: the monolithic gather path reads "
                "pages without per-page formats")
        if not cfg.quant.quantize_kv_cache:
            raise ValueError("tiered KV cache requires quantize_kv_cache")
        if _FMT_BITS.get(cfg.quant.fmt) != 8:
            raise ValueError(
                f"tiered KV cache needs an 8-bit base KV format (new "
                f"writes land full-width), got {cfg.quant.fmt!r}")
        for name, fmt in (("mid_fmt", tp.mid_fmt), ("cold_fmt", tp.cold_fmt)):
            if fmt not in FORMAT_IDS:
                raise ValueError(f"unknown tier {name} {fmt!r}")
        if not (_FMT_BITS[cfg.quant.fmt] > _FMT_BITS[tp.mid_fmt]
                >= _FMT_BITS[tp.cold_fmt]):
            raise ValueError(
                f"tier ladder must narrow monotonically, got "
                f"{cfg.quant.fmt} -> {tp.mid_fmt} -> {tp.cold_fmt}")
        if tp.hot_steps < 1 or tp.cold_steps < tp.hot_steps:
            raise ValueError(
                "tier_policy needs hot_steps >= 1 and "
                "cold_steps >= hot_steps")
        if tp.repack_pages_per_step < 0 or tp.repack_list_len < 1:
            raise ValueError(
                "tier_policy needs repack_pages_per_step >= 0 and "
                "repack_list_len >= 1")

    # -- internals ----------------------------------------------------------

    def _shard_put(self, tree, specs):
        """Place ``tree`` per a matching PartitionSpec tree on the mesh."""
        from jax.sharding import NamedSharding

        flat, treedef = jax.tree_util.tree_flatten(tree)
        flat_s = treedef.flatten_up_to(specs)
        placed = [jax.device_put(x, NamedSharding(self.mesh, s))
                  for x, s in zip(flat, flat_s)]
        return jax.tree_util.tree_unflatten(treedef, placed)

    def _lru_trace(self, store: OrderedDict, key, build):
        """Fetch-or-build a jitted trace with LRU eviction at the cap.

        The monolithic path traces per prompt length (and per
        (tail, prefix) pair), so an unbounded dict grows with every novel
        length a long-running server sees; evicting the LRU entry drops
        the jit wrapper and its compiled executables with it.
        """
        fn = store.get(key)
        if fn is None:
            fn = build()
            store[key] = fn
        else:
            store.move_to_end(key)
        while len(store) > self.serve_cfg.prefill_trace_cache:
            store.popitem(last=False)
        return fn

    def _prefill_for(self, length: int):
        """Jitted single-request prefill, LRU-cached per prompt length.

        max_seq rounds up to the page boundary so the cache T dim factors
        into whole pages. No padding of the tokens themselves: prefill
        numerics stay exactly those of the fixed-slot batch prefill.
        """
        ps = self.serve_cfg.page_size
        max_seq = kv_cache.pages_for(length, ps) * ps
        return self._lru_trace(
            self._prefill_fns, length,
            lambda: jax.jit(lambda p, toks: model.prefill(
                p, self.cfg_prefill, tokens=toks, max_seq=max_seq)))

    def _prefill_tail_for(self, tail_len: int, n_gather: int, pos0: int):
        """Jitted tail prefill, LRU-cached per (tail length, gathered
        prefix pages, prefix tokens).

        Reads the shared prefix pages out of the live paged cache and
        prefills only the uncached tail at absolute positions — the
        prefix-cache fast path of the monolithic mode. ``pos0`` (the hit
        length) need not be a page multiple: a partial-page hit gathers
        ``n_gather = ceil(pos0 / page_size)`` pages and the model masks
        the last page's rows past ``pos0``.
        """
        max_seq = kv_cache.pages_for(tail_len, self.serve_cfg.page_size) \
            * self.serve_cfg.page_size
        return self._lru_trace(
            self._prefill_tail_fns, (tail_len, n_gather, pos0),
            lambda: jax.jit(lambda p, c, toks, rows: model.prefill_with_prefix(
                p, self.cfg_prefill, c, toks, rows, pos0,
                max_seq=max_seq)))

    def _next_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    def _count_dispatch(self, kind: str, n: int = 1) -> None:
        """Record ``n`` device dispatches of ``kind`` against the current
        engine step (see ``dispatch_counts`` / ``cache_stats``)."""
        self.dispatch_counts[kind] += n
        self._step_dispatches += n

    def _audit_dispatches(self, call_args) -> None:
        """Measure ``pallas_calls_per_step`` from the traced step's jaxpr.

        Runs ONCE, lazily, on the first ragged step's real argument
        shapes (abstract trace only — nothing executes), so the number
        in ``cache_stats()`` / the serve log is derived from the same
        program the engine dispatches, not asserted from code structure.
        """
        jaxpr = jax.make_jaxpr(self._ragged_fn_raw)(*call_args)
        self.pallas_calls_per_step = _pallas_calls_in(jaxpr.jaxpr)
        log.info(
            "step audit: %d pallas_call(s) per engine step (%s)",
            self.pallas_calls_per_step,
            "layer-fused megakernel" if self.megakernel
            else "per-layer ragged step")

    def _record_first_token(self, req) -> None:
        """Admission-latency sample: arrival -> first sampled token."""
        if req.first_token is None:
            req.first_token = time.perf_counter()
            lat = req.first_token - req.arrival
            self.admission_latencies.append(lat)
            self.overload.observe_first_token(lat)

    # -- sampling parameter plumbing ----------------------------------------

    def _req_sampling(self, req) -> SamplingParams:
        return req.sampling if req.sampling is not None \
            else self._default_sampling

    def _slot_sampling(self, seqs):
        """Per-slot sampling parameter vectors for one jitted step.

        Inactive slots stay at the neutral greedy defaults (their sampled
        token is computed and discarded, like their logits always were).
        Each active row's counter is its request's next stream index —
        ``len(generated)`` — which is what makes the stream a pure
        function of (seed, index): slot id, batch composition, and
        preemption history never enter the key.
        """
        arrs = sampling.slot_arrays(self.serve_cfg.max_slots)
        for seq in seqs:
            sp = self._req_sampling(seq.req)
            slot = seq.slot
            arrs["temps"][slot] = sp.temperature
            arrs["top_ps"][slot] = sp.top_p
            arrs["top_ks"][slot] = sp.top_k
            arrs["seeds"][slot] = seq.req.seed
            arrs["counters"][slot] = len(seq.req.generated)
        return (jnp.asarray(arrs["temps"]), jnp.asarray(arrs["top_ps"]),
                jnp.asarray(arrs["top_ks"]), jnp.asarray(arrs["seeds"]),
                jnp.asarray(arrs["counters"]))

    def _sample_prefill_rows(self, seqs, logits):
        """Sample each row's first token from prefill logits (N, V) —
        counter 0 of each request's stream; one dispatch per batch, its
        (N,) tokens left on the device."""
        n = len(seqs)
        temps = np.zeros((n,), np.float32)
        tps = np.ones((n,), np.float32)
        tks = np.zeros((n,), np.int32)
        seeds = np.zeros((n,), np.uint32)
        for i, seq in enumerate(seqs):
            sp = self._req_sampling(seq.req)
            temps[i], tps[i], tks[i] = sp.temperature, sp.top_p, sp.top_k
            seeds[i] = seq.req.seed
        self._count_dispatch("prefill")
        return self._sample_fn(
            logits, jnp.asarray(temps), jnp.asarray(tps),
            jnp.asarray(tks), jnp.asarray(seeds),
            jnp.zeros((n,), jnp.int32))

    # -- tiered mixed-format pool internals ---------------------------------

    def _sync_fmts(self):
        """Device mirror of the per-page format ids (refresh on change)."""
        if self._fmts_dirty:
            self._page_fmts_dev = jnp.asarray(self.page_fmts)
            self._fmts_dirty = False
        return self._page_fmts_dev

    def _drain_allocs(self) -> None:
        """Reset recycled pages to the base format.

        Every page the pool handed out since the last drain starts life
        hot: its next write is full-width fp8. A page that was repacked
        to fp4, freed, and re-allocated would otherwise keep its stale
        narrow format id — the reader would then misdecode the fresh fp8
        bytes. Idempotent; called before every device dispatch and
        before swap-restore format fix-ups.
        """
        if not self.tiered:
            return
        for pid in self.scheduler.pool.alloc_log:
            if self.page_fmts[pid] != self._base_fmt_id:
                self.page_fmts[pid] = self._base_fmt_id
                self._fmts_dirty = True
            self._last_write[pid] = self._tick
        self.scheduler.pool.alloc_log.clear()

    def _mark_write(self, pids) -> None:
        """Record that this step writes rows into ``pids`` (keeps hot)."""
        if self.tiered:
            for pid in pids:
                self._last_write[pid] = self._tick

    def _set_page_fmt(self, pid: int, fmt: str) -> None:
        """Flip one page's format id + unit cost (after a device repack).

        The flip is the atomic commit point: every holder of the page —
        other sequences' tables, the prefix tree, the next dispatch —
        reads the one shared ``page_fmts`` array, so a shared page is
        repacked once and all readers switch together.
        """
        self.page_fmts[pid] = FORMAT_IDS[fmt]
        self._fmts_dirty = True
        self.scheduler.pool.set_cost(pid, UNITS_BY_BITS[_FMT_BITS[fmt]])

    def _repack_fn_for(self, dst_fmt: str):
        """Jitted whole-cache repack to ``dst_fmt``, one trace per target
        format (the page list is padded to a fixed length)."""
        fn = self._repack_fns.get(dst_fmt)
        if fn is None:
            cpu = jax.default_backend() == "cpu"
            mf = self._mixed_fmts
            bs_cfg = self.cfg.quant.block_size
            keys = ("k_elems", "k_scales", "v_elems", "v_scales")

            def run(cache, ids, fmts, count):
                for path, blk, grouped in kv_cache._iter_blocks(cache):
                    if not kv_cache._is_pool(blk):
                        continue
                    leaves = [blk[key] for key in keys]
                    bs = min(bs_cfg, leaves[0].shape[-1])
                    if grouped:
                        outs = [mx_repack_pages(
                            *(leaf[g] for leaf in leaves), ids, fmts,
                            count, dst_fmt_name=dst_fmt, mixed_fmts=mf,
                            block_size=bs)
                            for g in range(leaves[0].shape[0])]
                        new = {key: jnp.stack([o[j] for o in outs])
                               for j, key in enumerate(keys)}
                    else:
                        new = dict(zip(keys, mx_repack_pages(
                            *leaves, ids, fmts, count,
                            dst_fmt_name=dst_fmt, mixed_fmts=mf,
                            block_size=bs)))
                    cache = kv_cache._set_block(cache, path, new)
                return cache

            run_fn = run
            if self.mesh is not None:
                # the repack kernel's grid is (page-list, KVH): with the
                # pool's KV-head axis sharded it runs shard-local on each
                # device's head slice — the page ids / formats / count
                # are replicated, no collective anywhere
                from jax.sharding import PartitionSpec as P

                run_fn = jax.shard_map(
                    run, mesh=self.mesh,
                    in_specs=(self._pool_specs, P(), P(), P()),
                    out_specs=self._pool_specs, check_vma=False)
            fn = jax.jit(run_fn, donate_argnums=() if cpu else (0,))
            self._repack_fns[dst_fmt] = fn
        return fn

    def _repack_pages_to(self, pids, dst_fmt: str) -> None:
        """Requantize ``pids`` (current formats per ``page_fmts``) to
        ``dst_fmt`` in place, in fixed-length padded dispatches."""
        ll = self.tier.repack_list_len
        for lo in range(0, len(pids), ll):
            group = pids[lo:lo + ll]
            # pad by repeating the last live id: the kernel predicates
            # on count, so padding rows are never written
            ids = group + [group[-1]] * (ll - len(group))
            fmts = [int(self.page_fmts[p]) for p in ids]
            self.cache = self._repack_fn_for(dst_fmt)(
                self.cache, jnp.asarray(ids, jnp.int32),
                jnp.asarray(fmts, jnp.int32),
                jnp.asarray(len(group), jnp.int32))
            self.repack_dispatches += 1
            self._count_dispatch("repack")
            for pid in group:
                self._set_page_fmt(pid, dst_fmt)
            self.repacked_pages += len(group)
            self._repacked_this_step += len(group)

    def _protected_pages(self) -> set:
        """Pages the tiering pass must not touch this step: every page of
        a still-prefilling sequence from its resume point on (chunk
        writes land there in the base format), and every decode-ready
        sequence's live write window (decode/verify writes land there).
        """
        sched = self.scheduler
        ps = self.serve_cfg.page_size
        protected = set()
        for seq in sched.prefilling():
            protected.update(seq.pages[seq.prefill_pos // ps:])
        span = 1 + (self.serve_cfg.num_draft_tokens
                    if self.spec_enabled else 0)
        for seq in sched.decode_ready():
            lo = seq.pos // ps
            hi = min(len(seq.pages), (seq.pos + span - 1) // ps + 1)
            protected.update(seq.pages[lo:hi])
        return protected

    def _run_repack(self) -> None:
        """One background tiering pass: demote aged pages down the ladder
        under the per-step page budget (coldest candidates first)."""
        if not self.tiered or self.tier.repack_pages_per_step <= 0:
            return
        self._drain_allocs()
        tp, pool = self.tier, self.scheduler.pool
        protected = self._protected_pages()
        mid_id = FORMAT_IDS[tp.mid_fmt]
        to_mid, to_cold = [], []
        for pid in range(self.num_pages):
            if pool.ref(pid) == 0 or pid in protected:
                continue
            age = self._tick - int(self._last_write[pid])
            fmt = int(self.page_fmts[pid])
            if fmt == self._base_fmt_id and age >= tp.hot_steps:
                to_mid.append((age, pid))
            elif fmt == mid_id and mid_id != FORMAT_IDS[tp.cold_fmt] \
                    and age >= tp.cold_steps:
                to_cold.append((age, pid))
        budget = tp.repack_pages_per_step
        self._repacked_this_step = 0
        for cands, dst in ((to_cold, tp.cold_fmt), (to_mid, tp.mid_fmt)):
            if budget <= 0 or not cands:
                continue
            cands.sort(key=lambda t: -t[0])  # oldest first
            take = [pid for _, pid in cands[:budget]]
            self._repack_pages_to(take, dst)
            budget -= len(take)
        self.max_repacked_in_step = max(self.max_repacked_in_step,
                                        self._repacked_this_step)

    def _admit(self) -> list:
        """Admit what fits; returns the admitted request ids."""
        sched = self.scheduler
        admitted = []
        while True:
            seq = sched.admit_next()
            if seq is None:
                return admitted
            admitted.append(seq.req.id)
            if seq.req.swap is not None:
                # swapped-out sequence: restore the exact bytes of the
                # pages it exclusively owned into their fresh replacements
                # (shared prefix pages stayed resident under other refs);
                # its pending token decodes — or its prefill resumes —
                # next step
                snapshot, owned_idx, *_ = seq.req.swap
                seq.req.swap = None
                if owned_idx:
                    self.cache = self._restore(
                        self.cache, snapshot,
                        jnp.asarray(seq.slot, jnp.int32),
                        jnp.asarray([seq.pages[i] for i in owned_idx],
                                    jnp.int32))
                    self._count_dispatch("write")
                if self.tiered:
                    # the snapshot restored the pages' raw bytes, narrow
                    # encodings included — re-apply the format ids they
                    # were extracted with (drain first: alloc just reset
                    # these fresh pages to base)
                    self._drain_allocs()
                    saved = self._swap_fmts.pop(seq.req.id, None)
                    if saved is not None:
                        for i, fid in zip(owned_idx, saved):
                            self._set_page_fmt(seq.pages[i],
                                               FORMAT_BY_ID[fid])
                continue
            prompt = seq.req.prompt
            self.prompt_tokens += len(prompt)
            if seq.prefill_pos is not None:
                # chunked mode: admission only binds the slot and pages;
                # the prompt streams through _run_prefill_chunks under
                # the per-step token budget
                continue
            cached = seq.cached_tokens
            if cached:
                # prefix hit: prefill only the uncached tail against the
                # shared pages already resident in the pool. The hit may
                # end mid-page (partial-page entry): the tail then
                # extends the partial page in place — COW it first (the
                # tree and possibly other holders reference it) and
                # scatter the tail rows at the page-internal offset.
                ps_ = self.serve_cfg.page_size
                n_full, valid = cached // ps_, cached % ps_
                n_gather = n_full + (1 if valid else 0)
                tail = prompt[cached:]
                if valid and sched.pool.ref(seq.pages[n_full]) > 1:
                    old = seq.pages[n_full]
                    new = self._alloc_one(seq)
                    if new is not None:
                        self.cache = self._copy_page(
                            self.cache, jnp.asarray(old, jnp.int32),
                            jnp.asarray(new, jnp.int32))
                        self._count_dispatch("write")
                        sched.pool.free([old])
                        seq.pages[n_full] = new
                        sched.cow_copies += 1
                    elif not self._unpin_partial(old):
                        raise RuntimeError(
                            "page pool exhausted for a lone sequence")
                logits, pfcache = self._prefill_tail_for(
                    len(tail), n_gather, cached)(
                        self.params, self.cache,
                        jnp.asarray(tail, jnp.int32)[None],
                        jnp.asarray(seq.pages[:n_gather], jnp.int32))
                self._count_dispatch("prefill")
                self.prefill_tokens += len(tail)
                if valid:
                    install = self._lru_trace(
                        self._install_offset_fns,
                        (len(seq.pages) - n_full, valid, len(tail)),
                        lambda: jax.jit(
                            lambda c, pf, slot, ids,
                            off=valid, nr=len(tail):
                            kv_cache.install_prefill_offset(
                                c, pf, slot, ids, ps_, off, nr),
                            donate_argnums=()
                            if jax.default_backend() == "cpu" else (0,)))
                    self.cache = install(
                        self.cache, pfcache,
                        jnp.asarray(seq.slot, jnp.int32),
                        jnp.asarray(seq.pages[n_full:], jnp.int32))
                else:
                    self.cache = self._install(
                        self.cache, pfcache,
                        jnp.asarray(seq.slot, jnp.int32),
                        jnp.asarray(seq.pages[n_full:], jnp.int32))
                self._count_dispatch("write")
            else:
                logits, pfcache = self._prefill_for(len(prompt))(
                    self.params, jnp.asarray(prompt, jnp.int32)[None])
                self._count_dispatch("prefill")
                self.prefill_tokens += len(prompt)
                self.cache = self._install(
                    self.cache, pfcache, jnp.asarray(seq.slot, jnp.int32),
                    jnp.asarray(seq.pages, jnp.int32))
                self._count_dispatch("write")
            sched.register_prefix(seq)
            tok = int(np.asarray(
                self._sample_prefill_rows([seq], logits[:, -1]))[0])
            self._record_first_token(seq.req)
            sched.record_token(seq, tok, eos_id=self.serve_cfg.eos_id)

    def _run_prefill_chunks(self) -> None:
        """Advance chunked prefills by up to the per-step token budget.

        The budget is spent round-robin across prefilling sequences, with
        the rotation carried *across* steps (``_rr_clock``): a short
        prompt admitted behind a long one gets its first token after its
        own few chunks, not after the long prompt completes — the
        processor-sharing schedule that moves the admission-latency tail
        (a per-step restart from the oldest sequence would let a long
        prompt hog every one-chunk budget). Each chunk is one call of
        the single jitted trace; the final chunk of a prompt samples the
        request's first token and flips the sequence to decoding.
        """
        if not self.chunked:
            return
        sched = self.scheduler
        budget = self._chunks_per_step
        while budget > 0:
            pref = sched.prefilling()
            if not pref:
                return
            # one chunk per selected sequence, all in ONE kernel dispatch
            # (B rows) — the fix for the old per-sequence B=1 dispatch
            # loop, which serialized concurrently-prefilling sequences'
            # same-shape chunks into separate kernel launches. Only real
            # chunks enter the batch: the kernel unconditionally writes
            # at least one row per batch row (num_valid is clamped to
            # >= 1 in-kernel), so a padding row would scribble on a page.
            start = self._rr_clock % len(pref)
            take = min(budget, len(pref))
            batch = [pref[(start + i) % len(pref)] for i in range(take)]
            self._rr_clock += take
            self._prefill_chunk_batch(batch)
            budget -= take

    def _prefill_chunk_batch(self, seqs) -> None:
        """Run one fixed-size chunk for each sequence in ``seqs`` through
        a single batched paged-prefill dispatch; sequences on their final
        chunk sample their first token from their own logits row."""
        sched = self.scheduler
        c = self.serve_cfg.prefill_chunk
        bsz = len(seqs)
        phase = self._timer.phase
        with phase("assemble"):
            tokens = np.zeros((bsz, c), np.int32)
            rows = np.full((bsz, sched.pages_per_slot), -1, np.int32)
            starts = np.zeros((bsz,), np.int32)
            reals = np.zeros((bsz,), np.int32)
            for i, seq in enumerate(seqs):
                prompt = seq.req.prompt
                st = seq.prefill_pos
                real = min(c, len(prompt) - st)
                tokens[i, :real] = prompt[st:st + real]
                rows[i, : len(seq.pages)] = seq.pages
                starts[i], reals[i] = st, real
            args = ()
            if self.tiered:
                self._drain_allocs()
                ps = self.serve_cfg.page_size
                for i, seq in enumerate(seqs):
                    self._mark_write(seq.pages[starts[i] // ps:
                                               (starts[i] + reals[i] - 1)
                                               // ps + 1])
                args = (self._sync_fmts(),)
            call_args = (self.params, self.cache, jnp.asarray(tokens),
                         jnp.asarray(rows), jnp.asarray(starts),
                         jnp.asarray(reals), jnp.asarray(reals - 1), *args)
        finals = starts + reals >= np.asarray(
            [len(seq.req.prompt) for seq in seqs])
        with phase("dispatch"):
            logits, self.cache = self._prefill_chunk(*call_args)
            self._count_dispatch("prefill")
            # prompt-final rows sample their first token from their own
            # logits row, in one more dispatch
            sampled = (self._sample_prefill_rows(seqs, logits[:, -1])
                       if finals.any() else None)
        with phase("sync"):
            if sampled is not None:
                sampled = np.asarray(sampled)
        with phase("record"):
            self._step_had_prefill = True
            self.prefill_tokens += int(reals.sum())
            self.prefill_chunks += bsz
            self.prefill_dispatches += 1
            for i, seq in enumerate(seqs):
                seq.pos = int(starts[i] + reals[i])
                seq.prefill_pos = int(starts[i]) + c
                if finals[i]:
                    seq.prefill_pos = None
                    sched.register_prefix(seq)
                    self._record_first_token(seq.req)
                    sched.record_token(seq, int(sampled[i]),
                                       eos_id=self.serve_cfg.eos_id)

    def _swap_out(self, victim) -> None:
        """Preempt ``victim``: snapshot + free only the pages it
        exclusively owns; shared pages keep their other references."""
        sched = self.scheduler
        owned_idx, owned_ids = sched.exclusive_pages(victim)
        snapshot = None
        if owned_ids:
            snapshot = self._extract(
                self.cache, jnp.asarray(victim.slot, jnp.int32),
                jnp.asarray(owned_ids, jnp.int32))
            self._count_dispatch("write")
        if self.tiered:
            # snapshots carry raw page bytes, so the element format of
            # each owned page must travel with them — restore re-applies
            # these after the fresh allocation resets fmts to base
            self._swap_fmts[victim.req.id] = [
                int(self.page_fmts[p]) for p in owned_ids]
        sched.preempt(victim, snapshot, owned_idx)

    def _reclaim_swapped_refs(self) -> bool:
        """Last-resort pool reclamation: queued swapped-out requests still
        retain references on shared pages (normally the cheap choice — the
        pages stay resident under the tree's reference too). When those
        pins would starve a live sequence, extract the shared pages' exact
        bytes into the swap snapshots and drop the references, turning the
        pages evictable/freeable. Restore then treats them like any other
        owned page, so generation stays bit-identical. Returns True if any
        reference was dropped.
        """
        sched = self.scheduler
        released = False
        for req in sched.queue:
            if req.swap is None:
                continue
            snapshot, owned_idx, pages, pos, cached, prefill_pos = req.swap
            owned = set(owned_idx)
            shared_idx = [i for i in range(len(pages)) if i not in owned]
            if not shared_idx:
                continue
            extra = self._extract(
                self.cache, jnp.asarray(0, jnp.int32),
                jnp.asarray([pages[i] for i in shared_idx], jnp.int32))
            self._count_dispatch("write")
            req.swap = (kv_cache.merge_snapshots(snapshot, extra),
                        owned_idx + shared_idx, pages, pos, cached,
                        prefill_pos)
            if self.tiered:
                self._swap_fmts.setdefault(req.id, []).extend(
                    int(self.page_fmts[pages[i]]) for i in shared_idx)
            sched.pool.free([pages[i] for i in shared_idx])
            released = True
        return released

    def _relieve_pressure(self, seq) -> bool:
        """One escalation step when ``seq`` can't get a page (tree LRU
        eviction already ran inside ``_alloc_with_evict``): swap out the
        youngest other sequence, else reclaim swapped requests' pinned
        shared refs. False means the pool is genuinely exhausted. Single
        source of the escalation order for the grow and COW paths."""
        victim = self.scheduler.pick_victim(exclude=seq)
        if victim is not None:
            self._swap_out(victim)
            return True
        return self._reclaim_swapped_refs()

    def _alloc_one(self, seq) -> Optional[int]:
        """One fresh page for ``seq``, evicting / preempting as needed."""
        while True:
            ids = self.scheduler._alloc_with_evict(1)
            if ids is not None:
                return ids[0]
            if not self._relieve_pressure(seq):
                return None

    def _unpin_partial(self, pid: int) -> bool:
        """Pool-exhaustion fallback for the COW guard: when the copy a
        shared write page needs can't be allocated and the page's only
        other holder is the prefix tree's partial-tail entry, drop that
        entry so the writer owns the page outright. Trades a future hit
        opportunity for liveness — a pool sized exactly to its sequences
        must never deadlock on the pin the tree itself added."""
        prefix = self.scheduler.prefix
        return (prefix is not None and prefix.release_partial(pid)
                and self.scheduler.pool.ref(pid) == 1)

    def _ensure_pages(self, num_tokens: int = 1):
        """Grow each active sequence's page list for this step's write
        window (``num_tokens`` rows at ``seq.pos..`` — 1 for decode,
        1 + K for a speculative verify chunk), swapping out the youngest
        sequences when the pool runs dry, and give it exclusive ownership
        of *every* page in the window (copy-on-write: shared pages are
        never scribbled on — which is also what makes speculative
        rollback safe: a rejected draft's write only ever landed in a
        page this sequence owns alone)."""
        sched = self.scheduler
        ps = self.serve_cfg.page_size
        for seq in list(sched.decode_ready()):
            if sched.slots[seq.slot] is not seq:
                continue  # already preempted by an elder this pass
            while not sched.try_grow(seq, num_tokens):
                if not self._relieve_pressure(seq):
                    raise RuntimeError(
                        "page pool exhausted for a lone sequence")
            last = seq.pos + num_tokens - 1
            for wp in range(seq.pos // ps, last // ps + 1):
                pid = seq.pages[wp]
                if sched.pool.ref(pid) > 1:
                    # copy-on-write: this step writes into a page other
                    # holders reference — copy it to a fresh page and
                    # repoint
                    src_fmt = (int(self.page_fmts[pid])
                               if self.tiered else None)
                    new = self._alloc_one(seq)
                    if new is None:
                        if self._unpin_partial(pid):
                            continue  # sole holder now; write in place
                        raise RuntimeError(
                            "page pool exhausted for a lone sequence")
                    self.cache = self._copy_page(
                        self.cache, jnp.asarray(pid, jnp.int32),
                        jnp.asarray(new, jnp.int32))
                    self._count_dispatch("write")
                    sched.pool.free([pid])
                    seq.pages[wp] = new
                    sched.cow_copies += 1
                    if self.tiered and src_fmt != self._base_fmt_id:
                        # copy_page moved raw bytes, so the fresh page
                        # inherited the source's narrow encoding; this
                        # step's fp8 write would corrupt it. Promote the
                        # copy back to the base format (decode +
                        # re-encode — widening is lossless) first.
                        self._drain_allocs()
                        self._set_page_fmt(new, FORMAT_BY_ID[src_fmt])
                        self._repack_pages_to(
                            [new], FORMAT_BY_ID[self._base_fmt_id])
        if self.tiered:
            self._drain_allocs()
            for seq in sched.decode_ready():
                if sched.slots[seq.slot] is not seq:
                    continue
                last = seq.pos + num_tokens - 1
                self._mark_write(seq.pages[seq.pos // ps: last // ps + 1])

    def step(self) -> bool:
        """Admit what fits, advance prefill chunks under the token
        budget, run one decode (or speculative verify) step over the
        decode-ready slots — as ONE ragged dispatch by default
        (``step_mode="ragged"``), or as the split decode / verify /
        prefill dispatch sequence (``"split"``, the validated oracle).
        Returns True if any work remains afterwards."""
        self._step_dispatches = 0
        self._step_had_prefill = False
        self._step_had_decode = False
        self._tick += 1
        self._timer = StepTimer(self._tick)
        try:
            return self._step_inner()
        finally:
            self.step_log.append(
                self._timer.close(self._step_dispatches > 0))
            if self._step_had_decode and self._step_had_prefill:
                self.mixed_steps += 1
                self.mixed_step_dispatches += self._step_dispatches

    def _step_inner(self) -> bool:
        sched = self.scheduler
        phase = self._timer.phase
        with phase("admit") as span:
            admitted = self._admit()
            if (not sched.active() and sched.queue
                    and self._reclaim_swapped_refs()):
                # pinned shared pages were the blocker
                admitted += self._admit()
            if admitted and span.is_enabled():
                span.set_metadata(requests=" ".join(map(str, admitted)))
        if not sched.active():
            if sched.queue:
                raise RuntimeError("scheduler stalled with queued work")
            return sched.has_work
        if self.ragged:
            with phase("repack"):
                self._run_repack()
            self._ragged_step()
            return sched.has_work
        self._run_prefill_chunks()
        with phase("repack"):
            self._run_repack()
        if not sched.decode_ready():
            # every active sequence is still streaming its prompt; the
            # chunk(s) above were this step's progress
            return sched.has_work
        if self.spec_enabled:
            self._spec_step()
            return sched.has_work
        with phase("ensure_pages"):
            self._ensure_pages()
        with phase("assemble"):
            tokens, pos, page_rows, act = sched.assemble()
            args = (self._sync_fmts(),) if self.tiered else ()
            call_args = (self.params, self.cache, jnp.asarray(tokens),
                         jnp.asarray(page_rows), jnp.asarray(pos),
                         *self._slot_sampling(act), *args)
        with phase("dispatch"):
            toks_dev, self.cache = self._decode(*call_args)
            self._count_dispatch("decode")
        with phase("sync"):
            toks = np.asarray(toks_dev)
        with phase("record"):
            self._step_had_decode = True
            self.steps += 1
            for seq in act:
                sched.advance(seq)
                sched.record_token(seq, int(toks[seq.slot]),
                                   eos_id=self.serve_cfg.eos_id)
        return sched.has_work

    def _ragged_step(self) -> None:
        """One single-dispatch ragged engine step.

        Every decode-ready sequence contributes its pending token (plus K
        drafter proposals under speculative decoding) and every prefilling
        sequence contributes its next prompt chunk; the packed
        (max_slots, W) row batch runs through ONE jitted call of
        ``model.ragged_step_paged`` — attention over the paged MX cache,
        the in-kernel quantize-write of every row's new K/V (no
        ``.at[].set`` round-trip anywhere), next-token sampling and draft
        verification all inside the dispatch. Token streams match the
        split path bit-for-bit: each row runs the same projection / RoPE
        / quantize / flash math its split counterpart ran, and sampling
        keys are (request seed, stream index) in both modes. Unlike the
        split path's budgeted round-robin, every prefilling sequence
        advances one chunk per step — the per-step prefill cost is
        bounded by the batch width instead of ``prefill_token_budget``.
        """
        sched = self.scheduler
        k = self._ragged_k
        phase = self._timer.phase
        with phase("ensure_pages"):
            self._ensure_pages(1 + k)
            if self.tiered:
                self._drain_allocs()
                ps = self.serve_cfg.page_size
                for seq in sched.prefilling():
                    st = seq.prefill_pos
                    # same formula assemble_ragged is about to apply —
                    # the pre-pass must mark exactly the pages the step
                    # writes
                    real = sched.planned_prefill_real(seq,
                                                      self._ragged_width)
                    if real > 0:
                        self._mark_write(
                            seq.pages[st // ps: (st + real - 1) // ps + 1])
        with phase("assemble"):
            (tokens, row_start, seq_lens, logit_idx, page_rows, modes,
             decode, prefill) = sched.assemble_ragged(self._ragged_width,
                                                      extra_tokens=k)
            if not decode and not prefill:
                return
            if not self.megakernel:
                live = _walk_live(row_start, seq_lens,
                                  self.serve_cfg.page_size,
                                  self._walk_windows)
                grid = page_rows.shape[0] * page_rows.shape[1]
                self._timer.walk_live, self._timer.walk_grid = live, grid
                self.walk_live += live
                self.walk_grid += grid
            if k:
                for seq in decode:
                    history = np.concatenate(
                        [seq.req.prompt,
                         np.asarray(seq.req.generated, np.int32)])
                    drafts = np.asarray(self.drafter.propose(history, k),
                                        np.int32)
                    if drafts.shape != (k,):
                        raise ValueError(
                            f"drafter returned shape {drafts.shape}, "
                            f"wanted ({k},)")
                    tokens[seq.slot, 1:1 + k] = drafts
            # prefill-final rows sample at stream index 0 (len(generated)
            # is 0), decode/verify rows at their next index — one
            # parameter vector covers every mode
            samp = self._slot_sampling(decode + [t[0] for t in prefill])
            args = (self._sync_fmts(),) if self.tiered else ()
            call_args = (self._step_params, self.cache, jnp.asarray(tokens),
                         jnp.asarray(page_rows), jnp.asarray(row_start),
                         jnp.asarray(seq_lens), jnp.asarray(logit_idx),
                         *samp, *args)
        with phase("dispatch"):
            if self._step_shapes is None:
                # placed (committed) arrays keep their sharding, so that
                # step_program() lowers exactly the dispatched program
                self._step_shapes = jax.tree_util.tree_map(
                    lambda x: jax.ShapeDtypeStruct(
                        x.shape, x.dtype,
                        sharding=x.sharding if x.committed else None),
                    call_args)
            if self.pallas_calls_per_step is None and self.mesh is None:
                self._audit_dispatches(call_args)
            out = self._ragged_fn(*call_args)
            self._count_dispatch("ragged")
        with phase("sync"):
            n_emit = emitted = None
            if k:
                toks_dev, n_emit_dev, emitted_dev, self.cache = out
                n_emit = np.asarray(n_emit_dev)
                emitted = np.asarray(emitted_dev)
            else:
                toks_dev, self.cache = out
            toks = np.asarray(toks_dev)
        with phase("record"):
            self._record_ragged(toks, n_emit, emitted, decode, prefill)

    def _record_ragged(self, toks, n_emit, emitted, decode, prefill) -> None:
        """Book the ragged step's sampled tokens: decode and verify rows
        advance and record theirs, prompt-final chunks their first."""
        sched = self.scheduler
        k = self._ragged_k
        if decode:
            self.steps += 1
            self._step_had_decode = True
        if prefill:
            self._step_had_prefill = True
            self.prefill_chunks += len(prefill)
            self.prefill_tokens += int(sum(t[2] for t in prefill))
            self.prefill_dispatches += 1
        # decode / verify rows: the advance-then-record pairing of the
        # split loops, EOS and max_new recycling the slot the same step
        if k:
            if decode:
                self.spec_steps += 1
            for seq in decode:
                cnt = int(n_emit[seq.slot])
                self.spec_seq_steps += 1
                self.drafted_tokens += k
                self.accepted_tokens += cnt - 1
                for tok in emitted[seq.slot, :cnt]:
                    sched.advance(seq)
                    self.emitted_tokens += 1
                    if not sched.record_token(
                            seq, int(tok), eos_id=self.serve_cfg.eos_id):
                        break
        else:
            for seq in decode:
                sched.advance(seq)
                sched.record_token(seq, int(toks[seq.slot]),
                                   eos_id=self.serve_cfg.eos_id)
        # prefill rows: the chunk's K/V already landed in-dispatch; a
        # prompt-final chunk samples its request's first token from its
        # own logits row and flips the sequence to decoding
        for seq, st, real, final in prefill:
            seq.pos = st + real
            seq.prefill_pos = None if final else st + real
            if final:
                sched.register_prefix(seq)
                self._record_first_token(seq.req)
                sched.record_token(seq, int(toks[seq.slot]),
                                   eos_id=self.serve_cfg.eos_id)

    def _spec_step(self) -> None:
        """One speculative draft + batched verify + rollback step.

        Each active slot feeds its pending token plus K drafter
        proposals; one ``verify_step_paged`` call writes all K + 1
        tokens' K/V into the slot's (exclusively owned — see
        ``_ensure_pages``) pages and returns per-position logits under
        causal intra-chunk masking; acceptance runs in the same dispatch
        (``sampling.verify_rejection``). Greedy rows keep the longest
        draft prefix matching the model's own argmaxes plus one bonus
        token — token-identical to non-speculative decode regardless of
        the drafter. Stochastic rows run point-mass rejection sampling
        against the filtered target distribution, so every emitted token
        is distributed exactly as plain sampling at that stream position
        (lossless; see ``serve.sampling``). Rejected drafts are rolled
        back page-exactly by simply not advancing ``seq.pos`` past the
        accepted point: their rows are dead by position masking and the
        next write there overwrites them (nothing zeroed, nothing
        copied, shared pages never touched).
        """
        sched = self.scheduler
        k = self.serve_cfg.num_draft_tokens
        phase = self._timer.phase
        with phase("ensure_pages"):
            self._ensure_pages(1 + k)
        with phase("assemble"):
            tokens, pos, page_rows, act = sched.assemble(extra_tokens=k)
            for seq in act:
                history = np.concatenate(
                    [seq.req.prompt,
                     np.asarray(seq.req.generated, np.int32)])
                drafts = np.asarray(self.drafter.propose(history, k),
                                    np.int32)
                if drafts.shape != (k,):
                    raise ValueError(f"drafter returned shape "
                                     f"{drafts.shape}, wanted ({k},)")
                tokens[seq.slot, 1:] = drafts
            args = (self._sync_fmts(),) if self.tiered else ()
            call_args = (self.params, self.cache, jnp.asarray(tokens),
                         jnp.asarray(page_rows), jnp.asarray(pos),
                         *self._slot_sampling(act), *args)
        with phase("dispatch"):
            n_emit_dev, emitted_dev, self.cache = self._verify(*call_args)
            self._count_dispatch("verify")
        with phase("sync"):
            n_emit = np.asarray(n_emit_dev)
            emitted = np.asarray(emitted_dev)
        with phase("record"):
            self._record_verify(act, n_emit, emitted)

    def _record_verify(self, act, n_emit, emitted) -> None:
        """Book a verify step: each row's accepted drafts and bonus
        token, stopping at EOS / max_new (the rollback)."""
        sched = self.scheduler
        k = self.serve_cfg.num_draft_tokens
        self._step_had_decode = True
        self.steps += 1
        self.spec_steps += 1
        for seq in act:
            cnt = int(n_emit[seq.slot])
            self.spec_seq_steps += 1
            self.drafted_tokens += k
            self.accepted_tokens += cnt - 1
            for tok in emitted[seq.slot, :cnt]:
                # each emitted token validates one more written row
                # (advance) before it is recorded — the verify-time
                # mirror of the decode loop's advance/record pair; the
                # loop stopping early (EOS / max_new) is the rollback
                sched.advance(seq)
                self.emitted_tokens += 1
                if not sched.record_token(seq, int(tok),
                                          eos_id=self.serve_cfg.eos_id):
                    break

    # -- public API ---------------------------------------------------------

    def submit(self, prompt: np.ndarray, max_new_tokens: int,
               sampling_params: Optional[SamplingParams] = None,
               arrival: Optional[float] = None) -> int:
        """Queue one request; returns its id. Use with :meth:`run`.

        ``sampling_params`` overrides the engine-default temperature /
        top-p / top-k / seed for this request alone (None = defaults).
        ``arrival`` is when the request arrived, in ``time.perf_counter``
        seconds (default: now); admission latency counts from it.
        Raises :class:`~.overload.ShedError` when overload control is
        configured and admitting this request would already miss the
        SLO — shed at the door, before it costs a slot, pages, and
        prefill work.
        """
        self.overload.admit(len(self.scheduler.queue))
        sp = (sampling_params.validate() if sampling_params is not None
              else self._default_sampling)
        seed = sampling.resolve_seed(sp, self.serve_cfg.seed,
                                     self.scheduler._next_id)
        return self.scheduler.submit(prompt, max_new_tokens, sampling=sp,
                                     seed=seed, arrival=arrival)

    def cancel(self, request_id: int) -> bool:
        """Abandon a request mid-flight (client disconnect): frees its
        slot, exclusively-owned pages, and prefix-cache retains the same
        step, wherever it currently lives — queued, mid-prefill,
        decoding, or swapped out. True if the request was found (False:
        it already finished and its resources are long gone)."""
        found = self.scheduler.cancel(request_id)
        if found and self.tiered:
            self._swap_fmts.pop(request_id, None)
        return found

    def save_prefix_cache(self, path) -> int:
        """Persist the prefix cache — radix-tree structure AND the exact
        device bytes of every page it holds — to ``path`` (npz).

        A restarted engine :meth:`load_prefix_cache`-s this and
        warm-starts shared prompt heads without recomputing (or even
        re-quantizing) them: the restored pages are bit-identical, so
        decode over an imported hit is token-identical to decode over
        the original cache. Tiered engines save each page's element
        format alongside its bytes (an fp4-repacked page must be read as
        fp4 after import). Returns the number of pages saved.
        """
        prefix = self.scheduler.prefix
        if prefix is None:
            raise RuntimeError("engine has no prefix cache to save")
        state = prefix.export_state()
        pids = sorted({nd["page"] for nd in state["nodes"]}
                      | {ent["page"] for ent in state["partials"]})
        payload = {
            "structure": np.frombuffer(json.dumps(state).encode(),
                                       np.uint8),
            "page_ids": np.asarray(pids, np.int64),
        }
        if self.tiered:
            payload["page_fmts"] = np.asarray(
                [int(self.page_fmts[p]) for p in pids], np.int32)
        if pids:
            snap = self._extract(self.cache, jnp.asarray(0, jnp.int32),
                                 jnp.asarray(pids, jnp.int32))
            for i, leaf in enumerate(jax.tree_util.tree_leaves(snap)):
                arr = np.asarray(leaf)
                # raw bytes + dtype name + shape: survives MX element /
                # bf16-scale dtypes that plain savez may not round-trip
                payload[f"leaf_{i}_bytes"] = np.frombuffer(
                    arr.tobytes(), np.uint8)
                payload[f"leaf_{i}_dtype"] = np.asarray(arr.dtype.name)
                payload[f"leaf_{i}_shape"] = np.asarray(arr.shape,
                                                        np.int64)
        np.savez(path, **payload)
        return len(pids)

    def load_prefix_cache(self, path) -> int:
        """Warm-start the prefix cache from :meth:`save_prefix_cache`
        output: allocates fresh pages, restores the saved bytes into
        them verbatim, and rebuilds the radix tree over the new ids.
        Requires an empty prefix cache (call it before serving traffic).
        Returns the number of tree entries (nodes + partials) imported.
        """
        prefix = self.scheduler.prefix
        if prefix is None:
            raise RuntimeError("engine has no prefix cache to load into")
        data = np.load(path)
        state = json.loads(bytes(data["structure"]).decode())
        old_ids = [int(x) for x in data["page_ids"]]
        new_ids = []
        if old_ids:
            new_ids = self.scheduler._alloc_with_evict(len(old_ids))
            if new_ids is None:
                raise RuntimeError(
                    f"page pool cannot hold {len(old_ids)} imported "
                    "prefix pages")
            # the reference extract supplies the authoritative treedef,
            # dtypes, and shapes — the snapshot must match this engine's
            # model/page geometry exactly
            ref = self._extract(self.cache, jnp.asarray(0, jnp.int32),
                                jnp.asarray(new_ids, jnp.int32))
            leaves_ref, treedef = jax.tree_util.tree_flatten(ref)
            leaves = []
            for i, lr in enumerate(leaves_ref):
                dtype = np.dtype(lr.dtype)
                shape = tuple(int(s) for s in data[f"leaf_{i}_shape"])
                if str(data[f"leaf_{i}_dtype"]) != dtype.name \
                        or shape != tuple(lr.shape):
                    raise ValueError(
                        f"prefix snapshot leaf {i} is "
                        f"{str(data[f'leaf_{i}_dtype'])}{shape}, this "
                        f"engine expects {dtype.name}{tuple(lr.shape)} — "
                        "saved under a different model or page config")
                leaves.append(jnp.asarray(np.frombuffer(
                    data[f"leaf_{i}_bytes"].tobytes(),
                    dtype).reshape(shape)))
            self.cache = self._restore(
                self.cache, jax.tree_util.tree_unflatten(treedef, leaves),
                jnp.asarray(0, jnp.int32), jnp.asarray(new_ids, jnp.int32))
        count = prefix.import_state(state,
                                    dict(zip(old_ids, new_ids)))
        if self.tiered:
            # alloc reset the fresh pages to the base format; re-apply
            # the formats the bytes were saved under
            self._drain_allocs()
            for pid, fid in zip(new_ids, data["page_fmts"]):
                if int(fid) != self._base_fmt_id:
                    self._set_page_fmt(pid, FORMAT_BY_ID[int(fid)])
        return count

    def run(self) -> Dict[int, np.ndarray]:
        """Serve until drained. Returns {request_id: prompt + generated}."""
        while self.step():
            pass
        out = {}
        for req in self.scheduler.finished:
            out[req.id] = np.concatenate(
                [req.prompt, np.asarray(req.generated, np.int32)])
        self.scheduler.finished.clear()
        return out

    def generate(self, prompts: np.ndarray, max_new_tokens: int,
                 key=None) -> np.ndarray:
        """Batch API, shape-compatible with ``FixedSlotEngine.generate``.

        Rows that hit EOS early are right-padded with ``eos_id``.
        """
        if key is not None:
            self._key = key
        prompts = np.asarray(prompts, np.int32)
        b, s0 = prompts.shape
        ids = [self.submit(prompts[i], max_new_tokens) for i in range(b)]
        results = self.run()
        pad = self.serve_cfg.eos_id if self.serve_cfg.eos_id is not None else 0
        out = np.full((b, s0 + max_new_tokens), pad, np.int32)
        for row, rid in enumerate(ids):
            toks = results[rid]
            out[row, : len(toks)] = toks
        return out

    def cache_stats(self) -> Dict[str, float]:
        """Allocation + peak-usage + prefix-sharing + dispatch stats."""
        page_bytes = kv_cache.pool_page_nbytes(
            self.cache, self.num_pages + self._trash_pages)
        sched = self.scheduler
        stats = {
            "allocated_bytes": kv_cache.cache_nbytes(self.cache),
            "page_bytes": page_bytes,
            "state_bytes": kv_cache.state_nbytes(self.cache),
            "peak_pages": sched.peak_pages,
            "resident_tokens_at_peak": sched.resident_at_peak,
            "preemptions": sched.preemptions,
            "peak_paged_bytes": page_bytes * sched.peak_pages,
            "skipped_admissions": sched.skipped_admissions,
            "deferred_admissions": sched.deferred_admissions,
            "cancellations": sched.cancellations,
            "shed_count": self.overload.shed_count,
            "cow_copies": sched.cow_copies,
            "prompt_tokens": self.prompt_tokens,
            "prefill_tokens_computed": self.prefill_tokens,
            "prefix_hit_rate": (
                1.0 - self.prefill_tokens / self.prompt_tokens
                if self.prompt_tokens else 0.0),
            "prefill_chunks": self.prefill_chunks,
            "prefill_dispatches": self.prefill_dispatches,
            "deferral_fallbacks": sched.deferral_fallbacks,
            # the monolithic fallback's live jitted-trace population
            # (LRU-bounded); the chunked path's traces are keyed by
            # batch size only, bounded by max_slots
            "prefill_traces": (len(self._prefill_fns)
                               + len(self._prefill_tail_fns)),
            # sharded serving: KV-head shards the pool/projections are
            # split over (1 = single-device / unsharded fallback)
            "kv_head_shards": self.tp,
        }
        # device-dispatch accounting: the ragged step's claim is
        # dispatches_per_mixed_step == 1 — every step that does decode
        # AND prefill work issues exactly one jitted call
        for kind, n in self.dispatch_counts.items():
            stats[f"dispatches_{kind}"] = n
        total_dispatches = sum(self.dispatch_counts.values())
        stats.update({
            "dispatches_total": total_dispatches,
            "dispatches_per_step": (total_dispatches / self.steps
                                    if self.steps else 0.0),
            "mixed_steps": self.mixed_steps,
            "dispatches_per_mixed_step": (
                self.mixed_step_dispatches / self.mixed_steps
                if self.mixed_steps else 0.0),
            # jaxpr-derived device-kernel count of ONE traced engine step
            # (measured at the first ragged dispatch; None before then or
            # off the ragged path): the layer-fused megakernel's whole
            # claim is that this is 1 where the per-layer step pays L
            "pallas_calls_per_step": self.pallas_calls_per_step,
            "megakernel": getattr(self, "megakernel", False),
            # ragged-aware prefill budgeting: prompt rows retired per
            # ragged dispatch that carried prefill work (> chunk size
            # means multi-chunk bites were taken on undersubscribed steps)
            "prefill_rows_per_step": (
                self.prefill_tokens / self.prefill_dispatches
                if self.prefill_dispatches else 0.0),
            # share of the per-layer ragged kernel's grid steps that run
            # a page body (the rest are skipped: past a row's last page
            # or below its window)
            "walk_live_share": (self.walk_live / self.walk_grid
                                if self.walk_grid else 0.0),
        })
        if self.tiered:
            pool = sched.pool
            for fmt in self._mixed_fmts:
                fid = FORMAT_IDS[fmt]
                stats[f"pages_{fmt}"] = sum(
                    1 for pid in range(self.num_pages)
                    if pool.ref(pid) > 0 and self.page_fmts[pid] == fid)
            stats.update({
                "unit_budget": pool.unit_budget,
                "units_in_use": pool.units_in_use,
                "peak_units": pool.peak_units,
                "repacked_pages": self.repacked_pages,
                "repack_dispatches": self.repack_dispatches,
                "max_repacked_in_step": self.max_repacked_in_step,
            })
        if self.admission_latencies:
            lat = np.sort(np.asarray(self.admission_latencies))
            stats["admission_latency_p50"] = float(
                lat[int(0.50 * (len(lat) - 1))])
            stats["admission_latency_p95"] = float(
                lat[int(round(0.95 * (len(lat) - 1)))])
            stats["admission_latency_mean"] = float(lat.mean())
        if self.spec_enabled:
            stats.update({
                "spec_steps": self.spec_steps,
                "drafted_tokens": self.drafted_tokens,
                "accepted_tokens": self.accepted_tokens,
                "emitted_tokens": self.emitted_tokens,
                # the speculative payoff: tokens a sequence emits per
                # verify step it takes part in (1 = no better than plain
                # decode, K+1 = perfect drafts) — normalized per sequence
                # so continuous-batching parallelism doesn't inflate it
                "accepted_per_step": (
                    self.emitted_tokens / self.spec_seq_steps
                    if self.spec_seq_steps else 0.0),
                "draft_acceptance_rate": (
                    self.accepted_tokens / self.drafted_tokens
                    if self.drafted_tokens else 0.0),
            })
        if sched.prefix is not None:
            stats.update(sched.prefix.stats())
        return stats

    def step_program(self):
        """The ragged step as compiled for the argument shapes it runs
        at (a ``jax.stages.Compiled``: JAX keeps the executable it
        dispatched with the lowering, so nothing compiles again), or None
        before the first ragged dispatch. Its ``as_text()`` gives each
        instruction's named scope (``embed``, ``qkv_proj``,
        ``ragged_attn``, ``weight_quant``, ...) in its ``op_name``
        metadata, under the instruction names a profiler trace shows."""
        if self._step_shapes is None:
            return None
        return self._ragged_fn.lower(*self._step_shapes).compile()


# the default engine: continuous batching over the paged MX cache
ServeEngine = ContinuousBatchingEngine


def make_serve_step(cfg: ModelConfig):
    """The (cache, token, pos) -> (logits, cache) step used by the dry-run.

    This is what ``decode_*`` shapes lower: one new token against a KV cache
    of seq_len, global_batch requests in flight.
    """

    def serve_step(params, cache, tokens, pos):
        return model.decode_step(params, cfg, cache, tokens=tokens, pos=pos)

    return serve_step
