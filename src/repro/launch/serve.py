"""Serving launcher: MX weights + paged MX KV cache, continuous batching.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b --reduced \
      --batch 4 --prompt-len 16 --new-tokens 32 --quant mxfp8 --quantize-kv

``--engine continuous`` (default) runs the paged continuous-batching
engine with ragged arrivals; ``--engine fixed`` runs the fixed-slot
reference loop. ``--ragged`` staggers prompt lengths so paging has
something to win on.

``--serve`` starts the asyncio HTTP/SSE front end instead of the batch
workload: POST /v1/generate streams tokens as server-sent events,
/v1/cancel aborts a request mid-flight, /v1/health reports engine and
overload stats. ``--slo-ms``/``--max-queue`` arm load shedding (429),
``--temperature/--top-p/--top-k/--seed`` set the default sampling each
request can override in its own body:

  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b --reduced \
      --serve --port 8000 --temperature 0.8 --top-p 0.95 --slo-ms 500
"""
from __future__ import annotations

import argparse
import asyncio
import logging
import time

import jax
import numpy as np

from repro.configs import get_config, get_reduced
from repro.launch.compile_cache import setup_compile_cache
from repro.nn import model
from repro.serve import (AsyncServeEngine, FixedSlotEngine, ServeConfig,
                         ServeEngine, ServeHTTPServer, TierPolicy)

log = logging.getLogger("repro.serve")


def serving_config(arch: str, *, reduced: bool = False, quant: str = "",
                   quantize_kv: bool = False):
    """The model config of ``arch`` as served: weight-only MX quantization
    in ``quant``'s format (the arch's own block size) and, with
    ``quantize_kv``, an MX-quantized paged KV cache."""
    cfg = get_reduced(arch) if reduced else get_config(arch)
    if quant:
        from repro.core import MXFP4, MXFP8, WIDE

        q = {"wide": WIDE, "mxfp8": MXFP8, "mxfp4": MXFP4}[quant]
        cfg = cfg.replace(quant=q.replace(
            block_size=cfg.quant.block_size,
            quantize_acts=False,  # weight-only for serving
            quantize_kv_cache=quantize_kv))
    return cfg


def build_engine(cfg, serve_cfg, params, kind: str):
    if kind == "fixed":
        return FixedSlotEngine(params, cfg, serve_cfg)
    return ServeEngine(params, cfg, serve_cfg)


def _run_server(engine, args):
    """Run the HTTP/SSE front end until interrupted; graceful drain and
    prefix-snapshot write-back on the way out."""
    import os

    async def serve():
        if args.prefix_snapshot and os.path.exists(args.prefix_snapshot):
            n = engine.load_prefix_cache(args.prefix_snapshot)
            log.info("warm-started prefix cache: %d entries from %s",
                     n, args.prefix_snapshot)
        async_engine = AsyncServeEngine(engine)
        server = ServeHTTPServer(async_engine, host=args.host,
                                 port=args.port)
        await server.start()
        log.info("serving on http://%s:%d (POST /v1/generate, "
                 "/v1/cancel, /v1/drain; GET /v1/health)",
                 args.host, server.port)
        try:
            await server.serve_forever()
        except (KeyboardInterrupt, asyncio.CancelledError):
            pass
        finally:
            log.info("draining...")
            await async_engine.drain()
            await server.stop()
            if args.prefix_snapshot:
                n = engine.save_prefix_cache(args.prefix_snapshot)
                log.info("saved prefix cache: %d pages to %s",
                         n, args.prefix_snapshot)

    try:
        asyncio.run(serve())
    except KeyboardInterrupt:
        pass


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="default sampling temperature (0 = exact greedy)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="default nucleus-sampling mass (1.0 = disabled)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="default top-k cutoff (0 = disabled)")
    ap.add_argument("--seed", type=int, default=0,
                    help="engine base RNG seed; each request's stream is "
                         "derived from (seed, request id) unless the "
                         "request carries its own seed")
    ap.add_argument("--slo-ms", type=float, default=0,
                    help="admission-latency SLO in ms: shed submissions "
                         "(429) once the predicted first-token latency "
                         "exceeds it (0 = no latency-model shedding)")
    ap.add_argument("--max-queue", type=int, default=-1,
                    help="hard queue-depth cap; submissions past it are "
                         "shed (429). -1 = unbounded")
    ap.add_argument("--serve", action="store_true",
                    help="start the HTTP/SSE server instead of running "
                         "the batch workload")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8000)
    ap.add_argument("--prefix-snapshot", default="",
                    help="path to a prefix-cache snapshot "
                         "(save_prefix_cache): loaded at startup if it "
                         "exists, written back on clean server exit — "
                         "restarts warm-start shared prompt heads")
    ap.add_argument("--quant", default="",
                    choices=["", "wide", "mxfp8", "mxfp4"])
    ap.add_argument("--quantize-kv", action="store_true")
    ap.add_argument("--engine", default="continuous",
                    choices=["continuous", "fixed"])
    ap.add_argument("--max-slots", type=int, default=0,
                    help="decode slots for continuous batching "
                         "(default: --batch)")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--ragged", action="store_true",
                    help="vary prompt lengths across requests")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="tokens of common system-prompt head across "
                         "requests (exercises the prefix cache)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable radix-tree prompt sharing")
    ap.add_argument("--decode-kernel", default="fused",
                    choices=["fused", "einsum"],
                    help="paged decode attention path: single-pass fused "
                         "Pallas flash-decode (default) or the reference "
                         "gather-and-dequantize einsum")
    ap.add_argument("--prefill-mode", default="chunked",
                    choices=["chunked", "monolithic"],
                    help="prompt prefill path: 'chunked' (default) streams "
                         "fixed-size chunks straight into MX pages "
                         "(fused quantize-into-pages kernel, O(1) jit "
                         "traces, decode-interleaved admission); "
                         "'monolithic' is the dense reference oracle")
    ap.add_argument("--prefill-chunk", type=int, default=64,
                    help="chunked-prefill chunk length in tokens (must be "
                         "a multiple of --page-size)")
    ap.add_argument("--prefill-token-budget", type=int, default=0,
                    help="max prefill tokens per engine step, spent "
                         "round-robin across admitted prompts "
                         "(default: one chunk)")
    ap.add_argument("--tiered", action="store_true",
                    help="tiered mixed-format KV cache: new pages are "
                         "written in the base 8-bit MX format, idle pages "
                         "are background-repacked down the "
                         "fp8 -> fp6 -> fp4 ladder under a per-step "
                         "budget; --max-seq worth of fp8 bytes is "
                         "reinterpreted as a unit-metered byte budget")
    ap.add_argument("--tier-mid-fmt", default="fp6_e3m2",
                    choices=["fp6_e3m2", "fp6_e2m3", "fp4_e2m1"],
                    help="format warm pages repack to after "
                         "--tier-hot-steps idle steps")
    ap.add_argument("--tier-cold-fmt", default="fp4_e2m1",
                    choices=["fp6_e3m2", "fp6_e2m3", "fp4_e2m1"],
                    help="format cold pages repack to after "
                         "--tier-cold-steps idle steps")
    ap.add_argument("--tier-hot-steps", type=int, default=8,
                    help="engine steps without a write before a page "
                         "leaves the hot fp8 tier")
    ap.add_argument("--tier-cold-steps", type=int, default=32,
                    help="engine steps without a write before a mid-tier "
                         "page goes cold")
    ap.add_argument("--tier-repack-pages", type=int, default=4,
                    help="max pages repacked per engine step (bounds the "
                         "background repack work on the decode path)")
    ap.add_argument("--step-mode", default="ragged",
                    choices=["ragged", "split", "megakernel"],
                    help="engine step dispatch shape: 'ragged' (default) "
                         "packs decode tokens, speculative verify windows "
                         "and prefill chunks into ONE fused Pallas "
                         "dispatch per step with the K/V write done "
                         "in-kernel; 'split' runs the per-mode dispatches "
                         "(the validated oracle). Ragged needs the fused "
                         "kernel + a quantized KV cache and falls back to "
                         "split otherwise. 'megakernel' additionally "
                         "fuses the whole layer stack — norms, QKV+RoPE, "
                         "the paged MX page walk, output projection and "
                         "the gated MLP for EVERY layer — into ONE "
                         "pallas_call per step (the ragged step pays one "
                         "per layer); configs the fused stack cannot "
                         "serve fall back to the per-layer ragged step "
                         "with a logged reason")
    ap.add_argument("--prefill-max-chunks", type=int, default=1,
                    help="ragged-aware prefill budgeting: chunks one "
                         "prefilling sequence may stream in a single "
                         "ragged step while the batch is undersubscribed "
                         "(fewer active sequences than slots); a full "
                         "batch always drops back to 1 chunk/step so "
                         "decode rows are never starved")
    ap.add_argument("--mesh", type=int, default=0,
                    help="sharded serving: KV-head-parallel ways over a "
                         "(1, M) device mesh — the page pool and q/k/v "
                         "projections split along the KV-head axis, wo "
                         "stays replicated behind the step's one "
                         "all-gather, tokens stay identical to "
                         "single-device. Needs M devices (M chips, or "
                         "on CPU XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=M), the ragged step mode, and "
                         "num_kv_heads divisible by M. 0 = unsharded")
    ap.add_argument("--spec-decode", action="store_true",
                    help="greedy speculative decoding: draft K tokens per "
                         "step (prompt-lookup n-gram, no second model) and "
                         "verify them in one batched multi-token pass over "
                         "the paged MX cache — token-identical output, "
                         "fewer steps")
    ap.add_argument("--num-draft-tokens", type=int, default=4,
                    help="drafts per sequence per verify step (K)")
    args = ap.parse_args(argv)
    if args.spec_decode and args.engine != "continuous":
        ap.error("--spec-decode requires --engine continuous (the "
                 "fixed-slot reference engine has no verify path)")
    if args.serve and args.engine != "continuous":
        ap.error("--serve requires --engine continuous (the async front "
                 "end drives the continuous-batching step loop)")
    if args.mesh > 1 and args.engine != "continuous":
        ap.error("--mesh requires --engine continuous (sharding wraps "
                 "the continuous-batching ragged step)")
    if args.tiered:
        if args.engine != "continuous":
            ap.error("--tiered requires --engine continuous")
        if args.quant not in ("", "mxfp8") or not args.quantize_kv:
            ap.error("--tiered requires --quant mxfp8 --quantize-kv "
                     "(new writes land in the 8-bit base format)")
        args.quant = args.quant or "mxfp8"
    logging.basicConfig(level=logging.INFO)

    setup_compile_cache()
    cfg = serving_config(args.arch, reduced=args.reduced, quant=args.quant,
                         quantize_kv=args.quantize_kv)
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    max_seq = args.shared_prefix + args.prompt_len + args.new_tokens
    if args.spec_decode:
        # room for the worst-case verify window near the end of a request
        max_seq += args.num_draft_tokens
    serve_cfg = ServeConfig(
        max_seq=max_seq, temperature=args.temperature,
        top_p=args.top_p, top_k=args.top_k, seed=args.seed,
        slo_ms=args.slo_ms or None,
        max_queue=args.max_queue if args.max_queue >= 0 else None,
        max_slots=args.max_slots or args.batch, page_size=args.page_size,
        prefix_cache=not args.no_prefix_cache,
        decode_kernel=args.decode_kernel,
        spec_decode=args.spec_decode,
        num_draft_tokens=args.num_draft_tokens,
        prefill_mode=args.prefill_mode,
        prefill_chunk=args.prefill_chunk,
        prefill_token_budget=args.prefill_token_budget or None,
        step_mode=args.step_mode,
        prefill_max_chunks=args.prefill_max_chunks,
        mesh_shape=(1, args.mesh) if args.mesh > 1 else None,
        tiered=args.tiered,
        tier_policy=TierPolicy(
            mid_fmt=args.tier_mid_fmt, cold_fmt=args.tier_cold_fmt,
            hot_steps=args.tier_hot_steps, cold_steps=args.tier_cold_steps,
            repack_pages_per_step=args.tier_repack_pages)
        if args.tiered else None)
    engine = build_engine(cfg, serve_cfg, params, args.engine)
    if args.mesh > 1:
        if getattr(engine, "mesh", None) is not None:
            log.info("sharded serving: %d KV-head shards over a (1, %d) "
                     "device mesh", engine.tp, args.mesh)
        else:
            log.info("sharded serving fell back to single-device "
                     "(see engine log above for the reason)")
    if args.serve:
        return _run_server(engine, args)
    rng = np.random.default_rng(0)

    t0 = time.perf_counter()
    if args.engine == "continuous":
        lens = (rng.integers(max(1, args.prompt_len // 2),
                             args.prompt_len + 1, size=args.batch)
                if args.ragged else [args.prompt_len] * args.batch)
        head = rng.integers(0, cfg.vocab_size,
                            size=(args.shared_prefix,)).astype(np.int32)
        ids = [engine.submit(
            np.concatenate([head, rng.integers(
                0, cfg.vocab_size, size=(int(s),)).astype(np.int32)]),
            args.new_tokens) for s in lens]
        results = engine.run()
        dt = time.perf_counter() - t0
        prompt_toks = int(np.sum(lens)) + args.shared_prefix * len(ids)
        toks = sum(len(results[i]) for i in ids) - prompt_toks
        stats = engine.cache_stats()
        log.info("served %d requests in %.2fs (%.1f tok/s); peak pages %d "
                 "(%.1f KiB paged cache), %d preemptions, prefix hit rate "
                 "%.2f (%d/%d prompt tokens prefilled)",
                 len(ids), dt, toks / dt, stats["peak_pages"],
                 stats["peak_paged_bytes"] / 1024, stats["preemptions"],
                 stats["prefix_hit_rate"], stats["prefill_tokens_computed"],
                 stats["prompt_tokens"])
        if "dispatches_total" in stats:
            mode = ("megakernel" if getattr(engine, "megakernel", False)
                    else "ragged" if engine.ragged else "split")
            log.info("device dispatches: %d total over %d steps "
                     "(%.2f/step; %.2f per mixed decode+prefill step over "
                     "%d mixed steps) — ragged %d, decode %d, verify %d, "
                     "prefill %d, write %d, repack %d [step mode: %s]",
                     stats["dispatches_total"], engine.steps,
                     stats["dispatches_per_step"],
                     stats["dispatches_per_mixed_step"],
                     stats["mixed_steps"], stats["dispatches_ragged"],
                     stats["dispatches_decode"], stats["dispatches_verify"],
                     stats["dispatches_prefill"], stats["dispatches_write"],
                     stats["dispatches_repack"], mode)
            # the serving claim, measured end to end: every mixed
            # decode+prefill step is ONE jitted call, and (megakernel)
            # that call traces to ONE device kernel for the whole stack
            if stats["mixed_steps"] and mode in ("ragged", "megakernel"):
                gate = stats["dispatches_per_mixed_step"] == 1.0
                log.info("dispatch gate: dispatches_per_mixed_step == 1 "
                         "%s", "HELD" if gate else "FAILED")
            if stats.get("pallas_calls_per_step") is not None:
                log.info("step audit: %d pallas_call(s) per engine step "
                         "(%.1f prefill tokens retired per prefill-"
                         "carrying dispatch)",
                         stats["pallas_calls_per_step"],
                         stats["prefill_rows_per_step"])
        if "admission_latency_p95" in stats:
            log.info("admission latency (submit -> first token): "
                     "p50 %.3fs p95 %.3fs mean %.3fs over %d requests "
                     "(%s prefill, %d chunks, %d live prefill traces)",
                     stats["admission_latency_p50"],
                     stats["admission_latency_p95"],
                     stats["admission_latency_mean"],
                     len(engine.admission_latencies) or len(ids),
                     "chunked" if engine.chunked else "monolithic",
                     stats["prefill_chunks"], stats["prefill_traces"])
        if args.spec_decode:
            log.info("speculative decode: %.2f accepted tokens/step over "
                     "%d verify steps (draft acceptance %.2f)",
                     stats["accepted_per_step"], stats["spec_steps"],
                     stats["draft_acceptance_rate"])
        if args.tiered:
            fmt_counts = ", ".join(
                f"{k[len('pages_'):]}: {v}" for k, v in stats.items()
                if k.startswith("pages_"))
            log.info("tiered KV: %d/%d quarter-page units in use (peak "
                     "%d); live pages by format: %s; %d pages repacked "
                     "over %d dispatches (max %d in one step)",
                     stats["units_in_use"], stats["unit_budget"],
                     stats["peak_units"], fmt_counts,
                     stats["repacked_pages"], stats["repack_dispatches"],
                     stats["max_repacked_in_step"])
        return results
    # same workload shape as the continuous branch (minus raggedness): a
    # shared head plus per-request tails, so --engine A/Bs compare like
    # for like even though the fixed engine cannot exploit the sharing
    head = rng.integers(0, cfg.vocab_size,
                        size=(args.shared_prefix,)).astype(np.int32)
    prompts = np.concatenate(
        [np.broadcast_to(head, (args.batch, args.shared_prefix)),
         rng.integers(0, cfg.vocab_size,
                      size=(args.batch, args.prompt_len)).astype(np.int32)],
        axis=1).astype(np.int32)
    out = engine.generate(prompts, args.new_tokens)
    dt = time.perf_counter() - t0
    toks = args.batch * args.new_tokens
    log.info("generated %s in %.2fs (%.1f tok/s, first row: %s...)",
             out.shape, dt, toks / dt, out[0, :12].tolist())
    return out


if __name__ == "__main__":
    main()
