"""Chunked-prefill serve benchmark: admission backlog + decode stalls.

Monolithic prefill runs each prompt as one dense forward of its full
length inside a single engine step: a short request admitted behind a
long prompt gets its first token only after the long prompt's *entire*
prefill, every resident decoder stalls for the same duration, and the
engine retraces per prompt length. Chunked prefill
(``ServeConfig.prefill_mode="chunked"``) streams prompts through
fixed-size chunks straight into the MX page pool — quantize-into-pages
inside the fused kernel — interleaved with decode steps under a per-step
token budget spent round-robin across admitted prompts.

Gates are measured in **prefill tokens**, not wall seconds: off-TPU the
Pallas kernels run in interpret mode, whose per-call dispatch cost says
nothing about hardware (same reasoning as ``decode_attention``'s modeled
HBM gate). Prefill tokens processed between two scheduling events are
deterministic, hardware-independent, and exactly the quantity a roofline
turns into wall time on a real chip. Wall-clock per mode is reported but
not gated.

  * **admission backlog p95**: prefill tokens the engine processes
    between a short request's submission and its first sampled token,
    p95 over shorts each submitted right behind a long prompt. Under
    monolithic prefill that includes the whole long prompt; under
    chunked it is ~one long chunk + the short's own chunk.
    Gate: monolithic p95 >= 2x chunked p95.
  * **decode stall**: the maximum prefill tokens processed inside one
    engine step while a decoder is resident — the per-step ceiling on
    how long a decode token can be delayed by admission work.
    Monolithic: the full long prompt; chunked: the token budget.
    Gate: >= 2x reduction.
  * **page-visit audit**: the prefill kernel's ``debug_visits`` counter
    over a chunked prompt must equal sum over chunks and kv-heads of
    ceil((start + real_tokens)/PS) exactly — the falsifiable skip check
    (interpret mode predicates the body away but walks every grid cell,
    so wall-clock cannot catch a loosened predicate).
  * **trace population**: the chunked engine must finish with zero
    per-length prefill traces (its one chunk trace serves everything);
    the monolithic engine's per-length cache is reported alongside.

  PYTHONPATH=src python benchmarks/prefill.py [--smoke]
"""
from __future__ import annotations

import argparse
import time

import numpy as np

try:  # package mode (python -m benchmarks.run)
    from . import common
    from .serve_throughput import tiny_cfg
except ImportError:  # script mode (python benchmarks/prefill.py)
    import pathlib
    import sys

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                           / "src"))
    import common
    from serve_throughput import tiny_cfg

ADMIT_GATE = 2.0
STALL_GATE = 2.0


def mixed_load(params, cfg, mode, *, n_pairs, long_len, short_len,
               decode_new, ps, chunk):
    """One resident decoder + a stream of (long, short) admission pairs.

    Returns per-short admission backlogs (prefill tokens processed
    between submit and first token), the max per-step prefill tokens
    while the decoder is live (its stall ceiling), wall seconds, and the
    engine (for trace stats).
    """
    from repro.serve import ServeConfig, ServeEngine

    rng = np.random.default_rng(0)
    eng = ServeEngine(params, cfg, ServeConfig(
        max_seq=long_len + decode_new + ps, max_slots=3, page_size=ps,
        prefix_cache=False, prefill_mode=mode, prefill_chunk=chunk))
    t0 = time.perf_counter()
    eng.submit(rng.integers(0, 256, size=(short_len,)).astype(np.int32),
               decode_new)
    decoder = eng.scheduler.queue[-1]
    eng.step()  # decoder resident and emitting

    backlogs, stall = [], 0

    def run_until(req, limit=500):
        """Step until ``req`` has its first token, tracking the stall."""
        nonlocal stall
        for _ in range(limit):
            if req.generated:
                return
            before = eng.prefill_tokens
            eng.step()
            if not decoder.done:
                stall = max(stall, eng.prefill_tokens - before)
        raise AssertionError("request never produced a first token")

    for _ in range(n_pairs):
        long_p = rng.integers(0, 256, size=(long_len,)).astype(np.int32)
        short_p = rng.integers(0, 256, size=(short_len,)).astype(np.int32)
        eng.submit(long_p, 2)
        long_req = eng.scheduler.queue[-1]
        mark = eng.prefill_tokens
        eng.submit(short_p, 2)
        short_req = eng.scheduler.queue[-1]
        run_until(short_req)
        backlogs.append(eng.prefill_tokens - mark)
        run_until(long_req)
        while any(s.req in (long_req, short_req)
                  for s in eng.scheduler.active()):
            eng.step()  # drain the pair so the next one sees free slots
    while eng.step():
        pass
    return backlogs, stall, time.perf_counter() - t0, eng


def kernel_visit_audit(*, prompt_len, chunk, ps, kvh, g, d):
    """The prefill kernel's executed-page counter vs the exact expectation."""
    import jax.numpy as jnp

    from repro.kernels import mx_attention_prefill_fused

    rng = np.random.default_rng(2)
    pad = -(-prompt_len // chunk) * chunk
    npg = pad // ps + 2
    pmax = pad // ps
    kw = rng.normal(size=(1, pad, kvh, d)).astype(np.float32)
    vw = rng.normal(size=(1, pad, kvh, d)).astype(np.float32)
    qw = rng.normal(size=(1, kvh, pad, g, d)).astype(np.float32)
    pools = [jnp.zeros((npg, kvh, ps, d), jnp.float8_e4m3fn),
             jnp.zeros((npg, kvh, ps, d // 32), jnp.uint8),
             jnp.zeros((npg, kvh, ps, d), jnp.float8_e4m3fn),
             jnp.zeros((npg, kvh, ps, d // 32), jnp.uint8)]
    table = np.full((1, pmax), -1, np.int32)
    need = -(-prompt_len // ps)
    table[0, :need] = rng.permutation(npg)[:need]
    table = jnp.asarray(table)
    visited = expected = 0
    for start in range(0, pad, chunk):
        real = min(chunk, prompt_len - start)
        _, pools, vis = mx_attention_prefill_fused(
            jnp.asarray(qw[:, :, start:start + chunk]),
            jnp.asarray(kw[:, start:start + chunk].swapaxes(1, 2)),
            jnp.asarray(vw[:, start:start + chunk].swapaxes(1, 2)),
            *pools, table, jnp.asarray([start], jnp.int32),
            jnp.asarray([start + real], jnp.int32),
            fmt_name="fp8_e4m3", block_size=32, debug_visits=True)
        pools = list(pools)
        visited += int(np.asarray(vis).sum())
        expected += kvh * (-(-(start + real) // ps))
    return visited, expected


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for the CI smoke step")
    args = ap.parse_args(argv)
    import jax

    from repro.nn import model as M

    if args.smoke:
        n_pairs, long_len, short_len, chunk, ps, decode_new = 2, 64, 8, 16, 8, 40
    else:
        n_pairs, long_len, short_len, chunk, ps, decode_new = 4, 128, 8, 16, 8, 96
    cfg = tiny_cfg(True)
    params, _ = M.init(jax.random.PRNGKey(0), cfg)

    results = {}
    for mode in ("chunked", "monolithic"):
        backlogs, stall, wall, eng = mixed_load(
            params, cfg, mode, n_pairs=n_pairs, long_len=long_len,
            short_len=short_len, decode_new=decode_new, ps=ps, chunk=chunk)
        lat = np.sort(np.asarray(backlogs))
        p95 = float(lat[int(round(0.95 * (len(lat) - 1)))])
        results[mode] = dict(
            admission_backlog_p95_tokens=p95,
            admission_backlog_mean_tokens=float(lat.mean()),
            max_decode_stall_tokens=stall, wall_s=wall,
            prefill_traces=eng.cache_stats()["prefill_traces"],
            prefill_chunks=eng.prefill_chunks)
        common.emit(
            f"serve/prefill_{mode}{'_smoke' if args.smoke else ''}/"
            f"long{long_len}_short{short_len}_c{chunk}_x{n_pairs}",
            wall * 1e6,
            f"p95 admission backlog {p95:.0f} tok, decode stall "
            f"{stall} tok/step, {results[mode]['prefill_traces']} traces")

    ch, mo = results["chunked"], results["monolithic"]
    admit_win = (mo["admission_backlog_p95_tokens"]
                 / ch["admission_backlog_p95_tokens"])
    stall_win = mo["max_decode_stall_tokens"] / ch["max_decode_stall_tokens"]
    visited, expected = kernel_visit_audit(
        prompt_len=long_len - 3, chunk=chunk, ps=ps, kvh=2, g=2, d=64)
    audit_ok = visited == expected

    common.emit_json("prefill", {
        "pairs": n_pairs, "long_prompt": long_len, "short_prompt": short_len,
        "chunk": chunk, "page_size": ps,
        "chunked": ch, "monolithic": mo,
        "admission_backlog_p95_reduction": admit_win,
        "decode_stall_reduction": stall_win,
        "prefill_page_tiles_visited": visited,
        "prefill_page_tiles_expected": expected,
    })
    ok = (admit_win >= ADMIT_GATE and stall_win >= STALL_GATE and audit_ok
          and ch["prefill_traces"] == 0)
    print(f"\nadmission backlog p95 {mo['admission_backlog_p95_tokens']:.0f} "
          f"-> {ch['admission_backlog_p95_tokens']:.0f} prefill tokens "
          f"({admit_win:.2f}x, gate >= {ADMIT_GATE}), max decode stall "
          f"{mo['max_decode_stall_tokens']} -> "
          f"{ch['max_decode_stall_tokens']} tokens/step ({stall_win:.2f}x, "
          f"gate >= {STALL_GATE}), prefill kernel page tiles {visited} "
          f"(expected {expected}, must match exactly), chunked traces "
          f"{ch['prefill_traces']} (monolithic {mo['prefill_traces']}): "
          f"{'PASS' if ok else 'FAIL'}")
    if not ok:
        raise SystemExit(1)
    return admit_win, stall_win


def run():
    main([])


if __name__ == "__main__":
    main()
