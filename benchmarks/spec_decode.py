"""Speculative-decoding serve benchmark: accepted tokens/step + wall win.

One-token-per-step decode pays a full page-table walk + in-register
dequant per emitted token; speculative decoding amortizes that over a
verify chunk. This benchmark measures the two quantities that matter:

  * **accepted tokens per verify step** (per sequence) on a
    repetitive-text workload — prompts built from a repeated motif, the
    regime prompt-lookup drafting targets (code, extraction, templated
    text). The number is deterministic and hardware-independent.
    Gate: >= 1.5 (plain decode is exactly 1.0 by construction).
  * **wall-clock tokens/s** vs the non-speculative engine on the same
    requests, both engines pre-warmed so jit compile time is excluded.
    Fewer engine steps means fewer kernel dispatches and fewer
    host-device round-trips; the win survives even the interpret-mode
    Pallas backend. Gate: >= 1.1x.

Correctness is asserted inline (speculative output token-identical to
the plain engine), and a third, kernel-falsifiable gate audits the
verify kernel's page skip: `mx_attention_verify_fused(debug_visits=True)`
must report exactly ``sum(ceil(seq_len / PS))`` page-body executions
over (batch, kv-head) cells — the multi-query chunk shares one page walk,
so the count is identical to the decode kernel's, and any loosening of
the ``pl.when`` predicate (work scaling with the padded table) or
over-skip (dropped context) fails this on any backend.

  PYTHONPATH=src python benchmarks/spec_decode.py [--smoke]
"""
from __future__ import annotations

import argparse
import time

import numpy as np

try:  # package mode (python -m benchmarks.run)
    from . import common
    from .serve_throughput import tiny_cfg
except ImportError:  # script mode (python benchmarks/spec_decode.py)
    import pathlib
    import sys

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                           / "src"))
    import common
    from serve_throughput import tiny_cfg

ACCEPT_GATE = 1.5
WALL_GATE = 1.1


def repetitive_requests(rng, n, motif_len, prompt_len, max_new):
    """Prompts that cycle a short motif — the prompt-lookup sweet spot."""
    reqs = []
    for _ in range(n):
        motif = rng.integers(0, 256, size=(motif_len,)).astype(np.int32)
        reps = -(-prompt_len // motif_len)
        reqs.append((np.tile(motif, reps)[:prompt_len], max_new))
    return reqs


def run_engine(params, cfg, reqs, serve_kw, warm_req):
    """Warm the engine's jit caches on a throwaway request, then serve
    ``reqs`` timed. Same treatment for both engines, so the comparison is
    steady-state dispatch + kernel time, not compile time."""
    import jax

    from repro.serve import ServeConfig, ServeEngine

    eng = ServeEngine(params, cfg, ServeConfig(**serve_kw))
    eng.submit(*warm_req)
    eng.run()
    (jax.block_until_ready(jax.tree_util.tree_leaves(eng.cache)[0]))
    # snapshot counters so the warmup request doesn't pollute the stats
    steps0, spst0, sst0, em0, dr0, ac0 = (
        eng.steps, eng.spec_steps, eng.spec_seq_steps, eng.emitted_tokens,
        eng.drafted_tokens, eng.accepted_tokens)
    ids = [eng.submit(p, m) for p, m in reqs]
    t0 = time.perf_counter()
    out = eng.run()
    dt = time.perf_counter() - t0
    new_toks = sum(m for _, m in reqs)
    sst = eng.spec_seq_steps - sst0
    return ({str(i): out[i] for i in ids},
            dict(eng.cache_stats(), wall_s=dt, tok_s=new_toks / dt,
                 steps=eng.steps - steps0,
                 spec_steps=eng.spec_steps - spst0,
                 accepted_per_step=((eng.emitted_tokens - em0) / sst
                                    if sst else 0.0),
                 draft_acceptance_rate=(
                     (eng.accepted_tokens - ac0)
                     / max(1, eng.drafted_tokens - dr0))))


def kernel_visit_audit(rng, b, kvh, g, d, ps, pmax, tq):
    """The verify kernel's own executed-page counter vs sum(ceil(len/PS))."""
    import jax.numpy as jnp

    from repro.core import quantize
    from repro.kernels import mx_attention_verify_fused

    npg = b * pmax + 2
    q = jnp.asarray(rng.normal(size=(b, kvh, tq, g, d)).astype(np.float32))
    kv = [quantize(jnp.asarray(
        rng.normal(size=(npg * ps, d)).astype(np.float32)), "fp8_e4m3", 32)
        for _ in range(2)]
    pools = [x.reshape(npg, 1, ps, -1).repeat(kvh, axis=1)
             for t in kv for x in (np.asarray(t.elements), np.asarray(t.scales))]
    table = np.full((b, pmax), -1, np.int32)
    lens = rng.integers(tq, pmax * ps + 1, size=b).astype(np.int32)
    used = 0
    for i in range(b):
        need = int(np.ceil(lens[i] / ps))
        table[i, :need] = np.arange(used, used + need) % npg
        used += need
    _, visits = mx_attention_verify_fused(
        q, *[jnp.asarray(p) for p in pools], jnp.asarray(table),
        jnp.asarray(lens), fmt_name="fp8_e4m3", block_size=32,
        debug_visits=True)
    visited = int(np.asarray(visits).sum())
    resident = int(kvh * np.ceil(lens / ps).sum())
    return visited, resident, b * kvh * pmax


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for the CI smoke step")
    args = ap.parse_args(argv)
    import jax

    from repro.nn import model as M

    if args.smoke:
        n, motif, prompt_len, max_new, ps, k = 2, 8, 24, 24, 8, 4
    else:
        n, motif, prompt_len, max_new, ps, k = 4, 8, 32, 96, 16, 6
    max_seq = prompt_len + max_new + k
    rng = np.random.default_rng(0)
    reqs = repetitive_requests(rng, n, motif, prompt_len, max_new)
    warm = (rng.integers(0, 256, size=(prompt_len,)).astype(np.int32),
            max(2, max_new // 8))
    cfg = tiny_cfg(True)
    params, _ = M.init(jax.random.PRNGKey(0), cfg)

    base = dict(max_seq=max_seq, max_slots=n, page_size=ps)
    out_plain, plain = run_engine(params, cfg, reqs, base, warm)
    out_spec, spec = run_engine(
        params, cfg, reqs,
        dict(base, spec_decode=True, num_draft_tokens=k), warm)
    for key in out_plain:
        np.testing.assert_array_equal(
            out_spec[key], out_plain[key],
            err_msg="speculative decoding changed greedy outputs")

    accepted = spec["accepted_per_step"]
    wall_win = spec["tok_s"] / plain["tok_s"]
    visited, resident, grid = kernel_visit_audit(
        rng, b=n, kvh=2, g=2, d=64, ps=ps, pmax=max_seq // ps, tq=1 + k)
    skip_exact = visited == resident

    print("engine,steps,tok_s,accepted_per_step,acceptance_rate")
    print(f"plain,{plain['steps']},{plain['tok_s']:.1f},1.00,-")
    print(f"spec_k{k},{spec['spec_steps']},{spec['tok_s']:.1f},"
          f"{accepted:.2f},{spec['draft_acceptance_rate']:.2f}")
    common.emit(
        f"serve/spec_{'smoke' if args.smoke else 'full'}/"
        f"r{n}_k{k}_new{max_new}", 1e6 / spec["tok_s"],
        f"{accepted:.2f} accepted tok/step, {wall_win:.2f}x wall vs plain")
    common.emit_json("spec_decode", {
        "requests": n, "prompt_tokens": prompt_len, "max_new": max_new,
        "num_draft_tokens": k, "page_size": ps,
        "tok_s": spec["tok_s"], "tok_s_plain": plain["tok_s"],
        "wall_speedup": wall_win,
        "accepted_per_step": accepted,
        "draft_acceptance_rate": spec["draft_acceptance_rate"],
        "verify_steps": spec["spec_steps"],
        "page_tiles_visited": visited,
        "page_tiles_resident": resident,
        "page_tiles_in_grid": grid,
        "outputs_token_identical": True,
    })
    ok = accepted >= ACCEPT_GATE and wall_win >= WALL_GATE and skip_exact
    print(f"\naccepted tokens/step {accepted:.2f} (gate >= {ACCEPT_GATE}), "
          f"wall-clock {wall_win:.2f}x vs plain (gate >= {WALL_GATE}), "
          f"verify-kernel page tiles visited {visited}/{grid} (resident "
          f"{resident}, must match exactly): {'PASS' if ok else 'FAIL'}")
    if not ok:
        raise SystemExit(1)
    return accepted, wall_win


def run():
    main([])


if __name__ == "__main__":
    main()
