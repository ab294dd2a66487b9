"""Paged decode-attention microbenchmark: einsum vs two-pass vs fused.

Three implementations of the same op — decode attention for B sequences
through a page table over an MX page pool — measured on two axes:

  * **wall-clock** (this host). The einsum path is pure XLA; the two-pass
    and fused paths are Pallas kernels which, off-TPU, run under the
    interpreter, where per-grid-cell dispatch (not dataflow) dominates.
    Pallas-vs-pallas is therefore the like-for-like wall-clock comparison,
    and the single-pass fused kernel must beat its two-pass predecessor
    (gather kernel + contiguous attend) >= 1.5x — it does one grid walk
    instead of two and skips every page past ``ceil(seq_len/PS)``.
  * **modeled v5e step time** (``common.v5e_time_model``) from each
    dataflow's actual HBM traffic — the hardware-relevant axis, since
    decode attention is bandwidth-bound (the paper's premise). The einsum
    path gathers the *padded* table compact (read + write), dequantizes it
    to wide bf16 in HBM (read + write), then attends over the wide copy
    (read): cost scales with max_pages. The fused kernel reads only the
    *resident* compact pages, once. Gate: fused >= 1.5x over einsum at the
    acceptance operating point — batch 8, page_size 8, <= 25 % table
    occupancy — where the padded table is mostly empty (measured ~20x:
    4x occupancy times ~5x bytes-per-token).

A third, kernel-falsifiable gate audits the page skip itself: the fused
kernel counts page bodies it actually executes (``debug_visits``), and
the count must equal ``sum(ceil(seq_len / PS))`` over (batch, kv-head)
cells *exactly* — if the ``pl.when`` predicate loosens (work scales with
the padded table again) or over-skips (dropped context), this fails on
any backend. Wall-clock cannot stand in for it off-TPU: the interpreter
visits every grid cell and only predicates the body away, so skip wins
are invisible to CPU timing.

Sweeps (batch, pages-resident, page_size, fp8/fp4, block 16/32/64); the
numbers land in ``BENCH_decode.json`` via ``python -m benchmarks.run``.

  PYTHONPATH=src python benchmarks/decode_attention.py [--smoke]
"""
from __future__ import annotations

import argparse

import numpy as np

try:  # package mode (python -m benchmarks.run)
    from . import common
except ImportError:  # script mode
    import pathlib
    import sys

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                           / "src"))
    import common

GATE = 1.5


def build_case(b, kvh, g, d, ps, pages_resident, occupancy, fmt, bsz, rng):
    """A shuffled page pool + table at the given occupancy.

    Every sequence holds ``pages_resident`` pages of a table sized
    ``pages_resident / occupancy`` — the rest is padding the einsum path
    pays for and the fused kernel skips.
    """
    import jax.numpy as jnp

    from repro.core import quantize

    t_res = pages_resident * ps
    pmax = int(round(pages_resident / occupancy))
    npg = b * pmax + 2
    kq = quantize(jnp.asarray(
        rng.normal(size=(b, kvh, t_res, d)).astype(np.float32)), fmt, bsz)
    vq = quantize(jnp.asarray(
        rng.normal(size=(b, kvh, t_res, d)).astype(np.float32)), fmt, bsz)
    table = np.full((b, pmax), -1, np.int32)
    table[:, :pages_resident] = rng.permutation(npg)[
        : b * pages_resident].reshape(b, pages_resident)
    pools = {}
    for name, src in [("ke", kq.elements), ("ks", kq.scales),
                      ("ve", vq.elements), ("vs", vq.scales)]:
        src = np.asarray(src)
        pool = np.zeros((npg, kvh, ps, src.shape[-1]), src.dtype)
        for i in range(b):
            for p in range(pages_resident):
                pool[table[i, p]] = src[i, :, p * ps:(p + 1) * ps]
        pools[name] = jnp.asarray(pool)
    q = jnp.asarray(rng.normal(size=(b, kvh, g, d)).astype(np.float32))
    lens = jnp.asarray(rng.integers(t_res - ps + 1, t_res + 1, size=b),
                       jnp.int32)
    return q, pools, jnp.asarray(table), lens


def einsum_decode(q, ke, ks, ve, vs, table, lens, *, fmt, bsz):
    """The engine's pre-kernel decode path: gather the whole padded table,
    dequantize it to wide bf16 in HBM, masked softmax over padded T. The
    dequantize goes through the engine's own cache reader
    (``attention._read_cache``) so the baseline stays the dataflow the
    einsum path actually runs, by construction."""
    import jax
    import jax.numpy as jnp

    from repro.core import QuantConfig
    from repro.nn import attention as A

    npg, ps = ke.shape[0], ke.shape[2]
    b, pmax = table.shape
    d = q.shape[-1]
    idx = jnp.clip(table, 0, npg - 1)

    view = A._pages_view({"k_elems": ke, "k_scales": ks,
                          "v_elems": ve, "v_scales": vs}, idx)
    acfg = A.AttnConfig(d_model=0, num_heads=q.shape[1] * q.shape[2],
                        num_kv_heads=q.shape[1], head_dim=d)
    quant = QuantConfig(fmt=fmt, block_size=bsz, quantize_kv_cache=True)
    k, v = A._read_cache(view, quant, acfg, jnp.bfloat16)  # (B,T,KVH,D) wide
    t = k.shape[1]
    logits = jnp.einsum("bkgd,btkd->bkgt", q.astype(jnp.bfloat16), k,
                        preferred_element_type=jnp.float32) * (d ** -0.5)
    mask = jnp.arange(t)[None] < lens[:, None]
    logits = jnp.where(mask[:, None, None], logits, -2.0e38)
    probs = jax.nn.softmax(logits, axis=-1).astype(jnp.bfloat16)
    return jnp.einsum("bkgt,btkd->bkgd", probs, v)


def modeled_bytes(b, kvh, g, d, ps, pages_resident, pmax, fmt, bsz):
    """HBM bytes each dataflow moves for one decode step (K+V)."""
    elem_bits = 4 if fmt == "fp4_e2m1" else 8
    compact = d * elem_bits / 8 + d // bsz  # per token per head, one of K/V
    wide = d * 2  # bf16
    padded = b * pmax * ps * kvh * 2  # token-head slots, K and V
    resident = b * pages_resident * ps * kvh * 2
    qo = b * kvh * g * d * (4 + 4)  # f32 q read + f32 out write
    return {
        # gather (read+write compact) + dequant (read compact, write wide)
        # + attend (read wide)
        "einsum": padded * (3 * compact + 2 * wide) + qo,
        # gather kernel (read+write compact) + contiguous attend (read
        # compact — the gathered operands stay compact)
        "two_pass": padded * 3 * compact + qo,
        # one walk over resident compact pages, nothing materialized
        "fused": resident * compact + qo,
    }


def modeled_us(bytes_moved, b, kvh, g, d, tokens):
    flops = 4 * b * kvh * g * d * tokens  # QK^T + PV
    return common.v5e_time_model(flops, bytes_moved) * 1e6


def run_case(b, kvh, g, d, ps, pages_resident, occupancy, fmt, bsz, rng,
             iters=3, warmup=1, paths=("einsum", "two_pass", "fused")):
    import jax

    from repro.kernels import (mx_attention_decode_fused,
                               mx_attention_decode_paged)

    q, pools, table, lens = build_case(b, kvh, g, d, ps, pages_resident,
                                       occupancy, fmt, bsz, rng)
    args = (q, pools["ke"], pools["ks"], pools["ve"], pools["vs"], table,
            lens)
    fns = {
        "einsum": jax.jit(lambda *a: einsum_decode(*a, fmt=fmt, bsz=bsz)),
        "two_pass": jax.jit(lambda *a: mx_attention_decode_paged(
            *a, fmt_name=fmt, block_size=bsz)),
        "fused": jax.jit(lambda *a: mx_attention_decode_fused(
            *a, fmt_name=fmt, block_size=bsz)),
    }
    pmax = table.shape[1]
    wall = {name: common.time_fn(fns[name], *args, iters=iters,
                                 warmup=warmup)
            for name in paths}
    mbytes = modeled_bytes(b, kvh, g, d, ps, pages_resident, pmax, fmt, bsz)
    model = {
        "einsum": modeled_us(mbytes["einsum"], b, kvh, g, d, pmax * ps),
        "two_pass": modeled_us(mbytes["two_pass"], b, kvh, g, d, pmax * ps),
        "fused": modeled_us(mbytes["fused"], b, kvh, g, d,
                            pages_resident * ps),
    }
    label = (f"decode/b{b}_kvh{kvh}_d{d}_ps{ps}_res{pages_resident}"
             f"_occ{occupancy:.2f}_{fmt}_k{bsz}")
    for name in paths:
        common.emit(f"{label}/{name}", wall[name],
                    f"modeled v5e {model[name]:.2f}us")
    return wall, model


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="gate operating point only (CI)")
    args = ap.parse_args(argv)
    rng = np.random.default_rng(0)

    # the acceptance operating point: batch 8, page_size 8, 25 % occupancy
    # (padded table mostly empty). Smoke shrinks resident pages so the
    # two-pass interpreter run stays CI-friendly.
    res = 4 if args.smoke else 8
    gate_pt = dict(b=8, kvh=2, g=4, d=64, ps=8, pages_resident=res,
                   occupancy=0.25)
    sweep = [dict(gate_pt, fmt="fp8_e4m3", bsz=32)]
    if not args.smoke:
        sweep += [
            dict(gate_pt, fmt="fp4_e2m1", bsz=32),
            dict(gate_pt, fmt="fp8_e4m3", bsz=16),
            dict(gate_pt, fmt="fp8_e4m3", bsz=64),
            dict(gate_pt, fmt="fp4_e2m1", bsz=16),
            dict(gate_pt, fmt="fp4_e2m1", bsz=64),
            # smaller batch, bigger pages, half-full table
            dict(b=4, kvh=2, g=4, d=64, ps=16, pages_resident=4,
                 occupancy=0.5, fmt="fp8_e4m3", bsz=32),
        ]

    results = []
    for case in sweep:
        wall, model = run_case(rng=rng, **case)
        results.append({**case, "wall_us": wall, "modeled_v5e_us": model})

    # page-skip audit: the kernel's own visit counter must equal the
    # resident page count exactly — the falsifiable check that per-step
    # work scales with ceil(seq_len/PS), not the padded table (module
    # docstring explains why wall-clock cannot gate this off-TPU)
    import jax
    from repro.kernels import mx_attention_decode_fused

    gp = sweep[0]
    q, pools, table, lens = build_case(
        gp["b"], gp["kvh"], gp["g"], gp["d"], gp["ps"],
        gp["pages_resident"], gp["occupancy"], gp["fmt"], gp["bsz"], rng)
    _, visits = mx_attention_decode_fused(
        q, pools["ke"], pools["ks"], pools["ve"], pools["vs"], table, lens,
        fmt_name=gp["fmt"], block_size=gp["bsz"], debug_visits=True)
    visited = int(np.asarray(visits).sum())
    resident = int(gp["kvh"] * np.ceil(np.asarray(lens) / gp["ps"]).sum())
    grid_tiles = gp["b"] * gp["kvh"] * table.shape[1]
    skip_exact = visited == resident

    gate_wall, gate_model = results[0]["wall_us"], results[0]["modeled_v5e_us"]
    wall_vs_twopass = gate_wall["two_pass"] / gate_wall["fused"]
    modeled_vs_einsum = gate_model["einsum"] / gate_model["fused"]
    common.emit_json("decode_attention", {
        "gate_point": {k: v for k, v in sweep[0].items()},
        "wall_us": gate_wall,
        "modeled_v5e_us": gate_model,
        "fused_wall_speedup_vs_two_pass": wall_vs_twopass,
        "fused_modeled_speedup_vs_einsum": modeled_vs_einsum,
        "page_tiles_visited": visited,
        "page_tiles_resident": resident,
        "page_tiles_in_grid": grid_tiles,
        "cases": results,
    })
    ok = wall_vs_twopass >= GATE and modeled_vs_einsum >= GATE and skip_exact
    print(f"\nfused vs two-pass wall-clock {wall_vs_twopass:.2f}x, "
          f"fused vs einsum modeled v5e {modeled_vs_einsum:.2f}x, "
          f"page tiles visited {visited}/{grid_tiles} (resident "
          f"{resident}): {'PASS' if ok else 'FAIL'} (gates >= {GATE}x + "
          f"exact visit count; einsum wall-clock off-TPU reflects "
          f"interpreter dispatch, see module docstring)")
    if not ok:
        raise SystemExit(1)
    return wall_vs_twopass, modeled_vs_einsum, visited


def run():
    main([])


if __name__ == "__main__":
    main()
