"""One-dispatch ragged engine step: kernel + engine identity matrix.

The ragged kernel (`mx_attention_ragged_fused`) must be *bit-identical*
to the split-dispatch oracle it replaces, at both layers:

  * kernel level — a ragged row whose write window was pre-written
    host-side (exact `core.quantize` math) and then verified with
    `mx_attention_verify_fused` must match the ragged kernel's output
    AND its in-kernel written pool bytes, across fp8 e4m3/e5m2 + fp4
    and block sizes 16/32/64;
  * engine level — `step_mode="ragged"` must emit the same per-request
    token streams as `step_mode="split"` (the validated oracle) under
    churn, preemption, speculative decoding, chunked prefill, tiering,
    and prefix sharing — while running exactly ONE device dispatch per
    steady-state mixed step.

Plus the structural guarantee: one `pallas_call` per engine step layer
and no pool-shaped scatter (`.at[].set` K/V write) on the ragged path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MXFP8, quantize
from repro.kernels import (mx_attention_ragged_fused,
                           mx_attention_verify_fused)
from repro.nn import BlockDef, ModelConfig, model
from repro.serve import ContinuousBatchingEngine, ServeConfig


# ---------------------------------------------------------------------------
# kernel-level identity: ragged row == host-write + verify oracle
# ---------------------------------------------------------------------------


def _scatter_rows(pool, table_row, quant, lo, hi, ps):
    """Write contiguous token rows [lo, hi) of one sequence into `pool`.

    quant.elements/.scales are (KVH, T, ·); pool pages are (KVH, PS, ·).
    """
    el = np.asarray(quant.elements)
    sc = np.asarray(quant.scales)
    ke, ks = pool
    for t in range(lo, hi):
        pg = table_row[t // ps]
        ke[pg, :, t % ps] = el[:, t]
        ks[pg, :, t % ps] = sc[:, t]


def _ragged_case(fmt, block_size, d=64, g=2, kvh=2, ps=8, seed=101):
    """Three coexisting row modes against per-row verify oracles.

    Row 0: plain decode (n_new=1, mid-page start). Row 1: verify window
    (n_new=3, straddling a page boundary). Row 2: fresh prefill chunk
    (n_new=W from row 0). Row 3: continuation chunk with an unaligned,
    mid-page start — the case the aligned prefill kernel cannot run.
    """
    rng = np.random.default_rng(seed)
    w = 8
    starts = [13, 9, 0, 12]
    n_news = [1, 3, w, w]
    r = len(starts)
    totals = [s + n for s, n in zip(starts, n_news)]
    pages_per = [-(-t // ps) for t in totals]
    npages = sum(pages_per) + 3  # spare + trash page (last)
    pmax = max(pages_per) + 1    # room for a -1 tail entry
    perm = rng.permutation(npages - 1)  # never hand out the trash page
    table = np.full((r, pmax), -1, np.int32)
    off = 0
    for i, npg in enumerate(pages_per):
        table[i, :npg] = perm[off:off + npg]
        off += npg

    # decoy codes everywhere: garbage pages must never contribute and
    # unwritten rows of written pages must survive the merge untouched
    def _pool_from(cache):
        q_ = quantize(jnp.asarray(cache), fmt, block_size)
        el = np.asarray(q_.elements).reshape(kvh, npages, ps, -1)
        sc = np.asarray(q_.scales).reshape(kvh, npages, ps, -1)
        return (np.ascontiguousarray(el.transpose(1, 0, 2, 3)),
                np.ascontiguousarray(sc.transpose(1, 0, 2, 3)))

    decoy = rng.normal(size=(kvh, npages * ps, d)).astype(np.float32)
    ke0, ks0 = _pool_from(decoy)
    ve0, vs0 = _pool_from(decoy[:, ::-1])

    # per-row contiguous wide caches; quantize row-wise (block along D) —
    # identical math whether done in one batch or token-by-token
    caches = [(rng.normal(size=(kvh, t, d)).astype(np.float32),
               rng.normal(size=(kvh, t, d)).astype(np.float32))
              for t in totals]
    kq = [quantize(jnp.asarray(kc), fmt, block_size) for kc, _ in caches]
    vq = [quantize(jnp.asarray(vc), fmt, block_size) for _, vc in caches]

    # want pool: every token row host-written; input pool: only the
    # resident prefix [0, start) — the ragged kernel must produce the
    # missing window bytes itself
    want = [a.copy() for a in (ke0, ks0, ve0, vs0)]
    have = [a.copy() for a in (ke0, ks0, ve0, vs0)]
    for i in range(r):
        _scatter_rows((want[0], want[1]), table[i], kq[i], 0, totals[i], ps)
        _scatter_rows((want[2], want[3]), table[i], vq[i], 0, totals[i], ps)
        _scatter_rows((have[0], have[1]), table[i], kq[i], 0, starts[i], ps)
        _scatter_rows((have[2], have[3]), table[i], vq[i], 0, starts[i], ps)

    q = rng.normal(size=(r, kvh, w, g, d)).astype(np.float32)
    k_new = rng.normal(size=(r, kvh, w, d)).astype(np.float32)  # padding
    v_new = rng.normal(size=(r, kvh, w, d)).astype(np.float32)
    for i in range(r):
        for t in range(n_news[i]):
            k_new[i, :, t] = caches[i][0][:, starts[i] + t]
            v_new[i, :, t] = caches[i][1][:, starts[i] + t]

    out, pools, visits = mx_attention_ragged_fused(
        jnp.asarray(q), jnp.asarray(k_new), jnp.asarray(v_new),
        *(jnp.asarray(a) for a in have), jnp.asarray(table),
        jnp.asarray(starts, jnp.int32), jnp.asarray(totals, jnp.int32),
        fmt_name=fmt, block_size=block_size, debug_visits=True)
    return (np.asarray(out), [np.asarray(p) for p in pools],
            np.asarray(visits), want, have, q, table, starts, n_news,
            totals, ps)


@pytest.mark.parametrize("fmt", ["fp8_e4m3", "fp8_e5m2", "fp4_e2m1"])
@pytest.mark.parametrize("block_size", [16, 32, 64])
def test_ragged_kernel_bit_matches_split_oracle(fmt, block_size):
    (out, pools, visits, want, have, q, table, starts, n_news, totals,
     ps) = _ragged_case(fmt, block_size)

    # 1) in-kernel written pool bytes == host core.quantize writes, and
    #    rows the step does not own keep their exact old codes
    for i in range(len(starts)):
        for t in range(totals[i]):
            pg, prow = table[i, t // ps], t % ps
            for got, exp in zip(pools, want):
                np.testing.assert_array_equal(
                    got[pg, :, prow].view(np.uint8),
                    exp[pg, :, prow].view(np.uint8))
    owned = {int(table[i, p]) for i in range(len(starts))
             for p in range(starts[i] // ps, -(-totals[i] // ps))}
    for pg in range(pools[0].shape[0]):
        if pg in owned:
            continue
        for got, old in zip(pools, have):
            np.testing.assert_array_equal(got[pg].view(np.uint8),
                                          old[pg].view(np.uint8))

    # 2) attention output bit-matches the split verify kernel reading the
    #    host-written pool (same page walk, same flash accumulation)
    for i in range(len(starts)):
        n = n_news[i]
        ref = np.asarray(mx_attention_verify_fused(
            jnp.asarray(q[i:i + 1, :, :n]),
            *(jnp.asarray(a) for a in want), jnp.asarray(table[i:i + 1]),
            jnp.asarray([totals[i]], jnp.int32),
            fmt_name=fmt, block_size=block_size))
        np.testing.assert_array_equal(
            out[i:i + 1, :, :n].view(np.uint32), ref.view(np.uint32))

    # 3) exact page-visit audit: every page in [0, ceil(total/PS)) and
    #    nothing else
    expect = np.array([-(-t // ps) for t in totals], np.int32)
    np.testing.assert_array_equal(
        visits[:, :, 0], np.broadcast_to(expect[:, None], visits.shape[:2]))


def test_ragged_kernel_head_tiling_at_large_gdim():
    """head_dim 128 x G 8 pushes W*G*D past one flash row tile: the tiled
    `_flash_update` path must stay bit-identical to the verify oracle
    (which shares the same tiling, so this also regression-checks both
    against the f32 einsum reference at kernel tolerance)."""
    (out, pools, visits, want, have, q, table, starts, n_news, totals,
     ps) = _ragged_case("fp8_e4m3", 32, d=128, g=8, kvh=2, seed=131)
    for i in range(len(starts)):
        n = n_news[i]
        ref = np.asarray(mx_attention_verify_fused(
            jnp.asarray(q[i:i + 1, :, :n]),
            *(jnp.asarray(a) for a in want), jnp.asarray(table[i:i + 1]),
            jnp.asarray([totals[i]], jnp.int32),
            fmt_name="fp8_e4m3", block_size=32))
        np.testing.assert_array_equal(
            out[i:i + 1, :, :n].view(np.uint32), ref.view(np.uint32))


def test_ragged_kernel_inactive_rows_only_touch_trash_page():
    """An inactive slot row (start=0, len=1, all -1 table) must write its
    garbage exclusively to the reserved trash page (pool page NP-1)."""
    rng = np.random.default_rng(7)
    kvh, d, ps, w, g = 2, 32, 8, 4, 2
    npages = 5
    decoy = rng.normal(size=(kvh, npages * ps, d)).astype(np.float32)
    qd = quantize(jnp.asarray(decoy), "fp8_e4m3", 32)
    el = np.asarray(qd.elements).reshape(kvh, npages, ps, -1)
    sc = np.asarray(qd.scales).reshape(kvh, npages, ps, -1)
    ke = np.ascontiguousarray(el.transpose(1, 0, 2, 3))
    ks = np.ascontiguousarray(sc.transpose(1, 0, 2, 3))
    pools = [ke, ks, ke.copy(), ks.copy()]
    table = np.full((1, 3), -1, np.int32)
    out, new_pools = mx_attention_ragged_fused(
        jnp.asarray(rng.normal(size=(1, kvh, w, g, d)).astype(np.float32)),
        jnp.asarray(rng.normal(size=(1, kvh, w, d)).astype(np.float32)),
        jnp.asarray(rng.normal(size=(1, kvh, w, d)).astype(np.float32)),
        *(jnp.asarray(a) for a in pools), jnp.asarray(table),
        jnp.asarray([0], jnp.int32), jnp.asarray([1], jnp.int32),
        fmt_name="fp8_e4m3", block_size=32)
    for got, old in zip(new_pools, pools):
        got = np.asarray(got)
        np.testing.assert_array_equal(got[:-1].view(np.uint8),
                                      old[:-1].view(np.uint8))
    assert np.isfinite(np.asarray(out)).all()


# ---------------------------------------------------------------------------
# engine-level identity matrix: ragged vs the split-dispatch oracle
# ---------------------------------------------------------------------------


def _cfg(fmt="fp8_e4m3", block_size=16):
    return ModelConfig(
        name="t", family="dense", d_model=64, vocab_size=128,
        pattern=(BlockDef("attn"),), num_groups=1, num_heads=4,
        num_kv_heads=2, head_dim=16, d_ff=128,
        quant=MXFP8.replace(fmt=fmt, block_size=block_size,
                            quantize_acts=False, quantize_kv_cache=True))


def _churn_reqs(rng):
    return [(rng.integers(0, 128, (s,)).astype(np.int32), m)
            for s, m in [(4, 12), (4, 12), (7, 5), (3, 8)]]


def _run_both(cfg, reqs, **kw):
    outs, engines = {}, {}
    for mode in ("split", "ragged"):
        params, _ = model.init(jax.random.PRNGKey(0), cfg)
        eng = ContinuousBatchingEngine(params, cfg, ServeConfig(
            step_mode=mode, **kw))
        ids = [eng.submit(p, m) for p, m in reqs]
        out = eng.run()
        outs[mode] = [out[i] for i in ids]
        engines[mode] = eng
    assert engines["ragged"].ragged, "unexpected fallback to split"
    for a, b in zip(outs["split"], outs["ragged"]):
        np.testing.assert_array_equal(a, b)
    return engines


SCENARIOS = {
    "churn-prefix": dict(max_seq=24, max_slots=2, page_size=4, num_pages=7,
                         prefix_cache=True),
    "chunked": dict(max_seq=48, max_slots=2, page_size=8, prefill_chunk=8),
    "spec": dict(max_seq=24, max_slots=2, page_size=4, num_pages=7,
                 prefix_cache=True, spec_decode=True, num_draft_tokens=2),
    "spec-chunk": dict(max_seq=48, max_slots=2, page_size=8,
                       prefill_chunk=16, spec_decode=True,
                       num_draft_tokens=3),
    "tiered": dict(max_seq=48, max_slots=2, page_size=8, prefill_chunk=8,
                   num_pages=14, tiered=True),
}


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_ragged_engine_token_identical(scenario):
    """Mixed batches (decode-only / +verify / +prefill-chunk / all three)
    under churn, preemption, tiering, and prefix sharing: per-request
    streams must equal the split-dispatch oracle exactly."""
    cfg = _cfg()
    reqs = _churn_reqs(np.random.default_rng(3))
    engines = _run_both(cfg, reqs, **SCENARIOS[scenario])
    eng = engines["ragged"]
    if "num_pages" in SCENARIOS[scenario] and not SCENARIOS[scenario].get(
            "tiered"):
        assert eng.scheduler.preemptions >= 1, "pool must force a swap"
    stats = eng.cache_stats()
    if stats["mixed_steps"]:
        assert stats["dispatches_per_mixed_step"] == 1.0, stats


@pytest.mark.parametrize("fmt,block_size",
                         [("fp8_e5m2", 16), ("fp4_e2m1", 16),
                          ("fp8_e4m3", 8)])
def test_ragged_engine_formats(fmt, block_size):
    """KV-format sweep rides the engine too: e5m2 and packed-nibble fp4
    pools must stay token-identical through the in-kernel write path."""
    cfg = _cfg(fmt, block_size)
    reqs = _churn_reqs(np.random.default_rng(9))[:2]
    _run_both(cfg, reqs, max_seq=32, max_slots=2, page_size=4,
              prefill_chunk=4)


def test_ragged_one_dispatch_per_mixed_step():
    """The acceptance gate in test form: a workload built to overlap
    decode with a long multi-chunk prefill must run every mixed step as
    exactly ONE device dispatch — while the split oracle needs >= 2."""
    cfg = _cfg()
    rng = np.random.default_rng(17)
    reqs = [(rng.integers(0, 128, (4,)).astype(np.int32), 8),
            (rng.integers(0, 128, (20,)).astype(np.int32), 4)]
    engines = _run_both(cfg, reqs, max_seq=32, max_slots=2, page_size=4,
                        prefill_chunk=4)
    rs = engines["ragged"].cache_stats()
    ss = engines["split"].cache_stats()
    assert rs["mixed_steps"] >= 2, rs
    assert rs["dispatches_per_mixed_step"] == 1.0, rs
    assert rs["dispatches_ragged"] == rs["dispatches_total"], rs
    assert ss["mixed_steps"] >= 1 and ss["dispatches_per_mixed_step"] >= 2.0
    for key in ("decode", "verify", "prefill", "ragged", "write", "repack"):
        assert f"dispatches_{key}" in rs


# ---------------------------------------------------------------------------
# structural: one pallas_call per step, no pool scatter on the ragged path
# ---------------------------------------------------------------------------


def _subjaxprs(params):
    for v in params.values():
        if isinstance(v, jax.extend.core.ClosedJaxpr):
            yield v.jaxpr
        elif hasattr(v, "eqns"):
            yield v
        elif isinstance(v, (tuple, list)):
            for x in v:
                if isinstance(x, jax.extend.core.ClosedJaxpr):
                    yield x.jaxpr
                elif hasattr(x, "eqns"):
                    yield x


def _all_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _subjaxprs(eqn.params):
            yield from _all_eqns(sub)


def test_ragged_step_jaxpr_one_pallas_call_no_pool_scatter():
    """Trace the engine's actual jitted ragged step on its real argument
    shapes: exactly one `pallas_call` per attention layer (one layer
    here => one total) and no scatter writing a pool-shaped operand —
    the 1-row `.at[].set` K/V write is gone from the ragged path."""
    cfg = _cfg()
    params, _ = model.init(jax.random.PRNGKey(0), cfg)
    eng = ContinuousBatchingEngine(params, cfg, ServeConfig(
        max_seq=24, max_slots=2, page_size=4, prefill_chunk=4))
    assert eng.ragged
    captured = {}
    orig = eng._ragged_fn

    def spy(*a, **k):
        captured.setdefault("args", a)
        return orig(*a, **k)

    eng._ragged_fn = spy
    eng.submit(np.arange(5, dtype=np.int32), 3)
    eng.run()
    jaxpr = jax.make_jaxpr(orig)(*captured["args"])

    pool_shapes = {tuple(leaf.shape)
                   for leaf in jax.tree_util.tree_leaves(eng.cache)
                   if getattr(leaf, "ndim", 0) == 4}
    pallas_calls = 0
    for eqn in _all_eqns(jaxpr.jaxpr):
        pallas_calls += eqn.primitive.name == "pallas_call"
        if eqn.primitive.name.startswith("scatter"):
            for var in eqn.outvars:
                shape = tuple(getattr(var.aval, "shape", ()))
                assert shape not in pool_shapes, (
                    f"pool-shaped scatter on the ragged path: {shape}")
    assert pallas_calls == 1, f"{pallas_calls} pallas_calls in step jaxpr"
