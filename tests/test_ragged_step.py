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
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MXFP8, quantize
from repro.core.formats import FORMAT_IDS
from repro.kernels import (mx_attention_ragged_fused,
                           mx_attention_verify_fused, mx_repack_pages)
from repro.nn import BlockDef, ModelConfig, model
from repro.serve import ContinuousBatchingEngine, ServeConfig
from repro.serve.steplog import phase_means_ms


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


def _ragged_case(fmt, block_size, d=64, g=2, kvh=2, ps=8, seed=101,
                 window=None, mixed=False):
    """Three coexisting row modes against per-row verify oracles.

    Row 0: plain decode (n_new=1, mid-page start). Row 1: verify window
    (n_new=3, straddling a page boundary). Row 2: fresh prefill chunk
    (n_new=W from row 0). Row 3: continuation chunk with an unaligned,
    mid-page start — the case the aligned prefill kernel cannot run.

    ``window`` runs a sliding-window layer; ``mixed`` turns the pools
    into a tiered uint8 pool (``fmt`` must be fp8_e4m3) whose first
    resident pages of rows 0 and 1 were repacked to fp6 and fp4.
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

    page_fmts = mixed_fmts = None
    if mixed:
        assert fmt == "fp8_e4m3"
        mixed_fmts = ("fp8_e4m3", "fp6_e3m2", "fp4_e2m1")
        page_fmts = np.full((npages,), FORMAT_IDS[fmt], np.int32)
        want = [a.view(np.uint8) for a in want]
        have = [a.view(np.uint8) for a in have]
        # resident-only pages (wholly below the row's write window) age
        # into narrower formats, identically in both pools
        for row, dst in ((0, "fp6_e3m2"), (1, "fp4_e2m1")):
            pg = int(table[row, 0])
            assert starts[row] >= ps
            for pools in (want, have):
                pools[:] = [np.asarray(x) for x in mx_repack_pages(
                    *(jnp.asarray(a) for a in pools),
                    jnp.asarray([pg], jnp.int32),
                    jnp.asarray([page_fmts[pg]], jnp.int32),
                    jnp.asarray(1, jnp.int32), dst_fmt_name=dst,
                    mixed_fmts=mixed_fmts, block_size=block_size)]
            page_fmts[pg] = FORMAT_IDS[dst]
        kw = dict(page_fmts=jnp.asarray(page_fmts), mixed_fmts=mixed_fmts)
    else:
        kw = {}

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
        fmt_name=fmt, block_size=block_size, window=window,
        debug_visits=True, **kw)
    return SimpleNamespace(
        out=np.asarray(out), pools=[np.asarray(p) for p in pools],
        visits=np.asarray(visits), want=want, have=have, q=q, k_new=k_new,
        v_new=v_new, table=table, starts=starts, n_news=n_news,
        totals=totals, ps=ps, fmt=fmt, block_size=block_size, window=window,
        oracle_kw=kw)


def _assert_matches_split_oracle(c):
    """The ragged kernel's written bytes, outputs and page visits are
    those of the host write + verify-kernel oracle.

    A decode row at G = 1 is left out of the output comparison: there
    the oracle's query tile is one row, and XLA:CPU rounds a one-row
    product differently from the ragged kernel's W-row one (the parent
    one-head-a-step kernel differs from it in the same bits);
    :func:`_assert_each_head_alone` covers those rows."""
    # 1) in-kernel written pool bytes == host core.quantize writes, and
    #    rows the step does not own keep their exact old codes
    for i in range(len(c.starts)):
        for t in range(c.totals[i]):
            pg, prow = c.table[i, t // c.ps], t % c.ps
            for got, exp in zip(c.pools, c.want):
                np.testing.assert_array_equal(
                    got[pg, :, prow].view(np.uint8),
                    exp[pg, :, prow].view(np.uint8))
    owned = {int(c.table[i, p]) for i in range(len(c.starts))
             for p in range(c.starts[i] // c.ps, -(-c.totals[i] // c.ps))}
    for pg in range(c.pools[0].shape[0]):
        if pg in owned:
            continue
        for got, old in zip(c.pools, c.have):
            np.testing.assert_array_equal(got[pg].view(np.uint8),
                                          old[pg].view(np.uint8))

    # 2) attention output bit-matches the split verify kernel reading the
    #    host-written pool (same page walk, same flash accumulation)
    for i in range(len(c.starts)):
        n = c.n_news[i]
        if n * c.q.shape[3] == 1:
            continue
        ref = np.asarray(mx_attention_verify_fused(
            jnp.asarray(c.q[i:i + 1, :, :n]),
            *(jnp.asarray(a) for a in c.want),
            jnp.asarray(c.table[i:i + 1]),
            jnp.asarray([c.totals[i]], jnp.int32),
            fmt_name=c.fmt, block_size=c.block_size, window=c.window,
            **c.oracle_kw))
        np.testing.assert_array_equal(
            c.out[i:i + 1, :, :n].view(np.uint32), ref.view(np.uint32))

    # 3) exact page-visit audit, per (row, kv-head): every page in
    #    [first, ceil(total/PS)) and nothing else, where first skips the
    #    pages wholly below the oldest query's window
    totals, starts = np.array(c.totals), np.array(c.starts)
    first = (0 if c.window is None
             else np.maximum((starts - c.window + 1) // c.ps, 0))
    expect = (-(-totals // c.ps) - first).astype(np.int32)
    np.testing.assert_array_equal(
        c.visits[:, :, 0],
        np.broadcast_to(expect[:, None], c.visits.shape[:2]))


def _assert_each_head_alone(c):
    """Each KV head run alone through the kernel (a one-head walk, one
    grid step per (row, head, page) as before heads shared a step) gives
    the bits the all-heads call gave: outputs, written pools, visits."""
    kw = dict(fmt_name=c.fmt, block_size=c.block_size, window=c.window,
              debug_visits=True, **c.oracle_kw)
    for h in range(c.q.shape[1]):
        hs = slice(h, h + 1)
        out, pools, visits = mx_attention_ragged_fused(
            jnp.asarray(c.q[:, hs]), jnp.asarray(c.k_new[:, hs]),
            jnp.asarray(c.v_new[:, hs]),
            *(jnp.asarray(a[:, hs]) for a in c.have), jnp.asarray(c.table),
            jnp.asarray(c.starts, jnp.int32),
            jnp.asarray(c.totals, jnp.int32), **kw)
        np.testing.assert_array_equal(np.asarray(out).view(np.uint32),
                                      c.out[:, hs].view(np.uint32))
        for got, all_heads in zip(pools, c.pools):
            np.testing.assert_array_equal(
                np.asarray(got).view(np.uint8),
                all_heads[:, hs].view(np.uint8))
        np.testing.assert_array_equal(np.asarray(visits), c.visits[:, hs])


@pytest.mark.parametrize("fmt", ["fp8_e4m3", "fp8_e5m2", "fp4_e2m1"])
@pytest.mark.parametrize("block_size", [16, 32, 64])
def test_ragged_kernel_bit_matches_split_oracle(fmt, block_size):
    _assert_matches_split_oracle(_ragged_case(fmt, block_size))


@pytest.mark.parametrize("g", [1, 3, 4])
@pytest.mark.parametrize("kvh", [1, 2, 8])
def test_ragged_kernel_all_heads_a_step_bit_match_split_oracle(kvh, g):
    """One grid step per (row, page) walks every KV head of the page in
    turn: each head's output, written bytes and visits are still those
    of the one-head-a-step verify oracle, and of the kernel run on that
    head alone, for any head count and group."""
    c = _ragged_case("fp8_e4m3", 32, d=32, g=g, kvh=kvh, seed=7 * kvh + g)
    _assert_matches_split_oracle(c)
    _assert_each_head_alone(c)


@pytest.mark.parametrize("kvh", [1, 2, 8])
def test_ragged_kernel_window_bit_matches_split_oracle(kvh):
    """A sliding window of 2 skips the head page of rows 0, 1 and 3
    (visits per head drop by one each) and masks the rest exactly."""
    c = _ragged_case("fp8_e4m3", 32, d=32, g=2, kvh=kvh, window=2,
                     seed=40 + kvh)
    _assert_matches_split_oracle(c)
    _assert_each_head_alone(c)
    assert (c.visits[[0, 1, 3], :, 0]
            < -(-np.array(c.totals)[[0, 1, 3], None] // c.ps)).all()


@pytest.mark.parametrize("kvh", [2, 8])
def test_ragged_kernel_mixed_format_pool_bit_matches_split_oracle(kvh):
    """Tiered pool: resident pages in fp6 and fp4 beside fp8 ones; one
    format id a page serves all of its heads."""
    c = _ragged_case("fp8_e4m3", 32, d=32, g=3, kvh=kvh, mixed=True,
                     seed=50 + kvh)
    _assert_matches_split_oracle(c)
    _assert_each_head_alone(c)


def test_ragged_kernel_head_tiling_at_large_gdim():
    """head_dim 128 x G 8 pushes W*G*D past one flash row tile: the tiled
    `_flash_update` path must stay bit-identical to the verify oracle
    (which shares the same tiling, so this also regression-checks both
    against the f32 einsum reference at kernel tolerance)."""
    c = _ragged_case("fp8_e4m3", 32, d=128, g=8, kvh=2, seed=131)
    for i in range(len(c.starts)):
        n = c.n_news[i]
        ref = np.asarray(mx_attention_verify_fused(
            jnp.asarray(c.q[i:i + 1, :, :n]),
            *(jnp.asarray(a) for a in c.want),
            jnp.asarray(c.table[i:i + 1]),
            jnp.asarray([c.totals[i]], jnp.int32),
            fmt_name="fp8_e4m3", block_size=32))
        np.testing.assert_array_equal(
            c.out[i:i + 1, :, :n].view(np.uint32), ref.view(np.uint32))


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


# ---------------------------------------------------------------------------
# the step's own spans, step log and named scopes
# ---------------------------------------------------------------------------

_PHASES = ("admit", "repack", "ensure_pages", "assemble", "dispatch", "sync",
           "record")
_STEP_MODES = {
    "ragged": dict(step_mode="ragged", prefill_chunk=8),
    "ragged-spec": dict(step_mode="ragged", prefill_chunk=8,
                        spec_decode=True, num_draft_tokens=2),
    "split": dict(step_mode="split", prefill_chunk=8),
    "split-spec": dict(step_mode="split", prefill_chunk=8,
                       spec_decode=True, num_draft_tokens=2),
    "split-monolithic": dict(step_mode="split", prefill_mode="monolithic"),
}


def _small_engine(**kw):
    cfg = _cfg().replace(num_groups=2)
    params, _ = model.init(jax.random.PRNGKey(0), cfg)
    return ContinuousBatchingEngine(params, cfg, ServeConfig(
        max_seq=48, max_slots=2, page_size=8, **kw))


def _host_events(trace_dir):
    import glob

    from jax.profiler import ProfileData

    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)) for e in line.events
                        if e.name.startswith("serve.")]
    return out


@pytest.mark.parametrize("mode", sorted(_STEP_MODES))
def test_every_phase_span_nests_in_its_step_span(mode, tmp_path):
    """Under the profiler, each step is a ``serve.step`` span numbered by
    the engine's tick; every phase span lies inside one, and
    ``serve.admit`` names the requests it admitted."""
    eng = _small_engine(**_STEP_MODES[mode])
    rng = np.random.default_rng(3)
    ids = [eng.submit(p, m) for p, m in _churn_reqs(rng)]
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run()
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    steps = [e for e in events if e[0] == "serve.step"]
    assert [e[3]["step_num"] for e in steps] == list(
        range(1, len(steps) + 1))
    assert len(steps) == eng._tick
    phases = [e for e in events if e[0] != "serve.step"]
    assert {e[0] for e in phases} >= {f"serve.{p}" for p in _PHASES} - (
        {"serve.repack"} if mode.startswith("ragged") else set())
    for name, lo, hi, _ in phases:
        assert any(s[1] <= lo and hi <= s[2] for s in steps), name
    admitted = " ".join(str(e[3]["requests"]) for e in phases
                        if e[0] == "serve.admit" and "requests" in e[3])
    assert set(map(int, admitted.split())) == set(ids)


@pytest.mark.parametrize("mode", sorted(_STEP_MODES))
def test_step_log_phases_add_up_to_the_step(mode):
    eng = _small_engine(**_STEP_MODES[mode])
    for p, m in _churn_reqs(np.random.default_rng(4)):
        eng.submit(p, m)
    eng.run()
    log = list(eng.step_log)
    assert [r.tick for r in log] == list(range(1, eng._tick + 1))
    assert sum(r.dispatched for r in log) >= eng.steps
    # the statements between phases take microseconds; a phase left
    # unwrapped would show in every step, a pause of the host in one
    unphased = [r.wall_s - sum(b - a for _, a, b in r.phases) for r in log]
    assert min(unphased) >= 0 and np.median(unphased) < 1e-3, unphased
    for r in log:
        assert all(r.t0 <= a <= b <= r.t1 for _, a, b in r.phases)
        assert {p for p, _, _ in r.phases} <= set(_PHASES)
        assert r.host_s == pytest.approx(r.wall_s - r.seconds("sync"))
    means = phase_means_ms(log)
    done = [r for r in log if r.dispatched]
    assert means["wall"] == pytest.approx(
        1e3 * sum(r.wall_s for r in done) / len(done))
    assert means["host"] == pytest.approx(means["wall"] - means["sync"])


@pytest.mark.parametrize("window", [None, 6])
def test_step_log_counts_the_ragged_page_walk(window):
    """Each ragged step logs ``walk_live``, the page bodies its rows run
    a layer (sum of ceil(seq_len / PS) less the window's head pages, as
    the kernel's own visit counter reads them), and ``walk_grid``, rows
    x page-table width; ``walk_live_share`` is their ratio over the
    engine's life. A 20-token prompt streams its chunks while a short
    one decodes, so steps hold prefill and decode rows together."""
    ps = 8
    cfg = _cfg().replace(num_groups=2,
                         pattern=(BlockDef("attn", window=window),))
    params, _ = model.init(jax.random.PRNGKey(0), cfg)
    eng = ContinuousBatchingEngine(params, cfg, ServeConfig(
        max_seq=48, max_slots=2, page_size=ps, prefill_chunk=8))
    assert eng.ragged
    steps = []
    assemble = eng.scheduler.assemble_ragged

    def spy(*a, **kw):
        out = assemble(*a, **kw)
        _, start, lens, _, rows, modes, decode, prefill = out
        if decode or prefill:
            steps.append((start.copy(), lens.copy(), rows.copy(), modes))
        return out

    eng.scheduler.assemble_ragged = spy
    rng = np.random.default_rng(5)
    for n_prompt, n_out in [(4, 10), (20, 4), (6, 5)]:
        eng.submit(rng.integers(0, 128, (n_prompt,)).astype(np.int32), n_out)
    eng.run()
    recs = [r for r in eng.step_log if r.walk_grid]
    assert len(recs) == len(steps) > 0
    assert all(r.walk_live == 0 for r in eng.step_log if not r.walk_grid)
    mixed = [k for k, s in enumerate(steps) if {1, 2} <= set(s[3])]
    assert mixed, "no step held both prefill and decode rows"
    for rec, (start, lens, rows, _) in zip(recs, steps):
        first = (0 if window is None
                 else np.maximum((start - window + 1) // ps, 0))
        assert rec.walk_live == (-(-lens // ps) - first).sum()
        assert rec.walk_grid == rows.size
    if window is not None:
        assert any(r.walk_live < sum(-(-s[1] // ps))
                   for r, s in zip(recs, steps))

    # the kernel, given a mixed step's rows, visits what the log says
    start, lens, rows, _ = steps[mixed[0]]
    r, w = rows.shape[0], eng._ragged_width
    kvh, g, d = cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads, 16
    n_pages = eng.num_pages + 1  # + the trash page
    elems = jnp.zeros((n_pages, kvh, ps, d), jnp.float8_e4m3fn)
    scales = jnp.full((n_pages, kvh, ps, 1), 127, jnp.uint8)
    _, _, visits = mx_attention_ragged_fused(
        jnp.zeros((r, kvh, w, g, d)), jnp.zeros((r, kvh, w, d)),
        jnp.zeros((r, kvh, w, d)), elems, scales, elems, scales,
        jnp.asarray(rows), jnp.asarray(start), jnp.asarray(lens),
        fmt_name="fp8_e4m3", block_size=16, window=window,
        debug_visits=True)
    assert int(np.asarray(visits)[:, 0, 0].sum()) == recs[mixed[0]].walk_live

    share = sum(r.walk_live for r in recs) / sum(r.walk_grid for r in recs)
    assert eng.cache_stats()["walk_live_share"] == pytest.approx(share)
    assert 0 < share < 1


@pytest.fixture
def no_persistent_cache():
    """Compile afresh: the persistent cache's key leaves metadata out, so
    an entry from before a named scope was added carries the names of
    then (and a cache hit is no backend compile)."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_step_log_counts_a_forced_recompile(no_persistent_cache):
    """The step that compiles the ragged step counts its compile; a
    fresh trace of the same step compiles again, in the step that first
    runs it, and steps after it compile nothing."""
    eng = _small_engine(prefill_chunk=8)
    eng.submit(np.arange(5, dtype=np.int32), 6)
    eng.step()
    eng.step()
    raw = eng._ragged_fn_raw
    eng._ragged_fn = jax.jit(lambda *a: raw(*a))
    eng.step()
    eng.step()
    compiles = [r.compiles for r in eng.step_log]
    assert compiles[0] >= 1 and compiles[1] == 0, compiles
    assert compiles[2] >= 1 and compiles[3] == 0, compiles


def test_step_program_hlo_names_every_scope(no_persistent_cache):
    """The compiled ragged step's HLO carries each named scope in its
    instructions' op_name metadata, and the ragged kernel's name;
    ``step_program`` hands back the executable the engine dispatched."""
    import re

    eng = _small_engine(prefill_chunk=8)
    assert eng.step_program() is None
    eng.submit(np.arange(5, dtype=np.int32), 3)
    eng.run()
    program = eng.step_program()
    assert eng.step_program().as_text() == program.as_text()
    text = program.as_text()
    for scope in ("embed", "layer_loop", "qkv_proj", "rope", "ragged_attn",
                  "o_proj", "ffn", "weight_quant", "lm_head", "sample",
                  "mx_attn_ragged"):
        assert re.search(rf'op_name="[^"]*/{scope}[/"]', text), scope
    assert re.search(r'op_name="[^"]*/(qkv_proj|o_proj|ffn)/weight_quant/',
                     text)
