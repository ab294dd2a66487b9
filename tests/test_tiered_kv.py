"""Tiered mixed-format KV cache: repack kernel bit-exactness + engine
format-lifecycle correctness.

The load-bearing claims:

  * the Pallas repack kernel's narrow re-encode is bit-identical to a
    host decode -> ``core.quantize``-math re-encode of the same rows,
    leaves untouched pages byte-identical, zeroes dead tail bytes, and
    handles mixed source formats + padded page lists;
  * widening (the COW promote path) is lossless: fp4 -> fp8 repack
    decodes to exactly the fp4 values;
  * a tiered engine with the repack budget at zero is token-identical to
    the plain all-fp8 engine under churn (preemption pressure, prefix
    sharing, speculative decoding) — the unit-metered pool and format
    plumbing alone change nothing;
  * an aggressive tiering policy keeps its invariants under churn:
    per-step repack stays under budget, the unit accounting matches the
    per-page format census, and the engine is deterministic;
  * swap-out/restore preserves narrow page formats: a preempted
    sequence whose pages were already repacked resumes bit-identically
    to the same run without the preemption.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import formats as F
from repro.kernels import mx_repack_pages
from repro.kernels.mx_attention import _quantize_rows
from repro.nn import BlockDef, ModelConfig, model
from repro.serve import ContinuousBatchingEngine, ServeConfig, TierPolicy
from repro.serve.engine import _FMT_BITS
from repro.serve.kv_cache import UNITS_BY_BITS

MIXED = ("fp8_e4m3", "fp6_e3m2", "fp4_e2m1")


# ---------------------------------------------------------------------------
# repack kernel vs host oracle
# ---------------------------------------------------------------------------


def _host_decode(rows_bytes, scales, fmt_name, bs):
    """(PS, D) stored bytes + E8M0 scales -> (PS, D) f32, via the public
    formats API (independent of the kernel's in-Pallas decode)."""
    fmt = F.get_format(fmt_name)
    d = rows_bytes.shape[-1]
    stored = jnp.asarray(rows_bytes[..., : fmt.storage_len(d)])
    if fmt.bits == 8:
        stored = jax.lax.bitcast_convert_type(stored, fmt.storage_dtype)
    vals = F.decode_elements(stored, fmt_name)
    nb = d // bs
    s = F.e8m0_to_scale(jnp.asarray(scales))
    return np.asarray(
        (vals.reshape(-1, nb, bs) * s[..., None]).reshape(-1, d))


def _host_requant(rows_bytes, scales, src_fmt, dst_fmt, bs):
    """Decode + re-encode on the host: the repack oracle."""
    wide = _host_decode(rows_bytes, scales, src_fmt, bs)
    q_e, q_s = _quantize_rows(jnp.asarray(wide), dst_fmt, bs)
    if F.get_format(dst_fmt).bits == 8:
        q_e = jax.lax.bitcast_convert_type(q_e, jnp.uint8)
    return np.asarray(q_e), np.asarray(q_s)


def _fresh_pools(rng, npages=6, ps=4, kvh=2, d=32, bs=16):
    """uint8 tiered pools with every page holding fp8-encoded content."""
    nb = d // bs
    ke = np.zeros((npages, kvh, ps, d), np.uint8)
    ks = np.zeros((npages, kvh, ps, nb), np.uint8)
    ve = np.zeros_like(ke)
    vs = np.zeros_like(ks)
    for elems, sc in ((ke, ks), (ve, vs)):
        for p in range(npages):
            for h in range(kvh):
                wide = rng.normal(size=(ps, d)).astype(np.float32) * 3.0
                q_e, q_s = _quantize_rows(jnp.asarray(wide), "fp8_e4m3", bs)
                elems[p, h] = np.asarray(
                    jax.lax.bitcast_convert_type(q_e, jnp.uint8))
                sc[p, h] = np.asarray(q_s)
    return tuple(jnp.asarray(a) for a in (ke, ks, ve, vs)), bs


def _repack(pools, ids, fmts, count, dst, bs, nlist=4):
    ids = ids + [ids[-1]] * (nlist - len(ids))
    fmts = fmts + [fmts[-1]] * (nlist - len(fmts))
    return mx_repack_pages(
        *pools, jnp.asarray(ids, jnp.int32), jnp.asarray(fmts, jnp.int32),
        jnp.asarray(count, jnp.int32), dst_fmt_name=dst, mixed_fmts=MIXED,
        block_size=bs)


@pytest.mark.parametrize("dst", ["fp6_e3m2", "fp6_e2m3", "fp4_e2m1"])
def test_repack_kernel_matches_host_requant(dst):
    pools, bs = _fresh_pools(np.random.default_rng(0))
    before = [np.asarray(a) for a in pools]
    out = [np.asarray(a) for a in _repack(pools, [1, 3], [0, 0], 2, dst, bs)]
    w = F.get_format(dst).storage_len(before[0].shape[-1])
    for p in range(before[0].shape[0]):
        for h in range(before[0].shape[1]):
            for e_i, s_i in ((0, 1), (2, 3)):
                got_e, got_s = out[e_i][p, h], out[s_i][p, h]
                if p in (1, 3):
                    want_e, want_s = _host_requant(
                        before[e_i][p, h], before[s_i][p, h],
                        "fp8_e4m3", dst, bs)
                    np.testing.assert_array_equal(got_e[:, :w], want_e)
                    np.testing.assert_array_equal(got_e[:, w:], 0)
                    np.testing.assert_array_equal(got_s, want_s)
                else:  # untouched pages stay byte-identical
                    np.testing.assert_array_equal(got_e,
                                                  before[e_i][p, h])
                    np.testing.assert_array_equal(got_s,
                                                  before[s_i][p, h])


def test_repack_kernel_mixed_source_formats():
    """One call can repack pages whose *sources* differ (fp6 and fp8
    both heading to fp4) — the per-page format id rides scalar prefetch."""
    pools, bs = _fresh_pools(np.random.default_rng(1))
    pools = _repack(pools, [3], [0], 1, "fp6_e3m2", bs)
    mid = [np.asarray(a) for a in pools]
    out = [np.asarray(a) for a in _repack(
        pools, [3, 4], [F.FORMAT_IDS["fp6_e3m2"], 0], 2, "fp4_e2m1", bs)]
    w = F.get_format("fp4_e2m1").storage_len(mid[0].shape[-1])
    for p, src in ((3, "fp6_e3m2"), (4, "fp8_e4m3")):
        for h in range(mid[0].shape[1]):
            for e_i, s_i in ((0, 1), (2, 3)):
                want_e, want_s = _host_requant(
                    mid[e_i][p, h], mid[s_i][p, h], src,
                    "fp4_e2m1", bs)
                np.testing.assert_array_equal(out[e_i][p, h, :, :w], want_e)
                np.testing.assert_array_equal(out[e_i][p, h, :, w:], 0)
                np.testing.assert_array_equal(out[s_i][p, h], want_s)


def test_repack_widening_is_lossless():
    """The COW promote path: fp4 -> fp8 re-encode must decode to exactly
    the fp4 values (every fp4 grid point is on the fp8 grid)."""
    pools, bs = _fresh_pools(np.random.default_rng(2))
    pools = _repack(pools, [2], [0], 1, "fp4_e2m1", bs)
    narrow = [np.asarray(a) for a in pools]
    out = [np.asarray(a) for a in _repack(
        pools, [2], [F.FORMAT_IDS["fp4_e2m1"]], 1, "fp8_e4m3", bs)]
    for h in range(narrow[0].shape[1]):
        for e_i, s_i in ((0, 1), (2, 3)):
            want = _host_decode(narrow[e_i][2, h],
                                narrow[s_i][2, h], "fp4_e2m1", bs)
            got = _host_decode(out[e_i][2, h], out[s_i][2, h],
                               "fp8_e4m3", bs)
            np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# engine: format lifecycle under churn
# ---------------------------------------------------------------------------


def _cfg(quant=None):
    from repro.core import MXFP8

    quant = MXFP8 if quant is None else quant
    return ModelConfig(
        name="t", family="dense", d_model=64, vocab_size=128,
        pattern=(BlockDef("attn"),), num_groups=1, num_heads=4,
        num_kv_heads=2, head_dim=16, d_ff=128,
        quant=quant.replace(block_size=16, quantize_acts=False,
                            quantize_kv_cache=True))


def _churn_reqs(rng, n=6):
    """Shared-head + ragged tails: prefix sharing, page straddling."""
    head = rng.integers(0, 128, (16,)).astype(np.int32)
    reqs = []
    for i in range(n):
        tail = rng.integers(0, 128, (3 + 5 * (i % 3),)).astype(np.int32)
        prompt = np.concatenate([head, tail]) if i % 2 else tail
        reqs.append((prompt, 6))
    return reqs


def _serve(params, cfg, reqs, **kw):
    eng = ContinuousBatchingEngine(params, cfg, ServeConfig(
        max_seq=48, max_slots=2, page_size=8, decode_kernel="fused",
        prefill_chunk=8, **kw))
    ids = [eng.submit(p, m) for p, m in reqs]
    out = eng.run()
    return [out[i] for i in ids], eng


@pytest.mark.parametrize("spec", [False, True], ids=["decode", "spec"])
def test_tiered_repack_disabled_token_identical_under_churn(spec):
    cfg = _cfg()
    params, _ = model.init(jax.random.PRNGKey(0), cfg)
    reqs = _churn_reqs(np.random.default_rng(5))
    kw = dict(num_pages=14)  # tight: forces eviction/preemption pressure
    if spec:
        kw.update(spec_decode=True, num_draft_tokens=3)
    want, base = _serve(params, cfg, reqs, **kw)
    got, tier = _serve(params, cfg, reqs, tiered=True,
                       tier_policy=TierPolicy(repack_pages_per_step=0),
                       **kw)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(g, w)
    assert tier.cache_stats()["repacked_pages"] == 0


def test_tiered_requires_fp8_base_and_fp4_only_engine_still_serves():
    """The fp4-only corner of the format matrix: tiering over an fp4
    base is rejected loudly (new writes must land full-width — there is
    no narrower tier to demote to), while the plain all-fp4 engine
    serves the same churn workload to completion deterministically."""
    from repro.core import MXFP4

    cfg = _cfg(MXFP4)
    params, _ = model.init(jax.random.PRNGKey(0), cfg)
    reqs = _churn_reqs(np.random.default_rng(5))
    with pytest.raises(ValueError, match="8-bit base"):
        _serve(params, cfg, reqs, num_pages=14, tiered=True,
               tier_policy=TierPolicy(repack_pages_per_step=0))
    out1, eng = _serve(params, cfg, reqs, num_pages=14)
    assert all(len(g) == len(p) + m for g, (p, m) in zip(out1, reqs))
    out2, _ = _serve(params, cfg, reqs, num_pages=14)
    for a, b in zip(out1, out2):
        np.testing.assert_array_equal(a, b)


def _census_units(eng):
    pool = eng.scheduler.pool
    return sum(
        UNITS_BY_BITS[_FMT_BITS[F.FORMAT_BY_ID[int(eng.page_fmts[pid])]]]
        for pid in range(eng.num_pages) if pool.ref(pid) > 0)


def test_tiered_aggressive_churn_invariants():
    """Mixed-format churn: pages demote while requests come and go. The
    accounting invariants must hold and the run must be deterministic."""
    cfg = _cfg()
    params, _ = model.init(jax.random.PRNGKey(0), cfg)
    reqs = _churn_reqs(np.random.default_rng(9), n=8)
    policy = TierPolicy(hot_steps=1, cold_steps=3, repack_pages_per_step=3)
    out1, eng = _serve(params, cfg, reqs, num_pages=14, tiered=True,
                       tier_policy=policy)
    stats = eng.cache_stats()
    assert stats["repacked_pages"] > 0
    assert stats["max_repacked_in_step"] <= policy.repack_pages_per_step
    # unit metering == per-page format census, and narrow pages exist
    assert _census_units(eng) == eng.scheduler.pool.units_in_use
    assert all(int(f) in F.FORMAT_BY_ID for f in eng.page_fmts)
    for p, m in reqs:  # greedy, no EOS: every request runs to max_new
        pass
    assert all(len(g) == len(p) + m for g, (p, m) in zip(out1, reqs))
    out2, _ = _serve(params, cfg, reqs, num_pages=14, tiered=True,
                     tier_policy=policy)
    for a, b in zip(out1, out2):
        np.testing.assert_array_equal(a, b)


def test_trash_page_outside_tiering_and_census():
    """The ragged step appends ONE trash page past the schedulable pool
    (pid == num_pages) as the sink for masked-lane K/V writes. It is
    never allocated, never ages, never demotes, and never appears in
    the per-format census — an off-by-one in any geometry consumer
    (repack scan, stats census, pool bounds) would surface here."""
    cfg = _cfg()
    params, _ = model.init(jax.random.PRNGKey(0), cfg)
    reqs = _churn_reqs(np.random.default_rng(9), n=8)
    policy = TierPolicy(hot_steps=1, cold_steps=2, repack_pages_per_step=4)
    _, eng = _serve(params, cfg, reqs, num_pages=14, tiered=True,
                    tier_policy=policy)
    assert eng.ragged and eng._trash_pages == 1
    stats = eng.cache_stats()
    assert stats["repacked_pages"] > 0
    # the trash page sits at pid == num_pages (tiering doubles the
    # schedulable pool first, so num_pages here is the doubled count)
    trash = eng.num_pages
    assert len(eng.page_fmts) == eng.num_pages + 1
    assert int(eng.page_fmts[trash]) == eng._base_fmt_id, \
        "trash page was demoted/repacked"
    # it is not schedulable: the pool's bounds stop short of it
    pool = eng.scheduler.pool
    with pytest.raises(ValueError, match="unknown page"):
        pool.ref(trash)
    # census over schedulable pages only == unit metering
    assert _census_units(eng) == pool.units_in_use
    assert sum(stats[f"pages_{f}"] for f in eng._mixed_fmts) == \
        sum(1 for pid in range(eng.num_pages) if pool.ref(pid) > 0)
    # pool byte accounting covers the trash page exactly once
    from repro.serve.kv_cache import pool_page_nbytes
    assert stats["page_bytes"] == pool_page_nbytes(
        eng.cache, eng.num_pages + 1)


def test_swap_restore_preserves_narrow_page_formats():
    """A sequence whose prompt pages already demoted is preempted and
    restored; generation must continue exactly as if the preemption
    never happened (raw bytes AND format ids both survive the swap)."""
    cfg = _cfg()
    params, _ = model.init(jax.random.PRNGKey(0), cfg)
    prompt = np.random.default_rng(21).integers(0, 128, (24,)) \
        .astype(np.int32)

    def drive(force_swap):
        # no prefix tree: the sequence OWNS every page, so the swap
        # blob (not the tree) must carry the narrow format ids across
        eng = ContinuousBatchingEngine(params, cfg, ServeConfig(
            max_seq=64, max_slots=2, page_size=8, decode_kernel="fused",
            prefill_chunk=8, prefix_cache=False, tiered=True,
            tier_policy=TierPolicy(hot_steps=1, cold_steps=2,
                                   repack_pages_per_step=8)))
        rid = eng.submit(prompt, 24)
        frozen = saved = None
        while True:
            more = eng.step()
            seq = next((s for s in eng.scheduler.slots
                        if s is not None and s.req.id == rid), None)
            if (frozen is None and seq is not None
                    and seq.prefill_pos is None
                    and any(int(eng.page_fmts[p]) != eng._base_fmt_id
                            for p in seq.pages)):
                # freeze the tiers at a deterministic point (both runs
                # reach it at the same step) so the only difference
                # between the runs is the forced preemption itself
                frozen = eng.tier = dataclasses.replace(
                    eng.tier, repack_pages_per_step=0)
                if force_swap:
                    eng._swap_out(seq)
                    saved = list(eng._swap_fmts[rid])
            if not more:
                break
        assert frozen is not None, "no page demoted before completion"
        out = next(r for r in eng.scheduler.finished if r.id == rid)
        return np.asarray(out.generated), saved

    want, _ = drive(force_swap=False)
    got, saved = drive(force_swap=True)
    assert saved is not None and any(
        fid != F.FORMAT_IDS["fp8_e4m3"] for fid in saved), \
        "forced swap captured no narrow page (test setup drifted)"
    np.testing.assert_array_equal(got, want)
