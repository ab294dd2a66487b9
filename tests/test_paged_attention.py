"""Paged MX decode attention: gather vs contiguous (bit-exact) + fused.

The two-pass paged kernel gathers compact K/V tiles through the page table
and then runs the identical attention kernel, so paged and contiguous
caches must agree to the bit in interpret mode — any mismatch means the
page plumbing (table indexing, clamping, masking) is wrong, not the float
math.

The single-pass fused kernel (`mx_attention_decode_fused`) accumulates an
online softmax over page tiles, so it is checked against an f32 einsum
reference to <= 1e-5 (online rescaling reorders f32 additions), plus
structural checks: no gathered (B, KVH, T, ·) array — wide or compact —
may appear in its jaxpr, and unallocated/garbage pages must never
contribute.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quantize
from repro.kernels import (gather_kv_pages, mx_attention_decode,
                           mx_attention_decode_fused,
                           mx_attention_decode_paged,
                           mx_attention_ragged_fused,
                           mx_attention_verify_fused)

RNG = np.random.default_rng(123)


def _einsum_reference(q, kq, vq, lens):
    """f32 dequantize + masked softmax oracle on the contiguous cache."""
    q = np.asarray(q, np.float32)
    kd = np.asarray(kq.dequantize(jnp.float32))
    vd = np.asarray(vq.dequantize(jnp.float32))
    b, kvh, g, d = q.shape
    out = np.zeros((b, kvh, g, d), np.float32)
    for i in range(b):
        t = int(lens[i])
        s = np.einsum("kgd,ktd->kgt", q[i], kd[i, :, :t]) * d ** -0.5
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[i] = np.einsum("kgt,ktd->kgd", p, vd[i, :, :t])
    return out


def _paged_layout(kq, vq, b, kvh, t, ps, rng):
    """Scatter a contiguous (B, KVH, T, ·) cache into a shuffled page pool."""
    npg = t // ps
    pool_pages = b * npg + 3  # spare pages stay garbage (must be masked)
    perm = rng.permutation(pool_pages)[: b * npg]
    table = perm.reshape(b, npg).astype(np.int32)
    arrs = {}
    for name, src in [("ke", kq.elements), ("ks", kq.scales),
                      ("ve", vq.elements), ("vs", vq.scales)]:
        src = np.asarray(src)
        pool = np.full((pool_pages, kvh, ps, src.shape[-1]), 255,
                       dtype=src.dtype if src.dtype != np.uint8 else np.uint8)
        if pool.dtype != np.uint8:
            pool[:] = 0
        for i in range(b):
            for p in range(npg):
                pool[table[i, p]] = src[i, :, p * ps:(p + 1) * ps]
        arrs[name] = jnp.asarray(pool)
    return arrs, jnp.asarray(table)


@pytest.mark.parametrize("fmt", ["fp8_e4m3", "fp8_e5m2", "fp4_e2m1"])
@pytest.mark.parametrize("block_size", [16, 32, 64])
def test_paged_matches_contiguous_bit_exact(fmt, block_size):
    b, kvh, g, d, t, ps = 2, 2, 2, 64, 64, 16
    q = jnp.asarray(RNG.normal(size=(b, kvh, g, d)).astype(np.float32))
    kq = quantize(jnp.asarray(
        RNG.normal(size=(b, kvh, t, d)).astype(np.float32)), fmt, block_size)
    vq = quantize(jnp.asarray(
        RNG.normal(size=(b, kvh, t, d)).astype(np.float32)), fmt, block_size)
    lens = np.array([t - 3, t - 17], np.int32)

    want = []
    for i in range(b):
        kpos = jnp.where(jnp.arange(t) < lens[i], jnp.arange(t),
                         -1).astype(jnp.int32)
        want.append(np.asarray(mx_attention_decode(
            q[i:i + 1], kq.elements[i:i + 1], kq.scales[i:i + 1],
            vq.elements[i:i + 1], vq.scales[i:i + 1], kpos,
            int(lens[i]) - 1, fmt_name=fmt, block_size=block_size)))
    want = np.concatenate(want, axis=0)

    pools, table = _paged_layout(kq, vq, b, kvh, t, ps, RNG)
    got = np.asarray(mx_attention_decode_paged(
        q, pools["ke"], pools["ks"], pools["ve"], pools["vs"], table,
        jnp.asarray(lens), fmt_name=fmt, block_size=block_size))
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))


def test_gather_kv_pages_reorders_exactly():
    b, kvh, t, d, ps = 2, 3, 32, 32, 8
    kq = quantize(jnp.asarray(
        RNG.normal(size=(b, kvh, t, d)).astype(np.float32)), "fp8_e4m3", 32)
    vq = quantize(jnp.asarray(
        RNG.normal(size=(b, kvh, t, d)).astype(np.float32)), "fp8_e4m3", 32)
    pools, table = _paged_layout(kq, vq, b, kvh, t, ps, RNG)
    ke, ks, ve, vs = gather_kv_pages(pools["ke"], pools["ks"], pools["ve"],
                                     pools["vs"], table)
    np.testing.assert_array_equal(
        np.asarray(ke).astype(np.float32),
        np.asarray(kq.elements).astype(np.float32))
    np.testing.assert_array_equal(np.asarray(ks), np.asarray(kq.scales))
    np.testing.assert_array_equal(
        np.asarray(ve).astype(np.float32),
        np.asarray(vq.elements).astype(np.float32))
    np.testing.assert_array_equal(np.asarray(vs), np.asarray(vq.scales))


def test_unallocated_table_entries_never_contribute():
    """Rows past seq_len come from clamped/garbage pages; outputs must not
    depend on their contents."""
    b, kvh, g, d, t, ps = 1, 2, 2, 32, 32, 8
    q = jnp.asarray(RNG.normal(size=(b, kvh, g, d)).astype(np.float32))
    kq = quantize(jnp.asarray(
        RNG.normal(size=(b, kvh, t, d)).astype(np.float32)), "fp8_e4m3", 32)
    vq = quantize(jnp.asarray(
        RNG.normal(size=(b, kvh, t, d)).astype(np.float32)), "fp8_e4m3", 32)
    pools, table = _paged_layout(kq, vq, b, kvh, t, ps, RNG)
    seq_len = jnp.asarray([ps + 3], jnp.int32)  # only the first 2 pages valid
    base = np.asarray(mx_attention_decode_paged(
        q, pools["ke"], pools["ks"], pools["ve"], pools["vs"], table,
        seq_len))
    table2 = np.asarray(table).copy()
    table2[0, 2:] = -1  # drop the unallocated tail entirely
    got = np.asarray(mx_attention_decode_paged(
        q, pools["ke"], pools["ks"], pools["ve"], pools["vs"],
        jnp.asarray(table2), seq_len))
    np.testing.assert_array_equal(got.view(np.uint32), base.view(np.uint32))


def test_contiguous_kernel_per_sequence_positions():
    """(B,) pos / (B, T) kpos rows must equal per-row scalar calls."""
    b, kvh, g, d, t = 3, 2, 2, 32, 48
    q = jnp.asarray(RNG.normal(size=(b, kvh, g, d)).astype(np.float32))
    kq = quantize(jnp.asarray(
        RNG.normal(size=(b, kvh, t, d)).astype(np.float32)), "fp8_e4m3", 32)
    vq = quantize(jnp.asarray(
        RNG.normal(size=(b, kvh, t, d)).astype(np.float32)), "fp8_e4m3", 32)
    lens = np.array([10, 48, 33], np.int32)
    kpos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))
    got = np.asarray(mx_attention_decode(
        q, kq.elements, kq.scales, vq.elements, vq.scales, kpos,
        jnp.asarray(lens) - 1))
    for i in range(b):
        want = np.asarray(mx_attention_decode(
            q[i:i + 1], kq.elements[i:i + 1], kq.scales[i:i + 1],
            vq.elements[i:i + 1], vq.scales[i:i + 1],
            jnp.arange(t, dtype=jnp.int32), int(lens[i]) - 1))
        np.testing.assert_array_equal(got[i:i + 1].view(np.uint32),
                                      want.view(np.uint32))


# ---------------------------------------------------------------------------
# single-pass fused kernel: accuracy, edge cases, structural guarantees
# ---------------------------------------------------------------------------


def _fused_case(fmt, block_size, b, kvh, g, d, t, ps, lens, rng, **kw):
    """Build a shuffled paged layout, run fused, compare to the f32 oracle."""
    q = jnp.asarray(rng.normal(size=(b, kvh, g, d)).astype(np.float32))
    kq = quantize(jnp.asarray(
        rng.normal(size=(b, kvh, t, d)).astype(np.float32)), fmt, block_size)
    vq = quantize(jnp.asarray(
        rng.normal(size=(b, kvh, t, d)).astype(np.float32)), fmt, block_size)
    pools, table = _paged_layout(kq, vq, b, kvh, t, ps, rng)
    got = np.asarray(mx_attention_decode_fused(
        q, pools["ke"], pools["ks"], pools["ve"], pools["vs"], table,
        jnp.asarray(lens), fmt_name=fmt, block_size=block_size, **kw))
    return got, _einsum_reference(q, kq, vq, lens)


@pytest.mark.parametrize("fmt", ["fp8_e4m3", "fp8_e5m2", "fp4_e2m1"])
@pytest.mark.parametrize("block_size", [16, 32, 64])
def test_fused_matches_einsum_reference(fmt, block_size):
    rng = np.random.default_rng(11)
    lens = np.array([61, 17], np.int32)
    got, want = _fused_case(fmt, block_size, b=2, kvh=2, g=2, d=64, t=64,
                            ps=16, lens=lens, rng=rng)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)


@pytest.mark.parametrize("fmt", ["fp8_e4m3", "fp4_e2m1"])
@pytest.mark.parametrize(
    "lens",
    [np.array([16, 32], np.int32),   # exactly on a page boundary
     np.array([1, 1], np.int32),     # single-token sequences
     np.array([64, 64], np.int32)],  # fully-packed table, no padding
    ids=["page-boundary", "seq-len-1", "fully-packed"])
def test_fused_edge_lengths(fmt, lens):
    """Boundary occupancies the page-skip predicate must get right."""
    rng = np.random.default_rng(13)
    got, want = _fused_case(fmt, 32, b=2, kvh=2, g=2, d=64, t=64, ps=16,
                            lens=lens, rng=rng)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)


@pytest.mark.parametrize("d", [32, 64])
@pytest.mark.parametrize("block_size", [16, 32, 64])
def test_fused_fp4_packed_nibbles(d, block_size):
    """fp4 stores two nibbles per byte: the in-kernel unpack must cope
    with every (head_dim, block) tiling the serve configs use."""
    if block_size > d:
        pytest.skip("block cannot exceed head_dim")
    rng = np.random.default_rng(17)
    lens = np.array([37, 8, 40], np.int32)
    got, want = _fused_case("fp4_e2m1", block_size, b=3, kvh=2, g=4, d=d,
                            t=40, ps=8, lens=lens, rng=rng)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)


def test_fused_unallocated_pages_never_contribute():
    """Entries past ceil(seq_len / PS) are garbage/-1; flipping their
    contents or ids must not change the output at all."""
    rng = np.random.default_rng(19)
    b, kvh, g, d, t, ps = 1, 2, 2, 32, 32, 8
    q = jnp.asarray(rng.normal(size=(b, kvh, g, d)).astype(np.float32))
    kq = quantize(jnp.asarray(
        rng.normal(size=(b, kvh, t, d)).astype(np.float32)), "fp8_e4m3", 32)
    vq = quantize(jnp.asarray(
        rng.normal(size=(b, kvh, t, d)).astype(np.float32)), "fp8_e4m3", 32)
    pools, table = _paged_layout(kq, vq, b, kvh, t, ps, rng)
    seq_len = jnp.asarray([ps + 3], jnp.int32)  # only the first 2 pages valid
    base = np.asarray(mx_attention_decode_fused(
        q, pools["ke"], pools["ks"], pools["ve"], pools["vs"], table,
        seq_len))
    table2 = np.asarray(table).copy()
    table2[0, 2:] = -1  # drop the unallocated tail entirely
    got = np.asarray(mx_attention_decode_fused(
        q, pools["ke"], pools["ks"], pools["ve"], pools["vs"],
        jnp.asarray(table2), seq_len))
    np.testing.assert_array_equal(got.view(np.uint32), base.view(np.uint32))


def test_fused_sliding_window_matches_masked_reference():
    rng = np.random.default_rng(23)
    b, kvh, g, d, t, ps, window = 2, 2, 2, 64, 64, 16, 12
    q = jnp.asarray(rng.normal(size=(b, kvh, g, d)).astype(np.float32))
    kq = quantize(jnp.asarray(
        rng.normal(size=(b, kvh, t, d)).astype(np.float32)), "fp8_e4m3", 32)
    vq = quantize(jnp.asarray(
        rng.normal(size=(b, kvh, t, d)).astype(np.float32)), "fp8_e4m3", 32)
    pools, table = _paged_layout(kq, vq, b, kvh, t, ps, rng)
    lens = np.array([61, 30], np.int32)
    got = np.asarray(mx_attention_decode_fused(
        q, pools["ke"], pools["ks"], pools["ve"], pools["vs"], table,
        jnp.asarray(lens), window=window))
    kd = np.asarray(kq.dequantize(jnp.float32))
    vd = np.asarray(vq.dequantize(jnp.float32))
    for i in range(b):
        pos = int(lens[i]) - 1
        lo = max(0, pos - window + 1)
        s = np.einsum("kgd,ktd->kgt", np.asarray(q[i], np.float32),
                      kd[i, :, lo:pos + 1]) * d ** -0.5
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        want = np.einsum("kgt,ktd->kgd", p, vd[i, :, lo:pos + 1])
        np.testing.assert_allclose(got[i], want, atol=1e-5, rtol=0)


def test_fused_visits_exactly_the_resident_pages():
    """The skip predicate's audit trail: the kernel's visit counter must
    equal ceil(seq_len / PS) per (batch, kv-head) cell — more visits
    means work scales with the padded table again, fewer means dropped
    context. (Wall-clock can't falsify this off-TPU: the interpreter
    visits every grid cell and only predicates the body away.)"""
    rng = np.random.default_rng(29)
    b, kvh, g, d, t, ps = 3, 2, 2, 32, 32, 8
    q = jnp.asarray(rng.normal(size=(b, kvh, g, d)).astype(np.float32))
    kq = quantize(jnp.asarray(
        rng.normal(size=(b, kvh, t, d)).astype(np.float32)), "fp8_e4m3", 32)
    vq = quantize(jnp.asarray(
        rng.normal(size=(b, kvh, t, d)).astype(np.float32)), "fp8_e4m3", 32)
    pools, table = _paged_layout(kq, vq, b, kvh, t, ps, rng)
    lens = np.array([1, 8, 27], np.int32)  # 1, 1, and 4 resident pages
    _, visits = mx_attention_decode_fused(
        q, pools["ke"], pools["ks"], pools["ve"], pools["vs"], table,
        jnp.asarray(lens), debug_visits=True)
    want = np.broadcast_to(np.ceil(lens / ps).astype(np.int32)[:, None],
                           (b, kvh))
    np.testing.assert_array_equal(np.asarray(visits)[:, :, 0], want)


@pytest.mark.parametrize("window", [None, 10])
@pytest.mark.parametrize("kvh", [1, 2, 8])
def test_ragged_decode_rows_are_the_fused_decode_kernel(kvh, window):
    """Decode rows through the ragged kernel, whose grid step walks one
    page for all KV heads: each (row, head) visits exactly ceil(len/PS)
    pages less the window's head pages, the output is bitwise the
    one-head-a-step decode kernel's, and re-writing each row's newest
    token (already in the pool) leaves every pool byte as it was."""
    rng = np.random.default_rng(71 + kvh)
    b, g, d, t, ps, w = 3, 2, 32, 64, 8, 4
    wide_k = rng.normal(size=(b, kvh, t, d)).astype(np.float32)
    wide_v = rng.normal(size=(b, kvh, t, d)).astype(np.float32)
    kq = quantize(jnp.asarray(wide_k), "fp8_e4m3", 32)
    vq = quantize(jnp.asarray(wide_v), "fp8_e4m3", 32)
    pools, table = _paged_layout(kq, vq, b, kvh, t, ps, rng)
    lens = np.array([64, 41, 7], np.int32)  # deep, mid, under the window
    q = rng.normal(size=(b, kvh, w, g, d)).astype(np.float32)
    k_new = rng.normal(size=(b, kvh, w, d)).astype(np.float32)  # padding
    v_new = rng.normal(size=(b, kvh, w, d)).astype(np.float32)
    for i in range(b):
        k_new[i, :, 0] = wide_k[i, :, lens[i] - 1]
        v_new[i, :, 0] = wide_v[i, :, lens[i] - 1]
    names = ("ke", "ks", "ve", "vs")
    got, new_pools, visits = mx_attention_ragged_fused(
        jnp.asarray(q), jnp.asarray(k_new), jnp.asarray(v_new),
        *(pools[k] for k in names), table, jnp.asarray(lens - 1),
        jnp.asarray(lens), fmt_name="fp8_e4m3", block_size=32,
        window=window, debug_visits=True)
    first = (0 if window is None
             else np.maximum((lens - 1 - window + 1) // ps, 0))
    want_visits = np.ceil(lens / ps).astype(np.int32) - first
    np.testing.assert_array_equal(
        np.asarray(visits)[:, :, 0],
        np.broadcast_to(want_visits[:, None], (b, kvh)))
    want = mx_attention_decode_fused(
        jnp.asarray(q[:, :, 0]), *(pools[k] for k in names), table,
        jnp.asarray(lens), window=window)
    np.testing.assert_array_equal(
        np.asarray(got)[:, :, 0].view(np.uint32),
        np.asarray(want).view(np.uint32))
    for k, pool in zip(names, new_pools):
        np.testing.assert_array_equal(np.asarray(pool).view(np.uint8),
                                      np.asarray(pools[k]).view(np.uint8))


def test_fused_never_materializes_gathered_cache():
    """Structural guarantee: the fused path's jaxpr contains exactly one
    pallas_call and no intermediate shaped like a gathered cache — neither
    the wide f32/bf16 copy nor the compact one the two-pass kernel
    produces, in either the kernel layout (B, KVH, T, ·) or the nn einsum
    layout (B, T, KVH, ·). ``d != t`` so a padded-T axis is unambiguous."""
    b, kvh, g, d, t, ps = 2, 2, 2, 16, 32, 8
    pmax = t // ps
    npg = b * pmax + 2

    def run(q, ke, ks, ve, vs, table, lens):
        return mx_attention_decode_fused(q, ke, ks, ve, vs, table, lens,
                                         fmt_name="fp8_e4m3", block_size=16)

    jaxpr = jax.make_jaxpr(run)(
        jnp.zeros((b, kvh, g, d), jnp.float32),
        jnp.zeros((npg, kvh, ps, d), jnp.float8_e4m3fn),
        jnp.zeros((npg, kvh, ps, 1), jnp.uint8),
        jnp.zeros((npg, kvh, ps, d), jnp.float8_e4m3fn),
        jnp.zeros((npg, kvh, ps, 1), jnp.uint8),
        jnp.zeros((b, pmax), jnp.int32),
        jnp.zeros((b,), jnp.int32))
    pallas_calls = 0
    for eqn in jaxpr.jaxpr.eqns:
        pallas_calls += eqn.primitive.name == "pallas_call"
        for var in eqn.outvars:
            shape = getattr(var.aval, "shape", ())
            assert not (len(shape) == 4 and shape[0] == b
                        and t in (shape[1], shape[2])), (
                f"gathered cache materialized: {eqn.primitive} -> {shape}")
    assert pallas_calls == 1, jaxpr


# ---------------------------------------------------------------------------
# Tq > 1 fused verify kernel (speculative decoding's batched verify)
# ---------------------------------------------------------------------------


def _verify_reference(q, kq, vq, lens, window=None):
    """f32 oracle for the multi-query verify kernel, one query at a time.

    q: (B, KVH, Tq, G, D). Query ``ti`` of sequence ``i`` sits at absolute
    position ``lens[i] - Tq + ti`` and attends keys ``<= that position``
    (minus the sliding window, if any) — per-row causal masking is the
    whole point, so the oracle computes every row independently.
    """
    q = np.asarray(q, np.float32)
    kd = np.asarray(kq.dequantize(jnp.float32))
    vd = np.asarray(vq.dequantize(jnp.float32))
    b, kvh, tq, g, d = q.shape
    out = np.zeros((b, kvh, tq, g, d), np.float32)
    for i in range(b):
        for ti in range(tq):
            p = int(lens[i]) - tq + ti
            lo = 0 if window is None else max(0, p - window + 1)
            s = np.einsum("kgd,ktd->kgt", q[i, :, ti],
                          kd[i, :, lo:p + 1]) * d ** -0.5
            pr = np.exp(s - s.max(-1, keepdims=True))
            pr /= pr.sum(-1, keepdims=True)
            out[i, :, ti] = np.einsum("kgt,ktd->kgd", pr, vd[i, :, lo:p + 1])
    return out


def _verify_case(fmt, block_size, b, kvh, g, d, t, ps, tq, lens, rng,
                 **kw):
    q = jnp.asarray(rng.normal(size=(b, kvh, tq, g, d)).astype(np.float32))
    kq = quantize(jnp.asarray(
        rng.normal(size=(b, kvh, t, d)).astype(np.float32)), fmt, block_size)
    vq = quantize(jnp.asarray(
        rng.normal(size=(b, kvh, t, d)).astype(np.float32)), fmt, block_size)
    pools, table = _paged_layout(kq, vq, b, kvh, t, ps, rng)
    got = mx_attention_verify_fused(
        q, pools["ke"], pools["ks"], pools["ve"], pools["vs"], table,
        jnp.asarray(lens), fmt_name=fmt, block_size=block_size, **kw)
    window = kw.get("window")
    if kw.get("debug_visits"):
        out, visits = got
        return (np.asarray(out), np.asarray(visits),
                _verify_reference(q, kq, vq, lens, window))
    return np.asarray(got), _verify_reference(q, kq, vq, lens, window)


@pytest.mark.parametrize("fmt", ["fp8_e4m3", "fp8_e5m2", "fp4_e2m1"])
@pytest.mark.parametrize("block_size", [16, 32, 64])
def test_verify_matches_einsum_reference(fmt, block_size):
    rng = np.random.default_rng(31)
    lens = np.array([61, 23], np.int32)
    got, want = _verify_case(fmt, block_size, b=2, kvh=2, g=2, d=64, t=64,
                             ps=16, tq=4, lens=lens, rng=rng)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)


@pytest.mark.parametrize("tq", [1, 2, 3, 4, 5])
def test_verify_every_chunk_length(tq):
    """Chunk lengths 1..K: the per-row causal mask must be exact at every
    draft count the engine can run, including the Tq == 1 decode case."""
    rng = np.random.default_rng(37)
    lens = np.array([29, 40, tq], np.int32)  # incl. a chunk-only sequence
    got, want = _verify_case("fp8_e4m3", 32, b=3, kvh=2, g=2, d=32, t=40,
                             ps=8, tq=tq, lens=lens, rng=rng)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)


@pytest.mark.parametrize("fmt", ["fp8_e4m3", "fp4_e2m1"])
@pytest.mark.parametrize(
    "lens",
    [np.array([18, 33], np.int32),   # chunk straddles a page boundary
     np.array([16, 32], np.int32),   # chunk ends exactly on a boundary
     np.array([4, 20], np.int32),    # chunk is the whole first page tail
     np.array([64, 50], np.int32)],  # fully-packed table / interior
    ids=["straddle", "boundary-end", "first-page", "packed"])
def test_verify_page_boundary_straddling_chunks(fmt, lens):
    """A verify chunk whose tokens span two pages: rows of the same chunk
    live in different page tiles and the online softmax must stitch them
    per query row."""
    rng = np.random.default_rng(41)
    got, want = _verify_case(fmt, 32, b=2, kvh=2, g=2, d=64, t=64, ps=16,
                             tq=4, lens=lens, rng=rng)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)


def test_verify_sliding_window_matches_masked_reference():
    rng = np.random.default_rng(43)
    lens = np.array([61, 30], np.int32)
    got, want = _verify_case("fp8_e4m3", 32, b=2, kvh=2, g=2, d=64, t=64,
                             ps=16, tq=3, lens=lens, rng=rng, window=12)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)


def test_verify_visits_exactly_the_resident_pages():
    """The page-skip audit holds for multi-query chunks too: visits per
    (batch, kv-head) cell == ceil(seq_len / PS), independent of Tq."""
    rng = np.random.default_rng(47)
    lens = np.array([3, 17, 40], np.int32)
    got, visits, want = _verify_case(
        "fp8_e4m3", 32, b=3, kvh=2, g=2, d=32, t=40, ps=8, tq=3,
        lens=lens, rng=rng, debug_visits=True)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)
    expect = np.broadcast_to(np.ceil(lens / 8).astype(np.int32)[:, None],
                             (3, 2))
    np.testing.assert_array_equal(visits[:, :, 0], expect)


def test_fused_window_head_pages_skipped_exactly():
    """Sliding-window head skip audit: pages wholly below the query's
    window must not execute (visits == pages actually inside the
    window), and the output must equal the masked reference — too few
    visits would drop in-window context, too many means the head DMA
    and dequant work came back."""
    rng = np.random.default_rng(61)
    b, kvh, g, d, t, ps, window = 3, 2, 2, 64, 64, 8, 10
    q = jnp.asarray(rng.normal(size=(b, kvh, g, d)).astype(np.float32))
    kq = quantize(jnp.asarray(
        rng.normal(size=(b, kvh, t, d)).astype(np.float32)), "fp8_e4m3", 32)
    vq = quantize(jnp.asarray(
        rng.normal(size=(b, kvh, t, d)).astype(np.float32)), "fp8_e4m3", 32)
    pools, table = _paged_layout(kq, vq, b, kvh, t, ps, rng)
    lens = np.array([64, 41, 7], np.int32)  # deep, mid, shorter-than-window
    got, visits = mx_attention_decode_fused(
        q, pools["ke"], pools["ks"], pools["ve"], pools["vs"], table,
        jnp.asarray(lens), window=window, debug_visits=True)
    first = np.maximum((lens - 1 - window + 1) // ps, 0)
    want_visits = np.ceil(lens / ps).astype(np.int32) - first
    np.testing.assert_array_equal(
        np.asarray(visits)[:, :, 0],
        np.broadcast_to(want_visits[:, None], (b, kvh)))
    kd = np.asarray(kq.dequantize(jnp.float32))
    vd = np.asarray(vq.dequantize(jnp.float32))
    for i in range(b):
        pos = int(lens[i]) - 1
        lo = max(0, pos - window + 1)
        s = np.einsum("kgd,ktd->kgt", np.asarray(q[i], np.float32),
                      kd[i, :, lo:pos + 1]) * d ** -0.5
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        want = np.einsum("kgt,ktd->kgd", p, vd[i, :, lo:pos + 1])
        np.testing.assert_allclose(np.asarray(got)[i], want, atol=1e-5,
                                   rtol=0)


def test_verify_window_head_pages_skipped_exactly():
    """The multi-query chunk's head skip is bounded by the *oldest*
    query: visits == ceil(len/PS) - max(0, (len - Tq - W + 1) // PS),
    and every row still matches the per-row masked oracle."""
    rng = np.random.default_rng(67)
    tq, ps, window = 3, 8, 10
    lens = np.array([62, 30, 11], np.int32)
    got, visits, want = _verify_case(
        "fp8_e4m3", 32, b=3, kvh=2, g=2, d=64, t=64, ps=ps, tq=tq,
        lens=lens, rng=rng, window=window, debug_visits=True)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)
    first = np.maximum((lens - tq - window + 1) // ps, 0)
    expect = np.ceil(lens / ps).astype(np.int32) - first
    np.testing.assert_array_equal(
        visits[:, :, 0], np.broadcast_to(expect[:, None], (3, 2)))


def test_verify_tq1_is_bitwise_the_decode_kernel():
    """decode_fused is the Tq == 1 case of verify_fused by delegation;
    pin that equivalence bit-for-bit so the two can never drift."""
    rng = np.random.default_rng(53)
    b, kvh, g, d, t, ps = 2, 2, 2, 64, 64, 16
    q = jnp.asarray(rng.normal(size=(b, kvh, g, d)).astype(np.float32))
    kq = quantize(jnp.asarray(
        rng.normal(size=(b, kvh, t, d)).astype(np.float32)), "fp8_e4m3", 32)
    vq = quantize(jnp.asarray(
        rng.normal(size=(b, kvh, t, d)).astype(np.float32)), "fp8_e4m3", 32)
    pools, table = _paged_layout(kq, vq, b, kvh, t, ps, rng)
    lens = jnp.asarray([61, 17], jnp.int32)
    dec = np.asarray(mx_attention_decode_fused(
        q, pools["ke"], pools["ks"], pools["ve"], pools["vs"], table, lens))
    ver = np.asarray(mx_attention_verify_fused(
        q[:, :, None], pools["ke"], pools["ks"], pools["ve"], pools["vs"],
        table, lens))[:, :, 0]
    np.testing.assert_array_equal(dec.view(np.uint32), ver.view(np.uint32))


def test_verify_rejected_region_never_contributes():
    """Rows past seq_len hold garbage (e.g. rejected speculated K/V from
    an earlier, longer chunk): flipping the garbage pages' ids to -1 must
    not change any query row's output — the rollback-by-truncation
    guarantee at the kernel level."""
    rng = np.random.default_rng(59)
    b, kvh, g, d, t, ps, tq = 1, 2, 2, 32, 32, 8, 3
    q = jnp.asarray(rng.normal(size=(b, kvh, tq, g, d)).astype(np.float32))
    kq = quantize(jnp.asarray(
        rng.normal(size=(b, kvh, t, d)).astype(np.float32)), "fp8_e4m3", 32)
    vq = quantize(jnp.asarray(
        rng.normal(size=(b, kvh, t, d)).astype(np.float32)), "fp8_e4m3", 32)
    pools, table = _paged_layout(kq, vq, b, kvh, t, ps, rng)
    seq_len = jnp.asarray([ps + 3], jnp.int32)  # only the first 2 pages valid
    base = np.asarray(mx_attention_verify_fused(
        q, pools["ke"], pools["ks"], pools["ve"], pools["vs"], table,
        seq_len))
    table2 = np.asarray(table).copy()
    table2[0, 2:] = -1
    got = np.asarray(mx_attention_verify_fused(
        q, pools["ke"], pools["ks"], pools["ve"], pools["vs"],
        jnp.asarray(table2), seq_len))
    np.testing.assert_array_equal(got.view(np.uint32), base.view(np.uint32))
