"""Layer-fused megakernel: the ENTIRE ragged engine step as one pallas_call.

VMXDOTP's core argument is that MX's multi-step mixed-precision semantics
fragment regular pipelines — the fix is fusing the whole block-scaled
dot-product chain into one instruction so utilization stays dense. Our
serving stack had the same fragmentation one level up: the ragged step
(``mx_attention_ragged_fused``) fused decode/verify/prefill rows into one
dispatch *per layer*, but an L-layer model still paid L kernel launches,
L rounds of HLO glue, and an HBM round-trip of the residual stream (and
every q/k/v/attention/FFN intermediate) at every layer boundary.

This kernel runs the full attention-only decoder stack in ONE grid::

    grid = (L, R, KVH, P)      all dimensions sequential ("arbitrary")

with per-layer weights stacked along a leading ``L`` axis and
BlockSpec-indexed by the layer grid coordinate, and the residual stream
carried across layer steps in VMEM scratch (TPU grids iterate
sequentially, so the carry is well-defined: layer ``l`` of row ``i``
always runs after layer ``l - 1`` of row ``i`` has stored its output).
Each ``(l, i, j)`` cell is the ragged kernel's page walk verbatim; around
it the kernel folds the rest of the decoder layer:

  * at ``p == 0``: RMSNorm of the carried residual, the cell's KV-head
    column slice of the fused QKV projection (+ RoPE) — column-slicing a
    matmul is bitwise identical to slicing its output, which is the same
    argument that makes the KV-head-sharded serve step exact;
  * pages ``first..valid``: the EXACT per-layer ragged page walk —
    in-register MX dequant (``_dequant_rows`` / ``_dequant_rows_mixed``),
    per-row-causal online softmax (``_flash_update``), in-kernel
    quantized K/V writes through aliased stacked-pool outputs
    (``_quantize_rows`` + code-domain merge), per-page format select,
    trash-page isolation — all helpers imported from ``mx_attention`` so
    the arithmetic (and accumulation order) is bit-identical to the
    per-layer oracle by construction;
  * at the cell's last page: the head-group's normalized output parks in
    VMEM scratch; at the LAST kv-head's last page the layer tail runs —
    output projection, residual add, FFN RMSNorm, the gated MLP, second
    residual add — by calling the nn layer's own ``linear.apply`` /
    ``rmsnorm_apply`` / ``ffn.apply`` on the loaded blocks, so every
    elementwise op and matmul matches the oracle's XLA lowering exactly.

The device dispatch count of a mixed engine step collapses from O(L) to
exactly 1, and no inter-layer intermediate (residual, q/k/v, attention
output, FFN hidden) ever reaches HBM — the serving-stack analogue of the
paper's fuse-the-whole-MX-chain-into-one-instruction thesis.

Weight/pool layouts (``L`` = layer axis, indexed by grid dim 0)::

    x0          (R, W, DM)          post-embedding residual (compute dtype)
    norm_mixer  (L, DM)             RMSNorm scales (pre-``1 +``)
    wq          (L, DM, H*D)        fused; cell (l, j) reads cols [jGD,(j+1)GD)
    wk, wv      (L, DM, KVH*D)      cell (l, j) reads cols [jD, (j+1)D)
    wo          (L, H*D, DM)
    norm_ffn    (L, DM)
    gate/up     (L, DM, DFF)        (gate absent for ffn_kind "gelu")
    down        (L, DFF, DM)
    pools       (L, NP, KVH, PS, ED/NB)  stacked per-layer MX page pools
    page_table  (R, P) i32          shared by all layers; entries < 0 map
                                    to each layer's trash page (NP - 1)
    row_start   (R,) i32            first new-token row per ragged row
    seq_lens    (R,) i32            row_start + n_new

Returns ``(x (R, W, DM) final residual, (ke, ks, ve, vs) updated stacked
pools)`` — pool outputs alias the inputs. The final norm, logit-row
gather, and LM head stay outside (they are row-gathered to ``num_logits``
rows first; fusing the vocab matmul would multiply VMEM pressure for no
dispatch win). ``debug_visits=True`` additionally returns the
(L, R, KVH, 1) executed-page counter (an SMEM output): each layer's page
walk visits exactly the pages the per-layer ragged kernel reports, so
summing over ``L`` gives the whole step's page-visit audit.

VMEM budget note: every per-layer weight block must fit in VMEM
simultaneously with a pool tile, so very wide FFN blocks (8B-class
``DM x DFF``) exceed a real TPU core's ~16 MB VMEM — on hardware that
point needs an extra DFF-tiling grid dimension (a follow-on); off-TPU
interpret mode and the test/benchmark model sizes are unaffected.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import formats as F

from .mx_attention import (NEG_INF, _VISITS_SPEC, _check_fmt, _dequant_rows,
                           _dequant_rows_mixed, _first_window_page,
                           _flash_update, _quantize_rows,
                           MIXED_FMTS_DEFAULT)


def _mx_megakernel(*refs, page_size: int, fmt_name: str, block_size: int,
                   softcap, window, width: int, group: int, kvh: int,
                   head_dim: int, d_model: int, rope_theta: float,
                   norm_eps: float, ffn_kind: str, has_gate: bool, quant,
                   compute_dtype, mixed_fmts=None):
    """One page tile of one (layer, row, kv-head) megakernel cell."""
    # the nn layer's own math, applied in-kernel on loaded blocks so the
    # op sequence (and therefore every f32/bf16 rounding) matches the
    # per-layer oracle exactly; imported lazily to keep kernels <-> nn
    # imports acyclic
    from repro.nn import ffn as ffn_mod
    from repro.nn import linear
    from repro.nn.norms import rmsnorm_apply
    from repro.nn.rotary import apply_rope

    nw = 8 if has_gate else 7  # weight operands before the pools
    if mixed_fmts is None:
        (tbl_ref, start_ref, lens_ref, x0_ref, *rest) = refs
        fmts_ref = None
    else:
        (tbl_ref, start_ref, lens_ref, fmts_ref, x0_ref, *rest) = refs
    w_refs = rest[:nw + 1]
    (ke_ref, ks_ref, ve_ref, vs_ref, xo_ref,
     oke_ref, oks_ref, ove_ref, ovs_ref, visits_ref,
     m_ref, l_ref, acc_ref, q_s, kn_s, vn_s, attn_s, x_s) = rest[nw + 1:]
    if has_gate:
        (nm_ref, wq_ref, wk_ref, wv_ref, wo_ref, nf_ref,
         gate_ref, up_ref, down_ref) = w_refs
    else:
        (nm_ref, wq_ref, wk_ref, wv_ref, wo_ref, nf_ref,
         up_ref, down_ref) = w_refs
        gate_ref = None

    li = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    p = pl.program_id(3)
    last = pl.num_programs(3) - 1
    rows = width * group
    rs = pl.ds(i * width, width)
    cell = (li * pl.num_programs(1) + i) * kvh + j  # visit counter slot

    @pl.when((li == 0) & (j == 0) & (p == 0))
    def _load_residual():
        # the residual stream enters VMEM exactly once per step (layer 0)
        # and lives in scratch until the last layer writes it back out
        x_s[rs, :] = x0_ref[0]

    start = start_ref[i]
    seq_len = lens_ref[i]
    n_new = seq_len - start
    w0 = start // page_size
    valid_pages = pl.cdiv(seq_len, page_size)
    first_page = _first_window_page(start, window, page_size)

    @pl.when(p == 0)
    def _start_cell():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        visits_ref[cell] = 0
        # this layer's pre-norm + this cell's KV-head slice of the fused
        # QKV projection (+ RoPE): the wq/wk/wv BlockSpecs already carved
        # out columns [j*G*D, (j+1)*G*D) / [j*D, (j+1)*D), and a
        # column-sliced matmul is bitwise identical to slicing the full
        # product — the same KV-major layout argument the sharded step
        # relies on. rmsnorm is recomputed per kv-head cell (same inputs,
        # same ops, bit-identical result; DM-wide, so the recompute is
        # noise next to the page walk).
        x = x_s[rs, :]
        h = rmsnorm_apply({"scale": nm_ref[0, 0]}, x, norm_eps)
        q = linear.apply({"w": wq_ref[0]}, h, quant, compute_dtype)
        k = linear.apply({"w": wk_ref[0]}, h, quant, compute_dtype)
        v = linear.apply({"w": wv_ref[0]}, h, quant, compute_dtype)
        posv = start + jax.lax.broadcasted_iota(
            jnp.int32, (1, width), 1)[0]  # (W,)
        q = apply_rope(q.reshape(width, group, head_dim), posv, rope_theta)
        k = apply_rope(k.reshape(width, 1, head_dim), posv, rope_theta)
        q_s[...] = q.reshape(rows, head_dim)
        kn_s[...] = k.reshape(width, head_dim)
        vn_s[...] = v.reshape(width, head_dim)

    def _attend_tile(k, v):
        q = q_s[...].astype(jnp.float32)  # (W * G, D)
        kpos = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        t = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // group
        qpos = start + jnp.minimum(t, n_new - 1)
        mask = kpos <= qpos  # (R, PS)
        if window is not None:
            mask &= kpos > qpos - window
        _flash_update(m_ref, l_ref, acc_ref, q, k, v, mask, softcap)

    @pl.when((p >= first_page) & (p < w0))
    def _resident_page():
        visits_ref[cell] += 1
        if mixed_fmts is None:
            k = _dequant_rows(ke_ref[0, 0, 0], ks_ref[0, 0, 0],
                              fmt_name, block_size)  # (PS, D)
            v = _dequant_rows(ve_ref[0, 0, 0], vs_ref[0, 0, 0],
                              fmt_name, block_size)
        else:
            fid = fmts_ref[tbl_ref[i, p]]
            k = _dequant_rows_mixed(ke_ref[0, 0, 0],
                                    ks_ref[0, 0, 0],
                                    fid, mixed_fmts, block_size)
            v = _dequant_rows_mixed(ve_ref[0, 0, 0],
                                    vs_ref[0, 0, 0],
                                    fid, mixed_fmts, block_size)
        _attend_tile(k, v)

    @pl.when((p >= w0) & (p < valid_pages))
    def _write_page():
        visits_ref[cell] += 1
        kw = kn_s[...].astype(jnp.float32)  # (W, D) wide new rows
        vw = vn_s[...].astype(jnp.float32)
        # one-hot scatter + code-domain merge + aliased write: verbatim
        # the per-layer ragged kernel's write window (same helpers, same
        # accumulation order)
        jrow = jax.lax.broadcasted_iota(
            jnp.int32, (page_size, width), 0)  # page row
        tcol = jax.lax.broadcasted_iota(
            jnp.int32, (page_size, width), 1)  # new-row index
        kpos_rows = p * page_size + jrow[:, :1]  # (PS, 1)
        onehot = ((start + tcol) == (p * page_size + jrow)
                  ).astype(jnp.float32)  # (PS, W)
        k_page = jax.lax.dot_general(
            onehot, kw, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # (PS, D)
        v_page = jax.lax.dot_general(
            onehot, vw, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        kq_e, kq_s = _quantize_rows(k_page, fmt_name, block_size)
        vq_e, vq_s = _quantize_rows(v_page, fmt_name, block_size)
        if mixed_fmts is not None:
            kq_e = jax.lax.bitcast_convert_type(kq_e, jnp.uint8)
            vq_e = jax.lax.bitcast_convert_type(vq_e, jnp.uint8)
        in_w = (kpos_rows >= start) & (kpos_rows < seq_len)  # (PS, 1)
        k_codes = jnp.where(in_w, kq_e, ke_ref[0, 0, 0])
        v_codes = jnp.where(in_w, vq_e, ve_ref[0, 0, 0])
        k_scales = jnp.where(in_w, kq_s, ks_ref[0, 0, 0])
        v_scales = jnp.where(in_w, vq_s, vs_ref[0, 0, 0])
        oke_ref[0, 0, 0] = k_codes
        ove_ref[0, 0, 0] = v_codes
        oks_ref[0, 0, 0] = k_scales
        ovs_ref[0, 0, 0] = v_scales
        if mixed_fmts is None:
            _attend_tile(
                _dequant_rows(k_codes, k_scales, fmt_name, block_size),
                _dequant_rows(v_codes, v_scales, fmt_name, block_size))
        else:
            fid = fmts_ref[tbl_ref[i, p]]
            _attend_tile(
                _dequant_rows_mixed(k_codes, k_scales, fid, mixed_fmts,
                                    block_size),
                _dequant_rows_mixed(v_codes, v_scales, fid, mixed_fmts,
                                    block_size))

    @pl.when(p == last)
    def _finish_head():
        # normalized head-group output parks in scratch until the layer's
        # last kv-head cell assembles the full attention output — same
        # f32 value the per-layer kernel writes to its output ref
        attn_s[pl.ds(j * rows, rows), :] = acc_ref[...] / l_ref[...]

    @pl.when((j == kvh - 1) & (p == last))
    def _layer_tail():
        x = x_s[rs, :]
        # (KVH, W, G, D) -> (W, KVH*G*D): exactly the oracle wrapper's
        # transpose(0, 2, 1, 3, 4) + reshape, per row
        out = attn_s[...].reshape(kvh, width, group, head_dim)
        out = out.transpose(1, 0, 2, 3).reshape(width,
                                                kvh * group * head_dim)
        out = out.astype(compute_dtype)
        h = linear.apply({"w": wo_ref[0]}, out, quant, compute_dtype,
                         tp_on="in")
        x = x + h
        # the dense gated MLP tail (blocks._decode_tail with ffn "dense"):
        # same rmsnorm + ffn.apply calls on the loaded stacked blocks
        h = rmsnorm_apply({"scale": nf_ref[0, 0]}, x, norm_eps)
        fparams = {"up": {"w": up_ref[0]}, "down": {"w": down_ref[0]}}
        if has_gate:
            fparams["gate"] = {"w": gate_ref[0]}
        h = ffn_mod.apply(fparams, h, quant, ffn_kind, compute_dtype)
        x = x + h
        x_s[rs, :] = x
        # the residual output block is (re)written at every layer; the
        # last flush (layer L-1) is what lands in HBM
        xo_ref[0] = x


def mx_megakernel_step(x0, norm_mixer, wq, wk, wv, wo, norm_ffn, gate, up,
                       down, ke_pool, ks_pool, ve_pool, vs_pool, page_table,
                       row_start, seq_lens, *, head_dim: int,
                       rope_theta: float, norm_eps: float, ffn_kind: str,
                       quant, fmt_name: str = "fp8_e4m3",
                       block_size: int = 32, softcap=None, window=None,
                       compute_dtype=jnp.bfloat16, page_fmts=None,
                       mixed_fmts=None, debug_visits: bool = False,
                       interpret: bool | None = None):
    """Run the whole decoder layer stack over a ragged row batch as ONE
    pallas_call. See the module docstring for layouts and semantics.

    ``gate`` is None for ffn_kind "gelu". ``quant`` is the model's
    ``QuantConfig`` (weight-only or disabled; activation quantization is
    rejected by the engine's fallback ladder). Pool layouts, the
    trash-page contract, and ``page_fmts``/``mixed_fmts`` match
    ``mx_attention_ragged_fused`` with a leading layer axis.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    mixed = page_fmts is not None
    _check_fmt(ke_pool, fmt_name, mixed=mixed)
    if mixed:
        if mixed_fmts is None:
            mixed_fmts = MIXED_FMTS_DEFAULT
        mixed_fmts = tuple(mixed_fmts)
        if F.get_format(fmt_name).bits != 8:
            raise ValueError(
                "tiered megakernel steps write the window in the hot "
                f"format, which must be an fp8; got {fmt_name!r}")
    else:
        mixed_fmts = None
    if quant is not None and quant.enabled:
        if quant.quantize_acts:
            raise ValueError(
                "the megakernel runs weight-only or unquantized linears; "
                "activation quantization is rejected by the engine's "
                "fallback ladder")
        # Pre-fake-quantize the stacked weights OUTSIDE the kernel: the
        # per-layer oracle fake-quants each layer's weight at use
        # (linear.apply, axis 0 = the contraction dim), and blocking the
        # (L, d_in, d_out) stack along axis 1 is the same computation per
        # layer — bit-identical values. Hoisting it keeps the in-kernel
        # linears on the plain-matmul path, which (a) avoids re-deriving
        # the quantization grid in every grid cell and (b) keeps fp4/fp6
        # value-grid lookup tables out of the kernel trace (Pallas rejects
        # captured constant arrays).
        from repro.core import fake_quant

        def _prequant(ws):
            wq_ = fake_quant(ws.astype(jnp.float32), quant.fmt,
                             quant.block_size, 1)
            return wq_.astype(compute_dtype)

        wq, wk, wv, wo = (_prequant(t) for t in (wq, wk, wv, wo))
        up, down = _prequant(up), _prequant(down)
        if gate is not None:
            gate = _prequant(gate)
        quant = quant.replace(enabled=False)
    r, w, dm = x0.shape
    layers, npages, ps = ke_pool.shape[0], ke_pool.shape[1], ke_pool.shape[3]
    ed = ke_pool.shape[-1]
    nb = ks_pool.shape[-1]
    d = head_dim
    hd = wq.shape[-1]
    kvh = wk.shape[-1] // d
    g = (hd // d) // kvh
    rows = w * g
    pmax = page_table.shape[1]
    has_gate = gate is not None
    table = jnp.asarray(page_table, jnp.int32)
    table = jnp.where(table < 0, npages - 1,
                      jnp.clip(table, 0, npages - 1))
    start = jnp.asarray(row_start, jnp.int32)
    lens = jnp.clip(jnp.asarray(seq_lens, jnp.int32), start + 1, start + w)

    def pool_in_spec(width_):
        def imap(li, i, j, p, tbl, st, ln, *_fmts):
            valid = pl.cdiv(ln[i], ps)
            first = _first_window_page(st[i], window, ps)
            return (li, tbl[i, jnp.clip(p, first, valid - 1)], j, 0, 0)
        return pl.BlockSpec((1, 1, 1, ps, width_), imap)

    def pool_out_spec(width_):
        def imap(li, i, j, p, tbl, st, ln, *_fmts):
            w0 = st[i] // ps
            valid = pl.cdiv(ln[i], ps)
            return (li, tbl[i, jnp.clip(p, w0, valid - 1)], j, 0, 0)
        return pl.BlockSpec((1, 1, 1, ps, width_), imap)

    def wspec(shape, imap):
        return pl.BlockSpec(shape, imap)

    in_specs = [
        # x0: one (W, DM) slab per row, read once at layer 0
        wspec((1, w, dm), lambda li, i, j, p, *_: (i, 0, 0)),
        wspec((1, 1, dm), lambda li, i, j, p, *_: (li, 0, 0)),  # norm_mixer
        wspec((1, dm, g * d), lambda li, i, j, p, *_: (li, 0, j)),  # wq
        wspec((1, dm, d), lambda li, i, j, p, *_: (li, 0, j)),      # wk
        wspec((1, dm, d), lambda li, i, j, p, *_: (li, 0, j)),      # wv
        wspec((1, hd, dm), lambda li, i, j, p, *_: (li, 0, 0)),     # wo
        wspec((1, 1, dm), lambda li, i, j, p, *_: (li, 0, 0)),  # norm_ffn
    ]
    # norms ride as (L, 1, DM) so each layer's block spans both tiled dims
    weight_ops = [x0, norm_mixer[:, None], wq, wk, wv, wo, norm_ffn[:, None]]
    if has_gate:
        dff = gate.shape[-1]
        in_specs.append(
            wspec((1, dm, dff), lambda li, i, j, p, *_: (li, 0, 0)))
        weight_ops.append(gate)
    dff = up.shape[-1]
    in_specs += [
        wspec((1, dm, dff), lambda li, i, j, p, *_: (li, 0, 0)),    # up
        wspec((1, dff, dm), lambda li, i, j, p, *_: (li, 0, 0)),    # down
        pool_in_spec(ed), pool_in_spec(nb),
        pool_in_spec(ed), pool_in_spec(nb),
    ]
    weight_ops += [up, down]

    scalar_ops = [table, start, lens]
    if mixed:
        scalar_ops.append(jnp.asarray(page_fmts, jnp.int32))
    ns = len(scalar_ops)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=ns,
        grid=(layers, r, kvh, pmax),
        in_specs=in_specs,
        out_specs=[
            # final residual: one (W, DM) slab per row, flushed at every
            # layer boundary — the last flush (layer L-1) wins
            wspec((1, w, dm), lambda li, i, j, p, *_: (i, 0, 0)),
            pool_out_spec(ed), pool_out_spec(nb),
            pool_out_spec(ed), pool_out_spec(nb),
            _VISITS_SPEC,
        ],
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),   # running max m
            pltpu.VMEM((rows, 1), jnp.float32),   # running denominator l
            pltpu.VMEM((rows, d), jnp.float32),   # rescaled partial output
            pltpu.VMEM((rows, d), compute_dtype),  # q (this cell's slice)
            pltpu.VMEM((w, d), compute_dtype),    # new K rows (RoPE'd)
            pltpu.VMEM((w, d), compute_dtype),    # new V rows
            pltpu.VMEM((kvh * rows, d), jnp.float32),  # per-head attn out
            pltpu.VMEM((r * w, dm), compute_dtype),    # residual carry
        ],
    )
    kernel = functools.partial(
        _mx_megakernel, page_size=ps, fmt_name=fmt_name,
        block_size=block_size, softcap=softcap, window=window, width=w,
        group=g, kvh=kvh, head_dim=d, d_model=dm, rope_theta=rope_theta,
        norm_eps=norm_eps, ffn_kind=ffn_kind, has_gate=has_gate,
        quant=quant, compute_dtype=compute_dtype, mixed_fmts=mixed_fmts)
    nin = len(weight_ops)  # operands between the scalars and the pools
    x_out, oke, oks, ove, ovs, visits = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((r, w, dm), x0.dtype),
            jax.ShapeDtypeStruct(ke_pool.shape, ke_pool.dtype),
            jax.ShapeDtypeStruct(ks_pool.shape, ks_pool.dtype),
            jax.ShapeDtypeStruct(ve_pool.shape, ve_pool.dtype),
            jax.ShapeDtypeStruct(vs_pool.shape, vs_pool.dtype),
            jax.ShapeDtypeStruct((layers * r * kvh,), jnp.int32),
        ],
        # stacked pools update in place (operand indices count the
        # scalar-prefetch operands, then x0 + weights, then the pools)
        input_output_aliases={ns + nin + k: 1 + k for k in range(4)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary",
                                 "arbitrary")),
        interpret=interpret,
    )(*scalar_ops, *weight_ops, ke_pool, ks_pool, ve_pool, vs_pool)
    pools = (oke, oks, ove, ovs)
    return ((x_out, pools, visits.reshape(layers, r, kvh, 1))
            if debug_visits else (x_out, pools))
