"""Mixture-of-Experts FFN with top-k routing, shared experts, and EP sharding.

Design for 1000+ node scale (DESIGN.md §5): experts live on the ``expert``
logical axis (mapped to the ``model`` mesh axis). Token dispatch uses the
dense one-hot einsum formulation — collective-free within a shard (dispatch
and combine contract locally; only the usual data-parallel reductions
remain), deterministic, and capacity-factor-free. For MX, per-expert weights
are block-quantized exactly like dense FFN weights — MoE is where MX weight
compression pays most (expert bytes dominate).

Router math stays f32 (routing decisions must be bit-stable across replicas
for SPMD determinism).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import QuantConfig, fake_quant

from . import common as C
from . import linear


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff_expert: int
    num_experts: int
    top_k: int
    num_shared: int = 0
    d_ff_shared: int = 0  # hidden dim of the shared-expert branch (total)
    ffn_kind: str = "swiglu"
    router_norm_topk: bool = True  # normalize top-k weights to sum 1
    aux_loss_weight: float = 0.01
    dispatch: str = "dense"  # "dense" | "sorted" (ragged_dot dropless)


def init(key, cfg: MoEConfig):
    ks = C.split_keys(key, 5)
    e, dm, dff = cfg.num_experts, cfg.d_model, cfg.d_ff_expert

    def expert_block(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return {
            "gate": C.truncated_normal_init(k1, (dm, dff), 1.0),
            "up": C.truncated_normal_init(k2, (dm, dff), 1.0),
            "down": C.truncated_normal_init(k3, (dff, dm), 1.0),
        }

    experts = jax.vmap(expert_block)(jnp.stack(C.split_keys(ks[0], e)))
    params = {
        "router": {"w": C.truncated_normal_init(ks[1], (dm, e), 1.0)},
        "experts": experts,
    }
    axes = {
        "router": {"w": (C.D_MODEL, C.EXPERT)},
        "experts": {
            "gate": (C.EXPERT, C.D_MODEL, C.D_FF),
            "up": (C.EXPERT, C.D_MODEL, C.D_FF),
            "down": (C.EXPERT, C.D_FF, C.D_MODEL),
        },
    }
    if cfg.num_shared:
        from . import ffn

        sp, sa = ffn.init(ks[2], dm, cfg.d_ff_shared, cfg.ffn_kind)
        params["shared"] = sp
        axes["shared"] = sa
    return params, axes


def _router(params, x, cfg: MoEConfig):
    """Top-k softmax routing in f32. Returns (weights, one_hot, aux_loss)."""
    logits = jnp.einsum(
        "btd,de->bte", x.astype(jnp.float32),
        params["router"]["w"].astype(jnp.float32),
    )
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_idx = jax.lax.top_k(probs, cfg.top_k)  # (B,T,K)
    if cfg.router_norm_topk:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    one_hot = jax.nn.one_hot(top_idx, cfg.num_experts, dtype=jnp.float32)
    # Switch-style load-balancing loss: E * <f_e, p_e>
    frac_tokens = jnp.mean(jnp.sum(one_hot, axis=2), axis=(0, 1))  # (E,)
    frac_probs = jnp.mean(probs, axis=(0, 1))
    aux = cfg.num_experts * jnp.sum(frac_tokens * frac_probs)
    return top_w, one_hot, aux


def _mx_expert_weight(wt, quant: QuantConfig, contract_axis: int, dtype,
                      dm_axis: int = 1):
    """Quantize an (E, d0, d1) expert stack shard-side, gather MX bytes.

    Same MX-FSDP move as ``core.dot._mx_fsdp_quantize`` but for stacked
    expert weights (§Perf iteration 8): GSPMD otherwise all-gathers the f32
    masters of every expert every layer — the single largest collective on
    mixtral train. Each device quantizes its local shard (MX blocks stay
    shard-local), the FSDP all-gather then moves fp8 elements + u8 scales,
    and the wide operand is rebuilt in-register per device.

    Layouts: gate/up are (E, d_model, d_ff) with contract_axis=1 (d_model =
    FSDP dim); down is (E, d_ff, d_model) with contract_axis=1 (d_ff = TP
    dim, d_model = FSDP dim at axis 2).
    """
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.core import formats as FF
    from repro.core import quantize
    from repro.core.mx_tensor import MXTensor
    from repro.parallel.ctx import current_mesh

    wt = wt.astype(jnp.float32)
    if not quant.enabled:
        return wt.astype(dtype)

    def fallback():
        return fake_quant(wt, quant.fmt, quant.block_size,
                          contract_axis).astype(dtype)

    mesh = current_mesh()
    fmt_i = FF.get_format(quant.fmt)
    fsdp = tuple(a for a in ("pod", "data")
                 if a in (mesh.axis_names if mesh else ()))
    if (mesh is None or fmt_i.packed or not fsdp
            or not quant.mx_weight_gather):
        return fallback()
    fsdp_size = int(np.prod([mesh.shape[a] for a in fsdp]))
    tp = "model" if "model" in mesh.axis_names else None
    tp_size = mesh.shape[tp] if tp else 1
    e, d0, d1 = wt.shape
    e_tp = tp is not None and e % tp_size == 0

    # dm_axis (caller-specified) marks the d_model/FSDP dim: gate/up are
    # (E, d_model, d_ff) -> dm=1; down is (E, d_ff, d_model) -> dm=2.
    other_axis = 2 if dm_axis == 1 else 1
    dims = [tp if e_tp else None, None, None]
    if wt.shape[dm_axis] % fsdp_size:
        return fallback()
    dims[dm_axis] = fsdp
    if not e_tp and tp is not None and wt.shape[other_axis] % tp_size == 0:
        dims[other_axis] = tp
    # the contraction dim's local shard must stay MX-block aligned
    ca_shard = wt.shape[contract_axis]
    if dims[contract_axis] == fsdp:
        ca_shard //= fsdp_size
    elif dims[contract_axis] == tp:
        ca_shard //= tp_size
    if ca_shard % quant.block_size:
        return fallback()
    w_spec = P(*dims)
    # element storage has the contract axis LAST; the remaining dims keep
    # their relative order
    non_contract = [i for i in range(3) if i != contract_axis]
    storage_of = {ax: i for i, ax in enumerate(non_contract)}
    storage_of[contract_axis] = 2
    gather_axis = storage_of[dm_axis]
    local_shape = [e, wt.shape[1], wt.shape[2]]
    for i, d in enumerate(dims):
        if d == fsdp and i != dm_axis:
            local_shape[i] //= fsdp_size
        elif d == tp:
            local_shape[i] //= tp_size

    def body(ws):
        t = quantize(ws, quant.fmt, quant.block_size, axis=contract_axis)
        elems = jax.lax.all_gather(t.elements, fsdp, axis=gather_axis,
                                   tiled=True)
        scales = jax.lax.all_gather(t.scales, fsdp, axis=gather_axis,
                                    tiled=True)
        shp = list(local_shape)
        shp[dm_axis] = wt.shape[dm_axis]  # gathered back to global
        g = MXTensor(elements=elems, scales=scales, fmt_name=fmt_i.name,
                     block_size=quant.block_size, axis=contract_axis,
                     shape=tuple(shp))
        return g.dequantize(dtype)

    out_dims = [d if i != dm_axis else None for i, d in enumerate(dims)]
    return jax.shard_map(body, mesh=mesh, in_specs=(w_spec,),
                         out_specs=P(*out_dims), check_vma=False)(wt)


def _expert_ffn(w, h_in, quant: QuantConfig, kind: str, dtype):
    """Apply all experts' gated FFN to dispatched tokens h_in (E,Cap,D)."""

    gate = jnp.einsum("ecd,edf->ecf", h_in,
                      _mx_expert_weight(w["gate"], quant, 1, dtype, dm_axis=1))
    up = jnp.einsum("ecd,edf->ecf", h_in,
                    _mx_expert_weight(w["up"], quant, 1, dtype, dm_axis=1))
    act = jax.nn.silu if kind == "swiglu" else jax.nn.gelu
    h = act(gate.astype(jnp.float32)).astype(dtype) * up
    return jnp.einsum("ecf,efd->ecd", h,
                      _mx_expert_weight(w["down"], quant, 1, dtype, dm_axis=2))


def _sorted_body(params, x, cfg: MoEConfig, quant: QuantConfig, dtype,
                 data_axes=()):
    """Dropless sorted dispatch on one data shard (tokens local).

    Each token is replicated top_k times, rows are sorted by expert id, and
    ``jax.lax.ragged_dot`` runs one grouped GEMM per projection — exactly
    top_k/E of the dense-dispatch FLOPs (mixtral: 4x less; deepseek: 10.7x)
    and no (E, T, D) dispatch buffer (§Perf iteration 9). Expert weights
    arrive FSDP-sharded on d_model; they are quantized shard-side and
    all-gathered as MX bytes (iteration 8 composed).
    """
    b, t, d = x.shape
    e, k = cfg.num_experts, cfg.top_k
    top_w, one_hot, aux = _router(params, x, cfg)
    if data_axes:
        aux = jax.lax.pmean(aux, data_axes)
    top_idx = jnp.argmax(one_hot, axis=-1)  # (B,T,K) recover indices
    n = b * t
    ids = top_idx.reshape(n * k)
    wts = top_w.reshape(n * k).astype(dtype)
    order = jnp.argsort(ids)
    token_of = order // k
    xs = x.reshape(n, d)[token_of].astype(dtype)  # (N*K, D) sorted rows
    group_sizes = jnp.zeros((e,), jnp.int32).at[ids[order]].add(1)

    def gathered(wt, contract_axis, gather_axis):
        """Quantize shard-side, all-gather MX bytes over data on the
        (tensor-coords) d_model dim, dequantize locally."""
        wt = wt.astype(jnp.float32)
        if quant.enabled:
            from repro.core import quantize as _q
            from repro.core.mx_tensor import MXTensor

            tq = _q(wt, quant.fmt, quant.block_size, axis=contract_axis)
            if data_axes:
                non_contract = [i for i in range(3) if i != contract_axis]
                storage_of = {ax: i for i, ax in enumerate(non_contract)}
                storage_of[contract_axis] = 2
                ga = storage_of[gather_axis]
                elems = jax.lax.all_gather(tq.elements, data_axes,
                                           axis=ga, tiled=True)
                scales = jax.lax.all_gather(tq.scales, data_axes,
                                            axis=ga, tiled=True)
                shp = list(wt.shape)
                shp[gather_axis] *= _axes_size(data_axes)
                tq = MXTensor(elems, scales, tq.fmt_name, tq.block_size,
                              contract_axis, tuple(shp))
            return tq.dequantize(dtype)
        if data_axes:
            wt = jax.lax.all_gather(wt, data_axes, axis=gather_axis,
                                    tiled=True)
        return wt.astype(dtype)

    wg = gathered(params["experts"]["gate"], 1, 1)
    wu = gathered(params["experts"]["up"], 1, 1)
    gate = jax.lax.ragged_dot(xs, wg, group_sizes)
    up = jax.lax.ragged_dot(xs, wu, group_sizes)
    act = jax.nn.silu if cfg.ffn_kind == "swiglu" else jax.nn.gelu
    h = act(gate.astype(jnp.float32)).astype(dtype) * up
    wd = gathered(params["experts"]["down"], 1, 2)
    rows = jax.lax.ragged_dot(h, wd, group_sizes)
    rows = rows * wts[order][:, None]
    out = jnp.zeros((n, d), dtype).at[token_of].add(rows)
    return out.reshape(b, t, d), aux


def _axes_size(axes):
    import numpy as np

    from repro.parallel.ctx import current_mesh

    mesh = current_mesh()
    return int(np.prod([mesh.shape[a] for a in axes])) if mesh else 1


def apply_sorted(params, x, cfg: MoEConfig, quant: QuantConfig,
                 compute_dtype=jnp.bfloat16):
    """Dropless sorted-dispatch MoE (ragged_dot grouped GEMMs).

    Under a mesh, runs manually over the data axes (each shard sorts its
    own tokens — results identical to dense dispatch) with the model axis
    left in auto mode so TP/GSPMD still applies inside.
    """
    from jax.sharding import PartitionSpec as P

    from repro.parallel.ctx import current_mesh

    mesh = current_mesh()
    data_axes = tuple(a for a in ("pod", "data")
                      if a in (mesh.axis_names if mesh else ()))
    b = x.shape[0]
    if mesh is None or not data_axes or b % _axes_size(data_axes):
        out, aux = _sorted_body(params, x, cfg, quant, compute_dtype)
        if cfg.num_shared:
            from . import ffn

            out = out + ffn.apply(params["shared"], x, quant, cfg.ffn_kind,
                                  compute_dtype)
        return out, aux

    def body(params, xs):
        return _sorted_body(params, xs, cfg, quant, compute_dtype,
                            data_axes=data_axes)

    pspec = jax.tree_util.tree_map(lambda _: P(), params)
    # d_model dim of expert stacks is FSDP-sharded (manual over data)
    pspec["experts"] = {"gate": P(None, data_axes, None),
                        "up": P(None, data_axes, None),
                        "down": P(None, None, data_axes)}
    if "shared" in params:
        del pspec["shared"]
        params = dict(params)
        shared = params.pop("shared")
    else:
        shared = None
    out, aux = jax.shard_map(
        body, mesh=mesh, axis_names=set(data_axes),
        in_specs=(pspec, P(data_axes, None, None)),
        out_specs=(P(data_axes, None, None), P()),
        check_vma=False)(params, x)
    if shared is not None:
        from . import ffn

        out = out + ffn.apply(shared, x, quant, cfg.ffn_kind, compute_dtype)
    return out, aux


def apply(params, x, cfg: MoEConfig, quant: QuantConfig,
          compute_dtype=jnp.bfloat16):
    """MoE FFN. x: (B, T, D). Returns (out, aux_loss).

    Dispatch mode "sorted" uses the dropless grouped-GEMM path
    (``apply_sorted``); "dense" is the einsum fallback below.

    Dense-dispatch: combine[b,t,e] = sum_k w_k * 1[idx_k == e]; dispatch is
    its 0/1 indicator. Per-shard einsums only — EP sharding turns the
    expert axis contraction into a local compute + one all-reduce that XLA
    merges with the existing output reduction.
    """
    if cfg.dispatch == "sorted":
        return apply_sorted(params, x, cfg, quant, compute_dtype)
    b, t, d = x.shape
    top_w, one_hot, aux = _router(params, x, cfg)
    combine = jnp.einsum("btk,btke->bte", top_w, one_hot)  # (B,T,E)
    dispatch = (combine > 0).astype(compute_dtype)
    from repro.parallel.ctx import maybe_constrain

    xw = x.astype(compute_dtype)
    h_in = jnp.einsum("bte,btd->ebtd", dispatch, xw)
    h_in = h_in.reshape(cfg.num_experts, b * t, d)
    # EP: dispatched activations shard over the expert axis; when the expert
    # count doesn't divide the TP axis (mixtral: 8 experts, 16-way model),
    # the flat token dim absorbs the model axis instead.
    h_in = maybe_constrain(h_in, "model", "tokens_all", None)
    h_out = _expert_ffn(params["experts"], h_in, quant, cfg.ffn_kind,
                        compute_dtype)
    h_out = h_out.reshape(cfg.num_experts, b, t, d)
    out = jnp.einsum("ebtd,bte->btd", h_out, combine.astype(compute_dtype))
    if cfg.num_shared:
        from . import ffn

        out = out + ffn.apply(params["shared"], x, quant, cfg.ffn_kind,
                              compute_dtype)
    return out, aux.astype(jnp.float32)
