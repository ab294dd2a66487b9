"""Logical-axis sharding rules: DP/FSDP/TP/EP over the production mesh.

The mesh is (pod, data, model) — see ``launch/mesh.py``. Parameters carry
logical axis names (``nn.common``); the rules below map them to mesh axes
with divisibility-aware fallback:

  * TP  — vocab / d_ff / heads / kv_heads / expert / rnn dims shard over
    ``model`` (Megatron-style tensor parallelism; EP for expert dims),
  * FSDP — the d_model dim of weights shards over (``pod``, ``data``)
    (ZeRO-3-style: params + optimizer state fully sharded; XLA inserts the
    all-gathers and overlaps them with compute),
  * anything that does not divide evenly falls back to replication
    (e.g. MQA's kv_heads=1, mixtral's 8 experts on a 16-way model axis —
    the d_ff dim then picks up the model axis instead).

Activations are sharded via the input specs (batch over (pod, data)) and
XLA sharding propagation; `constraint` offers hand-placed overrides for the
perf iteration loop.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.nn import common as C

FSDP_AXES = ("pod", "data")
TP_AXIS = "model"

# logical axis -> preferred mesh axes, in priority order per tensor dim
PARAM_RULES = {
    C.VOCAB: (TP_AXIS,),
    C.D_FF: (TP_AXIS,),
    C.HEADS: (TP_AXIS,),
    C.KV_HEADS: (TP_AXIS,),
    C.EXPERT: (TP_AXIS,),
    C.RNN: (TP_AXIS,),
    C.KV_LORA: (TP_AXIS,),
    C.D_MODEL: FSDP_AXES,
    C.LAYERS: (),
    C.CONV: (),
    C.STATE: (),
    C.HEAD_DIM: (),
    C.BATCH: ("pod", "data"),
    C.SEQ: (),
}


def make_abstract_mesh(shape, axis_names):
    """Device-free AbstractMesh, for sharding-rule tests and dry-run
    tooling that reason about placement without 512 real devices."""
    from jax.sharding import AbstractMesh

    return AbstractMesh(tuple(shape), tuple(axis_names))


def _mesh_axes_present(mesh: Mesh, axes):
    return tuple(a for a in axes if a in mesh.axis_names)


def spec_for(mesh: Mesh, dims, axes_names) -> P:
    """Build a PartitionSpec for one array given its logical axes."""
    used = set()
    entries = []
    for dim, name in zip(dims, axes_names):
        cand = _mesh_axes_present(mesh, PARAM_RULES.get(name, ()))
        cand = tuple(a for a in cand if a not in used)
        size = int(np.prod([mesh.shape[a] for a in cand])) if cand else 1
        if cand and dim % size == 0 and dim >= size:
            entries.append(cand if len(cand) > 1 else cand[0])
            used.update(cand)
        else:
            entries.append(None)
    return P(*entries)


def tree_shardings(mesh: Mesh, params_or_shapes, axes_tree):
    """NamedSharding tree for a params tree (arrays or ShapeDtypeStructs)."""
    flat_p, treedef = jax.tree_util.tree_flatten(params_or_shapes)
    flat_a = treedef.flatten_up_to(axes_tree)
    out = [
        NamedSharding(mesh, spec_for(mesh, p.shape, a))
        for p, a in zip(flat_p, flat_a)
    ]
    return jax.tree_util.tree_unflatten(treedef, out)


def batch_shardings(mesh: Mesh, batch_shapes):
    """Input batch: leading batch dim over (pod, data), rest replicated."""
    axes = _mesh_axes_present(mesh, ("pod", "data"))

    def one(s):
        size = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
        if s.shape and s.shape[0] % size == 0 and size > 1:
            return NamedSharding(
                mesh, P(axes if len(axes) > 1 else axes[0],
                        *([None] * (len(s.shape) - 1))))
        return NamedSharding(mesh, P(*([None] * len(s.shape))))

    return jax.tree_util.tree_map(one, batch_shapes)


def cache_shardings(mesh: Mesh, cache_shapes, batch_size: int):
    """KV-cache shardings: shard the *batch* dim over (pod, data).

    Stacked group caches carry a leading layers dim, so the batch dim is
    located by size (first dim == batch_size), not by position — sharding
    dim 0 blindly replicates the cache and forces an all-gather of the
    entire KV state every decode step (§Perf iteration 11, deepseek
    decode_32k: a 3.4 TB/step gather).
    """
    axes = _mesh_axes_present(mesh, ("pod", "data"))
    size = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1

    def one(s):
        entries = [None] * len(s.shape)
        if axes and size > 1:
            for i, d in enumerate(s.shape):
                if d == batch_size and d % size == 0:
                    entries[i] = axes if len(axes) > 1 else axes[0]
                    break
        return NamedSharding(mesh, P(*entries))

    return jax.tree_util.tree_map(one, cache_shapes)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


def serve_param_specs(params, axis: str = TP_AXIS):
    """PartitionSpec tree for the KV-head-sharded serve step's params.

    Attention projections are recognized structurally (a dict carrying
    all of wq/wk/wv/wo — ``attention.init``'s output, whether stacked
    under a scanned group or not): wq/wk/wv shard their *output* (heads)
    dim on ``axis`` — heads are laid out KV-major, so a contiguous
    column shard is exactly the device's KV-head slice — while ``wo``
    and every other parameter stay replicated.

    This deliberately deviates from ``PARAM_RULES`` (which would also
    shard ``wo``'s heads input dim): a row-sharded ``wo`` needs a psum
    that *splits* the f32 contraction across devices, and a split
    reduction is not bit-identical to the single-device matmul. The
    serve step instead all-gathers the (small) attention output over
    the KV-head axis and runs the replicated ``wo`` — the token-identity
    guarantee the engine tests pin down. Everything outside attention is
    replicated because it is already per-token work the engine runs in
    lockstep on each device.

    The megakernel's packed params (``model.pack_megakernel_params``)
    keep the ``wq/wk/wv/wo`` key structure with a leading stacked-layer
    axis, so this walk covers them too: head columns stay the last dim
    of each stacked leaf, the layer axis lands on a leading ``None``.
    ``megakernel_param_specs`` below pins that down for the sharded-
    megakernel ROADMAP rung.
    """
    def shard_last(a):
        return P(*([None] * (a.ndim - 1)), axis)

    def rep(node):
        return jax.tree_util.tree_map(lambda a: P(), node)

    def walk(node):
        if isinstance(node, dict):
            if {"wq", "wk", "wv", "wo"} <= set(node):
                return {name: (jax.tree_util.tree_map(shard_last, sub)
                               if name in ("wq", "wk", "wv") else rep(sub))
                        for name, sub in node.items()}
            return {key: walk(val) for key, val in node.items()}
        if isinstance(node, (list, tuple)):
            return type(node)(walk(v) for v in node)
        return rep(node)

    return walk(params)


def megakernel_param_specs(packed, axis: str = TP_AXIS):
    """PartitionSpec tree for a ``pack_megakernel_params`` tree.

    Groundwork for running the layer-fused megakernel under the serve
    engine's KV-head ``shard_map`` (ROADMAP rung — the engine currently
    falls back to the per-layer ragged step on a >1-way mesh): the
    stacked ``(L, d_in, heads*head_dim)`` q/k/v leaves shard their head
    columns on ``axis`` exactly like the per-layer specs, layer axis
    replicated, everything else replicated. Delegates to
    ``serve_param_specs``'s structural walk — the packed dict keeps the
    wq/wk/wv/wo keys precisely so that recognition still fires — and
    exists as a named entry point so tests can pin the stacked layout's
    placement independently of the per-layer one.
    """
    return serve_param_specs(packed, axis)


def constraint(x, mesh: Mesh, *spec_entries):
    """Hand-placed activation sharding constraint (perf-iteration hook)."""
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec_entries)))
