"""Sharded multi-device serving: KV-head-parallel ragged step over a mesh.

The contract under test is *token identity*: the engine on a (1, M)
(data, model) mesh — page-pool K/V leaves and wq/wk/wv head columns
sharded along the KV-head axis, wo and everything else replicated, one
all-gather of the attention output per step — must emit per-request
token streams bit-identical to the single-device engine, under churn,
preemption, speculative decoding, and tiered background repack.

Multi-device cases run in a subprocess (device count is locked at first
jax init and the main pytest process must keep 1 device — same pattern
as test_distributed.py). Fallback/validation paths run in-process: they
never build a mesh.
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import MXFP8
from repro.nn import BlockDef, ModelConfig, model
from repro.serve import ContinuousBatchingEngine, ServeConfig


def _cfg(num_heads=4, num_kv_heads=2):
    return ModelConfig(
        name="t", family="dense", d_model=64, vocab_size=128,
        pattern=(BlockDef("attn"),), num_groups=1, num_heads=num_heads,
        num_kv_heads=num_kv_heads, head_dim=16, d_ff=128,
        quant=MXFP8.replace(block_size=16, quantize_acts=False,
                            quantize_kv_cache=True))


# ---------------------------------------------------------------------------
# fallback + validation (no mesh is ever built: runs on 1 device)
# ---------------------------------------------------------------------------


def test_mesh_1x1_falls_back_to_unsharded():
    cfg = _cfg()
    params, _ = model.init(jax.random.PRNGKey(0), cfg)
    eng = ContinuousBatchingEngine(params, cfg, ServeConfig(
        max_seq=24, max_slots=2, page_size=4, mesh_shape=(1, 1)))
    assert eng.mesh is None and eng.tp == 1
    assert eng.cache_stats()["kv_head_shards"] == 1


def test_mesh_requires_ragged_step_or_falls_back():
    """A config the ragged step rejects (einsum decode kernel) must run
    unsharded rather than die — the same fallback ladder the ragged step
    itself uses."""
    cfg = _cfg()
    params, _ = model.init(jax.random.PRNGKey(0), cfg)
    eng = ContinuousBatchingEngine(params, cfg, ServeConfig(
        max_seq=24, max_slots=2, page_size=4, decode_kernel="einsum",
        mesh_shape=(1, 2)))
    assert not eng.ragged and eng.mesh is None
    out = eng.generate(np.arange(1, 5, dtype=np.int32)[None], 4)
    assert out.shape == (1, 8)


def test_mesh_validation_errors():
    cfg = _cfg(num_kv_heads=2)
    params, _ = model.init(jax.random.PRNGKey(0), cfg)
    base = dict(max_seq=24, max_slots=2, page_size=4)
    # KV heads must divide over the model axis
    with pytest.raises(ValueError, match="divisible"):
        ContinuousBatchingEngine(params, cfg, ServeConfig(
            mesh_shape=(1, 3), **base))
    # data-parallel serving is a router-level follow-on, not a mesh dim
    with pytest.raises(ValueError, match="data"):
        ContinuousBatchingEngine(params, cfg, ServeConfig(
            mesh_shape=(2, 1), **base))
    with pytest.raises(ValueError, match="mesh_shape"):
        ContinuousBatchingEngine(params, cfg, ServeConfig(
            mesh_shape=(1, 0), **base))
    # divisible but more devices than this 1-device process has
    with pytest.raises(ValueError, match="devices"):
        ContinuousBatchingEngine(params, cfg, ServeConfig(
            mesh_shape=(1, 2), **base))


def test_pool_specs_shard_kv_head_axis_only():
    from jax.sharding import PartitionSpec as P

    from repro.serve import kv_cache
    cfg = _cfg()
    cache = model.init_paged_cache(cfg, 2, 8, 4)
    specs = kv_cache.pool_specs(cache, "model")
    flat_c = jax.tree_util.tree_leaves(cache)
    flat_s, treedef = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_c) == len(flat_s)
    for leaf, spec in zip(flat_c, flat_s):
        # KVH is always ndim-3 of a pool leaf (KV-head-major pages); NP,
        # PS and the storage dim stay unsharded so page gathers remain
        # shard-local
        assert spec[leaf.ndim - 3] == "model"
        assert all(e is None for i, e in enumerate(spec)
                   if i != leaf.ndim - 3)


def test_serve_param_specs_shard_qkv_replicate_wo():
    from jax.sharding import PartitionSpec as P

    from repro.parallel.sharding import serve_param_specs
    cfg = _cfg()
    params, _ = model.init(jax.random.PRNGKey(0), cfg)
    specs = serve_param_specs(params)

    def walk(p, s, inside=None):
        if isinstance(p, dict):
            for key, val in p.items():
                walk(val, s[key],
                     key if key in ("wq", "wk", "wv", "wo") else inside)
        elif isinstance(p, (list, tuple)):
            for pv, sv in zip(p, s):
                walk(pv, sv, inside)
        else:
            if inside in ("wq", "wk", "wv"):
                assert s[p.ndim - 1] == "model", (inside, s)
            else:
                # wo + everything outside attention: replicated
                assert all(e is None for e in s), (inside, s)

    walk(params, specs)


# ---------------------------------------------------------------------------
# multi-device: token identity + structure (subprocess, 8 host devices)
# ---------------------------------------------------------------------------

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np
from repro.core import MXFP8
from repro.nn import BlockDef, ModelConfig, model
from repro.serve import ContinuousBatchingEngine, ServeConfig
from repro.serve.engine import TierPolicy

assert len(jax.devices()) == 8
cfg = ModelConfig(
    name="t", family="dense", d_model=64, vocab_size=128,
    pattern=(BlockDef("attn"),), num_groups=1, num_heads=8,
    num_kv_heads=8, head_dim=16, d_ff=128,
    quant=MXFP8.replace(block_size=16, quantize_acts=False,
                        quantize_kv_cache=True))
rng = np.random.default_rng(3)
reqs = [(rng.integers(0, 128, (s,)).astype(np.int32), m)
        for s, m in [(4, 12), (4, 12), (7, 5), (3, 8), (12, 6)]]

SCENARIOS = {
    # pool sized to force preemption, shared prefixes in play
    "churn": dict(max_seq=24, max_slots=2, page_size=4, num_pages=7,
                  prefix_cache=True),
    # speculative decoding: verify windows ride the sharded kernel
    "spec": dict(max_seq=24, max_slots=2, page_size=4, num_pages=7,
                 prefix_cache=True, spec_decode=True, num_draft_tokens=2),
    # tiered repack: demotions run as shard-local sharded dispatches
    "tiered": dict(max_seq=48, max_slots=2, page_size=8, prefill_chunk=8,
                   num_pages=14, tiered=True,
                   tier_policy=TierPolicy(hot_steps=2, cold_steps=4,
                                          repack_pages_per_step=2)),
}

for name, kw in SCENARIOS.items():
    outs, stats = {}, {}
    for mesh in (None, (1, 8)):
        params, _ = model.init(jax.random.PRNGKey(0), cfg)
        eng = ContinuousBatchingEngine(params, cfg, ServeConfig(
            mesh_shape=mesh, **kw))
        if mesh is not None:
            assert eng.mesh is not None, "unexpected fallback to unsharded"
            assert eng.tp == 8
        ids = [eng.submit(p, m) for p, m in reqs]
        out = eng.run()
        outs[mesh] = [out[i] for i in ids]
        stats[mesh] = eng.cache_stats()
    for a, b in zip(outs[None], outs[(1, 8)]):
        np.testing.assert_array_equal(a, b)
    s = stats[(1, 8)]
    assert s["kv_head_shards"] == 8
    if name == "churn":
        assert s["preemptions"] >= 1, "pool must force a swap"
    if name == "tiered":
        assert s["repacked_pages"] >= 1, "policy must demote some pages"
        assert s["repacked_pages"] == stats[None]["repacked_pages"]
    print(name, "identical;",
          "mixed", s["mixed_steps"], "dpm", s["dispatches_per_mixed_step"])

# structural: the sharded step's jaxpr still contains exactly ONE
# pallas_call (one attention layer here) — shard_map partitions the
# kernel grid along KV heads, it must not replicate or split the call
params, _ = model.init(jax.random.PRNGKey(0), cfg)
eng = ContinuousBatchingEngine(params, cfg, ServeConfig(
    max_seq=24, max_slots=2, page_size=4, prefill_chunk=4,
    mesh_shape=(1, 8)))
assert eng.mesh is not None
captured = {}
orig = eng._ragged_fn

def spy(*a, **k):
    captured.setdefault("args", a)
    return orig(*a, **k)

eng._ragged_fn = spy
eng.submit(np.arange(5, dtype=np.int32), 3)
eng.run()
jaxpr = jax.make_jaxpr(orig)(*captured["args"])

def _subjaxprs(prms):
    for v in prms.values():
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

def _all_eqns(j):
    for eqn in j.eqns:
        yield eqn
        for sub in _subjaxprs(eqn.params):
            yield from _all_eqns(sub)

names = [e.primitive.name for e in _all_eqns(jaxpr.jaxpr)]
assert names.count("pallas_call") == 1, names.count("pallas_call")
assert any(n in ("shard_map", "smap") for n in names), sorted(set(names))
assert names.count("all_gather") == 1, names.count("all_gather")
print("SHARDED_SERVE_OK")
"""


@pytest.mark.slow
def test_sharded_engine_token_identical_and_one_kernel_per_shard():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "SHARDED_SERVE_OK" in proc.stdout
