"""Sharded serving: KV-head-parallel ragged step over a (1, M) mesh.

Sharding the serve engine over the ``model`` mesh axis splits the page
pool's K/V/scale leaves and the wq/wk/wv head columns across devices;
wo and everything downstream stay replicated behind one all-gather of
the (small) attention output. Two axes:

  * **modeled per-device HBM bytes (gated >= 1.5x)**: at an 8B-class
    serving operating point (32 slots x 32k context resident — the
    regime the KV-head split exists for), the per-device footprint is
    ``weights - (M-1)/M * qkv + pool / M`` vs the single device's
    ``weights + pool``. The pool dominates at long context, so the
    capacity ratio approaches M; the gate pins it >= 1.5x at M = 8.
  * **measured (subprocess, exact)**: a live engine on a (1, 4) host
    mesh must (a) emit token streams bit-identical to the unsharded
    engine over a churn + chunked-prefill + spec workload, (b) keep the
    one-dispatch ragged contract (``dispatches_per_mixed_step == 1``),
    and (c) hold ONE jitted trace across every batch composition the
    run sees (``_ragged_fn._cache_size() == 1``) — sharding must not
    fracture the trace cache. Runs in a subprocess because the host
    device count is fixed at first jax import.

Wall-clock is reported but NOT gated: on a forced 4-device host CPU the
"devices" share one socket and the interpreter-mode Pallas kernels
dominate, so the bandwidth win is invisible (same reasoning as
``ragged_step.py``).

  PYTHONPATH=src python benchmarks/sharded_step.py [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys

try:  # package mode (python -m benchmarks.run)
    from . import common
except ImportError:  # script mode
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                           / "src"))
    import common

GATE = 1.5
MESH = 4  # live subprocess mesh (1, MESH)


# ---------------------------------------------------------------------------
# modeled per-device HBM footprint (8B-class long-context serving point)
# ---------------------------------------------------------------------------

OP_POINT = dict(
    layers=32, d_model=4096, heads=32, kv_heads=8, head_dim=128,
    weight_bytes=8.0e9,   # 8B-class, fp8 weights + E8M0 scales
    slots=32, context=32 * 1024,  # ~1M resident tokens
    bsz=32, elem_bits=8, shards=8,
)


def modeled_device_bytes(shards, *, layers, d_model, heads, kv_heads,
                         head_dim, weight_bytes, slots, context, bsz,
                         elem_bits):
    """Resident HBM bytes on ONE device at the operating point.

    Weights are replicated except wq/wk/wv, whose head-column shards
    live only on their device; the K/V page pool (elements + E8M0
    scales) shards its KV-head axis. Page tables and scheduler rows are
    metadata (KB) and ignored.
    """
    qkv = layers * d_model * (heads + 2 * kv_heads) * head_dim \
        * (elem_bits / 8 + 1.0 / bsz)
    pool = layers * slots * context * kv_heads * head_dim * 2 \
        * (elem_bits / 8 + 1.0 / bsz)
    return (weight_bytes - qkv * (shards - 1) / shards) + pool / shards


# ---------------------------------------------------------------------------
# measured: live sharded engine in a subprocess (own jax device count)
# ---------------------------------------------------------------------------

_CHILD = r"""
import json, os, sys, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%(mesh)d"
import jax, numpy as np
from repro.core import MXFP8
from repro.nn import BlockDef, ModelConfig, model
from repro.serve import ContinuousBatchingEngine, ServeConfig

smoke = %(smoke)r
cfg = ModelConfig(
    name="bench", family="dense", d_model=64, vocab_size=128,
    pattern=(BlockDef("attn"),), num_groups=1, num_heads=8,
    num_kv_heads=%(mesh)d, head_dim=16, d_ff=128,
    quant=MXFP8.replace(block_size=16, quantize_acts=False,
                        quantize_kv_cache=True))
params, _ = model.init(jax.random.PRNGKey(0), cfg)
rng = np.random.default_rng(11)
long_p = 16 if smoke else 40
m_short = 6 if smoke else 16
# short decoders + a long chunked prompt + spec verify: every batch
# composition the ragged step knows rides through one trace
reqs = [(rng.integers(0, 128, (4,)).astype(np.int32), m_short),
        (rng.integers(0, 128, (4,)).astype(np.int32), m_short),
        (rng.integers(0, 128, (long_p,)).astype(np.int32), 4)]
res = {}
for mesh in (None, (1, %(mesh)d)):
    eng = ContinuousBatchingEngine(params, cfg, ServeConfig(
        mesh_shape=mesh, max_seq=64, max_slots=3, page_size=4,
        prefill_chunk=4, spec_decode=True, num_draft_tokens=2))
    assert (eng.mesh is not None) == (mesh is not None), "mesh fallback"
    ids = [eng.submit(p, m) for p, m in reqs]
    t0 = time.perf_counter()
    streams = eng.run()
    wall = time.perf_counter() - t0
    key = "sharded" if mesh else "single"
    st = eng.cache_stats()
    res[key] = dict(
        wall_s=wall, kv_head_shards=st["kv_head_shards"],
        mixed_steps=st["mixed_steps"],
        dispatches_per_mixed_step=st["dispatches_per_mixed_step"],
        traces=eng._ragged_fn._cache_size(),
        streams=[np.asarray(streams[i]).tolist() for i in ids])
print("RESULT " + json.dumps(res))
"""


def run_child(smoke):
    src = pathlib.Path(__file__).resolve().parent.parent / "src"
    env = dict(os.environ)
    env["PYTHONPATH"] = str(src)
    env.pop("XLA_FLAGS", None)
    # the child models a mesh on host devices; on a chip host it must
    # never contend with its parent for the accelerator
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD % dict(mesh=MESH, smoke=smoke)],
        env=env, capture_output=True, text=True, timeout=900)
    if proc.returncode != 0:
        raise RuntimeError(f"sharded child failed:\n{proc.stderr[-3000:]}")
    line = next(l for l in proc.stdout.splitlines()
                if l.startswith("RESULT "))
    return json.loads(line[len("RESULT "):])


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="short workload for CI")
    args = ap.parse_args(argv)

    unsharded = modeled_device_bytes(
        1, **{k: v for k, v in OP_POINT.items() if k != "shards"})
    per_dev = modeled_device_bytes(
        OP_POINT["shards"],
        **{k: v for k, v in OP_POINT.items() if k != "shards"})
    capacity_ratio = unsharded / per_dev

    res = run_child(args.smoke)
    identical = res["single"]["streams"] == res["sharded"]["streams"]
    sh = res["sharded"]
    one_dispatch = (sh["mixed_steps"] >= 1
                    and sh["dispatches_per_mixed_step"] == 1.0)
    one_trace = sh["traces"] == 1
    for key in ("single", "sharded"):
        st = res[key]
        common.emit(
            f"sharded_step/{key}", st["wall_s"] * 1e6,
            f"{st['kv_head_shards']} shards, {st['traces']} traces, "
            f"per-mixed {st['dispatches_per_mixed_step']:.2f}")

    ok = (identical and one_dispatch and one_trace
          and sh["kv_head_shards"] == MESH and capacity_ratio >= GATE)
    common.emit_json("sharded_step", {
        "op_point": OP_POINT,
        "modeled_device_bytes": {"unsharded": unsharded,
                                 "per_device": per_dev,
                                 "ratio": capacity_ratio},
        "mesh": [1, MESH],
        "token_identical": identical,
        "traces": {k: res[k]["traces"] for k in res},
        "dispatches_per_mixed_step": {
            k: res[k]["dispatches_per_mixed_step"] for k in res},
        "wall_s": {k: res[k]["wall_s"] for k in res},
    })
    print(f"\nsharded ({1},{MESH}): token-identical={identical}, "
          f"{sh['traces']} trace(s), {sh['dispatches_per_mixed_step']:.2f} "
          f"dispatches per mixed step; modeled per-device HBM "
          f"{unsharded / 1e9:.1f} -> {per_dev / 1e9:.1f} GB at "
          f"{OP_POINT['shards']} shards ({capacity_ratio:.2f}x): "
          f"{'PASS' if ok else 'FAIL'} (gates: identity + one trace + "
          f"one dispatch per mixed step + >= {GATE}x capacity; "
          f"wall-clock reported ungated, see module docstring)")
    if not ok:
        raise SystemExit(1)
    return capacity_ratio


def run():
    main([])


if __name__ == "__main__":
    main()
