"""Four-chip cells: the serving configuration a cell's files give the
engine, and the sharded engine served through a whole run on four
virtual CPU devices. The one-chip cells keep the ServeConfig and
probe sizes they had before meshes existed."""
import json
import os
import subprocess
import sys
import textwrap

import _bench_path  # noqa: F401
import pytest

import harness

SEED = 2**34 + 77


@pytest.mark.parametrize("cell", ["mistral-decode", "phi4-decode"])
def test_one_chip_cells_keep_their_serve_config_and_probes(cell):
    from repro.serve import ServeConfig

    conf = harness.cell_spec(cell)["conf"]
    srv = conf["serving"]
    kw = harness.serve_kwargs(conf, SEED)
    assert kw == {"max_seq": srv["max_seq"], "max_slots": srv["max_slots"],
                  "seed": SEED & 0x7FFFFFFF}
    assert harness.mesh_size(kw) == 1
    assert ServeConfig(num_pages=999, **kw) == ServeConfig(
        num_pages=999, max_seq=srv["max_seq"], max_slots=srv["max_slots"],
        seed=SEED & 0x7FFFFFFF)
    assert harness.probe_pages(srv["max_seq"], ServeConfig.page_size) \
        == harness.PROBE_PAGES == 320


def test_serving_mesh_passes_to_the_serve_config():
    conf = {"serving": {"max_seq": 12544, "max_slots": 16, "mesh": [1, 4]}}
    kw = harness.serve_kwargs(conf, SEED)
    assert kw == {"max_seq": 12544, "max_slots": 16, "mesh_shape": (1, 4),
                  "seed": SEED & 0x7FFFFFFF}
    assert harness.mesh_size(kw) == 4
    # the smaller probe pool holds one whole sequence of max_seq
    assert harness.probe_pages(kw["max_seq"], 16) == 784


MESH_CASES = textwrap.dedent("""
    import json, sys
    sys.path.insert(0, "tests/bench")
    import jax, numpy as np
    import _bench_small, harness

    out = {}
    spec = _bench_small.small_spec("phi4-mini-3.8b", "decode", (1, 4))
    conf = spec["conf"]
    kept = []

    def keep(engine):
        kept.append(engine)

    # a sound run, its engine kept for what follows
    r = harness.run_cell(spec["cell"]["name"], 2**36 + 1, 1.5, False,
                         require_tpu=False, spec=spec, patch=keep)
    out["sound"] = [r["correct"], r["check"]["mean_logit_gap"]]
    engine = kept[0]
    out["mesh"] = dict(engine.mesh.shape)
    out["tp"] = engine.tp
    # the weights are drawn in the engine's layout, with the values of
    # the one-device draw that the reference makes
    program, _ = harness.family(conf)
    one = program.program_params(conf, 2**36 + 1)
    out["same_weights"] = all(jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(
            lambda a, b: bool((np.asarray(a) == np.asarray(b)).all())
            and a.sharding == b.sharding, engine.params,
            program.program_params(conf, 2**36 + 1, engine.mesh)))) and all(
        jax.tree_util.tree_leaves(jax.tree_util.tree_map(
            lambda a, b: bool((np.asarray(a) == np.asarray(b)).all()),
            one, engine.params)))
    # the step's memory on each device, with the pool laid out as the
    # engine lays it: a page holds a quarter of its KV heads there
    step_fn, slots = engine._ragged_fn, conf["serving"]["max_slots"]
    args = harness._step_args(engine)
    probes = [harness.step_memory(step_fn, args, engine.cfg, slots, n, 16,
                                  mesh=engine.mesh,
                                  pool_specs=engine._pool_specs)
              for n in (64, 128)]
    out["bytes_a_page"] = (probes[1]["argument"]
                           - probes[0]["argument"]) / 64
    out["refused"] = []
    for chips, mesh in ((1, [1, 4]), (4, None)):
        bad = {k: dict(v) if isinstance(v, dict) else v
               for k, v in spec.items()}
        bad["cell"]["chips"] = chips
        bad["conf"] = dict(conf, serving=dict(conf["serving"]))
        if mesh is None:
            del bad["conf"]["serving"]["mesh"]
        try:
            harness.run_cell("bad", 1, 1.0, False, require_tpu=False,
                             spec=bad)
        except SystemExit as e:
            out["refused"].append(str(e))

    def no_exchange(engine):
        # the step's all-gather of the attention output left out: each
        # chip puts its own heads' output in every other chip's place
        def own(x, axis_name, *, axis=0, tiled=False, **kw):
            return jax.numpy.concatenate([x] * engine.tp, axis=axis)

        jax.lax.all_gather = own

    r = harness.run_cell(spec["cell"]["name"], 2**36 + 1, 1.5, False,
                         require_tpu=False, spec=spec, patch=no_exchange)
    out["no_exchange"] = [r["correct"], r["check"]["mean_logit_gap"]]
    print(json.dumps(out))
""")


def test_sharded_engine_on_four_devices_and_refused_meshes():
    """One subprocess (the device count is fixed at JAX's start): a sound
    run of the four-chip spec serves on the (1, 4) mesh from weights
    drawn in its layout and comes out correct, its step is sized per
    device, cells whose chips and mesh disagree are refused before any
    set-up, and a whole run of the same seed with the exchange between
    chips left out comes out not correct."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(harness.ROOT / "src"),
                    os.environ.get("PYTHONPATH", "")]))
    run = subprocess.run([sys.executable, "-c", MESH_CASES],
                         cwd=harness.ROOT, env=env, capture_output=True,
                         text=True, timeout=300)
    assert run.returncode == 0, run.stderr[-4000:]
    out = json.loads(run.stdout.strip().splitlines()[-1])
    assert out["mesh"] == {"data": 1, "model": 4} and out["tp"] == 4
    assert out["same_weights"]
    # tiny conf: 2 layers x 8 KV heads x 32 dims x (1 + 1/32) bytes, K and
    # V, 16 tokens a page, over 4 devices
    assert out["bytes_a_page"] == 2 * 2 * 8 * 32 * 33 / 32 * 16 / 4
    assert len(out["refused"]) == 2
    assert all("asks for" in r and "spans" in r for r in out["refused"])
    # the sound run passes the limit that the run with the exchange
    # between chips left out fails
    correct, sound = out["sound"]
    assert correct is True
    correct, gap = out["no_exchange"]
    assert correct is False
    assert sound["value"] <= sound["limit"] == gap["limit"] < gap["value"]
