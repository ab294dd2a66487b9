"""A cell of the benchmark cut to a size that the CPU runs in seconds,
for rehearsals of the harness through the real engine."""
import copy
import math

import _bench_path  # noqa: F401

import harness

# mean logit gap at this size, by configuration, with room above the
# served tokens' readings and below the control's (CPU, ten seeds served
# to completion): mistral-7b-l16 reads 0.0011-0.0045, its fp8-activation
# control 0.016-0.031; phi4-mini-3.8b reads 0.00013-0.00088, its control
# 0.0044-0.0106
SMALL_LIMIT = {"phi4-mini-3.8b": 0.002, "mistral-7b-l16": 0.009}
# on the (1, 4) mesh (16/8 heads, four CPU devices, twelve whole 1.5 s
# runs of the decode mix): phi4-mini-3.8b reads 0-0.0019; with the
# all-gather before wo left out 0.59-0.68 (three seeds). The control
# reads 0.0040-0.0121 there, too near to set a limit by, so this one is
# set against the missing exchange, the fault that only a mesh can have
SMALL_MESH_LIMIT = {"phi4-mini-3.8b": 0.01}


def small_spec(config: str, mix: str, mesh: tuple = None) -> dict:
    """The configuration and mix cut to the CPU, as a cell that reports
    every metric the benchmark names; with ``mesh``, served on that
    ``(data, model)`` mesh, with 2 KV heads a device."""
    bench = harness.load_benchmark()
    chips = math.prod(mesh or (1,))
    spec = {"cell": {"name": f"{config}.{mix}", "config": config,
                     "traffic": mix, "chips": chips},
            "conf": copy.deepcopy(harness.load_config(config)),
            "mix": harness.loadgen.load_mix(mix),
            "end_to_end": bench["end_to_end"],
            "per_layer": bench["per_layer"]}
    conf = spec["conf"]
    conf.update(hidden_size=128, intermediate_size=256, num_hidden_layers=2,
                num_attention_heads=4 * chips, num_key_value_heads=2 * chips,
                head_dim=32, vocab_size=512)
    conf["serving"].update(max_slots=4, max_seq=256, num_pages=64)
    conf["check"] = {"mean_logit_gap": SMALL_LIMIT[config]}
    if mesh:
        conf["serving"]["mesh"] = list(mesh)
        conf["check"]["mean_logit_gap"] = SMALL_MESH_LIMIT[config]
    mix = copy.deepcopy(spec["mix"])
    if mix["loop"] == "open":
        mix.update(rate_per_s=3.0, preroll_s=0.5)
        mix["system_prompts"].update(count=2, min=32, max=64)
        mix["prompt"].update(median=16, min=8, max=32)
        mix["output"].update(median=8, min=4, max=16)
    else:
        mix.update(concurrency=4, requests=8)
        mix["prompt"].update(min=8, max=32)
        mix["output"].update(min=16, max=64)
    return dict(spec, conf=conf, mix=mix)


def rehearse(config: str, mix: str, seed: int, seconds: float,
             trace: bool = False, **kw) -> dict:
    spec = small_spec(config, mix)
    return harness.run_cell(spec["cell"]["name"], seed, seconds, trace,
                            require_tpu=False, spec=spec, **kw)


def serve_to_completion(config: str, mix: str, seed: int, n: int = 4,
                        new: int = 24):
    """Serve the first ``n`` requests of the cell's traffic, at most
    ``new`` tokens each, with ``engine.run()``, untimed:
    (conf, {id: request}, {id: tokens})."""
    import numpy as np

    from repro.launch.serve import build_engine
    from repro.serve import ServeConfig

    spec = small_spec(config, mix)
    conf, srv = spec["conf"], spec["conf"]["serving"]
    program, _ = harness.family(conf)
    engine = build_engine(program.program_config(conf), ServeConfig(
        max_seq=srv["max_seq"], max_slots=srv["max_slots"],
        num_pages=srv["num_pages"]), program.program_params(conf, seed),
        "continuous")
    reqs = harness.loadgen.generate(spec["mix"], seed, 2.0,
                                    conf["vocab_size"])[:n]
    ids = {engine.submit(r.prompt, min(r.max_new, new)): r for r in reqs}
    out = engine.run()
    return conf, ids, {i: np.asarray(out[i][len(r.prompt):], np.int32)
                       for i, r in ids.items()}
