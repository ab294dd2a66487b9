"""The program side of a dense GQA decoder configuration: the served
model config and the weights, in the program's own layout."""
from __future__ import annotations

import functools
import operator

import weights

# program parameter path -> weight name (``weights.dense_gqa_specs``)
_LEAVES = {
    ("embedding", "embed"): "embed",
    ("embedding", "head"): "head",
    ("final_norm", "scale"): "final_norm",
    ("groups", "block0", "norm_mixer", "scale"): "attn_norm",
    ("groups", "block0", "norm_ffn", "scale"): "ffn_norm",
    ("groups", "block0", "mixer", "wq", "w"): "wq",
    ("groups", "block0", "mixer", "wk", "w"): "wk",
    ("groups", "block0", "mixer", "wv", "w"): "wv",
    ("groups", "block0", "mixer", "wo", "w"): "wo",
    ("groups", "block0", "ffn", "gate", "w"): "w_gate",
    ("groups", "block0", "ffn", "up", "w"): "w_up",
    ("groups", "block0", "ffn", "down", "w"): "w_down",
}


def program_config(conf: dict):
    """The program's ModelConfig for ``conf``, checked against it."""
    from repro.launch.serve import serving_config

    srv = conf["serving"]
    if srv["weights"] != "mxfp8" or srv["kv_cache"] != "mxfp8":
        raise ValueError("dense_gqa serves MXFP8 weights and KV cache")
    cfg = serving_config(conf["program_arch"], quant="mxfp8",
                         quantize_kv=True)
    cfg = cfg.replace(
        d_model=conf["hidden_size"], d_ff=conf["intermediate_size"],
        num_groups=conf["num_hidden_layers"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["head_dim"], vocab_size=conf["vocab_size"],
        rope_theta=conf["rope_theta"], norm_eps=conf["rms_norm_eps"],
        tied_embeddings=conf["tie_word_embeddings"],
        quant=cfg.quant.replace(block_size=srv["weight_block"]))
    want = {"ffn_kind": "swiglu", "attn_softcap": None,
            "logit_softcap": None, "post_norms": False,
            "scale_embeds_by_sqrt_dim": False, "num_codebooks": 1,
            "prologue": (), "epilogue": (), "compute_dtype": "bfloat16"}
    got = {k: getattr(cfg, k) for k in want}
    got["compute_dtype"] = getattr(cfg.compute_dtype, "__name__",
                                   str(cfg.compute_dtype))
    if got != want or len(cfg.pattern) != 1 or cfg.pattern[0].mixer != \
            "attn" or cfg.pattern[0].window is not None:
        raise ValueError(f"{conf['program_arch']} is not a plain dense "
                         f"GQA decoder: {got}, {cfg.pattern}")
    if conf.get("partial_rotary_factor", 1.0) != 1.0 or conf.get(
            "rope_scaling") is not None:
        raise ValueError("the program applies plain RoPE to every dim")
    if conf.get("attention_bias") or conf.get("mlp_bias"):
        raise ValueError("the program's projections have no bias")
    if conf.get("sliding_window", srv["max_seq"]) < srv["max_seq"]:
        raise ValueError("the program attends to every earlier position")
    return cfg


def _tree(flat: dict) -> dict:
    """name -> leaf, moved into the program's parameter tree."""
    flat = dict(flat)
    tree = {}
    for path, name in _LEAVES.items():
        if name not in flat:
            continue
        node = tree
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = flat.pop(name)
    if flat:
        raise ValueError(f"weights with no place in the program: {flat}")
    return tree


def program_params(conf: dict, seed: int, mesh=None):
    """The seed's weights (``weights.make``) in the program's tree; on a
    ``mesh``, drawn straight into the sharded serving engine's layout
    (``serve_param_specs``), so that placing them copies nothing."""
    specs = weights.dense_gqa_specs(conf)
    shardings = None
    if mesh is not None:
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding

        from repro.parallel.sharding import serve_param_specs

        pspecs = serve_param_specs(_tree({
            n: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
            for n, (shape, _) in specs.items()}))
        shardings = {name: NamedSharding(mesh, functools.reduce(
            operator.getitem, path, pspecs))
            for path, name in _LEAVES.items() if name in specs}
    return _tree(weights.make(specs, seed, shardings=shardings))


def check_layout(conf: dict, cfg, tree) -> None:
    """Fail unless ``tree`` has the program's own parameter layout."""
    import jax

    from repro.nn import model

    want = jax.eval_shape(lambda k: model.init(k, cfg)[0],
                          jax.random.PRNGKey(0))
    shape = lambda t: jax.tree_util.tree_map(lambda x: tuple(x.shape), t)
    if shape(want) != shape(tree):
        raise ValueError(f"weight layout {shape(tree)} is not the "
                         f"program's {shape(want)}")
