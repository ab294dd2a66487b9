"""Weights from a seed, made on the device by one jitted call.

Every leaf is named; its values depend only on the seed, the name and
the shape, so the program's adapter and the plain reference draw the
same tensors without either handing the other anything.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key for any whole number (the seed may exceed 32 bits)."""
    seed = int(seed)
    key = jax.random.PRNGKey(0)
    for word in (seed & 0x7FFFFFFF, (seed >> 31) & 0x7FFFFFFF, seed >> 62):
        key = jax.random.fold_in(key, word)
    return key


def dense_gqa_specs(conf: dict) -> dict:
    """name -> (shape, std) for a dense decoder with grouped-query
    attention and a SwiGLU FFN; layer leaves are stacked on axis 0.
    Norm leaves hold the offset g - 1 of the RMSNorm gain g."""
    d, f = conf["hidden_size"], conf["intermediate_size"]
    h, kvh = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd, n, v = conf["head_dim"], conf["num_hidden_layers"], conf["vocab_size"]
    specs = {
        "embed": ((v, d), 0.02),
        "final_norm": ((d,), 0.1),
        "attn_norm": ((n, d), 0.1),
        "ffn_norm": ((n, d), 0.1),
        "wq": ((n, d, h * hd), d ** -0.5),
        "wk": ((n, d, kvh * hd), d ** -0.5),
        "wv": ((n, d, kvh * hd), d ** -0.5),
        "wo": ((n, h * hd, d), (h * hd) ** -0.5),
        "w_gate": ((n, d, f), d ** -0.5),
        "w_up": ((n, d, f), d ** -0.5),
        "w_down": ((n, f, d), f ** -0.5),
    }
    if not conf["tie_word_embeddings"]:
        specs["head"] = ((d, v), d ** -0.5)
    return specs


def make(specs: dict, seed: int, dtype=jnp.bfloat16,
         shardings: dict = None) -> dict:
    """Draw every leaf of ``specs`` in ``dtype`` in one jitted call; each
    leaf is drawn and cast inside one fusion, so no f32 copy exists.
    ``shardings`` (name -> sharding) places each leaf as it is drawn,
    with the same values (JAX's partitionable threefry)."""
    def draw(key):
        out = {}
        for name, (shape, std) in specs.items():
            k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
            out[name] = (jax.random.normal(k, shape, jnp.float32)
                         * std).astype(dtype)
        return out

    jitted = (jax.jit(draw) if shardings is None
              else jax.jit(draw, out_shardings=shardings))
    return jax.block_until_ready(jitted(seed_key(seed)))
