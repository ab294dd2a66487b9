"""Ahead-of-time compiles of the main-path kernels for a TPU v5e.

Interpret mode runs everything the CPU can express; the TPU compiler
also enforces tiling (the last two dims of every block), which scalar
stores it lowers, and what fits in VMEM. These tests compile the serving
kernels at phi4-mini-3.8b widths for a described ``v5e:2x2`` chip — no
chip attached, nothing runs — so a refusal shows up here, not on the chip.

The topology is described inside a module-scoped fixture (never at
import time): only the worker that runs this file loads the TPU
compiler, and where it cannot be described the tests skip.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.formats import get_format
from repro.kernels import mx_attention_ragged_fused, mx_repack_pages
from repro.kernels.mx_matmul import mx_matmul_vv, mx_matmul_wo

# phi4-mini-3.8b decode widths: 8 rows, 8 KV heads x 3 query heads each,
# head_dim 128, a 64-token ragged window, pages of 16 rows over 2112
# tokens (2048 prompt + 64 new) per row
R, KVH, G, D, W, PS = 8, 8, 3, 128, 64, 16
P = (2048 + 64) // PS
NP = R * P + 1  # + the trash page
DM, DFF = 3072, 8192


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis() is not None
    return compiled


@pytest.mark.parametrize("fmt", ["fp8_e4m3", "fp4_e2m1"])
def test_ragged_kernel_compiles_at_phi4_widths(one_chip, fmt):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    edt, ed = ((jnp.float8_e4m3fn, D) if fmt == "fp8_e4m3"
               else (jnp.uint8, D // 2))
    elems, scales = s((NP, KVH, PS, ed), edt), s((NP, KVH, PS, D // 32),
                                                  jnp.uint8)

    def step(q, kn, vn, ke, ks, ve, vs, tbl, st, ln):
        return mx_attention_ragged_fused(
            q, kn, vn, ke, ks, ve, vs, tbl, st, ln, fmt_name=fmt,
            block_size=32, interpret=False)

    _compile(step, s((R, KVH, W, G, D), jnp.bfloat16),
             s((R, KVH, W, D), jnp.bfloat16), s((R, KVH, W, D), jnp.bfloat16),
             elems, scales, elems, scales, s((R, P), jnp.int32),
             s((R,), jnp.int32), s((R,), jnp.int32))


@pytest.mark.parametrize("dst", ["fp6_e3m2", "fp4_e2m1"])
def test_repack_kernel_compiles_at_phi4_widths(one_chip, dst):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = [s((NP, KVH, PS, D), jnp.uint8),
            s((NP, KVH, PS, D // 32), jnp.uint8)] * 2

    def repack(ke, ks, ve, vs, ids, fmts, count):
        return mx_repack_pages(ke, ks, ve, vs, ids, fmts, count,
                               dst_fmt_name=dst, interpret=False)

    _compile(repack, *pool, s((8,), jnp.int32), s((8,), jnp.int32),
             s((), jnp.int32))


@pytest.mark.parametrize("block_size", [32, 16])
@pytest.mark.parametrize("variant", ["wo", "vv"])
@pytest.mark.parametrize("fmt", ["fp8_e4m3", "fp4_e2m1", "fp6_e3m2"])
def test_mx_matmul_compiles_at_phi4_widths(one_chip, fmt, variant,
                                           block_size):
    """The paper's kernel on phi4's up projection (K=3072, N=8192), with
    fp8 element tiles and packed fp4/fp6 ones."""
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    m, k, n = 256, DM, DFF
    f = get_format(fmt)
    ek = f.storage_len(k)

    def mx(rows):
        return [s((rows, ek), f.storage_dtype),
                s((rows, k // block_size), jnp.uint8)]

    if variant == "wo":
        _compile(lambda a, e, sc: mx_matmul_wo(
            a, e, sc, fmt_name=fmt, block_size=block_size, interpret=False),
            s((m, k), jnp.bfloat16), *mx(n))
    else:
        _compile(lambda ae, asc, e, sc: mx_matmul_vv(
            ae, asc, e, sc, fmt_name=fmt, block_size=block_size,
            interpret=False), *mx(m), *mx(n))
