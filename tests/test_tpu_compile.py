"""Compile rehearsals for one TPU v5e chip, with no chip attached.

The TPU compiler is installed with JAX, and compiles for a v5e:2x2
topology that is only described. Interpret mode never checks the TPU's
block-shape rules or its fast-memory limits; this file does, at the
real packed size of full-width fedforecast-100m, for the four server
combine kernels and flash attention. Each test asserts that the Pallas
kernel is in the compiled program (``tpu_custom_call``) and that the
program's buffers fit one chip's 16 GB of HBM.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.compressed_agg import kernel as comp_k
from repro.kernels.flash_attention import kernel as flash_k
from repro.kernels.secure_agg import kernel as sec_k

T = 116_411_136                           # packed fedforecast-100m, fp32
TC = T + (-T) % comp_k.CHUNK              # CHUNK-padded for the dequant pair
HBM_BYTES = 16 * 10 ** 9                  # TPU v5e: 16 GB per chip


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # any failure means it cannot be described
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _combines(n):
    """name -> (fn, operand (shape, dtype) list) for one cohort size n."""
    f32, u32 = jnp.float32, jnp.uint32
    return {
        "masked_sum": (
            lambda x, w: sec_k.masked_sum_flat(x, w, interpret=False),
            [((n, T), f32), ((n,), f32)]),
        "masked_sum_corrected": (
            lambda x, c, w: sec_k.masked_sum_corrected_flat(
                x, c, w, interpret=False),
            [((n, T), f32), ((n, T), f32), ((n,), f32)]),
        "dequant_reduce": (
            lambda q, s, w: comp_k.dequant_reduce_flat(
                q, s, w, interpret=False),
            [((n, TC), jnp.int8), ((n, TC // comp_k.CHUNK), f32),
             ((n,), f32)]),
        "masked_dequant_reduce": (
            lambda z, s: comp_k.masked_dequant_reduce_flat(
                z, s, modulus_bits=16, interpret=False),
            [((n, TC), u32), ((TC // comp_k.CHUNK,), f32)]),
        "masked_dequant_reduce_corrected": (
            lambda z, c, s: comp_k.masked_dequant_reduce_flat(
                z, s, modulus_bits=32, corr=c, interpret=False),
            [((n, TC), u32), ((n, TC), u32), ((TC // comp_k.CHUNK,), f32)]),
    }


def _assert_kernel_fits(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used <= HBM_BYTES, f"{used / 1e9:.2f} GB > 16 GB"


@pytest.mark.parametrize("n", [8, 3])
@pytest.mark.parametrize("name", sorted(_combines(1)))
def test_combine_kernel_compiles_for_v5e(one_chip, name, n):
    fn, operands = _combines(n)[name]
    args = [_shape(one_chip, s, d) for s, d in operands]
    _assert_kernel_fits(jax.jit(fn).lower(*args).compile())


def test_flash_attention_compiles_for_v5e(one_chip):
    b, h, s, d = 8, 12, 512, 64           # fedforecast-100m heads, bf16
    qkv = [_shape(one_chip, (b, h, s, d), jnp.bfloat16)] * 3

    def fn(q, k, v):
        return flash_k.flash_attention_bhsd(
            q, k, v, scale=d ** -0.5, causal=True, window=0, softcap=0.0,
            interpret=False)
    _assert_kernel_fits(jax.jit(fn).lower(*qkv).compile())
