"""Compile the chip path for one described TPU v5e chip (no chip needed).

The TPU compiler refuses what interpret mode accepts: unaligned blocks,
lowerings a kernel lacks, programs that do not fit the chip's memory.  These
tests compile the served mamba2-370m programs at full width and the Pallas
kernels at real widths for a v5e.  Nothing runs, so they say nothing about
results or times.

The topology is described inside a module-scoped fixture (never at import):
only one process at a time may load the TPU library, and every test worker
imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels import ops
from repro.models import lm
from repro.serve import engine

V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one: keep the cache off around it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)


def test_mamba2_served_programs_fit_one_chip(one_chip, no_persistent_cache):
    """Full-width mamba2-370m prefill (4 × 1024) and decode step."""
    cfg = configs.get("mamba2-370m")
    batch, prompt_len, new_tokens = 4, 1024, 32
    max_len = prompt_len + new_tokens
    params = _on(one_chip, lm.init_shapes(cfg))
    prompt = jax.ShapeDtypeStruct((batch, prompt_len), jnp.int32,
                                  sharding=one_chip)
    prefill = engine.prefill.lower(params, cfg, prompt,
                                   max_len=max_len).compile()
    assert _device_bytes(prefill) < V5E_HBM_BYTES

    cache, _ = jax.eval_shape(
        lambda p, t: lm.prefill(p, cfg, t, max_len=max_len), params, prompt)
    token = jax.ShapeDtypeStruct((batch, 1), jnp.int32, sharding=one_chip)
    decode = engine.decode.lower(params, cfg, token,
                                 _on(one_chip, cache)).compile()
    assert _device_bytes(decode) < V5E_HBM_BYTES


def _compile_kernel(fn, *shapes, **static):
    return jax.jit(lambda *a: fn(*a, interpret=False, **static)).lower(
        *shapes).compile()


def test_flash_attention_compiles(one_chip, no_persistent_cache):
    q = jax.ShapeDtypeStruct((1, 2048, 8, 128), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 2048, 2, 128), jnp.bfloat16,
                              sharding=one_chip)
    compiled = _compile_kernel(ops.flash_attention, q, kv, kv,
                               block_q=512, block_k=512)
    assert "tpu_custom_call" in compiled.as_text()


def test_rglru_scan_compiles(one_chip, no_persistent_cache):
    x = jax.ShapeDtypeStruct((1, 1024, 2560), jnp.float32, sharding=one_chip)
    compiled = _compile_kernel(ops.rglru_scan, x, x)
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_scan_compiles_at_mamba2_widths(one_chip, no_persistent_cache):
    """mamba2-370m's SSD: H=32 heads of P=64, N=128 state, chunk 256."""
    bt, l, h, p, n = 4, 1024, 32, 64, 128

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = _compile_kernel(
        ops.ssd_scan, sds((bt, l, h, p), jnp.bfloat16), sds((bt, l, h)),
        sds((h,)), sds((bt, l, n), jnp.bfloat16), sds((bt, l, n), jnp.bfloat16),
        chunk=256)
    assert "tpu_custom_call" in compiled.as_text()
