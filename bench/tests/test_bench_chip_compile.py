"""Compile the qa-yi-9b cell's served programs for one described TPU v5e
chip (no chip needed): prefill of 4 x 1024 tokens and one decode step at
the configuration's sizes (16 layers of Yi-9B at published widths, bf16
weights).  Nothing runs, so this says nothing about results or times; it
finds what the TPU compiler refuses and what does not fit.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from harness.spec import BENCH  # noqa: E402
from harness.stage import model_config  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.serve import engine  # noqa: E402

V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
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


def _bytes(compiled):
    m = compiled.memory_analysis()
    return m.argument_size_in_bytes, m.output_size_in_bytes, m.temp_size_in_bytes


def test_yi_served_programs_fit_one_chip(one_chip, no_persistent_cache):
    with open(BENCH / "configs" / "qa-yi-9b.json") as f:
        cfg = model_config(json.load(f)["model"])
    with open(BENCH / "traffic" / "longdoc-closed8.json") as f:
        req = json.load(f)["request"]
    b, length = req["prompts"], req["prompt_len"]
    max_len = length + req["new_tokens"]
    params = _on(one_chip, jax.eval_shape(lambda k: lm.init(k, cfg),
                                          jax.random.PRNGKey(0)))
    prompt = jax.ShapeDtypeStruct((b, length), jnp.int32, sharding=one_chip)
    arg, out, temp = _bytes(engine.prefill.lower(params, cfg, prompt,
                                                 max_len=max_len).compile())
    assert arg > 6.5e9                     # 16 layers of bf16 weights
    assert arg + out + temp < V5E_HBM_BYTES
    cache, _ = jax.eval_shape(
        lambda p, t: lm.prefill(p, cfg, t, max_len=max_len), params, prompt)
    token = jax.ShapeDtypeStruct((b, 1), jnp.int32, sharding=one_chip)
    arg, out, temp = _bytes(engine.decode.lower(params, cfg, token,
                                                _on(one_chip, cache)).compile())
    assert arg + out + temp < V5E_HBM_BYTES
