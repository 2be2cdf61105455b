"""The chip entry point and the compile-cache rule, on the CPU.

``chip_smoke.py`` must refuse any device but a TPU; a test steers it onto
the CPU at a reduced size to run its whole path here.
"""

import json
import os
import subprocess
import sys

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache as cc

from repro.compile_cache import CHECKOUT_CACHE_DIR, use_compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.realpath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def test_compile_cache_env_var_sets_nothing(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = use_compile_cache()
        assert first == str(CHECKOUT_CACHE_DIR) == use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == first
        assert first == os.path.join(ROOT, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        cc.reset_cache()


def test_chip_smoke_refuses_a_non_tpu_device():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, SCRIPT], capture_output=True,
                       text=True, env=env, cwd=ROOT, timeout=300)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "not tpu" in r.stderr


@pytest.fixture
def chip_smoke(monkeypatch, tmp_path):
    # with the variable set, use_compile_cache leaves JAX's config alone
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.syspath_prepend(ROOT)
    import chip_smoke
    return chip_smoke


def test_chip_smoke_whole_path_on_cpu_when_steered(chip_smoke, capsys):
    sizes = chip_smoke.Sizes(smoke=True, prompt_len=64, new_tokens=4,
                             ref_len=32)
    assert chip_smoke.main([], platform="cpu", sizes=sizes) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    last = json.loads(lines[-1])
    assert last == {"ok": True, "device": {"platform": "cpu", "kind": "cpu",
                                           "count": jax.device_count()}}
    said = dict(line.split(": ", 1) for line in lines[:-1])
    assert said["drop_count"] == "0"
    assert said["compiles_in_window"] == "0"
    assert int(said["max_instances_in_flight"]) >= 2
    assert int(said["tokens_generated"]) == (
        chip_smoke.REQUESTS * chip_smoke.PROMPTS * sizes.new_tokens)
