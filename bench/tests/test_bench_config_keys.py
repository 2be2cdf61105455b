"""A configuration file's own keys, ``draw``, ``reference``, ``counts`` and
``smoke``, and the parsing of every sub-config of the model.

A file without the keys runs as it did before they existed: the digests
and counts below were taken with the harness as it was then.  A
configuration that harness refused, a hybrid pattern with experts, runs
through ``cli.main`` from files under ``bench/tests/data`` alone."""

import hashlib
import json
import os
import sys
from types import SimpleNamespace

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_smoke  # noqa: E402
from harness import layers, spec  # noqa: E402
from harness.stage import make_weights, model_config  # noqa: E402
from repro.models import lm, moe  # noqa: E402
from repro.models.common import (ModelConfig, MoEConfig, RGLRUConfig,  # noqa: E402
                                 SSMConfig)

BENCH = spec.BENCH
HYBRID = "bench/tests/data/hybrid-moe.json"
SEED = 2**31 + 7


def _config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def _request(traffic):
    return json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())["request"]


def _digest(tree):
    h = hashlib.sha256()
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(x)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("name", ["qa-mamba2-370m", "qa-yi-9b"])
def test_model_config_as_before_for_the_cells(name):
    m = _config(name)["model"]
    kw = dict(m, layer_pattern=tuple(m["layer_pattern"]))
    if "ssm" in m:
        kw["ssm"] = SSMConfig(**m["ssm"])
    assert model_config(m) == ModelConfig(**kw)


def test_model_config_builds_every_sub_config():
    m = json.loads((spec.ROOT / HYBRID).read_text())["model"]
    m = dict(m, rglru={"lru_width": 8, "block_pattern": ["rglru", "attn"]})
    cfg = model_config(m)
    assert cfg.moe == MoEConfig(**m["moe"])
    assert cfg.ssm == SSMConfig(**m["ssm"])
    assert cfg.rglru == RGLRUConfig(lru_width=8, block_pattern=("rglru", "attn"))
    assert cfg.layer_pattern == ("ssm", "attn")
    with pytest.raises(TypeError):
        model_config(dict(m, no_such_key=1))


# digests of make_weights and of the reference's logits (sound and control)
# at the smoke size, from the harness before configurations named their own
# code; and request_flops / mean_decode_bytes at the smoke size and at the
# cell's own sizes
PARENT = {
    "qa-mamba2-370m": ("short-burst", "ed320d23334e8d48", "96c8d0dd629d28e4",
                       "5380a8067cc365d6", (100483072.0, 532064.0),
                       (404455292928.0, 1144551424.0)),
    "qa-yi-9b": ("longdoc-closed8", "2563701abcf02c05", "787a9ad044768ac7",
                 "ec3dd9f03ca3b9ba", (2194702336.0, 1443968.0),
                 (24015158640640.0, 6197518336.0)),
}


@pytest.mark.parametrize("name", sorted(PARENT))
def test_weights_reference_and_counts_equal_the_parent(name):
    traffic, weights, logits, control, smoke_counts, cell_counts = PARENT[name]
    config = _config(name)
    m = bench_smoke.smoke_model(config)
    params = make_weights(model_config(m), SEED, config.get("draw"))
    assert _digest(params) == weights
    ref = spec.reference_of(config)
    tokens = np.random.default_rng(0).integers(0, 1024, (2, 24)).astype(np.int32)
    assert _digest(ref.logits(params, m, tokens, first=8)) == logits
    assert _digest(ref.logits(params, m, tokens, first=8, control=True)) == control
    counts, req = spec.counts_of(config), _request(traffic)
    for model, want in ((m, smoke_counts), (config["model"], cell_counts)):
        assert (counts.request_flops(model, req),
                counts.mean_decode_bytes(model, req)) == want


def _small_moe(**kw):
    m = bench_smoke.smoke_model(json.loads((spec.ROOT / HYBRID).read_text()))
    return model_config(dict(m, **kw))


def test_a_leaf_no_rule_names_raises():
    with pytest.raises(KeyError, match="router"):
        make_weights(_small_moe(), SEED)


def test_draw_rules_and_no_other_leaf_moves():
    cfg = model_config(bench_smoke.smoke_model(_config("qa-mamba2-370m")))
    draw = {"final_norm": {"const": 0.25}, "ln1": {"uniform": [2.0, 3.0]},
            "ssm/D": {"log_uniform": [1e-3, 1e-1]}, "embed": {"normal": 2.0},
            "wz": {"normal": "fan_in"}}
    base = make_weights(cfg, SEED)
    got = make_weights(cfg, SEED, draw)
    assert np.all(np.asarray(got["final_norm"]) == 0.25)
    ln1 = np.asarray(got["blocks"]["s0"]["ln1"])
    assert ln1.min() >= 2.0 and ln1.max() <= 3.0
    d = np.asarray(got["blocks"]["s0"]["ssm"]["D"])
    assert d.min() >= 1e-3 and d.max() <= 1e-1
    assert 1.8 < np.asarray(got["embed"]).std() < 2.2
    drawn = {"final_norm", "ln1", "D", "embed"}
    for (path, a), (_, b) in zip(jax.tree_util.tree_leaves_with_path(got),
                                 jax.tree_util.tree_leaves_with_path(base)):
        if path[-1].key not in drawn:         # "wz" by the built-in rule's own
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_parent_leaf_before_leaf_name():
    draw = {"router": {"normal": 0.1}, "w_gate": {"const": 1.0},
            "shared/w_gate": {"const": 2.0}}
    p = make_weights(_small_moe(), SEED, draw)["blocks"]["s1"]["moe"]
    assert np.all(np.asarray(p["w_gate"], np.float32) == 1.0)
    assert np.all(np.asarray(p["shared"]["w_gate"], np.float32) == 2.0)


@pytest.mark.parametrize("rel", ["../src/repro/models/lm.py",
                                 "harness/../../chip_smoke.py",
                                 "/usr/lib/python3/dist-packages/x.py",
                                 "peaks.json"])
def test_code_outside_the_benchmark_is_refused(rel):
    with pytest.raises(ValueError, match="not a Python file under"):
        spec.reference_of({"reference": rel})
    with pytest.raises(ValueError, match="not a Python file under"):
        spec.counts_of({"counts": rel})


def test_defaults_are_the_benchmarks_own():
    assert spec.reference_of({}).__file__ == str(BENCH / "harness" / "reference.py")
    assert spec.counts_of({}).__file__ == str(BENCH / "flops.py")


@pytest.mark.parametrize("change,named", [
    ({}, "moe"),
    ({"moe": None, "layer_pattern": ["rglru", "attn"]}, "rglru"),
])
def test_default_counts_refuse_what_they_cannot_count(change, named):
    m = bench_smoke.smoke_model(json.loads((spec.ROOT / HYBRID).read_text()))
    with pytest.raises(ValueError, match=named):
        spec.counts_of({}).request_flops(dict(m, **change), _request("short-burst"))


def test_smoke_merges_nested_groups():
    config = json.loads((spec.ROOT / HYBRID).read_text())
    m = bench_smoke.smoke_model(config)
    assert (m["n_layers"], m["d_model"], m["n_heads"], m["head_dim"]) == (2, 64, 4, 16)
    assert m["moe"] == dict(config["model"]["moe"], d_expert=32)
    assert m["ssm"] == dict(config["model"]["ssm"], d_state=16, head_dim=16,
                            chunk=32)


@pytest.mark.parametrize("kind", ["ssm", "attn"])
def test_hybrid_counts_match_the_program_tree(kind):
    config = json.loads((spec.ROOT / HYBRID).read_text())
    m = bench_smoke.smoke_model(config)
    shapes = lm.init_shapes(model_config(m))
    slot = m["layer_pattern"].index(kind)
    tree = shapes["blocks"][f"s{slot}"]
    held = sum(int(np.prod(s.shape[1:]))
               for s in jax.tree_util.tree_leaves(tree))
    assert sum(spec.counts_of(config).layer_split(m, kind)) == held


def test_layer_readers_use_the_cells_counts():
    counts = SimpleNamespace(request_flops=lambda m, r: 3e12,
                             mean_decode_bytes=lambda m, r: 4e9)
    cell = SimpleNamespace(counts=counts, chips=1)
    window = SimpleNamespace(t0_ms=0.0, t1_ms=1e4, seconds=10.0,
                             stage=SimpleNamespace(calls=[(0, 1.0, 2.0)] * 5))
    trace = SimpleNamespace(module_seconds=lambda name: [0.01, 0.01])
    run = SimpleNamespace(cell=cell, window=window, trace=trace, model={},
                          request={}, peaks=lambda: {"bf16_flops_per_s": 1e15,
                                                     "hbm_bytes_per_s": 8e11})
    assert layers.stage_mfu(run) == pytest.approx(100 * 5 * 3e12 / (10 * 1e15))
    assert layers.decode_roofline(run) == pytest.approx(100 * (4e9 / 8e11) / 0.01)


@pytest.fixture
def hybrid_root(tmp_path, monkeypatch):
    restore = bench_smoke.env_cache(monkeypatch, tmp_path)
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "hybrid-moe", "file": HYBRID})
    bench["workloads"].append({"name": "hybrid-moe.short-burst", "chips": 1,
                               "config": "hybrid-moe", "traffic": "short-burst"})
    yield bench_smoke.build(tmp_path / "checkout", bench=bench)
    restore()


@pytest.mark.parametrize("reference,correct", [
    ("tests/data/hybrid_moe_reference.py", True),
    ("tests/data/hybrid_moe_reference_noshared.py", False),
])
def test_hybrid_moe_cell_through_cli(reference, correct, hybrid_root, capsys):
    path = hybrid_root / HYBRID
    config = json.loads(path.read_text())
    cfg = model_config(config["model"])
    tokens = 4 * 64                                   # a prefill of the smoke traffic
    assert moe.capacity(tokens, cfg) >= tokens        # nothing can be dropped
    path.write_text(json.dumps(dict(config, reference=reference)))
    out, err = bench_smoke.run(hybrid_root, "hybrid-moe.short-burst", SEED, capsys)
    assert out["correct"] is correct
    assert out["attempted"] > 0
    gap = out["check"]["gap_over_std"]
    assert (gap["value"] <= gap["limit"]) is correct


def test_reference_follows_the_program_over_a_remainder_layer():
    # three layers of ("ssm", "attn"): one whole period in blocks.s0/s1, the
    # third layer in rem.r0; float32 throughout, so the program's forward
    # and the reference agree to float32 rounding
    config = json.loads((spec.ROOT / HYBRID).read_text())
    m = dict(bench_smoke.smoke_model(config), n_layers=3,
             param_dtype="float32", compute_dtype="float32")
    cfg = model_config(m)
    params = make_weights(cfg, SEED, config["draw"])
    assert set(params["rem"]) == {"r0"}
    tokens = np.random.default_rng(1).integers(0, 1024, (2, 16)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        prog = np.asarray(lm.forward(params, cfg, tokens)[0])[..., :m["vocab"]]
    ref = spec.reference_of(config).logits(params, m, tokens, first=0)
    np.testing.assert_allclose(prog, ref, atol=1e-4 * ref.std())
