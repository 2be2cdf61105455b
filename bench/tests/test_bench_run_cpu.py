"""``bench/run.py`` rehearsed on the CPU at a smoke size: every cell's
whole path, the result line's keys, and the refusal to measure without a
TPU."""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_smoke  # noqa: E402

ROOT = bench_smoke.ROOT


@pytest.fixture
def smoke_root(tmp_path, monkeypatch):
    restore = bench_smoke.env_cache(monkeypatch, tmp_path)
    yield bench_smoke.build(tmp_path / "checkout")
    restore()


def test_refuses_to_measure_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = bench_smoke.cells()[0]
    r = subprocess.run([sys.executable, "bench/run.py", "--workload", cell,
                        "--seed", "1", "--seconds", "2", "--trace", "0"],
                       capture_output=True, text=True, env=env, cwd=ROOT,
                       timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "tpu chip(s)" in r.stderr


def test_refuses_without_the_program(tmp_path):
    (tmp_path / "bench").symlink_to(ROOT / "bench")
    (tmp_path / "BENCHMARK.json").write_text((ROOT / "BENCHMARK.json").read_text())
    cell = bench_smoke.cells()[0]
    r = subprocess.run([sys.executable, "bench/run.py", "--workload", cell,
                        "--seed", "1", "--seconds", "2", "--trace", "0"],
                       capture_output=True, text=True, cwd=tmp_path,
                       env=dict(os.environ, JAX_PLATFORMS="cpu",
                                PYTHONPATH=""), timeout=300)
    assert r.returncode != 0 and r.stdout.strip() == ""


@pytest.mark.parametrize("cell", bench_smoke.cells())
def test_cell_runs_on_cpu_at_smoke_size(cell, smoke_root, capsys):
    out, err = bench_smoke.run(smoke_root, cell, 2**31 + 17, capsys)
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "check"]
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    want = {m["name"] for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(out["metrics"]) == want
    for m in bench["end_to_end"]:
        if m["name"] in want:
            got = out["metrics"][m["name"]]
            assert got["unit"] == m["unit"] and got["value"] > 0
    assert out["device"] == {"platform": "cpu", "kind": "cpu", "count": 1,
                             "memory_peak_bytes": 0}
    assert out["check"]["gap_over_std"]["limit"] == bench_smoke.TEST_LIMIT
    assert "compiles in window 0" in err
    assert err.strip().splitlines()[-1].startswith("check: ")
