"""A copy of the benchmark's data at a size the CPU runs in seconds, for
the tests: every cell of ``BENCHMARK.json`` with its model at the given
width and two layers, short prompts, and a small limit file."""

import json
import sys
from pathlib import Path
from typing import Any, Dict, Optional

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

# Program gaps at this size read 0.0001-0.017 and the control's 0.18-0.68
# (three seeds per configuration, CPU): the limit sits between them.
TEST_LIMIT = 0.1


def _merged(base: Dict[str, Any], over: Dict[str, Any]) -> Dict[str, Any]:
    """``base`` with ``over`` merged in, nested groups key by key."""
    out = dict(base)
    for k, v in over.items():
        nest = isinstance(v, dict) and isinstance(base.get(k), dict)
        out[k] = _merged(base[k], v) if nest else v
    return out


def smoke_model(config: Dict[str, Any], d_model: int = 64) -> Dict[str, Any]:
    """A configuration file's model at the smoke size: two layers of width
    ``d_model`` over a vocabulary of 1024, then the file's own ``smoke``
    overrides, or by default a rule by ``family`` (SSD at state 32, heads
    of 32 and chunks of 32; otherwise heads of 32 and ``d_ff`` 4 x width)."""
    m = dict(config["model"], n_layers=2, d_model=d_model, vocab=1024)
    if "smoke" in config:
        return _merged(m, config["smoke"])
    if m["family"] == "ssm":
        m["ssm"] = dict(m["ssm"], d_state=32, head_dim=32, chunk=32)
    else:
        m.update(n_heads=d_model // 32, n_kv_heads=max(1, d_model // 128),
                 d_ff=4 * d_model)
    return m


def build(dst: Path, d_model: int = 64, new_tokens: int = 4,
          bench: Optional[Dict[str, Any]] = None) -> Path:
    """The copy in ``dst``, of ``bench`` (by default ``BENCHMARK.json``):
    its configuration files at the smoke size, its traffic files at short
    prompts and low rates, and a limit file for each cell."""
    bench = bench or json.loads((ROOT / "BENCHMARK.json").read_text())
    for sub in ("configs", "traffic", "limits"):
        (dst / "bench" / sub).mkdir(parents=True, exist_ok=True)
    for c in bench["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        cfg["model"] = smoke_model(cfg, d_model)
        (dst / c["file"]).parent.mkdir(parents=True, exist_ok=True)
        (dst / c["file"]).write_text(json.dumps(cfg))
    for w in bench["workloads"]:
        t = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())
        t["request"] = {"prompts": 4, "prompt_len": 64, "new_tokens": new_tokens}
        a = t["arrivals"]
        if a["process"] == "poisson":
            a["rate_wf_s"] = 4.0
        elif a["process"] == "onoff":
            a.update(period_s=1.0, on_s=0.25, on_rate_wf_s=16.0)
        else:
            a["in_flight"] = 2
        (dst / "bench" / "traffic" / f"{w['traffic']}.json").write_text(json.dumps(t))
        (dst / "bench" / "limits" / f"{w['name']}.json").write_text(
            json.dumps({"gap_over_std": TEST_LIMIT, "sample_instances": 2}))
    (dst / "BENCHMARK.json").write_text(json.dumps(bench))
    return dst


def cells():
    return [w["name"] for w in
            json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


def run(root: Path, cell: str, seed: int, capsys, seconds: float = 2.0):
    """One in-process run of ``bench/run.py`` on the CPU: its result line
    and its standard error."""
    from harness import cli
    rc = cli.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", "0"], platform="cpu", root=root)
    assert rc == 0
    said = capsys.readouterr()
    return json.loads(said.out.strip().splitlines()[-1]), said.err


def env_cache(monkeypatch, tmp_path):
    """Keep the compile cache out of the checkout, and the process's JAX
    config as it was."""
    import jax
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    before = jax.config.jax_persistent_cache_min_compile_time_secs
    return lambda: jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", before)

