"""Resolve one cell of ``BENCHMARK.json`` into its data files.

A cell names a configuration and a traffic mix.  Everything is looked up
by name: the configuration's ``file``, ``bench/traffic/<mix>.json``,
``bench/limits/<cell>.json`` and the readers ``bench/metrics/<metric>.py``.
Data files resolve against the checkout root; readers are code and always
come from this benchmark directory.

A configuration file may name code of its own, which resolves the same
way, against this benchmark directory and never outside it:
``reference``, the plain reference that decides ``correct`` (default
``harness/reference.py``), and ``counts``, the operations and bytes its
programs need (default ``flops.py``).
"""

from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


@dataclass
class Metric:
    name: str
    unit: str
    read: Callable[[Any], Any]


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    end_to_end: List[Metric] = field(default_factory=list)
    per_layer: List[Metric] = field(default_factory=list)
    reference: Optional[ModuleType] = None   # ``reference_of(config)``
    counts: Optional[ModuleType] = None      # ``counts_of(config)``


def _module(path: Path, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(f"no module at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reader(name: str) -> Callable[[Any], Any]:
    return _module(BENCH / "metrics" / f"{name}.py", f"bench_metric_{name}").read


def load_code(rel: str) -> ModuleType:
    """The module at ``rel``, a path relative to this benchmark directory.
    A path that leads outside it, or to anything but a ``.py`` file, is
    refused with ``ValueError``."""
    path = (BENCH / rel).resolve()
    if not path.is_relative_to(BENCH) or path.suffix != ".py":
        raise ValueError(f"{rel!r} is not a Python file under {BENCH}")
    name = re.sub(r"\W", "_", path.relative_to(BENCH).with_suffix("").as_posix())
    return _module(path, f"bench_code_{name}")


def reference_of(config: Dict[str, Any]) -> ModuleType:
    """The plain reference a configuration file names."""
    return load_code(config.get("reference", "harness/reference.py"))


def counts_of(config: Dict[str, Any]) -> ModuleType:
    """The operation and byte counts a configuration file names."""
    return load_code(config.get("counts", "flops.py"))


def _metrics(entries: List[dict], cell: str) -> List[Metric]:
    return [Metric(m["name"], m["unit"], _reader(m["name"]))
            for m in entries if cell in m.get("workloads", [cell])]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in {root / 'BENCHMARK.json'}")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = load_json(root / conf["file"])
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        traffic=load_json(root / "bench" / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(root / "bench" / "limits" / f"{name}.json"),
        end_to_end=_metrics(bench["end_to_end"], name),
        per_layer=_metrics(bench["per_layer"], name),
        reference=reference_of(config),
        counts=counts_of(config),
    )
