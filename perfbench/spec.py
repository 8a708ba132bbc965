"""Cell lookup: everything a run needs is found by name from
``BENCHMARK.json`` and the data files under ``perfbench/``.

* a configuration is ``perfbench/configs/<name>.json``;
* a traffic mix is ``perfbench/traffic/<name>.json``;
* a per-layer metric is the reader ``perfbench/metrics/<name>.py``,
  whose ``read(run)`` returns a number or None.

A later cell, configuration or metric is added by adding files and
entries; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import typing
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List

BENCH_DIR = Path(__file__).resolve().parent


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    root: Path
    readers: Dict[str, Callable] = field(default_factory=dict)


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_reader(name: str, bench_dir: Path = BENCH_DIR) -> Callable:
    path = bench_dir / "metrics" / f"{name}.py"
    modspec = importlib.util.spec_from_file_location(
        f"perfbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(modspec)
    modspec.loader.exec_module(mod)
    return mod.read


def load_cell(root: Path, workload: str) -> Cell:
    """The cell named ``workload`` in ``<root>/BENCHMARK.json`` with its
    configuration, traffic mix and per-layer readers."""
    bench = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(root / configs[w["config"]]["file"])
    traffic = _load_json(root / "perfbench" / "traffic"
                         / f"{w['traffic']}.json")
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload)]
    per_layer = [m for m in bench["per_layer"] if _applies(m, workload)]
    bench_dir = root / "perfbench"
    cell = Cell(workload, int(w["chips"]), config, traffic, e2e, per_layer,
                root)
    cell.readers = {m["name"]: load_reader(m["name"], bench_dir)
                    for m in per_layer}
    return cell


def model_config(node: dict):
    """The node's ``ModelConfig`` exactly as its configuration file
    states it (the file, not the program's registry, is what is run).
    Each field is converted by its annotation: a nested group (``ssm``,
    ``moe``) to its dataclass, a list to a tuple."""
    from repro.configs.base import ModelConfig
    hints = typing.get_type_hints(ModelConfig)
    fields = dict(node["model"])
    for k, v in fields.items():
        if k not in hints:          # ModelConfig refuses it below
            continue
        if isinstance(v, dict):
            fields[k] = _nested(hints[k])(**v)
        elif isinstance(v, list) and typing.get_origin(hints[k]) is tuple:
            fields[k] = tuple(v)
    return ModelConfig(**fields)


def _nested(hint):
    """The dataclass a field annotated ``hint`` (``X`` or
    ``Optional[X]``) holds."""
    for t in (hint, *typing.get_args(hint)):
        if dataclasses.is_dataclass(t):
            return t
    raise TypeError(f"{hint} holds no dataclass")
