"""Resolve a cell of ``BENCHMARK.json`` to its parts, all found by name:

* the configuration's file (``configs[].file``), whose ``generator`` names
  a module under ``bench/generators/``;
* the traffic mix ``bench/traffic/<traffic>.json``;
* each end-to-end metric's reader ``bench/e2e/<name>.py`` and each
  per-layer metric's reader ``bench/metrics/<name>.py``, for the metrics
  whose ``workloads`` list the cell (or that list none).
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Dict, List

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict
    traffic_name: str
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_benchmark(root: Path = ROOT) -> Dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def resolve(name: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(root / conf["file"]) as f:
        config = json.load(f)
    with open(BENCH_DIR / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic_name=w["traffic"], traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])


def load_generator(config: Dict):
    """The configuration's generator, built from its ``params``."""
    from bench import generators
    return generators.load(config["generator"]).build(config["params"])


def _reader(folder: str, name: str):
    path = BENCH_DIR / folder / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(
        f"bench_{folder}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metric_reader(name: str):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    return _reader("metrics", name)


def end_to_end_reader(name: str):
    """The ``read(win)`` function of ``bench/e2e/<name>.py``."""
    return _reader("e2e", name)
