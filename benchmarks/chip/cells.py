"""Resolve a cell of ``BENCHMARK.json`` into the files that define it.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own under this directory, found by
the name that ``BENCHMARK.json`` gives it:

- ``configs/<config>.json``: the model configuration as it is run;
- ``traffic/<mix>.json``: the parameters of one traffic mix;
- ``limits/<cell>.json``: the limit on each number the correctness check
  compares, with the readings it was set from;
- ``layer_metrics/<metric>.py``: the reader of one per-layer metric;
- ``references/<name>.py``: a plain reference, named by the config;
- ``programs/<name>.py``: how the system under test is built from a
  configuration file (its ``ModelConfig``, the scale of each weight, the
  cut the CPU tests use, and in ``RUN_CONFIG`` the serving options it
  needs, as a mixture of experts such as granite-4.0-h-small will need
  ``moe_impl``), named by the config's ``"program"`` key, or
  ``dense_gqa`` where it has none.

So a cell, a mix or a metric is added with new files and entries only.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
DEFAULT_PROGRAM = "dense_gqa"


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[Metric]
    per_layer: List[Metric]


def load_benchmark(root: Optional[Path] = None) -> dict:
    return json.loads(((root or ROOT) / "BENCHMARK.json").read_text())


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def _metric(entry: dict) -> Metric:
    return Metric(entry["name"], entry["unit"])


def resolve(name: str, root: Optional[Path] = None) -> Cell:
    """The cell ``name`` with its configuration, traffic mix, limits and
    the metrics it reports; a missing file raises."""
    root = root or ROOT
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text())
    limits = json.loads((HERE / "limits" / f"{name}.json").read_text())
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        limits=limits,
        end_to_end=[_metric(m) for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[_metric(m) for m in bench["per_layer"] if _applies(m, name)],
    )


@functools.lru_cache(maxsize=None)
def load_module(path: Path) -> ModuleType:
    """Import one file by path (metric and reference names hold dots),
    once per path; the module is named by its directory and file."""
    stem = f"{path.parent.name}_{path.stem}".replace(".", "_").replace("-", "_")
    mod_name = "chipbench_" + stem
    spec = importlib.util.spec_from_file_location(mod_name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def layer_reader(name: str) -> ModuleType:
    return load_module(HERE / "layer_metrics" / f"{name}.py")


def reference_module(config: dict) -> ModuleType:
    return load_module(HERE / "references" / f"{config['reference']}.py")


def program_module(config: dict) -> ModuleType:
    return load_module(HERE / "programs" / f"{config.get('program', DEFAULT_PROGRAM)}.py")
