"""Find a cell's pieces by name.

``BENCHMARK.json`` names each cell's configuration and traffic; the
files behind those names live under the benchmark directory:

    configs/<config>.json            sizes, as run
    configs/<config>.reference.py    its plain reference (optional)
    traffic/<traffic>.json           the job mix
    metrics/<metric>.py              one per-layer metric reader

A later cell, traffic or metric is added by adding files and entries;
nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from types import ModuleType

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: tuple      # metric entries of BENCHMARK.json
    per_layer: tuple


def load_module(path: pathlib.Path) -> ModuleType:
    """Import a file by path (its name may hold dots, as metric names
    do)."""
    mod_name = "cnsbench_file_" + "".join(
        c if c.isalnum() else "_" for c in str(path))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _for_cell(metrics: list, cell: str) -> tuple:
    return tuple(m for m in metrics
                 if "workloads" not in m or cell in m["workloads"])


def load_cell(name: str, root: pathlib.Path,
              bench_dir: pathlib.Path = BENCH_DIR) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json``, with its
    configuration and traffic read from ``bench_dir``."""
    doc = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in doc["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    w = cells[name]
    config = json.loads(
        (bench_dir / "configs" / f"{w['config']}.json").read_text())
    traffic = json.loads(
        (bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(name=name, config=config, traffic=traffic,
                chips=int(w["chips"]),
                end_to_end=_for_cell(doc["end_to_end"], name),
                per_layer=_for_cell(doc["per_layer"], name))


def reference_module(config: dict,
                     bench_dir: pathlib.Path = BENCH_DIR) -> ModuleType | None:
    path = bench_dir / "configs" / f"{config['name']}.reference.py"
    return load_module(path) if path.exists() else None


def metric_reader(name: str, bench_dir: pathlib.Path = BENCH_DIR):
    """The ``read(ctx)`` function of ``metrics/<name>.py``."""
    return load_module(bench_dir / "metrics" / f"{name}.py").read
