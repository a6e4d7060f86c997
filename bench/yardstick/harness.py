"""Finding a cell's pieces by name.

`BENCHMARK.json` at the checkout root names the cells, configurations and
metrics. Everything else is a file found by its name under the
benchmark's directory, so a new configuration, traffic mix or metric is a
new file plus new entries, and no file that is there changes:

* ``configs/<config>.json``  — a deployment (the entry's ``file``);
* ``traffic/<traffic>.json`` — a mix: ``{"driver": <name>, ...parameters}``;
* ``drivers/<driver>.py``    — one general generator per kind of work,
  with ``run(ctx) -> record``;
* ``metrics/<metric>.py``    — one reader per metric, with
  ``read(record) -> number or None``. A reader that finds nothing to read
  returns None and the metric is left out of the result line.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    driver: ModuleType
    metrics: List[dict]       # BENCHMARK.json entries reported by this run


@dataclasses.dataclass
class Context:
    """What a driver is given for one run."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_start: float            # perf_counter at process start
    out_dir: Path             # scratch for this run, inside the checkout
    device_kind: str = ""
    log: object = print       # earlier-lines printer (never the last line)
    keep_answers: bool = False   # keep the answers in the record (control.py)


def _module(path: Path, name: str) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(f"no file {path} for {name!r}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{name}".replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_benchmark(root: Path = ROOT) -> dict:
    with open(Path(root) / "BENCHMARK.json") as fh:
        return json.load(fh)


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def find_cell(name: str, trace: bool, root: Path = ROOT,
              bench_dir: Optional[Path] = None) -> Cell:
    root = Path(root)
    bench_dir = Path(bench_dir) if bench_dir else root / "bench"
    spec = load_benchmark(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(one of {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    with open(root / configs[w["config"]]["file"]) as fh:
        config = json.load(fh)
    with open(bench_dir / "traffic" / f"{w['traffic']}.json") as fh:
        traffic = json.load(fh)
    driver = _module(bench_dir / "drivers" / f"{traffic['driver']}.py",
                     traffic["driver"])
    kind = "per_layer" if trace else "end_to_end"
    metrics = [m for m in spec[kind] if _applies(m, name)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, driver=driver, metrics=metrics)


def read_metrics(cell: Cell, record: dict,
                 bench_dir: Optional[Path] = None) -> Dict[str, dict]:
    """Each metric of the run from its own reader; None leaves it out."""
    bench_dir = Path(bench_dir) if bench_dir else BENCH_DIR
    out = {}
    for m in cell.metrics:
        reader = _module(bench_dir / "metrics" / f"{m['name']}.py", m["name"])
        value = reader.read(record)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def compile_cache_dir(root: Path = ROOT) -> str:
    """The persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR`` when
    the environment sets it, else a fixed directory inside the checkout
    (the path is part of the cache key, so it must not move)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        Path(root) / ".jax_cache")
