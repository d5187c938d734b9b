"""Find a cell's files by name: nothing here changes when a cell, a
configuration, a traffic mix or a per-layer metric is added.

    BENCHMARK.json                       cells, metrics, bounds
    bench/cells/<workload>.json          the options of one cell
    bench/builders/<builder>.py          builds the system a cell file names
    bench/configs/<config>.json          the model sizes, as run
    bench/traffic/<traffic>.json         the parameters of one traffic mix
    bench/generators/<generator>.py      makes the pool a traffic file names
    bench/metrics/<metric>.py            `read(ctx)` for one per-layer metric
    bench/reference/<reference>.py       the plain reference a config names
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class SpecError(ValueError):
    """A name in BENCHMARK.json has no file, or a file is malformed."""


def _load_json(path: Path) -> Dict[str, Any]:
    if not path.is_file():
        raise SpecError(f"missing {path.relative_to(ROOT)}")
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    if not path.is_file():
        raise SpecError(f"missing {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    options: Dict[str, Any]  # bench/cells/<name>.json
    config: Dict[str, Any]  # bench/configs/<config>.json
    traffic: Dict[str, Any]  # bench/traffic/<traffic>.json
    end_to_end: List[Dict[str, Any]]  # the metrics this cell reports
    per_layer: List[Dict[str, Any]]


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_dir: Path = BENCH_DIR,
              benchmark: Optional[Path] = None) -> Cell:
    bench = _load_json(benchmark or bench_dir.parent / "BENCHMARK.json")
    rows = [w for w in bench["workloads"] if w["name"] == name]
    if not rows:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json")
    w = rows[0]
    return Cell(
        name=name,
        chips=int(w["chips"]),
        options=_load_json(bench_dir / "cells" / f"{name}.json"),
        config=_load_json(bench_dir / "configs" / f"{w['config']}.json"),
        traffic=_load_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
    )


def builder(cell: Cell, bench_dir: Path = BENCH_DIR):
    """bench/builders/<builder>.py: `build(cell, key) -> (engine, state)`,
    and the program's readings `first_gradient_norms(engine, state)` and
    `change_norms(engine, state, key)`."""
    name = cell.options["builder"]
    return load_module(bench_dir / "builders" / f"{name}.py", f"bench_builder_{name}")


def generator(traffic: Dict[str, Any], bench_dir: Path = BENCH_DIR):
    """bench/generators/<generator>.py: `make_pool(key, traffic, vocab)`."""
    name = traffic["generator"]
    return load_module(bench_dir / "generators" / f"{name}.py", f"bench_generator_{name}")


def metric_reader(name: str, bench_dir: Path = BENCH_DIR) -> Callable:
    """`read(ctx) -> float | None` from bench/metrics/<name>.py."""
    mod = load_module(bench_dir / "metrics" / f"{name}.py",
                      f"bench_metric_{name.replace('.', '_')}")
    return mod.read


def reference_module(config: Dict[str, Any], bench_dir: Path = BENCH_DIR):
    ref = config["reference"]
    return load_module(bench_dir / "reference" / f"{ref}.py", f"bench_ref_{ref}")
