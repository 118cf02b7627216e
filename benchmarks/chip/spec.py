"""What a run measures, found by name.

`BENCHMARK.json` at the root of the checkout names the cells.  A cell
names a configuration, whose sizes sit in the file that its entry
gives, and a traffic mix, `traffic/<traffic>.json`; the limits that
decide whether its run is correct are `limits/<cell>.json`.  Each per-layer
metric is a reader of its own, `metrics/<name>.py`, with its unit, its
layer and the end-to-end metric it moves.  Adding a configuration, a
traffic mix or a metric is adding its file and its entry: no code here
names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    read: object = None           # per-layer: reader(ctx) -> float | None


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic_name: str
    traffic: dict
    limits: dict                  # number -> [op, limit], for check.judge
    end_to_end: list
    per_layer: list


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_reader(name: str, bench_dir: Path = HERE):
    """The module `metrics/<name>.py`: UNIT, LAYER, MOVES, SOURCE and
    `read(ctx)`."""
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"_bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell `name` of `<root>/BENCHMARK.json`, with its files."""
    bench_dir = root / HERE.relative_to(ROOT)
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    limits = load_json(bench_dir / "limits" / f"{name}.json")
    e2e = [Metric(m["name"], m["unit"])
           for m in bench["end_to_end"] if _applies(m, name)]
    per_layer = []
    for m in bench["per_layer"]:
        if not _applies(m, name):
            continue
        per_layer.append(Metric(m["name"], m["unit"],
                                load_reader(m["name"], bench_dir).read))
    return Cell(name, int(w["chips"]), config, w["traffic"], traffic,
                limits, e2e, per_layer)
