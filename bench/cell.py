"""Finds a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration (``bench/configs/<config>.json``, through
the ``configs`` entry's ``file``) and a traffic mix
(``bench/traffic/<traffic>.json``). Each metric is read by
``bench/metrics/<base>.py``, where ``<base>`` is the metric's name up to
its first ``.``: ``device_us_per_tick.rate`` and
``device_us_per_tick.tail`` are one quantity, split by the end-to-end
metric it moves. Adding a cell, a configuration, a traffic mix or a
metric adds files and entries and edits none.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]  # this cell's end-to-end metrics
    per_layer: list[dict]  # this cell's per-layer metrics


def _reports(metric: dict, cell: str, e2e_names: set[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def load_cell(name: str, spec_path: Path = ROOT / "BENCHMARK.json") -> Cell:
    spec = json.loads(Path(spec_path).read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; cells: "
                         f"{sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    root = Path(spec_path).resolve().parent
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"] if _reports(m, name, names)]
    return Cell(name, int(w["chips"]), config, traffic, e2e, layer)


def reader(metric_name: str):
    """The ``read(run)`` function of a metric's reader file."""
    base = metric_name.split(".")[0]
    path = BENCH / "metrics" / f"{base}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{base}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
