"""Find a cell's configuration, traffic mix, driver and metric readers by
the names `BENCHMARK.json` gives them.

A later cell, mix or metric is added by adding files and entries: this
module maps names to paths and never lists them itself.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import pathlib
from typing import Callable, List, Optional

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    workloads: Optional[tuple] = None
    moves: Optional[str] = None

    def applies_to(self, workload: str) -> bool:
        return self.workloads is None or workload in self.workloads


@dataclasses.dataclass(frozen=True)
class Cell:
    """One `workloads` entry with everything it names, loaded."""
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[Metric]
    per_layer: List[Metric]

    @property
    def kind(self) -> str:
        """Which driver runs the cell: the configuration says."""
        return self.config["driver"]


def _metric(entry: dict) -> Metric:
    wl = entry.get("workloads")
    return Metric(name=entry["name"], unit=entry["unit"],
                  better=entry["better"], source=entry["source"],
                  workloads=tuple(wl) if wl is not None else None,
                  moves=entry.get("moves"))


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def traffic_path(name: str) -> pathlib.Path:
    return BENCH / "traffic" / f"{name}.json"


def load_cell(workload: str, bench: Optional[dict] = None,
              root: pathlib.Path = ROOT) -> Cell:
    bench = load_benchmark(root) if bench is None else bench
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads(traffic_path(w["traffic"]).read_text())
    e2e = [m for m in map(_metric, bench["end_to_end"])
           if m.applies_to(workload)]
    per_layer = [m for m in map(_metric, bench["per_layer"])
                 if m.applies_to(workload)]
    return Cell(name=workload, config_name=w["config"],
                traffic_name=w["traffic"], chips=int(w["chips"]),
                config=config, traffic=traffic, end_to_end=e2e,
                per_layer=per_layer)


def driver(kind: str):
    """`bench/drivers/<kind>.py`: one driver per entry point."""
    return importlib.import_module(f"bench.drivers.{kind}")


def metric_reader(name: str) -> Callable:
    """The reader of a per-layer metric: `metrics/<name>.py`, else the
    reader of its base name (`device_idle.eq` -> `device_idle.py`),
    which serves every cell that reports that quantity."""
    folder = BENCH / "metrics"
    for stem in (name, name.split(".", 1)[0]):
        path = folder / f"{stem}.py"
        if path.exists():
            mod_name = "bench.metrics._" + stem.replace(".", "_").replace(
                "-", "_")
            spec = importlib.util.spec_from_file_location(mod_name, path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise KeyError(f"no reader for metric {name!r} under {folder}")
