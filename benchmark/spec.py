"""BENCHMARK.json and the data files it names.

Everything that belongs to one configuration, one traffic mix, one driver,
one builder or one per-layer metric sits in a file of its own and is found
by name, so a later PR adds files and entries and edits nothing here:

    configs/<config>.json          sizes, source, reduced/assumed, builder
    traffic/<mix>.json             driver name and the mix's parameters
    cells/<workload>.json          what belongs to one cell only (its rate)
    drivers/<driver>.py            one kind of traffic (a child process)
    builders/<builder>.py          one way of making a model from a seed
    layer_metrics/<quantity>.json  one per-layer quantity: reduction + args;
                                   metric `<quantity>.<cells>` reads it too
    reductions/<reduction>.py      one kind of reader (counter, loadgen, trace)
"""

from __future__ import annotations

import importlib
import json
from dataclasses import dataclass, field
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class SpecError(ValueError):
    """BENCHMARK.json or one of the files it names is missing or wrong."""


def _read_json(path: Path) -> dict:
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing benchmark file: {path}") from None
    except json.JSONDecodeError as e:
        raise SpecError(f"{path}: {e}") from None


@dataclass
class Cell:
    """One `workloads` entry with everything it resolves to."""

    name: str
    chips: int
    why: str
    config: dict
    traffic: dict
    cell: dict  # cells/<workload>.json, {} when the cell has no file
    end_to_end: list[dict] = field(default_factory=list)
    per_layer: list[dict] = field(default_factory=list)  # BENCHMARK.json entries
    layer_metrics: dict[str, dict] = field(default_factory=dict)  # name -> file


class Spec:
    def __init__(self, root: Path | str = ROOT) -> None:
        self.root = Path(root)
        self.bench_dir = self.root / "benchmark"
        self.doc = _read_json(self.root / "BENCHMARK.json")

    # -- lookups by name ------------------------------------------------------

    def workload_names(self) -> list[str]:
        return [w["name"] for w in self.doc["workloads"]]

    def config(self, name: str) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                return _read_json(self.root / c["file"])
        raise SpecError(f"no configuration named {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return _read_json(self.bench_dir / "traffic" / f"{name}.json")

    def layer_metric(self, name: str) -> dict:
        """The file of a per-layer metric: its own, or its quantity's. One
        quantity whose cells report different end-to-end metrics is split
        in BENCHMARK.json (`scan_kernel_ms_per_pass.open` moves
        `recommend_p95_ms` and lists the open cells, `.sat` moves
        `recommend_qps`); how it is read is one file."""
        folder = self.bench_dir / "layer_metrics"
        own = folder / f"{name}.json"
        if own.exists() or "." not in name:
            return _read_json(own)
        return _read_json(folder / f"{name.rsplit('.', 1)[0]}.json")

    def peaks(self, device_kind: str) -> dict:
        table = _read_json(self.bench_dir / "peaks.json")
        if device_kind not in table:
            raise SpecError(
                f"device_kind {device_kind!r} is not in benchmark/peaks.json "
                f"(known: {sorted(table)}); add its published peaks with their source"
            )
        return table[device_kind]

    def _reports(self, metric: dict, workload: str, moved: set[str] | None) -> bool:
        """Does `metric` belong to `workload`? With a `workloads` key: if
        listed. Without: a per-layer metric goes wherever the end-to-end
        metric it moves is reported; an end-to-end metric goes everywhere."""
        if "workloads" in metric:
            return workload in metric["workloads"]
        return moved is None or metric["moves"] in moved

    def cell(self, workload: str) -> Cell:
        for w in self.doc["workloads"]:
            if w["name"] == workload:
                break
        else:
            raise SpecError(
                f"no workload named {workload!r}; BENCHMARK.json has {self.workload_names()}"
            )
        cell_file = self.bench_dir / "cells" / f"{workload}.json"
        e2e = [m for m in self.doc["end_to_end"] if self._reports(m, workload, None)]
        moved = {m["name"] for m in e2e}
        per_layer = [m for m in self.doc["per_layer"] if self._reports(m, workload, moved)]
        return Cell(
            name=workload,
            chips=int(w["chips"]),
            why=w["why"],
            config=self.config(w["config"]),
            traffic=self.traffic(w["traffic"]),
            cell=_read_json(cell_file) if cell_file.exists() else {},
            end_to_end=e2e,
            per_layer=per_layer,
            layer_metrics={m["name"]: self.layer_metric(m["name"]) for m in per_layer},
        )


# -- code found by name ---------------------------------------------------------


def load_module(kind: str, name: str):
    """`benchmark.<kind>.<name>`: drivers, builders, reductions."""
    if not name.replace("_", "").isalnum():
        raise SpecError(f"bad {kind} name {name!r}")
    try:
        return importlib.import_module(f"benchmark.{kind}.{name}")
    except ModuleNotFoundError as e:
        if e.name == f"benchmark.{kind}.{name}":
            raise SpecError(f"no benchmark/{kind}/{name}.py") from None
        raise
