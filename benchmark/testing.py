"""For tests/benchmark/: a temporary copy of the benchmark with tiny cells
ADDED to it (a configuration, two traffic mixes, cells, and their entries
in BENCHMARK.json). No file that is there is edited; in BENCHMARK.json the
tiny cells' names are appended to the lists of the cells they are cut from
and one entry of their own is added. (That a cell can also join by added
entries alone, `.suffix` twins of its own, is
tests/benchmark/test_bench_contract.py's `_grown("own-suffix")`.)"""

from __future__ import annotations

import json
import shutil
from pathlib import Path

from benchmark.spec import ROOT

TINY_CONFIG = "tiny-als-16f"
TINY_OPEN = "tiny-recommend-open"
TINY_SAT = "tiny-recommend-sat"


def _write(path: Path, doc: dict) -> None:
    if path.exists():
        raise AssertionError(f"{path} exists: a test must add files, not edit them")
    path.write_text(json.dumps(doc, indent=1) + "\n", encoding="utf-8")


def make_copy(tmp: Path, features: int = 16, items: int = 3000, users: int = 400) -> Path:
    root = Path(tmp) / "checkout"
    root.mkdir()
    shutil.copytree(
        ROOT / "benchmark", root / "benchmark", ignore=shutil.ignore_patterns("__pycache__")
    )
    bench = root / "benchmark"
    base = json.loads((bench / "configs" / "als-50f-20m-f32.json").read_text())
    base.update(name=TINY_CONFIG, features=features, items=items, users=users,
                source="test", reduced=["items", "users"])
    _write(bench / "configs" / f"{TINY_CONFIG}.json", base)

    open_mix = json.loads((bench / "traffic" / "recommend-open.json").read_text())
    open_mix.update(name="tiny-open", warm_seconds=1, workers=16, warm_batch_buckets=[8],
                    check_users=16, check_sample_every=10, trace_seconds=1)
    _write(bench / "traffic" / "tiny-open.json", open_mix)
    closed_mix = json.loads((bench / "traffic" / "recommend-closed32.json").read_text())
    closed_mix.update(name="tiny-closed4", clients=4, warm_seconds=1, warm_batch_buckets=[8],
                      check_users=16, check_sample_every=10, trace_seconds=1)
    _write(bench / "traffic" / "tiny-closed4.json", closed_mix)
    _write(bench / "cells" / f"{TINY_OPEN}.json", {"rate_per_s": 60})

    # a per-layer metric of its own: a new file that names an existing reduction
    _write(
        bench / "layer_metrics" / "scan_queries.tiny.json",
        {"name": "scan_queries.tiny", "layer": "batcher", "unit": "queries", "better": "higher",
         "source": "program_counter", "reduction": "counter_ratio",
         "args": {"num": [["serving.scan.indexed.queries", "value"],
                          ["serving.scan.vector.queries", "value"]], "span": "window"}},
    )

    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    doc["configs"].append({"name": TINY_CONFIG, "source": "test", "reduced": ["items", "users"],
                           "file": f"benchmark/configs/{TINY_CONFIG}.json", "why": "tier-1 test"})
    doc["workloads"].append({"name": TINY_OPEN, "config": TINY_CONFIG, "traffic": "tiny-open",
                             "chips": 1, "why": "tier-1 test"})
    doc["workloads"].append({"name": TINY_SAT, "config": TINY_CONFIG, "traffic": "tiny-closed4",
                             "chips": 1, "why": "tier-1 test"})
    # each tiny cell joins the lists of the cell it is cut from, as a later
    # PR's cell may join a folded entry's list
    for m in doc["end_to_end"] + doc["per_layer"]:
        for twin, tiny in (("als50-recommend-open", TINY_OPEN), ("als250-recommend-sat", TINY_SAT)):
            if twin in m.get("workloads", ()):
                m["workloads"] = m["workloads"] + [tiny]
    doc["per_layer"].append(
        {"name": "scan_queries.tiny", "unit": "queries", "better": "higher",
         "source": "program_counter", "layer": "batcher", "moves": "recommend_p95_ms",
         "workloads": [TINY_OPEN]}
    )
    (root / "BENCHMARK.json").write_text(json.dumps(doc, indent=1) + "\n", encoding="utf-8")
    return root
