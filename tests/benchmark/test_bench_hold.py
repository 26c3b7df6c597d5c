"""`held_pass_pct` (PR 32): the share of a window's passes whose batch the
batcher kept open behind the pass ahead. A data file on `counter_ratio`, no
reader of its own: each of its entries in BENCHMARK.json reads that file, a
program without the counter reads 0 and not nothing, and the tiny CPU cell
prints a number. None is a device number."""

import json
from types import SimpleNamespace

import pytest

from benchmark import run as bench_run
from benchmark import testing
from benchmark.reductions import counter_ratio
from benchmark.spec import ROOT, Spec

DOC = json.loads((ROOT / "BENCHMARK.json").read_text())
ENTRIES = [m for m in DOC["per_layer"] if m["name"].startswith("held_pass_pct.")]


@pytest.mark.parametrize("entry", ENTRIES, ids=lambda m: m["name"])
def test_an_entry_reads_the_one_file_and_names_cells_that_exist(entry):
    file = Spec().layer_metric(entry["name"])
    assert file["name"] == "held_pass_pct" and file["reduction"] == "counter_ratio"
    assert file["args"]["num"] == [["serving.batcher.pass.held", "value"]]
    assert file["args"]["den"] == [["serving.batcher.passes", "value"]]
    assert entry["workloads"] and set(entry["workloads"]) <= {w["name"] for w in DOC["workloads"]}


def test_a_program_without_the_counter_reads_zero_and_one_with_it_its_share():
    args = Spec().layer_metric("held_pass_pct.open")["args"]
    read = lambda span: counter_ratio.read(SimpleNamespace(counters={"window": span}), args)
    passes = lambda n: {"serving.batcher.passes": {"type": "counter", "value": n}}
    held = lambda n: {"serving.batcher.pass.held": {"type": "counter", "value": n}}
    assert read((passes(10), passes(110))) == 0.0  # the parent: passes, and no such counter
    assert read(({**passes(10), **held(4)}, {**passes(110), **held(29)})) == 25.0
    assert read((passes(10), passes(10))) is None  # no pass in the window: no share
    assert read(None) is None


@pytest.mark.parametrize("workload, suffix", [(testing.TINY_OPEN, ".open"), (testing.TINY_SAT, ".sat")])
def test_the_tiny_cpu_cell_prints_it(tmp_path, monkeypatch, workload, suffix):
    """The real batcher under the real front: the share is printed beside
    its siblings and reads 0, because a tiny pass on the CPU (under a
    millisecond) is shorter than any lead: such a backend never holds."""
    from oryx_tpu.serving import batcher

    root = testing.make_copy(tmp_path)
    peaks = json.loads((root / "benchmark" / "peaks.json").read_text())
    monkeypatch.setattr(Spec, "peaks", lambda self, kind: peaks["TPU v5 lite"])
    batcher.close_default_batcher()  # one that predates a cleared registry holds stale handles
    out, _lines = bench_run.run_cell(Spec(root), workload, 2**31 + 32, 2.0, True, require_chip=False)
    assert out["correct"] is True
    share = out["metrics"]["held_pass_pct" + suffix]
    assert share["unit"] == "%" and share["value"] == 0.0
    assert "inflight_depth_mean" + suffix in out["metrics"]
