"""BENCHMARK.json against the contract, and every name it uses against
the files of benchmark/ (tier-1, no JAX)."""

import json
import re

import pytest

from benchmark import spec as spec_mod
from benchmark.spec import ROOT, Spec, SpecError

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
DOC = json.loads((ROOT / "BENCHMARK.json").read_text())
WORKLOADS = [w["name"] for w in DOC["workloads"]]
METRICS = DOC["end_to_end"] + DOC["per_layer"]


def test_top_level_keys_and_limits():
    assert set(DOC) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"
    }
    assert DOC["paths"] == ["benchmark", "tests/benchmark"]
    assert DOC["command"] == ["python3", "-m", "benchmark.run"]
    assert isinstance(DOC["run_seconds"], int) and 1 <= DOC["run_seconds"] <= 51
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert sum(w["chips"] == 4 for w in DOC["workloads"]) <= max(1, len(WORKLOADS) // 4)


@pytest.mark.parametrize("entry", DOC["configs"], ids=lambda c: c["name"])
def test_config_entry(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"])
    assert entry["file"].startswith("benchmark/") and (ROOT / entry["file"]).is_file()
    for text in (entry["source"], entry["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    held = json.loads((ROOT / entry["file"]).read_text())
    assert held["name"] == entry["name"] and held["source"] == entry["source"]
    assert held["reduced"] == entry["reduced"]
    # no width may be cut: the reference's table rows, as published
    assert not {"features", "items"} & set(entry["reduced"])
    assert any(w["config"] == entry["name"] for w in DOC["workloads"])


@pytest.mark.parametrize("entry", DOC["workloads"], ids=lambda w: w["name"])
def test_workload_entry_resolves_every_file_by_name(entry):
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert entry["chips"] in (1, 4)
    assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    for key in ("name", "config", "traffic"):
        assert NAME.match(entry[key])
    cell = Spec().cell(entry["name"])
    assert cell.config["name"] == entry["config"]
    assert cell.traffic["name"] == entry["traffic"]
    spec_mod.load_module("drivers", cell.traffic["driver"])
    spec_mod.load_module("builders", cell.config["builder"])
    if cell.traffic["driver"] == "open_http":
        assert cell.cell["rate_per_s"] > 0
    names = {m["name"] for m in cell.end_to_end}
    assert names - {"setup_s"} == set(cell.traffic["yields"])  # the mix says what it yields
    assert "setup_s" in names and len(names) >= 2 and cell.per_layer
    for m in cell.per_layer:
        file = cell.layer_metrics[m["name"]]
        assert hasattr(spec_mod.load_module("reductions", file["reduction"]), "read")
        assert m["moves"] in names


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    per_layer = metric in DOC["per_layer"]
    allowed = {"name", "unit", "better", "source", "workloads"}
    allowed |= {"layer", "moves"} if per_layer else {"bound"}
    assert set(metric) <= allowed and allowed - {"workloads"} <= set(metric)
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    sources = {"device_trace", "program_span", "program_counter", "host_clock"}
    assert metric["source"] in (sources if per_layer else {"host_clock", "device_trace"})
    for w in metric.get("workloads", []):
        assert w in WORKLOADS
    if per_layer:
        file = Spec().layer_metric(metric["name"])
        assert file["name"] in (metric["name"], metric["name"].rsplit(".", 1)[0])
        for key in ("unit", "better", "source", "layer"):
            assert file[key] == metric[key], key
        assert metric["moves"] in {m["name"] for m in DOC["end_to_end"]}
        if metric["name"].split(".")[0].endswith("_roofline"):
            assert metric["unit"] == "%"
    else:
        assert 0.01 <= metric["bound"] <= 0.1


def test_names_are_unique_and_layers_spelled_alike():
    for group in (DOC["configs"], DOC["workloads"], METRICS):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in DOC["workloads"]]
    assert len(pairs) == len(set(pairs))
    layers = {m["layer"] for m in DOC["per_layer"]}
    assert len({la.lower() for la in layers}) == len(layers)


@pytest.mark.parametrize("quantity", sorted({m["name"].rsplit(".", 1)[0] for m in DOC["per_layer"]}))
def test_one_file_reads_a_quantity_for_all_its_cells(quantity):
    """`<quantity>.open` and `<quantity>.sat` differ in what they move,
    which BENCHMARK.json says; how the quantity is read is one file."""
    names = [m["name"] for m in DOC["per_layer"] if m["name"].rsplit(".", 1)[0] == quantity]
    files = [Spec().layer_metric(n) for n in names]
    assert all(f == files[0] for f in files) and files[0]["name"] == quantity
    assert "moves" not in files[0]  # said once, in BENCHMARK.json
    assert (ROOT / "benchmark" / "layer_metrics" / f"{quantity}.json").is_file()


def test_a_metric_with_a_file_of_its_own_reads_that_one(tmp_path):
    from benchmark import testing

    root = testing.make_copy(tmp_path)
    assert Spec(root).layer_metric("scan_queries.tiny")["name"] == "scan_queries.tiny"
    with pytest.raises(SpecError):
        Spec(root).layer_metric("no_such_quantity.open")


def test_every_bucket_a_mix_can_reach_is_warmed_and_no_other():
    """A batch holds at most the requests the mix keeps outstanding (the
    open mix's connections, the closed mix's clients): every power-of-two
    bucket from 8 up to that is warmed, because a pause of the machine
    fills them and a cold one compiles inside the window (PERF.md,
    section 6, finding 8), and none beyond it."""
    for w in WORKLOADS:
        traffic = Spec().cell(w).traffic
        outstanding = int(traffic.get("workers") or traffic["clients"])
        want, b = [], 8
        while b < 2 * outstanding and (not want or want[-1] < outstanding):
            want.append(b)
            b *= 2
        assert traffic["warm_batch_buckets"] == want, w


def test_peaks_refuse_an_unknown_device_kind():
    assert Spec().peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SpecError, match="not in benchmark/peaks.json"):
        Spec().peaks("TPU v9 imaginary")
    with pytest.raises(SpecError, match="not in benchmark/peaks.json"):
        Spec().peaks("cpu")


def test_unknown_names_are_errors_not_defaults():
    with pytest.raises(SpecError):
        Spec().cell("no-such-cell")
    with pytest.raises(SpecError):
        spec_mod.load_module("drivers", "no_such_driver")
    with pytest.raises(SpecError):
        Spec().traffic("no-such-mix")
