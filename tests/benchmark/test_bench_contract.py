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
    assert 1 <= DOC["run_seconds"] <= 51
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


# -- one entry a (quantity, end-to-end metric) pair since PR 39 ----------------------------
#
# `<quantity>.open` moves `recommend_p95_ms` and LISTS the open cells that
# can read it, `<quantity>.sat` moves `recommend_qps`; what one cell alone
# reads keeps that cell's suffix. Nothing below needs an edit for a cell or
# an entry that a later PR ADDS: the sets written out are those of PR 39's
# six cells and say what each must still report (more is fine: a `tracing`
# PR's new entry may list them), the rules of the table are checked on the
# file as it stands AND on a copy grown by a seventh cell, once by each of
# the two ways a cell can join (`_grown`).

OPEN_EVERY_CELL = {  # counters, the generator's clock and the trace's planes: any open cell
    "generator_late_p99_ms", "generator_pause_max_ms", "handler_mean_ms", "server_pause_max_ms",
    "device_idle_pct", "compiles_in_window", "recommend_p50_ms", "recommend_p99_ms",
    "window_failed_pct", "queue_wait_mean_ms", "pass_inflight_mean_ms", "deliver_mean_ms",
    "window_rows_per_pass", "useful_rows_pct", "inflight_depth_mean", "scan_kernel_ms_per_pass",
    "held_pass_pct", "submit_mean_ms",
}
HOST_PATH = {  # PR 35's stages: the same code serves every cell
    "front_ingress_mean_ms", "front_respond_mean_ms", "handler_pre_mean_ms",
    "handler_post_mean_ms", "batcher_entry_mean_ms", "waiter_wake_mean_ms",
    "handler_cpu_ms_per_request", "server_cpu_ms_per_request", "pass_cpu_ms_per_pass",
    "result_lag_ms",
}
# readers that are wrong on four chips: 20M items against ONE chip's peaks,
# and counters by submit kind beside which a sharded row is counted again
ONE_CHIP_ONLY = {"scan_roofline", "scan_rows_per_pass", "indexed_submit_pct"}
SAT = {
    "closed_p95_ms", "generator_pause_max_ms", "handler_mean_ms", "server_pause_max_ms",
    "scan_rows_per_pass", "scan_roofline", "device_idle_pct", "window_failed_pct",
    "queue_wait_mean_ms", "pass_inflight_mean_ms", "window_rows_per_pass", "useful_rows_pct",
    "inflight_depth_mean", "scan_kernel_ms_per_pass", "deliver_mean_ms", "held_pass_pct",
    "submit_mean_ms", "front_ingress_mean_ms", "front_respond_mean_ms", "handler_pre_mean_ms",
    "handler_post_mean_ms", "batcher_entry_mean_ms", "waiter_wake_mean_ms",
    "server_cpu_ms_per_request",
}
X4_OWN = {"shard_scan_roofline.x4", "shard_merge_ms_per_pass.x4", "shard_skew_pct.x4",
          "sharded_submit_pct.x4"}
FOUR_CHIP_CELL = "als250x20m-recommend-open"


def _open(*groups):
    return {f"{q}.open" for g in groups for q in g}


REPORTS = {  # PR 39's six cells: 31, 31, 24, 32, 33 and 32 names
    "als50-recommend-open": _open(OPEN_EVERY_CELL, HOST_PATH, ONE_CHIP_ONLY),
    "als250-recommend-open": _open(OPEN_EVERY_CELL, HOST_PATH, ONE_CHIP_ONLY),
    "als250-recommend-sat": {f"{q}.sat" for q in SAT},
    FOUR_CHIP_CELL: _open(OPEN_EVERY_CELL, HOST_PATH) | X4_OWN,
    "als250u5m-recommend-open": _open(OPEN_EVERY_CELL, HOST_PATH, ONE_CHIP_ONLY)
    | {"unstaged_requests.users", "stage_users_s.users"},
    "als250-similarity-open": _open(OPEN_EVERY_CELL, HOST_PATH, ONE_CHIP_ONLY)
    | {"cosine_submit_pct.sim"},
}


def _reported(doc, workload):
    spec = Spec()
    spec.doc = doc
    return {m["name"] for m in spec.cell(workload).per_layer}


@pytest.mark.parametrize("workload", sorted(REPORTS))
def test_a_cell_of_pr_39_still_reports_these_per_layer_metrics(workload):
    """Written out, so that a cell which silently loses a reading fails
    here and not on the chip. A cell a later PR adds is not written out
    here and needs no line of this file."""
    missing = REPORTS[workload] - _reported(DOC, workload)
    assert not missing, sorted(missing)


PAIRS = [(m["name"], w) for m in DOC["per_layer"] for w in m.get("workloads", [])]


@pytest.mark.parametrize("name, workload", PAIRS, ids=[f"{n}-{w}" for n, w in PAIRS])
def test_a_listed_cell_carries_the_entry_and_can_read_it(name, workload):
    cell = Spec().cell(workload)
    entry = next(m for m in cell.per_layer if m["name"] == name)  # the cell carries it
    assert entry["moves"] in {m["name"] for m in cell.end_to_end}  # and reports what it moves
    file = cell.layer_metrics[name]
    assert file["name"] in (name, name.rsplit(".", 1)[0])
    reader = spec_mod.load_module("reductions", file["reduction"])
    assert callable(reader.read) and isinstance(file.get("args", {}), dict)


SEVENTH = "a-seventh-cell-open"
TWIN_OF = "als250-recommend-open"


def _grown(route):
    """BENCHMARK.json with a seventh open cell that reads what
    `als250-recommend-open` reads. `own-suffix`: the route of a PR that may
    only ADD entries: a `.seventh` twin of each shared quantity, each with
    the one cell on its list and read by the quantity's one file.
    `on-the-lists`: its name appended to the folded `.open` lists, as
    `recommend_p95_ms`'s list takes every new open cell's."""
    doc = json.loads(json.dumps(DOC))
    doc["workloads"].append({"name": SEVENTH, "config": "als-50f-20m-f32",
                             "traffic": "similarity-open", "chips": 1, "why": "a test's"})
    p95 = next(m for m in doc["end_to_end"] if m["name"] == "recommend_p95_ms")
    p95["workloads"].append(SEVENTH)
    shared = [m for m in doc["per_layer"]
              if m["name"].endswith(".open") and TWIN_OF in m["workloads"]]
    for m in shared:
        if route == "on-the-lists":
            m["workloads"].append(SEVENTH)
        else:
            twin = dict(m, name=m["name"].rsplit(".", 1)[0] + ".seventh", workloads=[SEVENTH])
            doc["per_layer"].append(twin)
    return doc


def _check_table(doc):
    """The rules of the per-layer table that hold however many cells and
    entries later PRs add."""
    cells = {w["name"] for w in doc["workloads"]}
    end_to_end = {m["name"]: m for m in doc["end_to_end"]}
    names = [m["name"] for m in doc["per_layer"]]
    assert len(names) == len(set(names))
    for m in doc["per_layer"]:
        # never without a list: a later cell that reports `recommend_p95_ms`
        # must not inherit an entry whose reader is wrong for it
        assert m.get("workloads"), m["name"]
        assert len(m["workloads"]) == len(set(m["workloads"])), m["name"]
        assert set(m["workloads"]) <= cells, m["name"]
        # every cell on the list reports what the entry moves
        moved = end_to_end[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", cells)), m["name"]
        if m["name"].rsplit(".", 1)[0] in ONE_CHIP_ONLY:
            assert FOUR_CHIP_CELL not in m["workloads"], m["name"]
    # the folded suffixes: one entry a (quantity, end-to-end metric) pair.
    # A cell's own suffix may twin a folded quantity: that is how a PR that
    # may only add brings a cell
    for suffix, moves in ((".open", "recommend_p95_ms"), (".sat", "recommend_qps")):
        folded = [m for m in doc["per_layer"] if m["name"].endswith(suffix)]
        assert all(m["moves"] == moves for m in folded), suffix
    # ... so that no cell reads one quantity twice for one end-to-end metric
    for cell in cells:
        spec = Spec()
        spec.doc = doc
        read = [(m["name"].rsplit(".", 1)[0], m["moves"]) for m in spec.cell(cell).per_layer]
        assert len(read) == len(set(read)), cell


@pytest.mark.parametrize("route", ["as-committed", "own-suffix", "on-the-lists"])
def test_the_table_s_rules_hold_as_committed_and_with_a_seventh_cell(route):
    doc = DOC if route == "as-committed" else _grown(route)
    _check_table(doc)
    for workload in WORKLOADS:  # no cell that was there reads more or less for the newcomer
        assert _reported(doc, workload) == _reported(DOC, workload)
    if route != "as-committed":
        got, twin = _reported(doc, SEVENTH), _reported(DOC, TWIN_OF)
        suffix = ".seventh" if route == "own-suffix" else ".open"
        assert {n.rsplit(".", 1)[0] for n in got} == {n.rsplit(".", 1)[0] for n in twin}
        assert all(n.endswith(suffix) for n in got) and len(got) == len(twin)
        spec = Spec()
        spec.doc = doc
        files = spec.cell(SEVENTH).layer_metrics
        assert all(files[n]["name"] == n.rsplit(".", 1)[0] for n in got)  # the quantity's one file


def test_the_fold_of_pr_39_stands():
    """What PR 39 folded stays folded: `.open` and `.sat` hold one entry a
    quantity, and none of the suffixes it took away (`.x4`, `.users`,
    `.sim` twins of a folded quantity) is back for a cell that is on the
    folded list."""
    for suffix in (".open", ".sat"):
        quantities = [m["name"] for m in DOC["per_layer"] if m["name"].endswith(suffix)]
        assert len(quantities) == len(set(quantities))
    folded = {m["name"].rsplit(".", 1)[0]: m for m in DOC["per_layer"] if m["name"].endswith(".open")}
    for m in DOC["per_layer"]:
        quantity, suffix = m["name"].rsplit(".", 1)
        if suffix not in ("open", "sat") and quantity in folded:
            assert not set(m["workloads"]) & set(folded[quantity]["workloads"]), m["name"]


def test_the_table_is_within_the_contract_s_limit():
    """The contract refuses a file of more than 128 per-layer entries; PR
    39 left 62, and a cell that twins the shared quantities brings about 32."""
    assert len(DOC["per_layer"]) <= 128


def test_the_two_quantities_pruned_by_pr_39_have_neither_entry_nor_file():
    """`scan_ms_per_pass` equalled `scan_kernel_ms_per_pass` to four digits
    on every ledger line; `inflight_cap_changes` read 0 since PR 28."""
    for q in ("scan_ms_per_pass", "inflight_cap_changes"):
        assert not [m["name"] for m in DOC["per_layer"] if m["name"].startswith(q + ".")]
        assert not (ROOT / "benchmark" / "layer_metrics" / f"{q}.json").exists()


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
