"""tools/scan_rounds.py `--compare`: two checkouts' outputs of the served
scan program agree when ids and the gate's counts are equal and scores lie
within 1e-6 of the case's score scale (since PR 30 a float32 matrix is
stored as a main and a tail plane, and a parent from before sums two of
its products in another order, so bit for bit is no longer the demand)."""

import importlib.util
from pathlib import Path

import numpy as np
import pytest

TOOL = Path(__file__).resolve().parents[2] / "tools" / "scan_rounds.py"


@pytest.fixture(scope="module")
def tool():
    spec = importlib.util.spec_from_file_location("scan_rounds_tool", TOOL)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _case(**changes):
    vals = np.array([[40.0, 30.0, 20.0], [35.0, 25.0, 15.0]], np.float32)
    case = dict(vals=vals, idxs=np.array([[7, 3, 9], [1, 8, 2]], np.int32), gated_tiles=12, rounds=31)
    case.update(changes)
    return {k: v for k, v in case.items() if v is not None}


OTHER = {
    "the same": (_case(), 0),
    "scores in the last bits": (_case(vals=_case()["vals"] * np.float32(1 + 2e-7)), 0),
    "scores off by 1e-5 of scale": (_case(vals=_case()["vals"] + np.float32(4e-4)), 1),
    "an id swapped": (_case(idxs=np.array([[3, 7, 9], [1, 8, 2]], np.int32)), 1),
    "one more round": (_case(rounds=32), 1),
    "one more gated tile": (_case(gated_tiles=13), 1),
    "scores as bfloat16-wide float16": (_case(vals=_case()["vals"].astype(np.float16)), 1),
    "no counts on one side": (_case(gated_tiles=None, rounds=None), 0),
}


@pytest.mark.parametrize("name", OTHER)
def test_compare_demands_ids_counts_and_scores_to_1e6_of_scale(tool, tmp_path, capsys, name):
    other, differ = OTHER[name]
    a, b = tmp_path / "a", tmp_path / "b"
    a.mkdir(), b.mkdir()
    np.savez(a / "20000000x50-b8-distinct8.npz", **_case())
    np.savez(b / "20000000x50-b8-distinct8.npz", **other)
    assert tool.compare(a, b) == differ
    assert f"compared 1 cases, {differ} differ" in capsys.readouterr().out


def test_compare_refuses_two_runs_of_other_cases(tool, tmp_path):
    a, b = tmp_path / "a", tmp_path / "b"
    a.mkdir(), b.mkdir()
    np.savez(a / "x.npz", **_case())
    assert tool.compare(a, b) == 1
