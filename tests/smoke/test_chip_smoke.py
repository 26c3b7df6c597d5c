"""chip_smoke.py on a machine without a chip: it must refuse, and its
phase helpers must work. The CPU rehearsal here (tiny sizes, Pallas under
the interpreter) is what the on-chip-measurement guide asks for before
chip time is spent; what it proves about speed is nothing."""

import json
import shutil
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent.parent
SMOKE = REPO / "chip_smoke.py"


def run_smoke(cwd, script, *args, env_extra=None):
    import os

    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run(
        [sys.executable, str(script), *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def last_line_is_result(stdout: str) -> bool:
    lines = [ln for ln in stdout.splitlines() if ln.strip()]
    try:
        return bool(lines) and json.loads(lines[-1]).get("ok") is True
    except ValueError:
        return False


def test_no_chip_fails_naming_the_phase_and_the_platform(tmp_path):
    """JAX_PLATFORMS=cpu in the caller's environment changes nothing: every
    child is started with JAX_PLATFORMS=tpu and JAX refuses the missing
    chip in the first phase."""
    r = run_smoke(
        REPO, SMOKE, "--out", str(tmp_path / "out"),
        "--users", "300", "--items", "500", "--ratings", "5000",
    )
    assert r.returncode != 0
    assert "FAILED in phase batch" in r.stdout
    assert "tpu" in r.stdout.lower()
    assert not last_line_is_result(r.stdout)
    # and nothing it started is left running
    ps = subprocess.run(["ps", "-eo", "args"], capture_output=True, text=True).stdout
    assert str(tmp_path / "out") not in ps


def test_alone_in_a_directory_it_fails_without_a_result(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    r = run_smoke(tmp_path, tmp_path / "chip_smoke.py")
    assert r.returncode != 0
    assert "FAILED in phase setup" in r.stdout
    assert not last_line_is_result(r.stdout)


def test_last_line_of_a_pass_is_the_contract_object_and_nothing_more(tmp_path, monkeypatch,
                                                                    capsys):
    """Whoever runs the smoke reads the last line of stdout and takes
    exactly {"ok", "device": {"platform", "kind", "count"}}; the phases,
    sizes and cuts are on the report line before it."""
    sys.path.insert(0, str(REPO))
    import chip_smoke as cs

    full = {"phases": {"batch": {"platform": "tpu", "wall_s": 1.0}}, "ok": True,
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
            "sizes": {"features": 250}, "reduced": [], "native": "built-here",
            "wall_s": 2.0, "note": "x"}
    monkeypatch.setattr(cs, "HERE", tmp_path)  # the report goes under it
    monkeypatch.setattr(cs, "run", lambda plan: full)
    assert cs.main(["--out", str(tmp_path / "out")]) == 0
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    last = json.loads(lines[-1])
    assert last == {"ok": True,
                    "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    assert list(last) == ["ok", "device"] and isinstance(last["device"]["count"], int)
    assert lines[-2].startswith("chip_smoke: report ")
    assert json.loads(lines[-2].split("report ", 1)[1]) == full
    assert json.loads((tmp_path / "chiprun_out/chip_smoke/result.json").read_text()) == full


def test_parent_imports_neither_jax_nor_the_package():
    code = (
        "import sys; sys.path.insert(0, %r); import chip_smoke; "
        "bad = [m for m in sys.modules if m == 'jax' or m.startswith(('jax.', 'oryx_tpu'))]; "
        "print(bad); sys.exit(1 if bad else 0)" % str(REPO)
    )
    r = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr


def test_phases_rehearsed_tiny_on_cpu(tmp_path):
    """batch -> serving -> speed -> kernels -> the mesh phases through the
    same helpers the chip run uses, each layer a `python -m oryx_tpu
    <layer>` child told its platform (cpu here, with conftest's 8 virtual
    devices, so the mesh paths run), references computed by the parent."""
    sys.path.insert(0, str(REPO))
    import chip_smoke as cs

    plan = cs.Plan(
        out=tmp_path / "out", platform="cpu", users=300, items=800, ratings=8000,
        sweeps=2, features=16, events=500, sample_users=8,
        kernel_args=("--tiny", "--interpret", "--only",
                     "scan/50f/int8/dot/one-group/single,fold-in"),
        deadline=time.monotonic() + 280,
    )
    result = cs.run(plan, {"serving-int8", "speed", "kernels", "mesh"})
    assert result["ok"] is True
    assert result["device"] == {"platform": "cpu", "kind": "cpu",
                                "count": result["device"]["count"]}
    assert json.loads(cs.result_line(result)) == {"ok": True, "device": result["device"]}
    phases = result["phases"]
    assert set(phases) == {"batch", "serving-int8", "speed", "kernels",
                           "batch-shard-factors", "serving-shard-items"}
    assert all(p["platform"] == "cpu" for p in phases.values())
    assert phases["batch"]["update_topic"]["UP-Y"] == phases["batch"]["items"]
    assert phases["batch"]["eval_auc"] >= cs.MIN_AUC
    assert phases["serving-int8"]["recall"] >= 0.99
    assert phases["serving-int8"]["indexed_queries"] >= 8
    assert phases["serving-int8"]["vector_queries"] >= 1
    assert phases["speed"]["host_fold_events"] == 0
    assert phases["speed"]["device_fold_events"] == phases["speed"]["aggregated_events"]
    assert phases["kernels"]["interpret"] is True and phases["kernels"]["checks"] == 2
    # the mesh phases say where the data is, and fail if it is not spread
    n_devices = result["device"]["count"]
    assert n_devices > 1
    for name in ("batch", "batch-shard-factors", "serving-shard-items"):
        assert phases[name]["shards"].count("dev") == n_devices, phases[name]["shards"]
    sharded = phases["serving-shard-items"]
    assert sharded["sharded_queries"] >= sharded["answers"]
    # through the batcher like any handle: counted by submit kind beside it
    assert sharded["sharded_queries"] == sharded["vector_queries"] + sharded["indexed_queries"]
    assert sharded["indexed_queries"] >= 8 and sharded["vector_queries"] >= 1
    # scale is cut here, so every cut with a recorded reason is listed
    assert {r["what"] for r in result["reduced"]} == {r["what"] for r in cs.REDUCED}

    # a child on another platform than the plan's fails the phase
    import pytest

    wrong = cs.Plan(out=tmp_path / "out2", platform="tpu", users=300, items=800,
                    ratings=8000, sweeps=2, features=16,
                    deadline=time.monotonic() + 120)
    with pytest.raises(cs.PhaseFailed) as e:
        cs.check_device("batch", phases["batch"], wrong)
    assert "expected platform tpu" in e.value.reason


def test_a_mesh_phase_that_did_not_shard_fails(tmp_path):
    sys.path.insert(0, str(REPO))
    import chip_smoke as cs
    import pytest

    plan = cs.Plan(out=tmp_path, platform="cpu")
    plan.logs.mkdir(parents=True)
    log = plan.logs / "serving-shard-items.log"
    spread = "sharded item matrix, 8 items, shards: dev0:(4, 250) dev1:(4, 250)"
    log.write_text(f"INFO x: {spread}\n")
    assert cs.sharded_over(plan, "serving-shard-items", "sharded item matrix", 2) == (
        "dev0:(4, 250) dev1:(4, 250)")
    for text in ("nothing logged\n",
                 "sharded item matrix, 8 items, shards: dev0:(8, 250)\n",
                 "sharded item matrix, 8 items, shards: dev0:(8, 250) dev1:(0, 250)\n"):
        log.write_text(text)
        with pytest.raises(cs.PhaseFailed):
            cs.sharded_over(plan, "serving-shard-items", "sharded item matrix", 2)
