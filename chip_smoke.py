#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that oryx_tpu still starts on the chip.

Drives the lambda path once through the entry points a user would call,
at the full width of the widest model the reference publishes (ALS
implicit, 250 features; BASELINE.md), on one `file:` bus and one
data/model root under ``--out``:

    batch    python -m oryx_tpu batch    one BatchLayer generation, promoted
    serving  python -m oryx_tpu serving  replay, /ready, HTTP answers at
                                         score-dtype float32, then int8
    speed    python -m oryx_tpu speed    fold-in-backend=device, UP deltas
    kernels  python tools/chip_kernels.py   every device kernel vs XLA f32
    (more than one device: a shard-factors generation and shard-items serving)

One chip has one owner, so every phase is its own process, started with
JAX_PLATFORMS=<platform> (so JAX itself refuses a missing chip) and
stopped before the next starts. THIS process never imports jax or
oryx_tpu: NumPy, an HTTP client and subprocess only. It computes the
references itself, from the generation's X/ and Y/ files and its own
seeded ratings, with the endpoints' semantics.

It fails (non-zero exit, the phase and the reason on the last lines, no
result line) on the first phase that fails, times out, reports another
platform, or answers wrongly. On success it prints two lines: first
`chip_smoke: report {...}`, one JSON object with per phase the device it
reported, sizes, wall and compile seconds (smoke observations, not
benchmark metrics), every cut of scale under "reduced", and whether the
native library was built on this machine (also kept in
chiprun_out/chip_smoke/result.json); then, as the last line of stdout, the
result to the smoke contract and nothing more:
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
with the device as the first child's JAX reported it.

Tolerances of the HTTP checks (an answer is a ranked list of (id, score);
S = the exact float32 NumPy scores; scale = |q| * max|y|, or 1 for cosine):

- float32: every score within 1e-5 * scale of S[id], and every id a member
  of the exact top N up to 1e-5 * scale. The device scores in f32 at
  HIGHEST precision; only the summation order over 250 terms differs.
- int8: 5e-4 * scale. The scan ranks on the first int8 plane and rescores
  4N candidates with the residual plane, which leaves <= 2.5e-4 * scale;
  on the TPU the first plane's dot takes one bf16 MXU pass, which rounds
  the QUERY to 8 mantissa bits: elementwise 2^-9 relative, over 250 terms
  a standard deviation of ~7e-5 * scale, so 5e-4 is 7 of them (a NumPy
  model of that pass gives a maximum of 3.5e-4 over 4096 scores of the
  kernel phase's data, where the chip measured 3.3e-4; this phase measured
  7.8e-5 and 8.7e-5 on the chip, PR 21). Scores of an int8 matrix travel
  as bfloat16, so the returned VALUE is additionally within 2^-8 of
  itself; that allowance is wider than what the residual plane corrects,
  so it is tools/chip_kernels.py (f32 scores, 2.5e-4) and not this phase
  that shows the residual plane ran. The first plane can in principle
  drop a true top-N item before the rescore: recall over all answers >=
  0.99 is the engine's documented contract (docs/serving-scan.md).
- speed deltas: device fold (f32 Cholesky) vs this process's float64 fold:
  1e-3 of the largest component (f32 rounding 6e-8 times the Gramian's
  condition number, <= 1e3 for factor matrices, with an order to spare).
"""

from __future__ import annotations

import argparse
import atexit
import gzip
import json
import math
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
FEATURES = 250  # width is never cut

# The run to establish (ISSUE 21). --users/--items/--ratings can ask for it;
# the time limits below are sized for DEFAULT.
TARGET = {"users": 100_000, "items": 1_000_000, "ratings": 10_000_000, "sweeps": 3}
# What the 1200 s limit of the smoke contract leaves room for on a cold
# compile cache: see REDUCED for the measurement behind each cut.
DEFAULT = {"users": 5_000, "items": 50_000, "ratings": 500_000, "sweeps": 3}
_ALS = (
    "TPU v5 lite, one chip, cold compile cache (my chip runs, PR 21). At 1/10 of the "
    "target (10K users, 100K items, 1M ratings) the whole smoke took 895 s of the "
    "1200 s limit: batch phase 306 s (generation 269 s: trainer ~133 s of which 41 s "
    "compile, writing the X/ Y/ gzip JSON shards ~86 s, eval 15 s, publishing 110K UP "
    "rows ~30 s), serving 38 + 34 s, speed 33 s, kernels 482 s, and a four-chip host "
    "adds two more phases. The trainer alone, measured apart at 1/5 of the target "
    "(20K + 200K rows, 2.0M ratings): 3 sweeps 118 s warm + 36 s compile; its time "
    "goes with the row count (one 250 x 250 solve per row), so the target's 1.1M rows "
    "come to ~590 s for the trainer and several times that for the phase. Not "
    "measured at the target size itself: that run does not fit the chip budget of "
    "this PR either."
)
REDUCED: list[dict] = [  # printed under "reduced" for every size below TARGET
    {"what": "users", "target": TARGET["users"], "why": _ALS},
    {"what": "items", "target": TARGET["items"], "why": _ALS},
    {"what": "ratings", "target": TARGET["ratings"],
     "why": "kept at the target's 100 ratings per user and 10 per item for the cut "
            "users and items; " + _ALS},
]

TASTES = 20  # groups of users and items in the seeded ratings (make_ratings)
# Ratings without structure gave eval AUC 0.51 (my chip run, PR 21), which is
# what an untrained model gives too. With four ratings in five inside the
# user's own group, 3 sweeps reach 0.79 at a tenth of the default size on the
# chip (my chip run, PR 21) and, on XLA:CPU, 0.83 at the default size, 0.81 at
# a fifth of it (all 250 features) and 0.89 at the tests' size (16 features);
# a model that learned nothing stays near 0.5.
MIN_AUC = 0.65
TOL = {"float32": 1e-5, "int8": 5e-4}
MIN_RECALL = {"float32": 1.0, "int8": 0.99}
BF16_WIRE = 2.0**-8
FOLD_TOL = 1e-3


class PhaseFailed(Exception):
    def __init__(self, phase: str, reason: str) -> None:
        super().__init__(f"{phase}: {reason}")
        self.phase, self.reason = phase, reason


@dataclass
class Plan:
    out: Path
    seed: int = 0
    platform: str = "tpu"
    users: int = DEFAULT["users"]
    items: int = DEFAULT["items"]
    ratings: int = DEFAULT["ratings"]
    sweeps: int = DEFAULT["sweeps"]
    features: int = FEATURES
    events: int = 20_000
    sample_users: int = 64
    kernel_args: tuple = ()  # ("--tiny", "--interpret") for the CPU rehearsal
    deadline: float = field(default_factory=lambda: time.monotonic() + 1150.0)
    children: list = field(default_factory=list)  # (name, Popen) still running

    @property
    def bus(self) -> Path:
        return self.out / "bus"

    @property
    def model_dir(self) -> Path:
        return self.out / "model"

    @property
    def logs(self) -> Path:
        return self.out / "logs"

    def remaining(self) -> float:
        return self.deadline - time.monotonic()


# -- children ------------------------------------------------------------------


def child_env(platform: str) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = platform
    env["PYTHONPATH"] = str(HERE) + os.pathsep + env.get("PYTHONPATH", "")
    return env


def spawn(plan: Plan, name: str, argv: list[str], platform: str) -> subprocess.Popen:
    """Start one child with its platform named; output goes to its log."""
    plan.logs.mkdir(parents=True, exist_ok=True)
    log = open(plan.logs / f"{name}.log", "wb")
    proc = subprocess.Popen(
        [sys.executable, *argv], cwd=HERE, env=child_env(platform),
        stdout=log, stderr=subprocess.STDOUT, start_new_session=True,
    )
    log.close()
    plan.children.append((name, proc))
    return proc


def stop(plan: Plan, proc: subprocess.Popen, grace: float = 30.0) -> int:
    """SIGTERM, wait, SIGKILL the child's whole process group (a layer may
    have forked workers): the next phase starts only after this one has
    exited, because it owns the chip until then."""
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=grace)
        except subprocess.TimeoutExpired:
            pass
    try:
        os.killpg(proc.pid, signal.SIGKILL)  # stragglers of the group, if any
    except ProcessLookupError:
        pass
    proc.wait(timeout=30)
    plan.children[:] = [(n, p) for n, p in plan.children if p is not proc]
    return proc.returncode


def stop_all(plan: Plan) -> None:
    for _, proc in list(plan.children):
        stop(plan, proc, grace=5.0)


def sharded_over(plan: Plan, phase: str, what: str, n_devices: int) -> str:
    """Where the child's sharded array actually is. The sharded paths log
    `<what> ... shards: dev0:(r, c) dev1:(r, c) ...` from the array's
    addressable shards (parallel/mesh.py shard_layout). The phase fails
    unless that line is there and every device of the process holds a
    non-empty slice: a path that quietly did not shard is not a pass."""
    text = (plan.logs / f"{phase}.log").read_text("utf-8", "replace")
    layouts = [ln.split("shards:", 1)[1] for ln in text.splitlines()
               if what in ln and "shards:" in ln]
    if not layouts:
        raise PhaseFailed(phase, f"no '{what} ... shards:' line in the log: that path did not run")
    slices = {
        int(dev): math.prod(int(d) for d in dims.split(",") if d.strip())
        for dev, dims in re.findall(r"dev(\d+):\(([\d, ]*)\)", layouts[-1])
    }
    if len(slices) != n_devices or min(slices.values()) <= 0:
        raise PhaseFailed(phase, f"{what} on {n_devices} devices is laid out as:{layouts[-1]}")
    return layouts[-1].strip()


def log_tail(plan: Plan, name: str, lines: int = 12) -> str:
    try:
        text = (plan.logs / f"{name}.log").read_text("utf-8", "replace")
    except OSError:
        return "(no log)"
    keep = [ln for ln in text.splitlines() if ln.strip()]
    return "\n".join(keep[-lines:])


def run_to_end(plan: Plan, phase: str, name: str, argv: list[str], platform: str,
               timeout: float) -> None:
    proc = spawn(plan, name, argv, platform)
    try:
        rc = proc.wait(timeout=max(1.0, min(timeout, plan.remaining())))
    except subprocess.TimeoutExpired:
        stop(plan, proc, grace=5.0)
        raise PhaseFailed(phase, f"{name} timed out\n{log_tail(plan, name)}") from None
    stop(plan, proc)
    if rc != 0:
        raise PhaseFailed(phase, f"{name} exited {rc}\n{log_tail(plan, name)}")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_json(url: str, timeout: float = 30.0):
    """(status, parsed body or None, headers)."""
    req = urllib.request.Request(url, headers={"Accept": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            status, body, headers = resp.status, resp.read(), dict(resp.headers)
    except urllib.error.HTTPError as e:
        status, body, headers = e.code, e.read(), dict(e.headers)
    try:
        return status, json.loads(body), headers
    except ValueError:
        return status, None, headers


def wait_for(plan: Plan, phase: str, name: str, proc: subprocess.Popen, what: str,
             probe, timeout: float, every: float = 0.5):
    """Poll ``probe()`` until it returns something truthy. The child dying,
    the phase's time or the whole run's time running out all fail the phase."""
    end = time.monotonic() + min(timeout, plan.remaining())
    while True:
        if proc.poll() is not None:
            raise PhaseFailed(
                phase, f"{name} exited {proc.returncode} while waiting for {what}\n"
                + log_tail(plan, name)
            )
        try:
            got = probe()
        except (OSError, ValueError):  # not listening yet / half-written body
            got = None
        if got:
            return got
        if time.monotonic() > end:
            raise PhaseFailed(
                phase, f"timed out waiting for {what}\n{log_tail(plan, name)}"
            )
        time.sleep(every)


def check_device(phase: str, device: dict, plan: Plan) -> dict:
    if not device or device.get("platform") != plan.platform:
        raise PhaseFailed(phase, f"process reports {device}, expected platform {plan.platform}")
    return device


def metric(snapshot: dict, name: str, key: str = "value", default=0.0):
    return (snapshot.get(name) or {}).get(key, default) or default


# -- config, data --------------------------------------------------------------


def write_conf(plan: Plan) -> Path:
    """One config for all three layers; phases override with --set."""
    conf = plan.out / "oryx.conf"
    conf.write_text(
        f"""
oryx {{
  id = "ChipSmoke"
  als {{
    implicit = true
    iterations = {plan.sweeps}
    hyperparams {{ features = {plan.features}, lambda = 0.01, alpha = 1.0 }}
  }}
  input-topic {{ broker = "file:{plan.bus}" }}
  update-topic {{ broker = "file:{plan.bus}" }}
  batch {{
    update-class = "oryx_tpu.app.als.update:ALSUpdate"
    storage {{ data-dir = "{plan.out}/data/", model-dir = "{plan.model_dir}/" }}
  }}
  speed {{ model-manager-class = "oryx_tpu.app.als.speed:ALSSpeedModelManager" }}
  serving {{
    model-manager-class = "oryx_tpu.app.als.serving_model:ALSServingModelManager"
    application-resources = "oryx_tpu.app.als"
  }}
}}
""",
        encoding="utf-8",
    )
    return conf


def layer_argv(plan: Plan, layer: str, sets: dict) -> list[str]:
    argv = ["-m", "oryx_tpu", layer, "--conf", str(plan.out / "oryx.conf")]
    for key, value in sets.items():
        argv += ["--set", f"{key}={value}"]
    return argv


@dataclass
class Ratings:
    users: np.ndarray  # int64 codes; ids are "u<code>" / "i<code>"
    items: np.ndarray
    values: np.ndarray
    t0_ms: int


def make_ratings(n: int, users: int, items: int, gen: np.random.Generator, t0_ms: int) -> Ratings:
    """Seeded power-law interactions with something to learn. Squared
    uniforms put ~10% of the ratings on 1% of the users and of the items.
    Users and items fall into TASTES groups by code, and four ratings in
    five go to an item of the user's own group (the one nearest in
    popularity rank to the item drawn), so a held-out rating can be
    predicted and a trained model's eval AUC has to clear MIN_AUC."""
    u = (gen.random(n) ** 2 * users).astype(np.int64)
    i = (gen.random(n) ** 2 * items).astype(np.int64)
    own = np.minimum(i - i % TASTES + u % TASTES, items - 1)
    i = np.where(gen.random(n) < 0.8, own, i)
    return Ratings(u, i, np.round(1.0 + 4.0 * gen.random(n), 1), t0_ms)


def write_input(path: Path, r: Ratings) -> None:
    """user,item,strength,timestamp lines, timestamps rising (the batch
    layer holds out the newest test-fraction)."""
    fmt = "u%d,i%d,%.1f,%d".__mod__
    with open(path, "w", encoding="utf-8") as f:
        for a in range(0, len(r.values), 500_000):
            b = min(a + 500_000, len(r.values))
            rows = zip(
                r.users[a:b].tolist(), r.items[a:b].tolist(), r.values[a:b].tolist(),
                range(r.t0_ms + a, r.t0_ms + b),
            )
            f.write("\n".join(map(fmt, rows)) + "\n")


def send_input(plan: Plan, phase: str, name: str, path: Path) -> None:
    """The CLI's bus-input, as a host-only child."""
    run_to_end(
        plan, phase, name,
        ["-m", "oryx_tpu", "bus-input", "--conf", str(plan.out / "oryx.conf"),
         "--input-file", str(path)],
        "cpu", timeout=600,
    )


# -- generation artefacts ------------------------------------------------------


def read_factors(dir_path: Path) -> tuple[list[str], np.ndarray]:
    """X/ or Y/ of a generation: gzip JSON lines ["id",[floats]]."""
    ids: list[str] = []
    rows: list[np.ndarray] = []
    for part in sorted(dir_path.glob("part-*.json.gz")):
        with gzip.open(part, "rb") as f:
            for line in f:
                cut = line.index(b'",[')
                ids.append(line[2:cut].decode("utf-8"))
                rows.append(np.fromstring(line[cut + 3 : line.rindex(b"]]")], np.float32, sep=","))
    return ids, np.stack(rows)


def topic_files(plan: Plan, topic: str) -> list[Path]:
    """A file-bus topic partition as one logical stream: archived segments
    by base offset, then the active segment (bus/filebus.py layout)."""
    d = plan.bus / topic
    archived = sorted(
        d.glob("partition-0.seg*.log"), key=lambda p: int(p.name.split(".seg")[1].split(".")[0])
    )
    return archived + [d / "partition-0.log"]


def topic_size(plan: Plan, topic: str) -> int:
    return sum(p.stat().st_size for p in topic_files(plan, topic) if p.exists())


def topic_lines_from(plan: Plan, topic: str, offset: int):
    """Lines (bytes, no newline) of the logical stream from byte ``offset``."""
    for p in topic_files(plan, topic):
        size = p.stat().st_size
        if offset >= size:
            offset -= size
            continue
        with open(p, "rb") as f:
            f.seek(offset)
            offset = 0
            for line in f:
                yield line.rstrip(b"\n")


def count_update_keys(plan: Plan, offset: int = 0) -> dict:
    counts: dict[str, int] = {}
    for line in topic_lines_from(plan, "OryxUpdate", offset):
        key = line.split(b"\t", 1)[0].decode("utf-8", "replace")
        if key == "UP":
            key = "UP-" + line[5:6].decode("ascii", "replace")  # UP\t["X",... / ["Y",...
        counts[key] = counts.get(key, 0) + 1
    return counts


# -- batch ---------------------------------------------------------------------


def phase_batch(plan: Plan, name: str, ratings: Ratings, sets: dict | None = None) -> dict:
    """One BatchLayer generation over ``ratings`` sent through bus-input."""
    t_phase = time.monotonic()
    port = free_port()
    # the layer drains whatever has arrived when its interval ticks: the
    # interval must outlast the send (the manifest's counts are checked)
    n = len(ratings.values)
    interval = 5 + int(n / 120_000)  # bus-input sends ~200K lines/s
    input_path = plan.out / f"{name}-input.csv"
    write_input(input_path, ratings)
    offset = topic_size(plan, "OryxUpdate") if (plan.bus / "OryxUpdate").exists() else 0
    proc = spawn(
        plan, name,
        layer_argv(plan, "batch", {
            "oryx.batch.ui.port": port,
            "oryx.batch.streaming.generation-interval-sec": interval,
            **(sets or {}),
        }),
        plan.platform,
    )
    url = f"http://127.0.0.1:{port}/status"

    def layer():
        status, body, _ = http_json(url, timeout=5)
        return body if status == 200 and body["layer"]["input_attached"] else None

    body = wait_for(plan, name, name, proc, "the batch layer to attach its input", layer, 300)
    device = check_device(name, body["layer"]["device"], plan)
    t_attached = time.monotonic()
    send_input(plan, name, f"{name}-bus-input", input_path)
    send_s = time.monotonic() - t_attached
    if send_s > interval - 2:
        raise PhaseFailed(name, f"sending took {send_s:.0f}s of a {interval}s interval")

    def generation_done():
        status, body, _ = http_json(url, timeout=30)
        if status == 200 and metric(body, "batch.generations.failed") > 0:
            raise PhaseFailed(name, f"generation failed\n{log_tail(plan, name, 25)}")
        return body if status == 200 and body["layer"]["generations"] >= 1 else None

    body = wait_for(plan, name, name, proc, "one generation", generation_done, 900, every=1.0)
    stop(plan, proc)
    shards = None
    if device["n_devices"] > 1:
        # the default mesh shards the neighbour buckets by row and replicates
        # the factors; shard-factors shards the factors too (ring trainer)
        ring = (sets or {}).get("oryx.batch.compute.shard-factors") == "true"
        shards = sharded_over(plan, name, "item factor" if ring else "widest user bucket",
                              device["n_devices"])

    champion = json.loads((plan.model_dir / "CHAMPION").read_text())["generation_id"]
    gen_dir = plan.model_dir / champion
    manifest = json.loads((gen_dir / "manifest.json").read_text())
    if manifest["status"] != "published":
        raise PhaseFailed(name, f"generation {champion} not published: {manifest}")
    if manifest["train_count"] + manifest["test_count"] < n:
        raise PhaseFailed(
            name, f"generation saw {manifest['train_count']} + {manifest['test_count']} "
            f"of {n} ratings sent"
        )
    if manifest["hyperparams"][0] != plan.features:
        raise PhaseFailed(name, f"trained at {manifest['hyperparams']}")
    if manifest["eval_metric"] is None or not manifest["eval_metric"] >= MIN_AUC:
        raise PhaseFailed(name, f"eval AUC {manifest['eval_metric']}, expected >= {MIN_AUC}")
    x_ids, x = read_factors(gen_dir / "X")
    y_ids, y = read_factors(gen_dir / "Y")
    for tag, m in (("X", x), ("Y", y)):
        if m.shape[1] != plan.features or not np.isfinite(m).all() or not np.abs(m).max() > 0:
            raise PhaseFailed(name, f"{tag} factors {m.shape}: not finite / all zero")
    keys = count_update_keys(plan, offset)
    if keys.get("MODEL-REF", 0) + keys.get("MODEL", 0) != 1:
        raise PhaseFailed(name, f"update topic holds {keys}")
    if keys.get("UP-X", 0) != len(x_ids) or keys.get("UP-Y", 0) != len(y_ids):
        raise PhaseFailed(name, f"update topic holds {keys}; X {len(x_ids)} Y {len(y_ids)}")
    phases = {
        k.split(".")[2]: round(metric(body, k, "sum"), 1)
        for k in body if k.startswith("batch.phase.")
    }
    return {
        **device,
        "generation": champion,
        "users": len(x_ids), "items": len(y_ids), "ratings": n,
        "sweeps": plan.sweeps, "features": plan.features,
        "eval_auc": round(manifest["eval_metric"], 4),
        "update_topic": keys,
        "wall_s": round(time.monotonic() - t_phase, 1),
        "send_s": round(send_s, 1), "interval_s": interval,
        "generation_s": round(metric(body, "batch.generation.seconds", "sum"), 1),
        "layer_phase_s": phases,
        "compile_s": round(metric(body, "jax.compile.seconds", "sum"), 1),
        "compiled_programs": int(metric(body, "jax.compile.seconds", "count")),
        **({"shards": shards} if shards else {}),
        "_model": (x_ids, x, y_ids, y),
    }


# -- serving -------------------------------------------------------------------


@dataclass
class Model:
    x_ids: list[str]
    x: np.ndarray
    y_ids: list[str]
    y: np.ndarray
    known: dict  # user id -> np.ndarray of Y rows the user has interacted with

    def __post_init__(self) -> None:
        self.x_row = {u: i for i, u in enumerate(self.x_ids)}
        self.y_row = {v: i for i, v in enumerate(self.y_ids)}
        self.y_norm = np.linalg.norm(self.y, axis=1)
        self.y_max = float(self.y_norm.max())


def known_items(model_y_row: dict, user_ids: list[str], all_ratings: list[Ratings]) -> dict:
    """user id -> Y rows of every item the user interacted with in the
    input (the endpoint excludes them; items absent from Y cannot be
    answered anyway)."""
    users = np.concatenate([r.users for r in all_ratings])
    items = np.concatenate([r.items for r in all_ratings])
    order = np.argsort(users, kind="stable")
    su, si = users[order], items[order]
    out = {}
    for uid in user_ids:
        code = int(uid[1:])
        lo, hi = np.searchsorted(su, [code, code + 1])
        rows = {model_y_row.get(f"i{c}") for c in np.unique(si[lo:hi]).tolist()}
        out[uid] = np.array(sorted(r for r in rows if r is not None), dtype=np.int64)
    return out


def pick_users(model_x_ids: list[str], all_ratings: list[Ratings], n: int,
               gen: np.random.Generator) -> tuple[list[str], int]:
    """A seeded sample of known users and the size of the scan they ask
    for. /recommend asks the device for howMany + (known items) results,
    rounded up to a power of two (serving/batcher.py), and every distinct
    size is another ~10-20 s kernel compile on a cold cache: the sample is
    drawn from the most populated size <= 128 (the kernel path; past it the
    scan materializes scores, which the kernel phase covers), so one
    compile per submit path serves the whole phase."""
    users = np.concatenate([r.users for r in all_ratings])
    items = np.concatenate([r.items for r in all_ratings])
    pair = np.unique(users * (items.max() + 1) + items)
    known = np.bincount(pair // (items.max() + 1), minlength=users.max() + 1)
    codes = np.array([int(u[1:]) for u in model_x_ids])
    size = np.maximum(16, 2 ** np.ceil(np.log2(10 + known[codes])).astype(np.int64))
    sizes, population = np.unique(size[size <= 128], return_counts=True)
    scan_k = int(sizes[np.argmax(population)])
    pool = codes[size == scan_k]
    take = gen.choice(pool, size=min(n, len(pool)), replace=False)
    return [f"u{int(t)}" for t in take], scan_k


class Judge:
    """Accumulates the verdict over all answers of one serving phase."""

    def __init__(self, phase: str, dtype: str) -> None:
        self.phase, self.dtype = phase, dtype
        self.tol = TOL[dtype]
        self.members = 0
        self.total = 0
        self.value_err = 0.0
        self.answers = 0

    def ranked(self, what: str, answer, ids: list[str], row_of: dict, ref: np.ndarray,
               excluded: np.ndarray, n: int, scale: float) -> None:
        """``answer``: [{"id", "value"}]; ``ref``: exact scores per candidate
        row; ``excluded`` rows must not appear."""
        fail = lambda why: PhaseFailed(self.phase, f"{what}: {why}; answer {answer}")  # noqa: E731
        if not isinstance(answer, list) or len(answer) != n:
            raise fail(f"expected {n} results")
        rows = [row_of.get(a["id"]) for a in answer]
        if None in rows or len(set(rows)) != n:
            raise fail("unknown or duplicate ids")
        rows = np.array(rows)
        if np.isin(rows, excluded).any():
            raise fail("an excluded item was returned")
        vals = np.array([a["value"] for a in answer], dtype=np.float64)
        if not np.isfinite(vals).all() or (np.diff(vals) > 0).any():
            raise fail("scores not finite and descending")
        masked = ref.copy()
        masked[excluded] = -np.inf
        kth = np.partition(masked, -n)[-n]
        wire = BF16_WIRE * np.abs(ref[rows]) if self.dtype == "int8" else 0.0
        err = np.abs(vals - ref[rows]) - wire
        self.value_err = max(self.value_err, float(err.max() / scale))
        if (err > self.tol * scale).any():
            raise fail(f"score off by {err.max():.3g} (scale {scale:.3g}, tol {self.tol})")
        member = ref[rows] >= kth - self.tol * scale
        self.members += int(member.sum())
        self.total += n
        self.answers += 1
        if MIN_RECALL[self.dtype] >= 1.0 and not member.all():
            raise fail(f"not the exact top {n} (k-th best {kth:.6g})")

    def finish(self) -> dict:
        recall = self.members / max(self.total, 1)
        if recall < MIN_RECALL[self.dtype]:
            raise PhaseFailed(self.phase, f"recall {recall:.4f} < {MIN_RECALL[self.dtype]}")
        return {"answers": self.answers, "recall": round(recall, 5),
                "max_value_err_of_scale": float(f"{self.value_err:.3g}")}


def full_quality(phase: str, what: str, status: int, headers: dict) -> None:
    stage = headers.get("X-Oryx-Shed-Stage")
    if status != 200 or stage not in (None, "full"):
        raise PhaseFailed(phase, f"{what}: HTTP {status}, shed stage {stage}")


def target_qui(value: float, current: float) -> float:
    """Implicit-feedback target strength (ALSUtils.computeTargetQui)."""
    if value > 0.0 and current < 1.0:
        return current + (value / (1.0 + value)) * (1.0 - max(0.0, current))
    if value < 0.0 and current > 0.0:
        return current + (value / (value - 1.0)) * -min(1.0, current)
    return math.nan


def gramian(m: np.ndarray) -> np.ndarray:
    """M^T M in float64, in row blocks."""
    g = np.zeros((m.shape[1], m.shape[1]))
    for a in range(0, len(m), 100_000):
        b = m[a : a + 100_000].astype(np.float64)
        g += b.T @ b
    return g


def phase_serving(plan: Plan, name: str, model: Model, generation: str, dtype: str,
                  users: list[str], scan_k: int, sharded: bool = False) -> dict:
    t_phase = time.monotonic()
    port = free_port()
    sets = {"oryx.serving.api.port": port, "oryx.als.serving.score-dtype": dtype}
    if sharded:
        sets["oryx.als.serving.shard-items"] = "true"
    proc = spawn(plan, name, layer_argv(plan, "serving", sets), plan.platform)
    base = f"http://127.0.0.1:{port}"

    def loaded():
        # every row of the expected generation, and nothing applied for 2 s:
        # the replay of the update topic (which may hold older generations
        # and speed deltas before this one) has reached its end
        status, body, _ = http_json(f"{base}/metrics", timeout=10)
        if status != 200 or metric(body, "serving.model.fraction_loaded") < 1.0:
            return False
        _, health, _ = http_json(f"{base}/healthz", timeout=10)
        return (
            health["live_generation"] == generation
            and (health["staleness_seconds"] or 0) >= 2.0
            and http_json(f"{base}/ready", timeout=10)[0] == 200
        )

    wait_for(plan, name, name, proc, f"generation {generation} to load", loaded, 400, every=1.0)
    load_s = time.monotonic() - t_phase
    _, health, _ = http_json(f"{base}/healthz")
    device = check_device(name, health.get("device"), plan)
    if not health.get("native_library"):
        raise PhaseFailed(name, "the serving layer runs the pure-Python twins (no native library)")
    if health["status"] != "ok" or health["shed_stage"] != "full":
        raise PhaseFailed(name, f"health {health}")

    judge = Judge(name, dtype)
    no_rows = np.array([], dtype=np.int64)

    def get(what: str, path: str):
        status, body, headers = http_json(base + path, timeout=600)
        full_quality(name, what, status, headers)
        return body

    def scan_counts() -> tuple[float, float]:
        _, m, _ = http_json(f"{base}/metrics")
        return (metric(m, "serving.scan.vector.queries"), metric(m, "serving.scan.indexed.queries"))

    def recommend_all(label: str) -> None:
        for u in users:
            xu = model.x[model.x_row[u]]
            judge.ranked(
                f"{label} /recommend/{u}", get(f"/recommend/{u}", f"/recommend/{u}?howMany=10"),
                model.y_ids, model.y_row, model.y @ xu, model.known[u], 10,
                float(np.linalg.norm(xu)) * model.y_max,
            )

    # 1. /recommend right after load: the first request starts staging the
    # user matrix on the device and is answered by vector submit
    t0 = time.monotonic()
    recommend_all("first pass")
    first_pass_s = time.monotonic() - t0
    vec1, idx1 = scan_counts()
    if vec1 < 1:
        raise PhaseFailed(name, "no /recommend was answered by vector submit")
    # 2. once staged, the same users are answered by int32 index submit
    def staged():
        get("staging probe", f"/recommend/{users[0]}?howMany=10")
        return scan_counts()[1] > idx1

    wait_for(plan, name, name, proc, "index submit to take over", staged, 120, every=0.5)
    _, idx2 = scan_counts()
    t0 = time.monotonic()
    recommend_all("second pass")
    second_pass_s = time.monotonic() - t0
    _, idx3 = scan_counts()
    if idx3 - idx2 < len(users):
        raise PhaseFailed(
            name, f"second pass: {idx3 - idx2} of {len(users)} answered by index submit"
        )

    # 3. /recommendToAnonymous: fold a temporary user in from (item, strength)
    # pairs against YtY, then rank (known = the pairs' items). howMany is
    # chosen so the scan has the size the /recommend requests compiled.
    many = scan_k - 3
    yty = gramian(model.y)
    gen = np.random.default_rng(plan.seed + 7)
    for _ in range(4):
        rows = gen.choice(len(model.y_ids), size=3, replace=False)
        pairs = [(model.y_ids[r], float(v)) for r, v in zip(rows, (1.0, 2.5, 4.0))]
        xu = None
        for (item, value), r in zip(pairs, rows):
            yi = model.y[r].astype(np.float64)
            qui = 0.0 if xu is None else float(xu.astype(np.float64) @ yi)
            target = target_qui(value, 0.5 if xu is None else qui)
            if math.isnan(target):
                continue
            d = np.linalg.solve(yty, (target - qui) * yi).astype(np.float32)
            xu = d if xu is None else xu + d
        path = "/recommendToAnonymous/" + "/".join(f"{i}={v}" for i, v in pairs)
        judge.ranked(
            path, get(path, f"{path}?howMany={many}"), model.y_ids, model.y_row, model.y @ xu,
            np.sort(rows), many, float(np.linalg.norm(xu)) * model.y_max,
        )

    # 4. /similarity: cosine to the item (one item: the mean of normalized
    # vectors is the normalized vector, so the answer is plain cosine)
    for r in gen.choice(len(model.y_ids), size=4, replace=False):
        item = model.y_ids[r]
        c = model.y[r] / model.y_norm[r]
        ref = (model.y @ c) / np.maximum(model.y_norm, 1e-12)
        judge.ranked(
            f"/similarity/{item}", get(f"/similarity/{item}", f"/similarity/{item}?howMany=10"),
            model.y_ids, model.y_row, ref, np.array([r]), 10, 1.0,
        )

    # 5. /estimate: host dot products of stored vectors
    u = users[0]
    rows = gen.choice(len(model.y_ids), size=5, replace=False)
    got = get("/estimate", f"/estimate/{u}/" + "/".join(model.y_ids[r] for r in rows))
    want = model.y[rows] @ model.x[model.x_row[u]]
    if not isinstance(got, list) or not np.allclose(got, want, rtol=1e-5, atol=1e-6):
        raise PhaseFailed(name, f"/estimate {got} != {want.tolist()}")

    _, metrics_body, _ = http_json(f"{base}/metrics")
    vec, idx = scan_counts()
    stop(plan, proc)
    if sharded:
        # every ranked answer came from the mesh-sharded scan, through the
        # batcher like any other (counted by submit kind beside it)
        over_mesh = metric(metrics_body, "serving.scan.sharded.queries")
        if over_mesh < judge.answers or over_mesh != vec + idx:
            raise PhaseFailed(
                name, f"{judge.answers} answers, scans: sharded {over_mesh}, vector {vec}, "
                f"indexed {idx}"
            )
    out = {
        **device,
        "score_dtype": dtype,
        "native_library": health["native_library"],
        "users_sampled": len(users), "scan_k": scan_k,
        "load_s": round(load_s, 1),
        "first_pass_s": round(first_pass_s, 1),
        "wall_s": round(time.monotonic() - t_phase, 1),
        "compile_s": round(metric(metrics_body, "jax.compile.seconds", "sum"), 1),
        "compiled_programs": int(metric(metrics_body, "jax.compile.seconds", "count")),
        "vector_queries": int(vec), "indexed_queries": int(idx),
        "second_pass_s": round(second_pass_s, 1),
        **judge.finish(),
    }
    if sharded:
        out["sharded_queries"] = int(over_mesh)
        out["shards"] = sharded_over(plan, name, "sharded item matrix", device["n_devices"])
    return out


# -- speed ---------------------------------------------------------------------


def phase_speed(plan: Plan, name: str, model: Model) -> dict:
    t_phase = time.monotonic()
    port = free_port()
    proc = spawn(
        plan, name,
        layer_argv(plan, "speed", {
            "oryx.speed.ui.port": port,
            "oryx.speed.fold-in-backend": "device",
            "oryx.speed.streaming.generation-interval-sec": 2,
        }),
        plan.platform,
    )
    url = f"http://127.0.0.1:{port}/status"

    def ready():
        status, body, _ = http_json(url, timeout=10)
        layer = body["layer"] if status == 200 else {}
        ok = layer.get("input_attached") and layer.get("model_fraction_loaded", 0) >= 1.0
        return body if ok else None

    body = wait_for(plan, name, name, proc, "input attached and the model loaded", ready, 400,
                    every=1.0)
    load_s = time.monotonic() - t_phase
    device = check_device(name, body["layer"]["device"], plan)
    batches0 = body["layer"]["batches"]

    # events like the ratings (they are the newest input, so the next
    # generation's eval holds THEM out), kept where the model knows both user
    # and item; small enough for ONE bus-input publish, so one micro-batch
    # folds them all against pre-batch state
    drawn = make_ratings(plan.events, plan.users, plan.items,
                         np.random.default_rng(plan.seed + 11), int(time.time() * 1000))
    keep = (np.isin(drawn.users, [int(u[1:]) for u in model.x_ids])
            & np.isin(drawn.items, [int(i[1:]) for i in model.y_ids]))
    events = Ratings(drawn.users[keep], drawn.items[keep], drawn.values[keep], drawn.t0_ms)
    n_events = len(events.values)
    events_path = plan.out / "speed-events.csv"
    write_input(events_path, events)
    if events_path.stat().st_size >= 1 << 20:
        raise PhaseFailed(name, "events file exceeds one bus-input publish")
    offset = topic_size(plan, "OryxUpdate")
    send_input(plan, name, "speed-bus-input", events_path)

    def folded():
        status, body, _ = http_json(url, timeout=10)
        return body if status == 200 and metric(body, "speed.events") >= n_events else None

    body = wait_for(plan, name, name, proc, "the events to be folded in", folded, 300)
    stop(plan, proc)
    batches = body["layer"]["batches"] - batches0
    if batches != 1:
        raise PhaseFailed(name, f"events were split over {batches} micro-batches")
    device_events = metric(body, "speed.fold.device.events")
    host_events = metric(body, "speed.fold.host.events")

    # the published deltas
    deltas = {"X": {}, "Y": {}}
    for line in topic_lines_from(plan, "OryxUpdate", offset):
        key, _, message = line.partition(b"\t")
        if key == b"UP":
            up = json.loads(message)
            deltas[up[0]][up[1]] = np.array(up[2], dtype=np.float32)
    n_deltas = len(deltas["X"]) + len(deltas["Y"])
    for side in deltas.values():
        for id_, vec in side.items():
            if vec.shape != (plan.features,) or not np.isfinite(vec).all():
                raise PhaseFailed(name, f"delta for {id_}: {vec.shape}, finite={np.isfinite(vec).all()}")

    # float64 host fold of a sample: (user, item) pairs whose user and item
    # each occur in exactly one aggregated event, so the published vector
    # is that event's (the layer publishes the last update per id)
    pair = events.users * (events.items.max() + 1) + events.items
    _, first, inverse = np.unique(pair, return_index=True, return_inverse=True)
    agg_value = np.bincount(inverse, weights=events.values)  # implicit: strengths add up
    au, ai = events.users[first], events.items[first]
    single = (np.bincount(au)[au] == 1) & (np.bincount(ai)[ai] == 1)
    sample = np.flatnonzero(single)[:200]
    if device_events < len(first) or host_events:
        raise PhaseFailed(
            name, f"{len(first)} aggregated events, fold counters: device "
            f"{device_events}, host {host_events}"
        )
    if len(sample) < min(50, n_events // 20):
        raise PhaseFailed(name, f"only {len(sample)} events with a user and item of their own")
    yty, xtx = gramian(model.y), gramian(model.x)
    worst = 0.0
    for j in sample:
        uid, iid = f"u{au[j]}", f"i{ai[j]}"
        xu = model.x[model.x_row[uid]].astype(np.float64)
        yi = model.y[model.y_row[iid]].astype(np.float64)
        qui = float(xu @ yi)
        target = target_qui(float(agg_value[j]), qui)
        if math.isnan(target):
            if uid in deltas["X"] or iid in deltas["Y"]:
                raise PhaseFailed(name, f"delta published for ({uid}, {iid}) with no target")
            continue
        want_x = xu + np.linalg.solve(yty, (target - qui) * yi)
        want_y = yi + np.linalg.solve(xtx, (target - qui) * xu)
        for tag, id_, want in (("X", uid, want_x), ("Y", iid, want_y)):
            got = deltas[tag].get(id_)
            if got is None:
                raise PhaseFailed(name, f"no {tag} delta for {id_}")
            err = float(np.abs(got - want).max() / np.abs(want).max())
            worst = max(worst, err)
            if err > FOLD_TOL:
                raise PhaseFailed(name, f"{tag} delta for {id_} off by {err:.3g} of its largest component")
    return {
        **device,
        "fold_in_backend": "device",
        "events": n_events, "aggregated_events": int(len(first)),
        "deltas": n_deltas, "deltas_checked": int(2 * len(sample)),
        "max_err_of_largest_component": float(f"{worst:.3g}"),
        "device_fold_events": int(device_events), "host_fold_events": int(host_events),
        "load_s": round(load_s, 1),
        "fold_s": round(metric(body, "speed.batch.seconds", "sum"), 2),
        "wall_s": round(time.monotonic() - t_phase, 1),
        "compile_s": round(metric(body, "jax.compile.seconds", "sum"), 1),
        "compiled_programs": int(metric(body, "jax.compile.seconds", "count")),
        "_events": events,
    }


# -- kernels -------------------------------------------------------------------


def phase_kernels(plan: Plan) -> dict:
    t_phase = time.monotonic()
    out = plan.out / "kernels"
    run_to_end(
        plan, "kernels", "kernels",
        [str(HERE / "tools" / "chip_kernels.py"), "--out", str(out), "--seed", str(plan.seed),
         "--platform", plan.platform, *plan.kernel_args],
        plan.platform, timeout=900,
    )
    result = json.loads((out / "kernels.json").read_text())
    device = check_device("kernels", result["device"], plan)
    if result["failed"] or result["interpret"] != ("--interpret" in plan.kernel_args):
        raise PhaseFailed("kernels", f"failed: {result['failed']}")
    return {
        **device,
        "checks": len(result["checks"]),
        "interpret": result["interpret"],
        "wall_s": round(time.monotonic() - t_phase, 1),
        "compile_s": result["compile_seconds"],
        "slowest_compiles": sorted(
            ((r["name"], r["compile_s"]) for r in result["checks"]), key=lambda t: -t[1]
        )[:5],
        "observations": result["observations"],
    }


# -- main ----------------------------------------------------------------------


def native_built_here(paths: set, t_start: float) -> str:
    """The serving phases report the library they loaded; it was built on
    this machine during this run if its file is newer than the run."""
    built = [p for p in paths if p and os.path.getmtime(p) >= t_start - 1]
    return "built-here" if built and len(built) == len(paths) else "reused"


ALL_PHASES = frozenset({"serving-float32", "serving-int8", "speed", "kernels", "mesh"})


def run(plan: Plan, phases=ALL_PHASES) -> dict:
    """All phases in order; whatever happens, no child outlives it. The
    command line always runs ALL_PHASES (exit 0 means all of it ran on the
    chip); the CPU rehearsal in tests/ passes a subset."""
    try:
        return _run(plan, phases)
    finally:
        stop_all(plan)


def _run(plan: Plan, phases: set) -> dict:
    t_start = time.time()
    if not (HERE / "oryx_tpu").is_dir():
        raise PhaseFailed("setup", f"no oryx_tpu package next to {Path(__file__).name}")
    plan.out.mkdir(parents=True, exist_ok=True)
    write_conf(plan)
    gen = np.random.default_rng(plan.seed)
    t0_ms = 1_700_000_000_000
    ratings = [make_ratings(plan.ratings, plan.users, plan.items, gen, t0_ms)]
    result: dict = {"phases": {}}
    done = result["phases"]

    def finished(name: str, row: dict) -> dict:
        done[name] = {k: v for k, v in row.items() if not k.startswith("_")}
        print(f"chip_smoke[{name}]: ok {json.dumps(done[name])}", flush=True)
        return row

    batch = finished("batch", phase_batch(plan, "batch", ratings[0]))
    n_devices = batch["n_devices"]

    def model_for(row: dict) -> tuple[Model, list[str], int]:
        x_ids, x, y_ids, y = row["_model"]
        y_row = {v: i for i, v in enumerate(y_ids)}
        picked, scan_k = pick_users(x_ids, ratings, plan.sample_users,
                                    np.random.default_rng(plan.seed + 3))
        return Model(x_ids, x, y_ids, y, known_items(y_row, picked, ratings)), picked, scan_k

    model, users, scan_k = model_for(batch)
    libs = set()
    for dtype in ("float32", "int8"):
        if f"serving-{dtype}" in phases:
            row = finished(f"serving-{dtype}",
                           phase_serving(plan, f"serving-{dtype}", model, batch["generation"],
                                         dtype, users, scan_k))
            libs.add(row["native_library"])
    if "speed" in phases:
        # the events went to the input topic: for every later phase they are
        # input like the rest (known items of their users; the next
        # generation's new data)
        ratings.append(finished("speed", phase_speed(plan, "speed", model))["_events"])
    if "kernels" in phases:
        finished("kernels", phase_kernels(plan))
    if n_devices > 1 and "mesh" in phases:
        # every local device is in the default mesh already; these add the
        # paths only several devices have: factors sharded over the mesh
        # (shard_map ring trainer) and the item matrix sharded for serving.
        # New input on top of the stored generation, so the eval has data.
        extra = make_ratings(max(plan.ratings // 20, 20_000), plan.users, plan.items, gen,
                             t0_ms + plan.ratings)
        ratings.append(extra)
        row = finished("batch-shard-factors", phase_batch(
            plan, "batch-shard-factors", extra,
            {"oryx.batch.compute.shard-factors": "true"}))
        model2, users2, scan_k2 = model_for(row)
        row = finished("serving-shard-items", phase_serving(
            plan, "serving-shard-items", model2, row["generation"], "float32", users2,
            scan_k2, sharded=True))
        libs.add(row["native_library"])

    first = done["batch"]
    result.update(
        ok=True,
        device={"platform": first["platform"], "kind": first["device_kind"],
                "count": first["n_devices"]},
        sizes={"features": plan.features, "users": plan.users, "items": plan.items,
               "ratings": plan.ratings, "sweeps": plan.sweeps, "events": plan.events},
        reduced=[{**r, "ran": getattr(plan, r["what"])} for r in REDUCED
                 if getattr(plan, r["what"]) < TARGET[r["what"]]],
        native=native_built_here(libs, t_start) if libs else "not-checked",
        wall_s=round(time.time() - t_start, 1),
        note="wall and compile seconds are smoke observations, not benchmark metrics",
    )
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=str(HERE / "chip_smoke_out"),
                    help="bus, data, model and logs of the run (emptied first)")
    for size, n in DEFAULT.items():
        ap.add_argument(f"--{size}", type=int, default=n, help=f"target {TARGET[size]}")
    args = ap.parse_args(argv)

    sizes = {k: getattr(args, k) for k in DEFAULT}
    out = Path(args.out).resolve()
    if out.exists():
        shutil.rmtree(out)
    plan = Plan(out=out, seed=args.seed, **sizes)
    atexit.register(stop_all, plan)
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))  # atexit stops the children
    report = HERE / "chiprun_out" / "chip_smoke"
    try:
        result = run(plan)
    except PhaseFailed as e:
        save_report(plan, report, {"ok": False, "phase": e.phase, "reason": e.reason})
        print(f"chip_smoke: FAILED in phase {e.phase}: {e.reason}", flush=True)
        return 1
    save_report(plan, report, result)
    shutil.rmtree(out, ignore_errors=True)
    print(f"chip_smoke: report {json.dumps(result)}", flush=True)
    print(result_line(result), flush=True)
    return 0


def result_line(result: dict) -> str:
    """The last line of stdout: exactly the keys the smoke contract names.
    Whoever reads it checks for these and no others; the phases, sizes and
    cuts are on the report line before it."""
    return json.dumps({"ok": result["ok"], "device": result["device"]})


def save_report(plan: Plan, report: Path, result: dict) -> None:
    """What survives the machine: the result and the ends of the logs."""
    report.mkdir(parents=True, exist_ok=True)
    (report / "result.json").write_text(json.dumps(result, indent=1))
    if plan.logs.is_dir():
        for log in plan.logs.glob("*.log"):
            (report / log.name).write_bytes(log.read_bytes()[-200_000:])
    kernels = plan.out / "kernels" / "kernels.json"
    if kernels.exists():
        shutil.copy(kernels, report / "kernels.json")


if __name__ == "__main__":
    sys.exit(main())
