"""Full speed-layer benchmark: sustained events/sec through the REAL
SpeedLayer over the file or shared-memory bus — not the build_updates
microbench.

Path measured per event (SpeedLayer.java:56-214 analogue, lambda_/speed.py):
producer -> bus input topic -> consumer poll (zero-copy columnar frames on
shm:) -> parse/aggregate (typed int fast path on shm:) -> batched two-sided
ALS fold-in -> update serialization -> batched publish to the update topic.

Two modes:

- backlog (--prefill N): pre-produce N events, then time draining them
  with run_one_batch in a loop. Producer cost is fully excluded from the
  timed window — this is layer capacity on its own core.
- live (default): producer processes race the layer for --seconds.
  Producers replay PRE-ENCODED columnar payloads (shm: one header pack +
  memcpy per frame, zero per-event format cost; file: a pre-rendered
  record list), so the measured split is producer=transport-only,
  layer=full parse->fold->publish. On a 1-core host all processes share
  the core.

--trials runs the timed phase N times and reports per-trial rates, the
median, and the spread ((max-min)/median; >20% is flagged NOISY).

--shards N (shm only) partitions the input topic N ways and runs N
independent parse->fold->publish pipeline chains (one per partition
subset, core-pinned where the platform allows). In backlog mode each
trial gets a FRESH layer so the prefill happens while the pipeline is
down — producer cost stays excluded from the timed drain.

Usage:
    python tools/speed_layer_benchmark.py --prefill 2000000 --trials 3
    python tools/speed_layer_benchmark.py --prefill 2000000 --shards 4
    python tools/speed_layer_benchmark.py --seconds 15 --trials 3 [--pipeline]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

CHUNK = 20_000
N_CHUNKS = 8  # distinct pre-encoded payloads producers cycle through


def build_chunks(seed: int, users: int, items: int):
    gen = np.random.default_rng(seed)
    out = []
    for _ in range(N_CHUNKS):
        u = gen.integers(0, users, CHUNK).astype(np.int32)
        i = gen.integers(0, items, CHUNK).astype(np.int32)
        v = (1.0 + gen.random(CHUNK)).astype(np.float32)
        out.append((u, i, v))
    return out


def produce(
    locator: str, users: int, items: int, stop_path: str, nparts: int = 1
) -> None:
    """Producer-process body: pump synthetic rating events until stopped.

    Everything format-shaped happens ONCE, before the loop: shm producers
    replay pre-encoded columnar payloads (send_payload = header pack +
    memcpy), file producers replay a pre-rendered record list. With
    ``nparts`` > 1, frames round-robin over the input partitions so every
    pipeline shard sees traffic.
    """
    from oryx_tpu import bus
    from oryx_tpu.bus import blockcodec

    broker = bus.get_broker(locator)
    chunks = build_chunks(os.getpid(), users, items)
    with broker.producer("OryxInput") as p:
        if hasattr(p, "send_payload"):  # shm: zero per-event cost replay
            frames = []
            for u, i, v in chunks:
                payload, flags, crc = blockcodec.encode_interactions_payload(u, i, v)
                frames.append((flags, len(v), payload, crc))
            j = 0
            while not os.path.exists(stop_path):
                flags, count, payload, crc = frames[j % len(frames)]
                try:
                    p.send_payload(
                        blockcodec.KIND_COLS, flags, count, payload, crc,
                        partition=j % nparts,
                    )
                except BlockingIOError:
                    time.sleep(0.002)  # ring full: consumer owns the core
                    continue
                j += 1
        else:  # file: pre-rendered lines, send_many re-blobs per call
            batches = [
                [
                    (None, f"u{uu},i{ii},{vv:.3f},{j}")
                    for j, (uu, ii, vv) in enumerate(zip(u, i, v))
                ]
                for u, i, v in chunks
            ]
            j = 0
            while not os.path.exists(stop_path):
                p.send_many(batches[j % len(batches)])
                j += 1


def prefill_events(
    broker, typed: bool, n: int, users: int, items: int, seed=7, nparts: int = 1
):
    """Pre-produce n events (typed columnar frames on shm, text on file),
    chunk-round-robined over ``nparts`` input partitions."""
    gen = np.random.default_rng(seed)
    t0 = time.perf_counter()
    with broker.producer("OryxInput") as p:
        left = n
        j = 0
        while left > 0:
            m = min(100_000, left)
            u = gen.integers(0, users, m).astype(np.int32)
            i = gen.integers(0, items, m).astype(np.int32)
            v = (1.0 + gen.random(m)).astype(np.float32)
            if typed:
                p.send_interactions(u, i, v, partition=j % nparts)
            else:
                p.send_many(
                    (None, f"u{uu},i{ii},{vv:.3f},{jj}")
                    for jj, (uu, ii, vv) in enumerate(zip(u, i, v))
                )
            left -= m
            j += 1
    return time.perf_counter() - t0


def summarize(rates: list[float]) -> tuple[float, float, str]:
    med = float(np.median(rates))
    spread = (max(rates) - min(rates)) / med if med else 0.0
    flag = "NOISY" if spread > 0.20 else "stable"
    return med, spread, flag


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--bus", default="shm", choices=["file", "shm"])
    ap.add_argument("--pipeline", action="store_true",
                    help="run the three-stage parse/fold/publish pipeline "
                    "(live mode only)")
    ap.add_argument("--shards", type=int, default=1,
                    help="partition the input topic this many ways and run "
                    "one pipeline chain per partition subset (shm only)")
    ap.add_argument("--trials", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=15.0,
                    help="per-trial window in live mode")
    ap.add_argument("--features", type=int, default=50)
    ap.add_argument("--users", type=int, default=50_000)
    ap.add_argument("--items", type=int, default=10_000)
    ap.add_argument("--producers", type=int, default=2)
    ap.add_argument(
        "--prefill",
        type=int,
        default=0,
        help="backlog mode: pre-produce this many events per trial and "
        "time draining them (layer capacity; producer cost excluded)",
    )
    ap.add_argument("--backend", default="auto", choices=["auto", "host", "device"])
    ap.add_argument(
        "--batch-events", type=int, default=400_000,
        help="micro-batch cap; larger batches amortize per-batch fixed costs",
    )
    ap.add_argument("--ring-mb", type=int, default=0,
                    help="shm ring size; 0 = auto-size to the prefill")
    ap.add_argument(
        "--toggle-env", default=None, metavar="VAR",
        help="A/B mode for overhead rows: flip this env var 1/0 across "
        "the timed trials (ABBA order) INSIDE one process, so both arms "
        "share the same JIT warm-up, memory layout, and host state. "
        "Per-arm rates land in the JSON as toggle.on / toggle.off. "
        "Single-trial subprocess A/Bs on a 1-core host measure minutes-"
        "apart machine drift (±10%% observed), not the toggled feature.",
    )
    ap.add_argument("--out", default=None, help="append an evidence block here")
    args = ap.parse_args()
    if args.shards < 1:
        ap.error("--shards must be >= 1")
    if args.shards > 1 and args.bus != "shm":
        ap.error("--shards > 1 requires --bus shm (the partitioned ring "
                 "transport)")
    if args.pipeline and args.prefill and args.shards == 1:
        ap.error("--pipeline is a live-mode flag (unsharded backlog mode "
                 "times run_one_batch directly; use --shards N for a "
                 "pipelined backlog drain)")

    root = Path(tempfile.mkdtemp(prefix="oryx-speedbench-"))
    stop_path = str(root / "STOP")
    if args.bus == "shm":
        # the ring must hold a whole prefill (typed: ~13B/event amortized)
        ring_mb = args.ring_mb or max(64, args.prefill * 14 // (1 << 20) + 16)
        locator = f"shm:{root}/bus?ring_mb={ring_mb}"
    else:
        locator = f"file:{root}/bus"

    from oryx_tpu import bus
    from oryx_tpu.app.pmml import add_extension, add_extension_content
    from oryx_tpu.bus.core import KeyMessage
    from oryx_tpu.common import config as C
    from oryx_tpu.common import pmml as pmml_io
    from oryx_tpu.common.metrics import registry
    from oryx_tpu.lambda_.speed import SpeedLayer

    if os.environ.get("ORYX_LOCK_WATCHDOG") == "1":
        # the lock watchdog's overhead: patch the lock factories
        # before the broker/layer allocate theirs, the same way the
        # chaos/fleet test suites run
        from oryx_tpu.common import locks

        locks.instrument(strict=True)

    broker = bus.get_broker(locator)
    nparts = max(1, args.shards)
    broker.create_topic("OryxInput", nparts)
    broker.create_topic("OryxUpdate", 1)

    cfg = C.get_default().with_overlay(
        f"""
        oryx.id = "SpeedBench"
        oryx.speed.model-manager-class = "oryx_tpu.app.als.speed:ALSSpeedModelManager"
        oryx.als.implicit = true
        oryx.als.no-known-items = true
        oryx.speed.fold-in-backend = "{args.backend}"
        oryx.input-topic.broker = "{locator}"
        oryx.input-topic.message.partitions = {nparts}
        oryx.update-topic.broker = "{locator}"
        oryx.speed.streaming.generation-interval-sec = 3600
        oryx.speed.streaming.max-batch-events = {args.batch_events}
        oryx.speed.pipeline.enabled = {str(args.pipeline or args.shards > 1).lower()}
        oryx.speed.pipeline.shards = {args.shards}
        """
    )

    def build_layer() -> SpeedLayer:
        # seed the model directly on the manager (no bus replay of a
        # 60K-id PMML blob): MODEL sets shape + expected ids, batched
        # setters load the factors so get_fraction_loaded() reaches 1.0
        built = SpeedLayer(cfg)
        t0 = time.perf_counter()
        gen = np.random.default_rng(42)
        root_pmml = pmml_io.build_skeleton_pmml()
        add_extension(root_pmml, "features", args.features)
        add_extension(root_pmml, "implicit", "true")
        add_extension_content(
            root_pmml, "XIDs", [f"u{j}" for j in range(args.users)]
        )
        add_extension_content(
            root_pmml, "YIDs", [f"i{j}" for j in range(args.items)]
        )
        built.manager.consume(
            iter([KeyMessage("MODEL", pmml_io.to_string(root_pmml))])
        )
        m = built.manager.model
        x = gen.standard_normal((args.users, args.features)).astype(np.float32)
        y = gen.standard_normal((args.items, args.features)).astype(np.float32)
        m.set_user_vectors([f"u{j}" for j in range(args.users)], x)
        m.set_item_vectors([f"i{j}" for j in range(args.items)], y)
        assert m.get_fraction_loaded() >= 1.0, m.get_fraction_loaded()
        print(f"model ready in {time.perf_counter() - t0:.1f}s", flush=True)
        return built

    sharded_backlog = bool(args.prefill) and args.shards > 1
    layer = None
    if not sharded_backlog:
        layer = build_layer()
        if args.shards == 1:
            # the input consumer must exist BEFORE any produce: its guard
            # pins the shm ring tail so prefilled frames are never
            # reclaimed underneath us. (Sharded chains own their
            # consumers — an idle layer consumer would stall the rings.)
            layer.prepare_input()
    typed = args.bus == "shm"
    events_counter = registry.counter("speed.events")
    rates: list[float] = []
    arms: list[str] = []  # per-trial "on"/"off" when --toggle-env is set

    def set_toggle(trial: int) -> None:
        """Flip the A/B env var for this timed trial. ABBA order (on, off,
        off, on, ...) balances both arms against monotonic host drift to
        first order; anything reading the var per call (e.g. the resource
        ledger's ``enabled()``) sees the flip immediately."""
        if not args.toggle_env or trial < 0:
            return
        on = trial % 4 in (0, 3)
        os.environ[args.toggle_env] = "1" if on else "0"
        arms.append("on" if on else "off")
    shard_rates: list[list[float]] = []
    producers: list[subprocess.Popen] = []
    total_events = total_updates = total_batches = 0

    try:
        if sharded_backlog:
            # one pipeline chain per partition subset drains the backlog;
            # each trial gets a fresh layer so the prefill lands while the
            # pipeline is down (producer cost excluded from the drain)
            first = True
            for trial in range(-1, args.trials):  # trial -1 = warm-up
                set_toggle(trial)  # before build_layer: registrations flip too
                n = 100_000 if trial < 0 else args.prefill
                broker.delete_topic("OryxUpdate")
                broker.create_topic("OryxUpdate", 1)
                layer = build_layer()
                if first:
                    # no stored offsets yet -> consumers would start at
                    # latest and skip the prefill; pin them to 0 first
                    broker.set_offsets(
                        layer.group_id, "OryxInput",
                        {p: 0 for p in range(nparts)},
                    )
                    first = False
                dt = prefill_events(
                    broker, typed, n, args.users, args.items,
                    seed=100 + trial, nparts=nparts,
                )
                label = "warm-up" if trial < 0 else f"trial {trial + 1}"
                print(f"{label}: prefilled {n} events in {dt:.1f}s",
                      flush=True)
                before = int(events_counter.value)
                shard_before = [
                    int(registry.counter(
                        f"speed.pipeline.shard.{s}.events").value)
                    for s in range(args.shards)
                ]
                start = time.perf_counter()
                layer.start()
                got, last_advance = 0, start
                while got < n:
                    time.sleep(0.01)
                    seen = int(events_counter.value) - before
                    now = time.perf_counter()
                    if seen > got:
                        got, last_advance = seen, now
                    elif now - last_advance > 60:
                        print(f"{label}: STALLED at {got}/{n}", flush=True)
                        break
                elapsed = time.perf_counter() - start
                batches = layer.batch_count
                layer.close()
                layer = None
                if trial < 0:
                    continue
                per_shard = [
                    (int(registry.counter(
                        f"speed.pipeline.shard.{s}.events").value) - b)
                    / elapsed
                    for s, b in enumerate(shard_before)
                ]
                shard_rates.append(per_shard)
                rates.append(got / elapsed)
                total_events += got
                total_batches += batches
                print(
                    f"{label}: {got} events in {elapsed:.2f}s -> "
                    f"{got / elapsed:,.0f} events/s  (per-shard: "
                    f"{', '.join(f'{r:,.0f}' for r in per_shard)})",
                    flush=True,
                )
        elif args.prefill:
            # warm-up: compile/calibrate the fold path before timing
            prefill_events(broker, typed, 100_000, args.users, args.items, seed=1)
            while layer.run_one_batch() or int(events_counter.value) == 0:
                pass
            for trial in range(args.trials):
                set_toggle(trial)
                dt = prefill_events(
                    broker, typed, args.prefill, args.users, args.items,
                    seed=100 + trial,
                )
                print(f"trial {trial + 1}: prefilled {args.prefill} events "
                      f"in {dt:.1f}s", flush=True)
                events = updates = batches = 0
                start = time.perf_counter()
                while True:
                    before = int(events_counter.value)
                    sent = layer.run_one_batch()
                    got = int(events_counter.value) - before
                    events += got
                    updates += sent
                    batches += 1
                    if got == 0:
                        break  # backlog drained
                elapsed = time.perf_counter() - start
                rates.append(events / elapsed)
                total_events += events
                total_updates += updates
                total_batches += batches
                print(f"trial {trial + 1}: {events} events in {elapsed:.2f}s "
                      f"-> {events / elapsed:,.0f} events/s", flush=True)
        else:
            producers = [
                subprocess.Popen(
                    [
                        sys.executable, os.path.abspath(__file__),
                        "--produce", locator,
                        "--produce-stop", stop_path,
                        "--users", str(args.users),
                        "--items", str(args.items),
                        "--nparts", str(nparts),
                    ],
                    # producers only write the bus: host-only children
                    env={**os.environ, "JAX_PLATFORMS": "cpu"},
                )
                for _ in range(args.producers)
            ]
            time.sleep(1.0)  # let the bus fill so the layer never starves
            if args.pipeline or args.shards > 1:
                layer.start()  # pipeline workers drain continuously
                time.sleep(2.0)  # warm-up / fold calibration
                for trial in range(args.trials):
                    set_toggle(trial)
                    before = int(events_counter.value)
                    start = time.perf_counter()
                    time.sleep(args.seconds)
                    elapsed = time.perf_counter() - start
                    events = int(events_counter.value) - before
                    rates.append(events / elapsed)
                    total_events += events
                    print(f"trial {trial + 1}: {events} events in "
                          f"{elapsed:.2f}s -> {events / elapsed:,.0f} events/s",
                          flush=True)
                total_batches = layer.batch_count
            else:
                layer.run_one_batch()  # warm-up
                for trial in range(args.trials):
                    set_toggle(trial)
                    events = updates = batches = 0
                    start = time.perf_counter()
                    deadline = start + args.seconds
                    while time.perf_counter() < deadline:
                        before = int(events_counter.value)
                        sent = layer.run_one_batch()
                        events += int(events_counter.value) - before
                        updates += sent
                        batches += 1
                    elapsed = time.perf_counter() - start
                    rates.append(events / elapsed)
                    total_events += events
                    total_updates += updates
                    total_batches += batches
                    print(f"trial {trial + 1}: {events} events in "
                          f"{elapsed:.2f}s -> {events / elapsed:,.0f} events/s",
                          flush=True)
    finally:
        Path(stop_path).touch()
        for p in producers:
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                # a wedged producer must not strand its sibling processes
                # or skip the layer teardown below
                p.kill()
                p.wait(timeout=10)
        if layer is not None:
            layer.close()
        if hasattr(broker, "close"):
            broker.close()  # shm: drop ring mmaps + fds held by this process
        import shutil

        # in the finally so an aborted run doesn't strand the work dir
        # (ring files are ring_mb x nparts of disk each)
        shutil.rmtree(root, ignore_errors=True)

    med, spread, flag = summarize(rates)
    framing = "typed-columnar frames" if typed else "text lines"
    if sharded_backlog:
        mode = (
            f"backlog: {args.trials} trial(s) x {args.prefill}-event prefill "
            f"over {nparts} partitions; {args.shards}-shard pipeline drain "
            f"(fresh layer per trial; producer cost excluded — prefill "
            f"lands while the pipeline is down)"
        )
    elif args.prefill:
        mode = (
            f"backlog: {args.trials} trial(s) x {args.prefill}-event prefill; "
            f"producer cost excluded from the timed drain (events were "
            f"pre-encoded onto the bus before timing)"
        )
    else:
        split = (
            "producers replay pre-encoded columnar payloads (header pack + "
            "memcpy per frame, zero per-event format cost)"
            if typed
            else "producers replay a pre-rendered record list"
        )
        mode = (
            f"live: {args.producers} host-only (JAX_PLATFORMS=cpu) "
            f"producer process(es) racing the layer "
            f"for {args.seconds:.0f}s windows; {split}; layer core pays the "
            f"full parse->fold->publish path"
            + (f"; {args.shards}-shard pipeline on"
               if args.shards > 1
               else ("; three-stage pipeline on" if args.pipeline else ""))
        )
    lines = [
        f"=== speed_layer_benchmark @ {time.strftime('%Y-%m-%d %H:%M:%S %Z')} ===",
        f"bus={args.bus} ({framing}); model {args.users}u x {args.items}i x "
        f"{args.features}f implicit; host cores: {os.cpu_count()}; "
        f"shards: {args.shards}",
        mode,
        f"per-trial events/s: [{', '.join(f'{r:,.0f}' for r in rates)}] -> "
        f"median {med:,.0f} events/s (spread {spread:.1%}, {flag}); "
        f"{total_events} events over {total_batches} micro-batches",
    ]
    if shard_rates:
        shard_medians = [
            float(np.median([t[s] for t in shard_rates]))
            for s in range(args.shards)
        ]
        lines.append(
            "per-shard median events/s: "
            + ", ".join(
                f"shard{s}={r:,.0f}" for s, r in enumerate(shard_medians)
            )
        )
    toggle: dict | None = None
    if args.toggle_env and arms:
        toggle = {
            "var": args.toggle_env,
            "on": [round(r, 0) for r, a in zip(rates, arms) if a == "on"],
            "off": [round(r, 0) for r, a in zip(rates, arms) if a == "off"],
        }
        lines.append(
            f"A/B {args.toggle_env}: "
            f"on [{', '.join(f'{r:,.0f}' for r in toggle['on'])}] vs "
            f"off [{', '.join(f'{r:,.0f}' for r in toggle['off'])}] events/s"
        )
    print("\n".join(lines), flush=True)
    print(
        json.dumps(
            {
                "metric": (
                    f"speed layer sustained fold-in over {args.bus} bus, "
                    f"{'backlog' if args.prefill else 'live'} mode "
                    + (f"[{args.shards} shards] " if args.shards > 1 else "")
                    + f"({args.features} feat, {args.users // 1000}K users, "
                    f"{args.items // 1000}K items)"
                ),
                "value": round(med, 0),
                "unit": "events/sec",
                "rates": [round(r, 0) for r in rates],
                "trials": len(rates),
                "spread": round(spread, 3),
                "shards": args.shards,
                "vs_baseline": round(med / 100_000.0, 2),
                **({"toggle": toggle} if toggle else {}),
            }
        )
    )
    if args.out:
        with open(args.out, "a", encoding="utf-8") as f:
            f.write("\n".join(lines) + "\n")


if __name__ == "__main__":
    # internal flag for the producer subprocess
    if "--produce-stop" in sys.argv:
        i = sys.argv.index("--produce-stop")
        stop = sys.argv[i + 1]
        del sys.argv[i : i + 2]
        ap = argparse.ArgumentParser()
        ap.add_argument("--produce")
        ap.add_argument("--users", type=int, default=50_000)
        ap.add_argument("--items", type=int, default=10_000)
        ap.add_argument("--nparts", type=int, default=1)
        a = ap.parse_args()
        produce(a.produce, a.users, a.items, stop, nparts=a.nparts)
    else:
        main()
