"""100M-rating ingest -> train demonstration (VERDICT r3 #5).

Exercises the REAL batch data path at north-star-adjacent scale on one
host: synthetic ratings are written as columnar npz micro-batches into a
data dir (the ingest side of SaveToHDFSFunction), then ALSUpdate runs a
full MLUpdate generation over them — lazy FileRecords streaming,
vectorized parse/decay/aggregate, train_als on the device, factor-shard
export and model promotion — recording per-phase wall and peak RSS.

Usage:
    python tools/scale_ingest_benchmark.py [--ratings 100000000]
        [--users 2000000] [--items 200000] [--rank 16] [--iterations 1]
        [--out evidence.txt]

The micro-batches and model land under --workdir (a temp dir by
default) and are deleted afterwards unless --keep.

`--pack-bench` runs the neighbor-bucket packing benchmark instead of
the full generation: the legacy composite-key reference packer vs the
sharded engine (oryx_tpu/ops/packing.py) at --ratings scale, serial and
at each --workers-list count, asserting bit-identical bucket layouts
and recording throughput + the live RSS curve:

    python tools/scale_ingest_benchmark.py --pack-bench \
        --ratings 50000000 --users 2500000 --items 250000 \
        --workers-list 1,2,4 --out evidence.txt
"""

from __future__ import annotations

import argparse
import json
import os
import resource
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def rss_gb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6


class RssSampler:
    """Background sampler of current (not peak) RSS from /proc/self/statm:
    the shape of the curve is the evidence that packing stays bounded,
    which ru_maxrss alone can't show."""

    def __init__(self, period: float = 5.0) -> None:
        import threading

        self.period = period
        self.samples: list[tuple[float, float]] = []
        self._stop = threading.Event()
        self._t0 = time.perf_counter()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self) -> None:
        page = os.sysconf("SC_PAGE_SIZE")
        while not self._stop.wait(self.period):
            try:
                with open("/proc/self/statm") as f:
                    rss = int(f.read().split()[1]) * page / 1e9
            except OSError:
                continue
            self.samples.append((time.perf_counter() - self._t0, rss))

    def stop(self) -> str:
        self._stop.set()
        self._thread.join(timeout=self.period + 1)
        if not self.samples:
            return "rss curve: (no samples)"
        step = max(1, len(self.samples) // 12)
        pts = self.samples[::step]
        return "rss curve (t_s: GB): " + " ".join(
            f"{t:.0f}:{r:.1f}" for t, r in pts
        )


def pack_bench(args) -> None:
    """Neighbor-bucket packing throughput: legacy composite-key reference
    vs the sharded engine, bit-identity asserted on every run. Packs the
    X-solve orientation (user rows) of a power-law synthetic at
    --ratings scale; numpy-only, no jax import in the timed path."""
    from oryx_tpu.ops import packing

    nnz, users, items = args.ratings, args.users, args.items
    gen = np.random.default_rng(7)
    t0 = time.perf_counter()
    # mild power-law over users/items via squared uniforms (same shape
    # generator as the ingest path below)
    u = (gen.random(nnz) ** 2 * users).astype(np.int32)
    i = (gen.random(nnz) ** 2 * items).astype(np.int32)
    v = (1.0 + 4.0 * gen.random(nnz)).astype(np.float32)
    gen_wall = time.perf_counter() - t0
    lines = [
        f"=== pack_bench @ {time.strftime('%Y-%m-%d %H:%M:%S %Z')} ===",
        f"{nnz} ratings, {users} users x {items} items, X-solve "
        f"orientation, host cores: {os.cpu_count()}; synthesis {gen_wall:.0f}s",
    ]

    sampler = RssSampler(period=2.0)
    t0 = time.perf_counter()
    ref = packing.build_neighbor_buckets_reference(u, i, v, users)
    ref_wall = time.perf_counter() - t0
    lines.append(
        f"legacy composite-key packer: {ref_wall:.2f}s "
        f"({nnz / ref_wall / 1e6:.2f}M entries/s), rss {rss_gb():.1f} GB"
    )
    print(lines[-1], flush=True)

    def identical(got) -> bool:
        return len(got) == len(ref) and all(
            rb.chunk == gb.chunk
            and np.array_equal(rb.rows, gb.rows)
            and np.array_equal(rb.idx, gb.idx)
            and np.array_equal(rb.val, gb.val)
            and np.array_equal(rb.deg, gb.deg)
            for rb, gb in zip(ref, got)
        )

    workers_list = [int(w) for w in args.workers_list.split(",")]
    for w in workers_list:
        opts = packing.PackingOptions(workers=w)
        t0 = time.perf_counter()
        got = packing.pack_neighbor_buckets(u, i, v, users, options=opts)
        wall = time.perf_counter() - t0
        same = identical(got)
        st = packing.last_pack_stats
        phases = " ".join(
            f"{k}={st[k]:.2f}" for k in
            ("plan", "alloc", "sort", "position", "scatter", "fill")
            if k in st
        )
        lines.append(
            f"engine workers={w}: {wall:.2f}s "
            f"({nnz / wall / 1e6:.2f}M entries/s), "
            f"{ref_wall / wall:.2f}x legacy, bit-identical: {same}; {phases}; "
            f"rss {rss_gb():.1f} GB"
        )
        print(lines[-1], flush=True)
        del got
        if not same:
            sampler.stop()
            sys.exit(1)
    lines.append(sampler.stop())
    lines.append(f"peak RSS: {rss_gb():.1f} GB")
    print("\n".join(lines[-2:]), flush=True)
    if args.out:
        with open(args.out, "a", encoding="utf-8") as f:
            f.write("\n".join(lines) + "\n")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--ratings", type=int, default=100_000_000)
    ap.add_argument("--users", type=int, default=2_000_000)
    ap.add_argument("--items", type=int, default=200_000)
    ap.add_argument("--rank", type=int, default=16)
    ap.add_argument("--iterations", type=int, default=1)
    ap.add_argument("--batches", type=int, default=10)
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--out", default=None)
    ap.add_argument("--keep", action="store_true")
    ap.add_argument("--pack-bench", action="store_true")
    ap.add_argument("--workers-list", default="1,2,4")
    args = ap.parse_args()

    if args.pack_bench:
        pack_bench(args)
        return

    root = Path(args.workdir or tempfile.mkdtemp(prefix="oryx-scale-"))
    data_dir = root / "data"
    model_dir = root / "model"
    data_dir.mkdir(parents=True, exist_ok=True)

    gen = np.random.default_rng(7)
    per = args.ratings // args.batches

    # -- ingest: vectorized message synthesis + columnar micro-batches ------
    t0 = time.perf_counter()
    total_bytes = 0
    for bi in range(args.batches):
        # mild power-law over users/items via squared uniforms
        u = (gen.random(per) ** 2 * args.users).astype(np.int64)
        i = (gen.random(per) ** 2 * args.items).astype(np.int64)
        v = (1.0 + 4.0 * gen.random(per)).astype(np.float32)
        ts = np.arange(bi * per, bi * per + per, dtype=np.int64)
        # "u<id>,i<id>,<val>,<ts>" built with a handful of C-level passes
        comma = np.full(per, b",", dtype="S1")
        msgs = np.char.add(
            np.char.add(
                np.char.add(
                    np.char.add(
                        np.char.add(np.char.add(b"u", u.astype("S")), comma),
                        np.char.add(b"i", i.astype("S")),
                    ),
                    comma,
                ),
                v.astype("S8"),
            ),
            np.char.add(comma, ts.astype("S")),
        )
        path = data_dir / f"oryx-{1000 + bi}.npz"
        with open(path, "wb") as f:
            np.savez(f, messages=msgs)  # uncompressed: 1-core zlib would dominate
        total_bytes += path.stat().st_size
        print(
            f"ingest: batch {bi + 1}/{args.batches} written "
            f"({total_bytes / 1e9:.1f} GB total, rss {rss_gb():.1f} GB)",
            flush=True,
        )
        del u, i, v, ts, msgs
    ingest_wall = time.perf_counter() - t0

    # -- train: one full MLUpdate generation over the stored history ---------
    from oryx_tpu.app.als.update import ALSUpdate
    from oryx_tpu.common import config as C
    from oryx_tpu.lambda_.data import FileRecords

    cfg = C.get_default().with_overlay(
        f"""
        oryx.id = "ScaleIngest"
        oryx.als.implicit = true
        oryx.als.no-known-items = true
        oryx.als.iterations = {args.iterations}
        oryx.als.hyperparams.features = {args.rank}
        oryx.ml.eval.test-fraction = 0
        oryx.ml.eval.candidates = 1
        """
    )
    update = ALSUpdate(cfg)
    past = FileRecords(data_dir)
    sampler = RssSampler()
    t0 = time.perf_counter()
    update.run_update(2_000_000_000, [], past, str(model_dir), None)
    train_wall = time.perf_counter() - t0
    curve = sampler.stop()

    promoted = model_dir / "2000000000"
    ok = (promoted / "model.pmml").exists() and (promoted / "Y").is_dir()
    peak = rss_gb()
    lines = [
        f"=== scale_ingest_benchmark @ {time.strftime('%Y-%m-%d %H:%M:%S %Z')} ===",
        f"{args.ratings} ratings, {args.users} users x {args.items} items, "
        f"rank {args.rank}, {args.iterations} sweep(s); host cores: {os.cpu_count()}",
        f"ingest: {args.batches} npz micro-batches, {total_bytes / 1e9:.1f} GB, "
        f"{ingest_wall:.0f}s ({args.ratings / ingest_wall / 1e6:.1f}M ratings/s)",
        f"train (parse->decay->aggregate->ALS->export->promote): {train_wall:.0f}s "
        f"({args.ratings / train_wall / 1e6:.2f}M ratings/s end-to-end)",
        f"peak RSS: {peak:.1f} GB; model promoted: {ok}",
        curve,
    ]
    print("\n".join(lines), flush=True)
    print(
        json.dumps(
            {
                "metric": (
                    f"ALS ingest->train end-to-end ({args.ratings / 1e6:.0f}M "
                    f"ratings, rank {args.rank}, peak RSS {peak:.1f} GB)"
                ),
                "value": round(args.ratings / train_wall, 0),
                "unit": "ratings/sec",
                "vs_baseline": 0.0,
            }
        ),
        flush=True,
    )
    if args.out:
        with open(args.out, "a", encoding="utf-8") as f:
            f.write("\n".join(lines) + "\n")
    if not args.keep:
        shutil.rmtree(root, ignore_errors=True)
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
