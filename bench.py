"""Driver benchmark: the full BASELINE metric set on TPU.

Emits ONE JSON line PER METRIC ({"metric","value","unit","vs_baseline",
"backend",...}) as each completes, then — tail-cap-proof — re-prints the
complete set as the LAST lines of output under a `=== BENCH SUMMARY ===`
header, ordered so the headline serving row is the very last line. Every
row carries `"backend"` ("tpu/TPU v5e" style); a CPU-fallback run
produces honestly-labeled `"backend":"cpu/..."` rows, never rows that
read as TPU. All rows (plus per-row detail) are appended to
`tools/bench_evidence.txt`.

Metrics (vs_baseline frames):
1. serving  — ALS /recommend exact-scan qps across the reference's
   published table shapes: 50/250 feat x 1M/5M/20M items
   (docs/performance.md:108-117 LSH-0.3 rows: 437/151/84/36/14/6 qps,
   32-core Xeon; ours is an exact scan, theirs sampled 30% of items).
   Rows carry `hbm_util` = effective item-matrix read bandwidth over the
   chip's peak HBM bandwidth (the scan is bandwidth-bound).
2. kmeans / als / rdf — train walls vs this build's r05 CPU-container
   floors (docs/performance.md); training rows carry `mfu` = analytic
   useful FLOPs / wall / chip peak bf16 FLOP/s.
3. als-scale — implicit power-law training ratings/s (f32 and bf16
   Gramians).
4. speed — sustained events/s through the REAL SpeedLayer over the shm
   bus vs the BASELINE.json 100K events/s target: a backlog row
   (pre-encoded ring drain, layer capacity) and a live row (producer
   processes racing the layer).
5. serving closed-loop — 1..3 concurrent SYNCHRONOUS clients through the
   real HTTP serving path (ServingLayer + endpoints + micro-batcher):
   true per-request p50/p99 next to the pipelined-throughput rows, the
   apples-to-apples view against the reference's 437 qps / 7 ms table.
6. tracing-overhead — speed backlog events/s and closed-loop serving qps
   with the distributed tracer on (default 1% sampling) vs off
   (ORYX_TRACING=0); vs_baseline = on/off median ratio, hard-fails when
   clearly below the 0.98 envelope (docs/observability.md).

Noise protocol: every metric is measured over >= 3 trials (cheap
trainers 5) after the discarded compile pass; rows record the MEDIAN as
`value` plus `trials` and `spread` ([min, max] in the row's own units).
A row whose median misses its floor while its best trial clears it is
flagged `noise-suspect` — the regression call would flip on re-run luck,
so treat it as noise until a clean round says otherwise.

Process model: the benchmark body runs once in a child process, on
whatever platform $JAX_PLATFORMS names (the accelerator by default);
there is no retry and no CPU fallback, and the run exits non-zero when
the child does or when any selected bench raised. The parent never
touches jax, so the child is the chip's one owner; rows that spawn
further processes (speed layer, fleet replicas) start them host-only
with JAX_PLATFORMS=cpu and label the row so. Child stdout streams
line-by-line so completed metrics survive a mid-run kill; the summary
block is printed by the parent after all stderr, so XLA warning spam
can never wash metrics out of a bounded stdout tail.

Env knobs: ORYX_BENCH_ITEMS/FEATURES/USERS/SECONDS/BATCH/DEPTH/DTYPE
(serving); ORYX_BENCH_SHAPES=headline|all (serving table coverage);
ORYX_BENCH_ONLY (comma list of metric names); ORYX_BENCH_INIT_TIMEOUT;
ORYX_BENCH_TRIALS / ORYX_BENCH_TRIALS_CHEAP
(noise protocol, default 3/5); ORYX_BENCH_CL_USERS/CL_SECONDS
(closed-loop serving); ORYX_BENCH_TRACE_PREFILL/ITEMS/SECONDS/ENVELOPE
(tracing-overhead); ORYX_BENCH_MAINTAIN_ITEMS/FEATURES/SECONDS/INTERVAL/
FRESH_BUDGET (live-maintenance ANN rows); ORYX_BENCH_COLD_ITEMS/COLD_RAM_MB
(cold-tier store row, sized down to free disk); ORYX_TB_* (training
shapes, see tools/train_benchmark.py).
"""

import json
import os
import statistics
import subprocess
import sys
import time
from collections import deque

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _HERE)

EVIDENCE_PATH = os.path.join(_HERE, "tools", "bench_evidence.txt")

# XLA:CPU AOT cache entries compiled on another machine spam stderr with
# E-level "machine features" lines (and can SIGILL); silence native logs
# below FATAL — bench prints its own diagnostics.
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

# r06 CPU-container floors (docs/performance.md, identical configs,
# re-measured 2026-08-06 under the trials/median protocol: one discarded
# compile pass, then 5 trials (k-means, ALS) / 3 trials (RDF, ALS-scale),
# median recorded; spreads were within 5% of the median for every floor.
# Much tighter than the 2026-07-30 r05 constants because the trainers
# themselves got faster in between (single-dispatch RDF level histograms,
# ALS solve caching, mini-batch k-means) — against the old floors every
# row would have read as a spurious speedup.
CPU_FLOOR_ALS_WALL = 0.42
CPU_FLOOR_ALS_SCALE_RPS = 575_000.0
CPU_FLOOR_KMEANS_WALL = 0.39
CPU_FLOOR_RDF_WALL = 7.2
SPEED_TARGET_EPS = 100_000.0

# Published /recommend qps at LSH sample-rate 0.3 on a 32-core Xeon
# (reference docs/performance.md:108-117), keyed by (features, items).
SERVING_BASELINE_QPS = {
    (50, 1_000_000): 437.0,
    (250, 1_000_000): 151.0,
    (50, 5_000_000): 84.0,
    (250, 5_000_000): 36.0,
    (50, 20_000_000): 14.0,
    (250, 20_000_000): 6.0,
}

# Chip peaks (bf16 FLOP/s, HBM bytes/s) by device-kind substring.
_CHIP_PEAKS = [
    ("v5 lite", 197e12, 819e9),
    ("v5e", 197e12, 819e9),
    ("v5p", 459e12, 2765e9),
    ("v6", 918e12, 1640e9),
    ("trillium", 918e12, 1640e9),
    ("v4", 275e12, 1228e9),
    ("v3", 123e12, 900e9),
    ("v2", 45e12, 700e9),
]


def _device_info():
    """(backend, device_kind, (peak_flops, peak_bw) or None)."""
    import jax

    backend = jax.default_backend()
    kind = getattr(jax.devices()[0], "device_kind", backend)
    peaks = None
    if backend == "tpu":
        low = kind.lower()
        for sub, fl, bw in _CHIP_PEAKS:
            if sub in low:
                peaks = (fl, bw)
                break
        if peaks is None:
            raise RuntimeError(
                f"device_kind {kind!r} is not in _CHIP_PEAKS: add its "
                "published peaks before benchmarking on it"
            )
    return backend, kind, peaks


# Rows that spawn further processes start them host-only: this process
# holds the chip, and a chip has one owner. The row carries the label.
_HOST_BACKEND = f"host/{os.cpu_count()}-core"


def _host_child_env(**extra) -> dict:
    return {**os.environ, "JAX_PLATFORMS": "cpu", **extra}


# Noise protocol: trials per metric. The cheap trainers (k-means, ALS
# ML-100K) get 5, everything else 3; medians go in `value`.
_TRIALS = max(1, int(os.environ.get("ORYX_BENCH_TRIALS", 3)))
_TRIALS_CHEAP = max(1, int(os.environ.get("ORYX_BENCH_TRIALS_CHEAP", 5)))


def _trial_fields(vals, ratios) -> dict:
    """`trials`/`spread` extras (plus the `noise-suspect` flag) for a set
    of per-trial measurements: spread is [min, max] in the row's own
    units; the row is noise-suspect when the MEDIAN misses the floor but
    the best trial clears it — the regression call would flip on re-run
    luck."""
    extra = {
        "trials": len(vals),
        "spread": [round(float(min(vals)), 3), round(float(max(vals)), 3)],
    }
    if statistics.median(ratios) < 1.0 <= max(ratios):
        extra["noise_suspect"] = True
    return extra


def _wall_row(walls, floor) -> tuple[float, float, dict]:
    """(median, vs_baseline, extras) for lower-is-better wall rows."""
    med = statistics.median(walls)
    return med, floor / max(med, 1e-9), _trial_fields(
        walls, [floor / max(w, 1e-9) for w in walls]
    )


def _rate_row(rates, floor) -> tuple[float, float, dict]:
    """(median, vs_baseline, extras) for higher-is-better rate rows."""
    med = statistics.median(rates)
    return med, med / floor, _trial_fields(rates, [v / floor for v in rates])


def _median_run(runs: list, key: str) -> dict:
    """The run dict whose `key` is the median trial's — its config,
    quality, and phase fields then describe a trial that was actually
    recorded rather than a synthetic average."""
    return sorted(runs, key=lambda r: r[key])[len(runs) // 2]


def _emit(
    metric: str,
    value: float,
    unit: str,
    vs_baseline: float,
    order: int = 50,
    detail: str = "",
    **extra,
) -> None:
    row = {
        "metric": metric,
        "value": round(float(value), 2),
        "unit": unit,
        "vs_baseline": round(float(vs_baseline), 2),
    }
    if extra.pop("noise_suspect", False):
        row["noise-suspect"] = True
    row["backend"] = extra.pop("backend", None)
    if row["backend"] is None:
        backend, kind, _ = _device_info()
        row["backend"] = f"{backend}/{kind}"
    for k, v in extra.items():
        if v is not None:
            row[k] = round(float(v), 4) if isinstance(v, float) else v
    row["order"] = order
    print(json.dumps(row), flush=True)
    try:
        with open(EVIDENCE_PATH, "a", encoding="utf-8") as f:
            ts = time.strftime("%Y-%m-%d %H:%M:%S UTC", time.gmtime())
            f.write(f"{ts} {json.dumps(row)}\n")
            if detail:
                f.write(f"    {detail}\n")
    except OSError:
        pass


# --------------------------------------------------------------------------
# Child: the benchmark bodies.
# --------------------------------------------------------------------------


def bench_serving_shape(
    items: int, features: int, order: int, seconds: float | None = None
) -> None:
    users = int(os.environ.get("ORYX_BENCH_USERS", 8192))
    seconds = seconds or float(os.environ.get("ORYX_BENCH_SECONDS", 10.0))
    group = int(os.environ.get("ORYX_BENCH_GROUP", 2048))  # queries/dispatch
    # narrower scans for wide features keep the kernel inside scoped VMEM
    scan_batch = int(
        os.environ.get("ORYX_BENCH_SCAN_BATCH", 256 if features <= 64 else 128)
    )
    depth = int(os.environ.get("ORYX_BENCH_DEPTH", 12))  # dispatches in flight
    # int8 by default: the row-quantized primary plane halves the scanned
    # bytes vs bf16 and the residual-plane rescore holds top-10 recall at
    # >= 0.99 of float32 (emitted below as its own metric row)
    dtype_name = os.environ.get("ORYX_BENCH_DTYPE", "int8")
    how_many = 10

    import numpy as np
    import jax
    import jax.numpy as jnp

    backend, kind, peaks = _device_info()
    if backend != "tpu":
        seconds = min(seconds, 5.0)
        depth = min(depth, 4)
        group = min(group, 512)

    from oryx_tpu.ops import topn as topn_ops

    gen = np.random.default_rng(1234)
    x = gen.standard_normal((users, features), dtype=np.float32)

    dtype = {"bfloat16": jnp.bfloat16, "int8": jnp.int8}.get(dtype_name, jnp.float32)
    # item matrix generated ON DEVICE: at 20M x 250 the bf16 matrix is
    # 10 GB that need not cross the host<->device link
    uploaded = topn_ops.upload_random(items, features, dtype=dtype, seed=97 + features)
    scans_per_dispatch = (group + scan_batch - 1) // scan_batch
    # "index": user-factor matrix staged on device once, each dispatch
    # ships int32 row indices (4 B/query up) — the serving layout where X
    # lives next to Y. "vector": full query vectors up per dispatch.
    submit_mode = os.environ.get("ORYX_BENCH_SUBMIT", "index")
    x_dev = topn_ops.upload_queries(x) if submit_mode == "index" else None
    idx_all = np.arange(users, dtype=np.int32)

    def submit(lo: int, hi: int):
        if submit_mode == "index":
            return topn_ops.submit_top_k_multi_indexed(
                uploaded, x_dev, idx_all[lo:hi], how_many, scan_batch=scan_batch
            )
        return topn_ops.submit_top_k_multi(
            uploaded, x[lo:hi], how_many, scan_batch=scan_batch
        )

    t0 = time.perf_counter()
    try:
        submit(0, group).result()
    except Exception as e:  # noqa: BLE001
        if submit_mode != "index":
            raise
        # index submit is the default but must never cost the metric:
        # fall back to vector upload if the indexed program won't build
        print(f"bench[serving]: index submit failed ({e!r}); vector fallback", file=sys.stderr)
        submit_mode = "vector"
        x_dev = None
        submit(0, group).result()
    print(
        f"bench[serving {features}f x {items} items]: warmup/compile "
        f"{time.perf_counter() - t0:.1f}s",
        file=sys.stderr,
    )

    # real row spans: the last (or only) group may be short of `group`
    bounds = [
        (lo, min(lo + group, users)) for lo in range(0, max(users, 1), group)
    ]

    def run_trial() -> tuple[float, float, list[float]]:
        """(qps, dispatches_per_sec, per-dispatch latencies) for one
        `seconds`-long pipelined pass."""
        served = 0
        inflight: deque = deque()
        lats: list[float] = []
        start = time.perf_counter()
        deadline = start + seconds
        i = 0
        while True:
            now = time.perf_counter()
            if now < deadline and len(inflight) < depth:
                lo, hi = bounds[i % len(bounds)]
                inflight.append((submit(lo, hi), hi - lo, time.perf_counter()))
                i += 1
            elif inflight:
                handle, rows, t_submit = inflight.popleft()
                handle.result()
                lats.append(time.perf_counter() - t_submit)
                served += rows
            else:
                break
        elapsed = time.perf_counter() - start
        return served / elapsed, i / elapsed, lats

    qps_trials: list[float] = []
    dispatch_rates: list[float] = []
    latencies: list[float] = []
    for _ in range(_TRIALS):
        q, dr, lats = run_trial()
        qps_trials.append(q)
        dispatch_rates.append(dr)
        latencies.extend(lats)
    lat = np.percentile(np.array(latencies) * 1000, [50, 99]) if latencies else [0, 0]
    # scanned bytes per full-matrix pass: int8 streams the 1 B/feat
    # primary plane (the residual plane is only gathered for the few
    # hundred rescore candidates), bf16 2 B/feat, f32 4 B/feat
    bytes_per_scan = items * features * {"bfloat16": 2, "int8": 1}.get(dtype_name, 4)
    gbps = statistics.median(dispatch_rates) * scans_per_dispatch * bytes_per_scan / 1e9
    hbm_util = gbps * 1e9 / peaks[1] if peaks else None
    published = (features, items) in SERVING_BASELINE_QPS
    base = SERVING_BASELINE_QPS.get((features, items), 437.0)
    qps, vs, tf = _rate_row(qps_trials, base)
    detail = (
        f"p50 {lat[0]:.0f} ms / p99 {lat[1]:.0f} ms queued-behind-pipeline at "
        f"depth {depth}; {tf['trials']} x {seconds:.0f}s trials, "
        f"{scans_per_dispatch} fused scans x {scan_batch} queries per dispatch, "
        f"{submit_mode}-submit; ~{gbps:.1f} GB/s "
        f"effective item-matrix read bandwidth"
        + (f" = {100 * hbm_util:.0f}% of {kind} peak {peaks[1] / 1e9:.0f} GB/s" if peaks else "")
    )
    print(f"bench[serving {features}f x {items}]: {detail}", file=sys.stderr)
    frame = (
        f"vs {base:.0f} qps published (LSH 0.3, 32-core Xeon)"
        if published
        else f"vs {base:.0f} qps headline figure (no published number for this shape)"
    )
    label_m = f"{items // 1_000_000}M" if items >= 1_000_000 else f"{items // 1000}K"
    _emit(
        f"ALS /recommend top-{how_many} exact scan, {features}f x {label_m} items, "
        f"{dtype_name}, {frame}",
        qps,
        "queries/sec",
        vs,
        order=order,
        detail=detail,
        hbm_util=hbm_util,
        p50_ms=float(lat[0]),
        p99_ms=float(lat[1]),
        effective_gbps=float(gbps),
        dispatch_depth=depth,
        **tf,
    )
    if dtype_name == "int8":
        _bench_serving_recall(items, features, how_many, order)


def _bench_serving_recall(
    items: int, features: int, how_many: int, order: int
) -> None:
    """Quantized-recall companion row: top-``how_many`` overlap of the
    int8 two-plane scan against the exact float32 ranking on a
    host-generated matrix of the same shape (capped at 1M items — the
    probe needs the float32 truth in host RAM). Tie-tolerant: a returned
    item counts as a hit when its true score reaches the true k-th best
    minus 1e-5, so exact-tie reorderings don't read as recall loss."""
    import numpy as np
    import jax.numpy as jnp

    from oryx_tpu.ops import topn as topn_ops

    n = min(items, 1_000_000)
    probes = int(os.environ.get("ORYX_BENCH_RECALL_PROBES", 32))
    gen = np.random.default_rng(4321)
    mat = gen.standard_normal((n, features), dtype=np.float32)
    up8 = topn_ops.upload(mat, dtype=jnp.int8)
    recalls: list[float] = []
    for t in range(_TRIALS):
        # fresh probe set per trial: the spread measures probe-sampling
        # noise on the one quantized matrix actually served
        qgen = np.random.default_rng(9876 + t)
        queries = qgen.standard_normal((probes, features), dtype=np.float32)
        hits = 0
        for r in range(probes):
            idx, _vals = topn_ops.top_k_scores(up8, queries[r], how_many)
            truth = mat @ queries[r]
            kth = np.partition(truth, -how_many)[-how_many]
            hits += int(np.sum(truth[np.asarray(idx)] >= kth - 1e-5))
        recalls.append(hits / (probes * how_many))
    recall, vs, tf = _rate_row(recalls, 0.99)
    label_m = f"{n // 1_000_000}M" if n >= 1_000_000 else f"{n // 1000}K"
    _emit(
        f"ALS /recommend top-{how_many} int8 recall vs exact float32, "
        f"{features}f x {label_m} items, vs 0.99 floor",
        recall,
        "recall@10",
        vs,
        order=order + 1,
        detail=f"{probes} probe queries x {tf['trials']} probe sets, "
        "tie-tolerant at 1e-5",
        **tf,
    )


def _ann_mixture(n: int, features: int, cells: int, seed: int, batch: int):
    """Cell-matched mixture catalog + queries. IVF's recall-vs-probe
    curve requires cluster structure (ALS item factors have it; isotropic
    gaussian is the adversarial no-structure case where probing p% of
    cells finds ~p% of neighbors) — the rows say so in their detail."""
    import numpy as np

    gen = np.random.default_rng(seed)
    centers = gen.standard_normal((cells, features), dtype=np.float32)
    mat = centers[gen.integers(0, cells, n)] + 0.3 * gen.standard_normal(
        (n, features), dtype=np.float32
    )
    queries = centers[gen.integers(0, cells, batch)] + 0.3 * gen.standard_normal(
        (batch, features), dtype=np.float32
    )
    return mat, queries


def _ann_recall_vs_exact(mat, queries, exact_ids, ann_ids, k: int) -> float:
    """recall@k of the ANN result against the exact int8 scan's result on
    the same matrix, tie-tolerant on true f32 scores (an ANN item whose
    true score reaches the exact k-th's minus 1e-5 is a hit)."""
    import numpy as np

    hits = 0
    for r in range(len(queries)):
        q = queries[r]
        e = np.asarray(exact_ids[r][:k])
        a = np.asarray(ann_ids[r][:k])
        a = a[a >= 0]
        kth = float(np.min(mat[e] @ q))
        hits += int(np.sum(mat[a] @ q >= kth - 1e-5))
    return hits / (len(queries) * k)


def _ann_measure(fn, batch: int, dispatches: int):
    """(per-trial qps list, per-dispatch walls) after one warm dispatch."""
    fn()  # warm: trace/compile + route-table caches
    rates: list[float] = []
    walls: list[float] = []
    for _ in range(_TRIALS):
        t0 = time.perf_counter()
        for _ in range(dispatches):
            td = time.perf_counter()
            fn()
            walls.append(time.perf_counter() - td)
        rates.append(dispatches * batch / (time.perf_counter() - t0))
    return rates, walls


def _bench_ann_shape(
    items: int,
    features: int,
    nprobe: int,
    sweep: tuple,
    order: int,
    dispatches: int,
    emit_p99: bool = False,
) -> None:
    import numpy as np
    import jax.numpy as jnp

    from oryx_tpu.ops import ivf as ivf_ops
    from oryx_tpu.ops import topn as topn_ops

    how_many = 10
    batch = int(os.environ.get("ORYX_BENCH_ANN_BATCH", 256))
    cells = max(64, int(round(items**0.5 / 8)) * 8)
    label_m = f"{items // 1_000_000}M" if items >= 1_000_000 else f"{items // 1000}K"
    mat, queries = _ann_mixture(items, features, cells, 4242 + features, batch)

    # in-run exact int8 baseline on the SAME matrix: the ANN speedup
    # claim is only honest against the scan it displaces, measured under
    # the same noise
    up8 = topn_ops.upload(mat, dtype=jnp.int8)
    exact_ids_box: list = []

    def exact_call():
        ids, _vals = topn_ops.top_k_scores_batch(up8, queries, how_many)
        if not exact_ids_box:
            exact_ids_box.append(np.asarray(ids))

    exact_rates, _ = _ann_measure(exact_call, batch, max(1, dispatches // 2))
    exact_qps = statistics.median(exact_rates)
    exact_ids = exact_ids_box[0]
    del up8

    t0 = time.perf_counter()
    index = ivf_ops.build_ivf(mat, n_cells=cells, seed=7)
    build_sec = time.perf_counter() - t0
    print(
        f"bench[serving-ann {features}f x {label_m}]: build_ivf {build_sec:.0f}s "
        f"({index.n_cells} cells), exact int8 {exact_qps:.0f} qps",
        file=sys.stderr,
    )

    for np_ in sorted(set((nprobe,) + tuple(sweep))):
        ann_ids_box: list = []

        def ann_call():
            ids, _vals = ivf_ops.top_k(index, queries, how_many, nprobe=np_)
            if not ann_ids_box:
                ann_ids_box.append(np.asarray(ids))

        rates, walls = _ann_measure(ann_call, batch, dispatches)
        recall = _ann_recall_vs_exact(mat, queries, exact_ids, ann_ids_box[0], how_many)
        qps, vs, tf = _rate_row(rates, exact_qps)
        frac = 100.0 * np_ / index.n_cells
        headline = np_ == nprobe
        detail = (
            f"IVF {index.n_cells} cells, nprobe {np_} ({frac:.1f}% probed), "
            f"recall@10 {recall:.3f} vs exact int8 (tie-tolerant 1e-5), "
            f"{tf['trials']} x {dispatches} dispatches x {batch} queries, "
            f"cell-matched mixture catalog (see docs/serving-scan.md data-model "
            f"caveat), build {build_sec:.0f}s; vs_baseline = speedup over the "
            f"in-run exact int8 scan ({exact_qps:.0f} qps)"
        )
        print(f"bench[serving-ann {features}f x {label_m}]: {detail}", file=sys.stderr)
        extra = dict(
            recall_at_10=round(recall, 4),
            nprobe=np_,
            cells=index.n_cells,
            exact_qps=round(exact_qps, 1),
            build_sec=round(build_sec, 1),
        )
        if emit_p99:
            lat = np.percentile(np.array(walls) * 1000.0, [50, 99])
            extra.update(p50_ms=float(lat[0]), p99_ms=float(lat[1]))
        kind = "ANN scan" if headline else f"ANN probe sweep nprobe={np_}"
        _emit(
            f"ALS /recommend top-{how_many} {kind}, {features}f x {label_m} items, "
            f"int8 IVF, vs in-run exact int8 qps",
            qps,
            "queries/sec",
            vs,
            order=order if headline else order - 1,
            detail=detail,
            **extra,
            **tf,
        )
        if headline:
            # the acceptance floor rides its own row: recall@10 >= 0.95
            _emit(
                f"ALS /recommend top-{how_many} ANN recall vs exact int8, "
                f"{features}f x {label_m} items, vs 0.95 floor",
                recall,
                "recall@10",
                recall / 0.95,
                order=order,
                detail=f"nprobe {np_} of {index.n_cells} cells ({frac:.1f}%), "
                "tie-tolerant at 1e-5 on true f32 scores",
                nprobe=np_,
                cells=index.n_cells,
            )


def bench_serving_ann() -> None:
    """IVF ANN tier rows: qps + recall@10 against the exact int8 scan on
    the same matrix in the same run (both 1M shapes), a probe-fraction
    sweep at the wide shape, and a >=10M-item steady-state row with
    per-dispatch p50/p99."""
    from oryx_tpu.ops import ivf as ivf_ops

    items = int(os.environ.get("ORYX_BENCH_ANN_ITEMS", 1_000_000))
    old_qb = ivf_ops.QUERY_BLOCK
    # small query groups keep the probed-cell union near nprobe cells per
    # group — the measured host-path knee
    ivf_ops.configure_ann(query_block=4)
    try:
        _bench_ann_shape(items, 50, nprobe=7, sweep=(), order=86, dispatches=4)
        # 0.3% probed is the measured qps/recall knee at the wide shape on
        # clustered catalogs (recall@10 1.0, ~4-8x exact); 7 and 15 chart
        # the recall-insurance side of the curve
        _bench_ann_shape(items, 250, nprobe=3, sweep=(7, 15), order=87, dispatches=4)
        if os.environ.get("ORYX_BENCH_SHAPES", "all") == "all":
            large = int(os.environ.get("ORYX_BENCH_ANN_LARGE_ITEMS", 10_000_000))
            cells = max(64, int(round(large**0.5 / 8)) * 8)
            _bench_ann_shape(
                large,
                50,
                nprobe=max(8, int(round(0.0025 * cells))),
                sweep=(),
                order=88,
                dispatches=2,
                emit_p99=True,
            )
    finally:
        ivf_ops.configure_ann(query_block=old_qb)


def bench_serving() -> None:
    # headline shape last so its row is the last line of the summary
    items = int(os.environ.get("ORYX_BENCH_ITEMS", 1_000_000))
    features = int(os.environ.get("ORYX_BENCH_FEATURES", 50))
    bench_serving_shape(items, features, order=100)


def bench_serving_250() -> None:
    items = int(os.environ.get("ORYX_BENCH_ITEMS", 1_000_000))
    bench_serving_shape(items, 250, order=90)


def bench_serving_large() -> None:
    """The reference table's 5M/20M-item rows (performance.md:114-117).
    TPU-only: HBM-resident bf16; on CPU these would measure host DRAM."""
    backend, _, _ = _device_info()
    if backend != "tpu":
        print("bench[serving-large]: skipped (no TPU)", file=sys.stderr)
        return
    for items, features, order in (
        (5_000_000, 50, 80),
        (5_000_000, 250, 81),
        (20_000_000, 50, 82),
        (20_000_000, 250, 83),
    ):
        bench_serving_shape(items, features, order=order, seconds=6.0)


def _emit_phases(name: str, runs: list, order: int) -> None:
    """Per-phase wall row next to a trainer's headline: value = iterate
    (the sweep itself) from the median-iterate trial, vs_baseline =
    iterate's share of that trial's phased wall; pack/init/eval ride
    along as extra fields. Makes host packing and dispatch overhead vs
    real iteration visible without a profiler."""
    phs = [r.get("phase_sec") or {} for r in runs]
    phs = [p for p in phs if p]
    if not phs:
        return
    phs.sort(key=lambda p: p.get("iterate", 0.0))
    ph = phs[len(phs) // 2]
    total = sum(ph.values())
    iters = [p.get("iterate", 0.0) for p in phs]
    _emit(
        f"{name} per-phase wall, iterate sec (share of pack+init+iterate+eval)",
        ph.get("iterate", 0.0),
        "sec",
        ph.get("iterate", 0.0) / total if total > 0 else 0.0,
        order=order,
        detail=json.dumps(ph),
        trials=len(phs),
        spread=[round(min(iters), 3), round(max(iters), 3)],
        pack_sec=ph.get("pack"),
        init_sec=ph.get("init"),
        iterate_sec=ph.get("iterate"),
        eval_sec=ph.get("eval"),
    )


def bench_kmeans() -> None:
    from tools import train_benchmark as tb

    tb.bench_kmeans()  # compile pass — generations reuse compiled programs
    runs = [tb.bench_kmeans() for _ in range(_TRIALS_CHEAP)]
    r = _median_run(runs, "wall_sec")
    wall, vs, tf = _wall_row([t["wall_sec"] for t in runs], CPU_FLOOR_KMEANS_WALL)
    _, _, peaks = _device_info()
    n, d, k, iters = int(os.environ.get("ORYX_TB_KMEANS_N", 200_000)), 20, 10, 20
    flops = 3.0 * n * d * k * iters  # dist matmul 2ndk + argmin/update ~ndk
    mfu = flops / max(wall, 1e-9) / peaks[0] if peaks else None
    _emit(
        f"k-means train wall, median of {tf['trials']} steady-state trials, "
        f"{r['config']}, vs {CPU_FLOOR_KMEANS_WALL}s CPU floor",
        wall,
        "sec",
        vs,
        order=10,
        detail=f"sse/pt {r['sse_per_point']}, silhouette {r['silhouette_2k_sample']}",
        mfu=mfu,
        **tf,
    )
    _emit_phases("k-means", runs, order=30)


def bench_als() -> None:
    from tools import train_benchmark as tb

    tb.bench_als()  # compile pass
    runs = [tb.bench_als() for _ in range(_TRIALS_CHEAP)]
    r = _median_run(runs, "wall_sec")
    wall, vs, tf = _wall_row([t["wall_sec"] for t in runs], CPU_FLOOR_ALS_WALL)
    _emit(
        f"ALS train wall, median of {tf['trials']} steady-state trials, "
        f"ML-100K shape rank 25, vs {CPU_FLOOR_ALS_WALL}s CPU floor",
        wall,
        "sec",
        vs,
        order=12,
        detail=f"{r['config']}; held-out RMSE {r['held_out_rmse']}",
        **tf,
    )
    _emit_phases("ALS", runs, order=32)


def _als_scale_mfu(r: dict) -> float | None:
    """Analytic useful FLOPs for the sweep: each rating contributes a
    rank^2 outer product to its row's Gramian on both sides (4*nnz*r^2
    FLOPs/sweep); rank^3 solves are lower-order at these shapes."""
    _, _, peaks = _device_info()
    if not peaks:
        return None
    nnz = int(float(os.environ.get("ORYX_TB_SCALE_NNZ", 2e6)))
    rank = int(os.environ.get("ORYX_TB_SCALE_RANK", 32))
    flops_per_sweep = 4.0 * nnz * rank * rank
    return flops_per_sweep * 3 / max(r["wall_sec"], 1e-9) / peaks[0]


def bench_als_scale() -> None:
    from tools import train_benchmark as tb

    # the baseline row must be f32 even if the experiment knob is exported
    prev = os.environ.pop("ORYX_TB_MATMUL_DTYPE", None)
    runs = [tb.bench_als_scale() for _ in range(_TRIALS)]
    r = _median_run(runs, "ratings_per_sec")
    rate, vs, tf = _rate_row(
        [t["ratings_per_sec"] for t in runs], CPU_FLOOR_ALS_SCALE_RPS
    )
    _emit(
        f"ALS implicit training throughput, f32 Gramians, median of "
        f"{tf['trials']} trials, "
        f"vs {CPU_FLOOR_ALS_SCALE_RPS / 1000:.0f}k ratings/s CPU floor",
        rate,
        "ratings/sec",
        vs,
        order=20,
        detail=r["config"],
        mfu=_als_scale_mfu(r),
        **tf,
    )
    # the pack phase dominates host-side cost at this shape — surface it
    _emit_phases("ALS implicit scale f32", runs, order=33)
    # the bf16-Gramian variant (oryx.batch.compute.matmul-dtype=bfloat16):
    # half the HBM traffic, full-rate MXU; same CPU-floor denominator
    os.environ["ORYX_TB_MATMUL_DTYPE"] = "bfloat16"
    try:
        runs_b = [tb.bench_als_scale() for _ in range(_TRIALS)]
    finally:
        if prev is None:
            os.environ.pop("ORYX_TB_MATMUL_DTYPE", None)
        else:
            os.environ["ORYX_TB_MATMUL_DTYPE"] = prev
    rb = _median_run(runs_b, "ratings_per_sec")
    rate_b, vs_b, tf_b = _rate_row(
        [t["ratings_per_sec"] for t in runs_b], CPU_FLOOR_ALS_SCALE_RPS
    )
    _emit(
        f"ALS implicit training throughput, bf16 Gramians, median of "
        f"{tf_b['trials']} trials, "
        f"vs {CPU_FLOOR_ALS_SCALE_RPS / 1000:.0f}k ratings/s CPU floor",
        rate_b,
        "ratings/sec",
        vs_b,
        order=21,
        detail=rb["config"],
        mfu=_als_scale_mfu(rb),
        **tf_b,
    )
    backend, _, peaks = _device_info()
    if backend == "tpu":
        # a TPU-scale row: 2M x rank-32 can't fill the MXU; 20M x rank-64
        # is the shape docs/performance.md's sharded-CPU run recorded at
        # 106k ratings/s (the closest this build has to a CPU floor there)
        saved = {
            k: os.environ.get(k)
            for k in ("ORYX_TB_SCALE_NNZ", "ORYX_TB_SCALE_RANK", "ORYX_TB_MATMUL_DTYPE")
        }
        os.environ.update(
            ORYX_TB_SCALE_NNZ="20000000",
            ORYX_TB_SCALE_RANK="64",
            ORYX_TB_MATMUL_DTYPE="bfloat16",
        )
        try:
            runs_t = [tb.bench_als_scale() for _ in range(_TRIALS)]
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        rt = _median_run(runs_t, "ratings_per_sec")
        rate_t, vs_t, tf_t = _rate_row(
            [t["ratings_per_sec"] for t in runs_t], 106_000.0
        )
        flops = 4.0 * 20e6 * 64 * 64 * 3
        _emit(
            "ALS implicit training throughput, 20M ratings rank 64 bf16, "
            f"median of {tf_t['trials']} trials, vs 106k ratings/s (this "
            "build's 8-virtual-CPU sharded run of the same shape)",
            rate_t,
            "ratings/sec",
            vs_t,
            order=22,
            detail=rt["config"],
            mfu=flops / max(rt["wall_sec"], 1e-9) / peaks[0] if peaks else None,
            **tf_t,
        )


def bench_rdf() -> None:
    from tools import train_benchmark as tb

    tb.bench_rdf()  # compile pass — generations reuse compiled programs
    runs = [tb.bench_rdf() for _ in range(_TRIALS)]
    r = _median_run(runs, "wall_sec")
    wall, vs, tf = _wall_row([t["wall_sec"] for t in runs], CPU_FLOOR_RDF_WALL)
    _emit(
        f"RDF train wall, median of {tf['trials']} steady-state trials, "
        f"covtype shape 20 trees depth 10, vs {CPU_FLOOR_RDF_WALL}s CPU floor",
        wall,
        "sec",
        vs,
        order=11,
        detail=f"{r['config']}; held-out accuracy {r['held_out_accuracy']}",
        **tf,
    )
    _emit_phases("RDF", runs, order=31)


def bench_speed() -> None:
    """Run the real-SpeedLayer bench as a subprocess (own process: it
    spins threads, producer processes, and an shm bus). Two rows:
    backlog mode (pre-encoded events drained from the ring — the
    layer-capacity measure) and live mode (producer processes racing the
    layer — the end-to-end measure). The trial protocol runs INSIDE the
    subprocess (--trials): model seeding is paid once per mode instead
    of once per trial, and the per-trial rates come back in the JSON."""

    def run_mode(label: str, extra: list) -> dict:
        proc = subprocess.run(
            [
                sys.executable,
                os.path.join(_HERE, "tools", "speed_layer_benchmark.py"),
                "--trials",
                str(_TRIALS),
                *extra,
            ],
            capture_output=True,
            text=True,
            timeout=600,
            env=_host_child_env(),
        )
        sys.stderr.write(proc.stderr[-1500:])
        line = None
        for ln in proc.stdout.splitlines():
            if ln.startswith("{") and '"metric"' in ln:
                line = ln
        if proc.returncode != 0 or line is None:
            raise RuntimeError(
                f"speed bench ({label}) failed rc={proc.returncode}"
            )
        return json.loads(line)

    # sharded row at N_cores shards (floor 2 so the multi-chain path is
    # exercised even on single-core CI hosts)
    n_shards = max(2, os.cpu_count() or 1)
    modes = [
        ("backlog", ["--prefill", "500000"]),
        (
            f"backlog {n_shards}-shard",
            ["--prefill", "500000", "--shards", str(n_shards)],
        ),
        ("live", ["--seconds", "12", "--producers", "2"]),
    ]
    for idx, (label, extra) in enumerate(modes):
        d = run_mode(label, extra)
        rates = d.get("rates") or [d["value"]]
        rate, vs, tf = _rate_row(rates, SPEED_TARGET_EPS)
        _emit(
            f"speed layer sustained fold-in over shm bus, {label} mode, "
            f"median of {tf['trials']} trials, vs 100K events/s BASELINE "
            f"target ({os.cpu_count()}-core host)",
            rate,
            "events/sec",
            vs,
            order=30 + idx,
            detail=d["metric"],
            # the speed layer child runs host-only (JAX_PLATFORMS=cpu)
            backend=_HOST_BACKEND,
            **tf,
        )


def bench_tracing_overhead() -> None:
    """Tracing-cost acceptance rows: the distributed tracer at its
    default 1% sample rate must cost <= 2% on both hot paths. Two
    comparisons, each >= 3-trial medians with tracing ON vs OFF:

    - speed layer backlog events/s — subprocess runs of the real
      SpeedLayer bench toggled via ORYX_TRACING (the layer process reads
      the env at import, exactly how an operator would disable tracing);
    - closed-loop serving qps through the real HTTP path (in-process
      `tracing.configure` toggle around the same layer + model).

    vs_baseline = on/off median ratio. A row whose median AND best trial
    both land below the 0.98 envelope hard-fails the bench; median-only
    misses are flagged `noise-suspect` per the repo's noise protocol."""
    import threading
    import urllib.request

    from oryx_tpu.common import config as C
    from oryx_tpu.common import tracing
    from oryx_tpu.serving.layer import ServingLayer
    from tools.load_benchmark import build_model
    from tools.traffic import worker

    envelope = float(os.environ.get("ORYX_BENCH_TRACE_ENVELOPE", 0.98))
    failures: list[str] = []

    def ratio_row(
        kind: str, unit: str, on_rates: list, off_rates: list, order: int
    ) -> None:
        med_on = statistics.median(on_rates)
        med_off = max(statistics.median(off_rates), 1e-9)
        ratio = med_on / med_off
        best = max(on_rates) / med_off
        detail = (
            f"tracing on {med_on:.0f} vs off {med_off:.0f} {unit} "
            f"(medians of {len(on_rates)}/{len(off_rates)} trials), "
            f"overhead {100 * (1 - ratio):.2f}%, envelope <= "
            f"{100 * (1 - envelope):.0f}%"
        )
        print(f"bench[tracing-overhead {kind}]: {detail}", file=sys.stderr)
        _emit(
            f"tracing overhead, {kind}, default 1% sampling on vs off "
            f"(vs_baseline = on/off ratio, floor {envelope})",
            med_on,
            unit,
            ratio,
            order=order,
            detail=detail,
            off_value=round(med_off, 2),
            overhead_pct=round(100 * (1 - ratio), 3),
            noise_suspect=ratio < envelope <= best,
            spread=[round(float(min(on_rates)), 2), round(float(max(on_rates)), 2)],
            trials=len(on_rates),
            # the speed arms are host-only child processes
            backend=_HOST_BACKEND if kind.startswith("speed") else None,
        )
        if ratio < envelope and best < envelope:
            failures.append(f"{kind}: on/off {ratio:.4f} < {envelope}")

    # --- speed backlog: subprocess per mode, env toggle ---------------------
    prefill = int(os.environ.get("ORYX_BENCH_TRACE_PREFILL", 300_000))

    def speed_rates(tracing_on: bool) -> list:
        env = _host_child_env(ORYX_TRACING="1" if tracing_on else "0")
        proc = subprocess.run(
            [
                sys.executable,
                os.path.join(_HERE, "tools", "speed_layer_benchmark.py"),
                "--trials",
                str(_TRIALS),
                "--prefill",
                str(prefill),
            ],
            capture_output=True,
            text=True,
            timeout=600,
            env=env,
        )
        sys.stderr.write(proc.stderr[-800:])
        line = None
        for ln in proc.stdout.splitlines():
            if ln.startswith("{") and '"metric"' in ln:
                line = ln
        if proc.returncode != 0 or line is None:
            raise RuntimeError(
                f"tracing-overhead speed run (on={tracing_on}) failed "
                f"rc={proc.returncode}"
            )
        d = json.loads(line)
        return d.get("rates") or [d["value"]]

    ratio_row(
        "speed backlog fold-in", "events/sec",
        speed_rates(True), speed_rates(False), order=40,
    )

    # --- serving closed-loop: in-process toggle around one warm layer ------
    items = int(os.environ.get("ORYX_BENCH_TRACE_ITEMS", 200_000))
    users = 10_000
    seconds = float(os.environ.get("ORYX_BENCH_TRACE_SECONDS", 4.0))
    cfg = C.get_default().with_overlay(
        """
        oryx {
          id = "BenchTracingOverhead"
          input-topic.broker = "inproc://benchtrc"
          update-topic.broker = "inproc://benchtrc"
          serving {
            api.port = 0
            api.read-only = true
            model-manager-class = "tools.load_benchmark:LoadTestModelManager"
            application-resources = "oryx_tpu.app.als.endpoints"
          }
        }
        """
    )
    layer = ServingLayer(cfg)
    layer.start()
    layer.model_manager.model = build_model(users, items, 50)
    base = f"http://127.0.0.1:{layer.port}"
    try:
        urllib.request.urlopen(f"{base}/recommend/u0", timeout=300).read()

        def serving_qps(tracing_on: bool) -> list:
            tracing.configure(enabled=tracing_on)
            rates: list = []
            for _ in range(_TRIALS):
                lats: list = []
                stop = threading.Event()
                deadline = time.perf_counter() + seconds
                t1 = time.perf_counter()
                worker(base, "/recommend/u%d", users, deadline, lats, [], stop)
                if not lats:
                    raise RuntimeError("tracing-overhead serving: no requests")
                rates.append(len(lats) / (time.perf_counter() - t1))
            return rates

        on = serving_qps(True)
        off = serving_qps(False)
    finally:
        tracing.configure(enabled=True)
        layer.close()
    ratio_row("serving closed-loop", "queries/sec", on, off, order=41)

    if failures:
        raise RuntimeError("tracing overhead above envelope: " + "; ".join(failures))


def bench_lock_watchdog_overhead() -> None:
    """OrderedLock watchdog cost acceptance rows (docs/static-analysis.md):
    the runtime lock-order/timeout instrumentation the chaos, fleet and
    pipeline suites run under must cost <= 2% on both hot paths. Two
    comparisons, each >= 3-trial medians instrumented vs plain locks:

    - speed layer backlog events/s — subprocess runs of the real
      SpeedLayer bench toggled via ORYX_LOCK_WATCHDOG (patched before
      the broker/layer allocate their locks, like the test fixture);
    - closed-loop serving qps through the real HTTP path, one layer
      built under instrument() vs one built with raw locks.

    Trials are INTERLEAVED on/off in alternating order (on-off,
    off-on, ...): the instrumented hot paths take O(10) lock acquires
    per drain, so any minutes-apart block comparison measures host
    drift, not the watchdog — pairing adjacent trials cancels it.

    vs_baseline = instrumented/plain median ratio. A row whose median
    AND best trial both land below the 0.98 envelope hard-fails; a
    median-only miss is flagged `noise-suspect`. Strict mode stays on,
    so an observed lock-order cycle under load also fails the bench."""
    import threading
    import urllib.request

    from oryx_tpu.common import config as C
    from oryx_tpu.common import locks
    from oryx_tpu.serving.layer import ServingLayer
    from tools.load_benchmark import build_model
    from tools.traffic import worker

    envelope = float(os.environ.get("ORYX_BENCH_LOCK_ENVELOPE", 0.98))
    failures: list[str] = []

    def ratio_row(
        kind: str, unit: str, on_rates: list, off_rates: list, order: int
    ) -> None:
        med_on = statistics.median(on_rates)
        med_off = max(statistics.median(off_rates), 1e-9)
        ratio = med_on / med_off
        best = max(on_rates) / med_off
        detail = (
            f"watchdog on {med_on:.0f} vs plain {med_off:.0f} {unit} "
            f"(medians of {len(on_rates)}/{len(off_rates)} trials), "
            f"overhead {100 * (1 - ratio):.2f}%, envelope <= "
            f"{100 * (1 - envelope):.0f}%"
        )
        print(f"bench[lock-watchdog {kind}]: {detail}", file=sys.stderr)
        _emit(
            f"OrderedLock watchdog overhead, {kind}, instrumented vs plain "
            f"locks (vs_baseline = on/off ratio, floor {envelope})",
            med_on,
            unit,
            ratio,
            order=order,
            detail=detail,
            off_value=round(med_off, 2),
            overhead_pct=round(100 * (1 - ratio), 3),
            noise_suspect=ratio < envelope <= best,
            spread=[round(float(min(on_rates)), 2), round(float(max(on_rates)), 2)],
            trials=len(on_rates),
            # the speed arms are host-only child processes
            backend=_HOST_BACKEND if kind.startswith("speed") else None,
        )
        if ratio < envelope and best < envelope:
            failures.append(f"{kind}: on/off {ratio:.4f} < {envelope}")

    # --- speed backlog: one single-trial subprocess per mode, interleaved ---
    prefill = int(os.environ.get("ORYX_BENCH_LOCK_PREFILL", 300_000))

    def speed_rate(watchdog_on: bool) -> float:
        env = _host_child_env(ORYX_LOCK_WATCHDOG="1" if watchdog_on else "0")
        proc = subprocess.run(
            [
                sys.executable,
                os.path.join(_HERE, "tools", "speed_layer_benchmark.py"),
                "--trials",
                "1",
                "--prefill",
                str(prefill),
            ],
            capture_output=True,
            text=True,
            timeout=600,
            env=env,
        )
        sys.stderr.write(proc.stderr[-800:])
        line = None
        for ln in proc.stdout.splitlines():
            if ln.startswith("{") and '"metric"' in ln:
                line = ln
        if proc.returncode != 0 or line is None:
            raise RuntimeError(
                f"lock-watchdog speed run (on={watchdog_on}) failed "
                f"rc={proc.returncode}"
            )
        return float(json.loads(line)["value"])

    speed_on: list = []
    speed_off: list = []
    for pair in range(_TRIALS):
        for mode_on in (True, False) if pair % 2 == 0 else (False, True):
            (speed_on if mode_on else speed_off).append(speed_rate(mode_on))
    ratio_row("speed backlog fold-in", "events/sec", speed_on, speed_off, order=42)

    # --- serving closed-loop: two live layers (one per lock flavor), --------
    # --- trials interleaved between them ------------------------------------
    items = int(os.environ.get("ORYX_BENCH_LOCK_ITEMS", 200_000))
    users = 10_000
    seconds = float(os.environ.get("ORYX_BENCH_LOCK_SECONDS", 4.0))
    cfg = C.get_default().with_overlay(
        """
        oryx {
          id = "BenchLockWatchdog"
          input-topic.broker = "inproc://benchlock"
          update-topic.broker = "inproc://benchlock"
          serving {
            api.port = 0
            api.read-only = true
            model-manager-class = "tools.load_benchmark:LoadTestModelManager"
            application-resources = "oryx_tpu.app.als.endpoints"
          }
        }
        """
    )

    def make_layer() -> tuple:
        layer = ServingLayer(cfg)
        layer.start()
        layer.model_manager.model = build_model(users, items, 50)
        base = f"http://127.0.0.1:{layer.port}"
        urllib.request.urlopen(f"{base}/recommend/u0", timeout=300).read()
        return layer, base

    def serving_trial(base: str) -> float:
        lats: list = []
        stop = threading.Event()
        deadline = time.perf_counter() + seconds
        t1 = time.perf_counter()
        worker(base, "/recommend/u%d", users, deadline, lats, [], stop)
        if not lats:
            raise RuntimeError("lock-watchdog serving: no requests")
        return len(lats) / (time.perf_counter() - t1)

    plain_layer, plain_base = make_layer()
    try:
        locks.instrument(strict=True)
        try:
            # built under instrument(): every lock this layer (and its
            # batcher/server/model) constructs is a tracked OrderedLock
            inst_layer, inst_base = make_layer()
            try:
                srv_on: list = []
                srv_off: list = []
                for pair in range(_TRIALS):
                    for mode_on in (True, False) if pair % 2 == 0 else (False, True):
                        r = serving_trial(inst_base if mode_on else plain_base)
                        (srv_on if mode_on else srv_off).append(r)
                if locks.violations():
                    raise RuntimeError(
                        f"lock watchdog violations under load: {locks.violations()}"
                    )
            finally:
                inst_layer.close()
        finally:
            locks.deinstrument()
            locks.reset()
    finally:
        plain_layer.close()
    ratio_row("serving closed-loop", "queries/sec", srv_on, srv_off, order=43)

    if failures:
        raise RuntimeError("lock watchdog overhead above envelope: " + "; ".join(failures))


def bench_experiment_overhead() -> None:
    """Online-experiment cost acceptance rows (docs/experiments.md): the
    champion/challenger A/B machinery — sticky arm routing, the
    per-request observe hook, per-arm instance metrics, and the attached
    evaluator consumer thread — must cost <= 2% on the serving hot path
    when an experiment is ACTIVE. Same protocol as the lock-watchdog
    rows: two live layers in one process (one with a 10% challenger
    split and the evaluator attached, one with experiments bypassed
    entirely), >= 3 closed-loop trials per arm INTERLEAVED in
    alternating order so host drift cancels pairwise.

    vs_baseline = attached/bypassed median qps ratio; a row whose median
    AND best trial both land below the 0.98 envelope hard-fails, a
    median-only miss is flagged `noise-suspect`. A second row pins the
    realized challenger share against the configured 10% split — if
    routing were silently inactive the overhead row would measure
    nothing, so a share outside [0.05, 0.20] hard-fails too."""
    import shutil
    import tempfile
    import threading
    import urllib.request

    from oryx_tpu.common import config as C
    from oryx_tpu.serving.layer import ServingLayer
    from tools.load_benchmark import build_model
    from tools.traffic import worker

    envelope = float(os.environ.get("ORYX_BENCH_EXPERIMENT_ENVELOPE", 0.98))
    failures: list[str] = []

    items = int(os.environ.get("ORYX_BENCH_EXPERIMENT_ITEMS", 200_000))
    users = 10_000
    seconds = float(os.environ.get("ORYX_BENCH_EXPERIMENT_SECONDS", 4.0))
    model_dir = tempfile.mkdtemp(prefix="oryx-bench-exp-")

    def overlay(ab_fraction: float, with_registry: bool) -> object:
        registry = (
            f'batch.storage.model-dir = "{model_dir}"' if with_registry else ""
        )
        return C.get_default().with_overlay(
            f"""
            oryx {{
              id = "BenchExperimentOverhead"
              input-topic.broker = "inproc://benchexp"
              update-topic.broker = "inproc://benchexp"
              {registry}
              serving {{
                api.port = 0
                api.read-only = true
                model-manager-class = "tools.load_benchmark:LoadTestModelManager"
                application-resources = "oryx_tpu.app.als.endpoints"
                ab.fraction = {ab_fraction}
              }}
            }}
            """
        )

    def make_layer(cfg) -> tuple:
        layer = ServingLayer(cfg)
        layer.start()
        layer.model_manager.model = build_model(users, items, 50)
        base = f"http://127.0.0.1:{layer.port}"
        urllib.request.urlopen(f"{base}/recommend/u0", timeout=300).read()
        return layer, base

    def serving_trial(base: str) -> float:
        lats: list = []
        stop = threading.Event()
        deadline = time.perf_counter() + seconds
        t1 = time.perf_counter()
        worker(base, "/recommend/u%d", users, deadline, lats, [], stop)
        if not lats:
            raise RuntimeError("experiment-overhead serving: no requests")
        return len(lats) / (time.perf_counter() - t1)

    off_layer, off_base = make_layer(overlay(0.0, with_registry=False))
    try:
        on_layer, on_base = make_layer(overlay(0.10, with_registry=True))
        try:
            # make the experiment genuinely ACTIVE: champion pointer set,
            # a challenger generation live in the tracker, so every
            # request pays arm assignment + observe + per-arm metrics
            # (the load-test manager serves both arms identically)
            on_layer.registry_store.set_champion("1970010100000000")
            on_layer.generation_tracker._set_live("1970010100000000")
            on_layer.generation_tracker._set_challenger("1970010100000001")
            if on_layer.experiments is None or not on_layer.experiments.active:
                raise RuntimeError(
                    "experiment-overhead: experiments failed to activate"
                )
            srv_on: list = []
            srv_off: list = []
            for pair in range(_TRIALS):
                for mode_on in (True, False) if pair % 2 == 0 else (False, True):
                    r = serving_trial(on_base if mode_on else off_base)
                    (srv_on if mode_on else srv_off).append(r)
            with urllib.request.urlopen(f"{on_base}/experiments", timeout=30) as resp:
                report = json.loads(resp.read())
        finally:
            on_layer.close()
    finally:
        off_layer.close()
        shutil.rmtree(model_dir, ignore_errors=True)

    med_on = statistics.median(srv_on)
    med_off = max(statistics.median(srv_off), 1e-9)
    ratio = med_on / med_off
    best = max(srv_on) / med_off
    detail = (
        f"experiment active {med_on:.0f} vs bypassed {med_off:.0f} "
        f"queries/sec (medians of {len(srv_on)}/{len(srv_off)} trials), "
        f"overhead {100 * (1 - ratio):.2f}%, envelope <= "
        f"{100 * (1 - envelope):.0f}%"
    )
    print(f"bench[experiment-overhead serving]: {detail}", file=sys.stderr)
    _emit(
        "online experiment overhead, serving closed-loop, 10% challenger "
        f"split + evaluator attached vs bypassed (vs_baseline = on/off "
        f"ratio, floor {envelope})",
        med_on,
        "queries/sec",
        ratio,
        order=46,
        detail=detail,
        off_value=round(med_off, 2),
        overhead_pct=round(100 * (1 - ratio), 3),
        noise_suspect=ratio < envelope <= best,
        spread=[round(float(min(srv_on)), 2), round(float(max(srv_on)), 2)],
        trials=len(srv_on),
    )
    if ratio < envelope and best < envelope:
        failures.append(f"serving closed-loop: on/off {ratio:.4f} < {envelope}")

    arms = (report.get("report") or {}).get("arms") or {}
    champ_serves = int((arms.get("champion") or {}).get("serves") or 0)
    chal_serves = int((arms.get("challenger") or {}).get("serves") or 0)
    total = champ_serves + chal_serves
    share = chal_serves / total if total else 0.0
    detail = (
        f"challenger served {chal_serves}/{total} assigned requests "
        f"(share {share:.4f}) under ab.fraction = 0.10; sticky blake2b "
        f"bucketing over {users} uniform users"
    )
    print(f"bench[experiment-overhead split]: {detail}", file=sys.stderr)
    _emit(
        "online experiment realized challenger share, 10% configured split "
        "(vs_baseline = share/0.10)",
        round(share, 4),
        "fraction",
        round(share / 0.10, 4),
        order=47,
        detail=detail,
        trials=total,
    )
    if total == 0 or not 0.05 <= share <= 0.20:
        failures.append(
            f"challenger share {share:.4f} outside [0.05, 0.20] "
            f"({chal_serves}/{total} serves) — routing not active?"
        )

    if failures:
        raise RuntimeError(
            "experiment overhead above envelope: " + "; ".join(failures)
        )


def bench_ledger_overhead() -> None:
    """Resource-ledger cost acceptance rows (docs/static-analysis.md):
    the weakref live-resource accounting every layer registers into must
    cost <= 2% on the same two hot paths the lock-watchdog rows guard.
    Registration happens per acquisition (layer/consumer/session
    construction), never per event or per request, so the expected
    overhead is indistinguishable from noise — these rows pin that down.

    Both halves pair the arms INSIDE one process — the ledger's cost is
    so small that any protocol comparing separate processes (or separate
    layers) measures placement/drift artifacts instead; median AND best
    must both miss the envelope before a row hard-fails.

    - speed layer backlog events/s: ONE subprocess run of the real
      SpeedLayer bench with --toggle-env ORYX_RESOURCE_LEDGER flipping
      the ledger between drain trials (``enabled()`` re-reads the env
      per call), so on/off trials share JIT warm-up and host state;
    - closed-loop serving qps under a 2 Hz /metrics scraper: ONE live
      layer (its resources registered at construction), with the env
      toggle flipping the ledger's only steady-state work — the gauge
      refresh that probes every weakref on each scrape. A same-layer
      A/B sidesteps the two-layers-in-one-process placement bias that
      dwarfs the real cost (the /recommend path itself never touches
      the ledger).
    """
    import threading
    import urllib.request

    from oryx_tpu.common import config as C
    from oryx_tpu.serving.layer import ServingLayer
    from tools.load_benchmark import build_model
    from tools.traffic import worker

    envelope = float(os.environ.get("ORYX_BENCH_LEDGER_ENVELOPE", 0.98))
    failures: list[str] = []

    def ratio_row(
        kind: str, unit: str, on_rates: list, off_rates: list, order: int
    ) -> None:
        med_on = statistics.median(on_rates)
        med_off = max(statistics.median(off_rates), 1e-9)
        ratio = med_on / med_off
        best = max(on_rates) / med_off
        detail = (
            f"ledger on {med_on:.0f} vs off {med_off:.0f} {unit} "
            f"(medians of {len(on_rates)}/{len(off_rates)} trials), "
            f"overhead {100 * (1 - ratio):.2f}%, envelope <= "
            f"{100 * (1 - envelope):.0f}%"
        )
        print(f"bench[resource-ledger {kind}]: {detail}", file=sys.stderr)
        _emit(
            f"resource ledger overhead, {kind}, registered vs disabled "
            f"(vs_baseline = on/off ratio, floor {envelope})",
            med_on,
            unit,
            ratio,
            order=order,
            detail=detail,
            off_value=round(med_off, 2),
            overhead_pct=round(100 * (1 - ratio), 3),
            noise_suspect=ratio < envelope <= best,
            spread=[round(float(min(on_rates)), 2), round(float(max(on_rates)), 2)],
            trials=len(on_rates),
            # the speed arms are host-only child processes
            backend=_HOST_BACKEND if kind.startswith("speed") else None,
        )
        if ratio < envelope and best < envelope:
            failures.append(f"{kind}: on/off {ratio:.4f} < {envelope}")

    # --- speed backlog: ONE subprocess, env flipped per drain trial ---------
    # (--toggle-env pairs the arms inside one process; separate on/off
    # subprocesses on this 1-core host measure minutes-apart machine
    # drift — a control run with the ledger off in BOTH arms showed
    # 3-11% phantom "overhead" under that protocol)
    prefill = int(os.environ.get("ORYX_BENCH_LEDGER_PREFILL", 300_000))
    # round up to a multiple of 4: the tool's ABBA toggle order is only
    # first-order balanced against host drift at 4k trials (drain trials
    # cost ~1.5s each, so the extra arms are nearly free)
    speed_trials = ((max(8, 2 * _TRIALS) + 3) // 4) * 4

    # construction registers under "on"
    env = _host_child_env(ORYX_RESOURCE_LEDGER="1")
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(_HERE, "tools", "speed_layer_benchmark.py"),
            "--trials",
            str(speed_trials),
            "--prefill",
            str(prefill),
            "--toggle-env",
            "ORYX_RESOURCE_LEDGER",
        ],
        capture_output=True,
        text=True,
        timeout=900,
        env=env,
    )
    sys.stderr.write(proc.stderr[-800:])
    line = None
    for ln in proc.stdout.splitlines():
        if ln.startswith("{") and '"metric"' in ln:
            line = ln
    if proc.returncode != 0 or line is None:
        raise RuntimeError(
            f"resource-ledger speed run failed rc={proc.returncode}"
        )
    toggle = json.loads(line)["toggle"]
    ratio_row(
        "speed backlog fold-in", "events/sec",
        [float(r) for r in toggle["on"]],
        [float(r) for r in toggle["off"]],
        order=44,
    )

    # --- serving closed-loop: ONE live layer, env toggle flips the ----------
    # --- /metrics-scrape refresh work, trials interleaved -------------------
    items = int(os.environ.get("ORYX_BENCH_LEDGER_ITEMS", 200_000))
    users = 10_000
    seconds = float(os.environ.get("ORYX_BENCH_LEDGER_SECONDS", 4.0))
    cfg = C.get_default().with_overlay(
        """
        oryx {
          id = "BenchResourceLedger"
          input-topic.broker = "inproc://benchledger"
          update-topic.broker = "inproc://benchledger"
          serving {
            api.port = 0
            api.read-only = true
            model-manager-class = "tools.load_benchmark:LoadTestModelManager"
            application-resources = "oryx_tpu.app.als.endpoints"
          }
        }
        """
    )
    layer = ServingLayer(cfg)  # built with the ledger at its default (on)
    try:
        layer.start()
        layer.model_manager.model = build_model(users, items, 50)
        base = f"http://127.0.0.1:{layer.port}"
        urllib.request.urlopen(f"{base}/recommend/u0", timeout=300).read()

        def serving_trial(ledger_on: bool) -> float:
            prev = os.environ.get("ORYX_RESOURCE_LEDGER")
            os.environ["ORYX_RESOURCE_LEDGER"] = "1" if ledger_on else "0"
            stop = threading.Event()

            def scrape():  # 2 Hz operator scrape: where refresh() runs
                while not stop.is_set():
                    try:
                        urllib.request.urlopen(f"{base}/metrics", timeout=10).read()
                    except OSError:
                        pass
                    stop.wait(0.5)

            scraper = threading.Thread(target=scrape, daemon=True)
            scraper.start()
            try:
                lats: list = []
                deadline = time.perf_counter() + seconds
                t1 = time.perf_counter()
                worker(base, "/recommend/u%d", users, deadline, lats, [], stop)
                if not lats:
                    raise RuntimeError("resource-ledger serving: no requests")
                return len(lats) / (time.perf_counter() - t1)
            finally:
                stop.set()
                scraper.join(timeout=10)
                if prev is None:
                    os.environ.pop("ORYX_RESOURCE_LEDGER", None)
                else:
                    os.environ["ORYX_RESOURCE_LEDGER"] = prev

        srv_on: list = []
        srv_off: list = []
        # an EVEN pair count keeps the alternating (on,off)/(off,on)
        # order positionally balanced against host drift
        for pair in range(((max(4, _TRIALS) + 1) // 2) * 2):
            for mode_on in (True, False) if pair % 2 == 0 else (False, True):
                (srv_on if mode_on else srv_off).append(serving_trial(mode_on))
    finally:
        layer.close()
    ratio_row("serving closed-loop", "queries/sec", srv_on, srv_off, order=45)

    if failures:
        raise RuntimeError(
            "resource ledger overhead above envelope: " + "; ".join(failures)
        )


def bench_serving_closed_loop() -> None:
    """Closed-loop /recommend latency through the REAL serving stack:
    ServingLayer HTTP server + ALS endpoints + request micro-batcher +
    device scan, driven by 1..3 SYNCHRONOUS clients (each waits for its
    response before sending the next request). Unlike the pipelined rows
    above — which measure device throughput with a deep submit queue —
    these are true per-request p50/p99 latencies, the number a single
    caller experiences, directly comparable to the reference's published
    437 qps / ~7 ms table (LSH 0.3, 32-core Xeon). Since ISSUE 18 the
    driver reuses persistent keep-alive connections (tools/traffic.py
    worker -> loadgen KeepAliveClient), so these rows re-measure the
    437-qps reference under the same protocol the native-front rows use:
    latency is the server's, not TCP setup's."""
    import threading
    import urllib.request

    import numpy as np

    from oryx_tpu.common import config as C
    from oryx_tpu.serving.layer import ServingLayer
    from tools.load_benchmark import build_model
    from tools.traffic import worker

    items = int(os.environ.get("ORYX_BENCH_ITEMS", 1_000_000))
    features = int(os.environ.get("ORYX_BENCH_FEATURES", 50))
    users = int(os.environ.get("ORYX_BENCH_CL_USERS", 10_000))
    seconds = float(os.environ.get("ORYX_BENCH_CL_SECONDS", 6.0))
    backend, _, _ = _device_info()
    if backend != "tpu":
        # each request exact-scans the whole item matrix; on a CPU
        # container keep the model small enough that a trial finishes
        items = min(items, int(os.environ.get("ORYX_BENCH_CL_CPU_ITEMS", 200_000)))
        seconds = min(seconds, 4.0)

    cfg = C.get_default().with_overlay(
        """
        oryx {
          id = "BenchClosedLoop"
          input-topic.broker = "inproc://benchcl"
          update-topic.broker = "inproc://benchcl"
          serving {
            api.port = 0
            api.read-only = true
            model-manager-class = "tools.load_benchmark:LoadTestModelManager"
            application-resources = "oryx_tpu.app.als.endpoints"
          }
        }
        """
    )
    t0 = time.perf_counter()
    model = build_model(users, items, features)
    layer = ServingLayer(cfg)
    layer.start()
    layer.model_manager.model = model
    base = f"http://127.0.0.1:{layer.port}"
    label_m = f"{items // 1_000_000}M" if items >= 1_000_000 else f"{items // 1000}K"
    try:
        # warm request uploads Y to device and compiles the scan kernel
        urllib.request.urlopen(f"{base}/recommend/u0", timeout=300).read()
        print(
            f"bench[serving-closed]: model+layer+warm in "
            f"{time.perf_counter() - t0:.1f}s ({users}u x {items}i x {features}f)",
            file=sys.stderr,
        )
        for clients, order in ((1, 94), (3, 95)):
            qps_trials: list[float] = []
            lats: list[float] = []
            errors: list[float] = []
            for _ in range(_TRIALS):
                trial_lats: list[float] = []
                stop = threading.Event()
                deadline = time.perf_counter() + seconds
                threads = [
                    threading.Thread(
                        target=worker,
                        args=(base, "/recommend/u%d", users, deadline,
                              trial_lats, errors, stop),
                        daemon=True,
                    )
                    for _ in range(clients)
                ]
                t1 = time.perf_counter()
                for th in threads:
                    th.start()
                for th in threads:
                    th.join()
                elapsed = time.perf_counter() - t1
                qps_trials.append(len(trial_lats) / max(elapsed, 1e-9))
                lats.extend(trial_lats)
            if not lats:
                raise RuntimeError(
                    f"closed-loop serving: no successful requests "
                    f"({len(errors)} errors)"
                )
            p50, p99 = np.percentile(np.array(lats) * 1000, [50, 99])
            qps, vs, tf = _rate_row(qps_trials, 437.0)
            detail = (
                f"true per-request HTTP latency: p50 {p50:.1f} ms / "
                f"p99 {p99:.1f} ms over {len(lats)} requests "
                f"({len(errors)} errors), {tf['trials']} x {seconds:.0f}s "
                f"trials; reference table: 437 qps / ~7 ms at LSH 0.3"
            )
            print(
                f"bench[serving-closed {clients} client(s)]: {detail}",
                file=sys.stderr,
            )
            _emit(
                f"ALS /recommend closed-loop, {clients} sync client(s), "
                f"{features}f x {label_m} items, vs 437 qps / 7 ms p50 "
                f"published (LSH 0.3, 32-core Xeon)",
                qps,
                "queries/sec",
                vs,
                order=order,
                detail=detail,
                p50_ms=float(p50),
                p99_ms=float(p99),
                clients=clients,
                **tf,
            )
    finally:
        layer.close()


def bench_native_front() -> None:
    """Native C++ HTTP front vs the Python front: the serving-latency
    identity rows (ISSUE 18). Two identically configured ServingLayers —
    one with ``oryx.serving.native.enabled = true``, one forced to the
    Python ``http.server`` front — share one prebuilt ALS model, and
    1/2/3 SYNCHRONOUS keep-alive clients drive each arm closed-loop with
    no pipeline co-tenancy, so p50/p99 are true per-request latencies of
    the data plane alone. Arms alternate order every trial (>= 3 trials,
    median/spread/NOISY protocol) so drift hits both equally.

    Two kinds of rows. The FORWARDED rows (orders 91-93) are the latency
    identity: /recommend full-quality requests travel the same Python
    dispatch on both arms (the native front forwards them as RBLK
    frames), so their ratio is ~1.0 by construction and the row proves
    the native plumbing adds nothing. The PAIRED-RATIO row (order 89)
    carries the acceptance floor — native/Python qps >= 1.5x — and is
    measured on the stale answer-cache rung (admission pinned at stage
    STALE over a primed cache): the same /recommend 200s, but answered
    entirely in C++ on one arm and through the Python ladder + cache on
    the other. That is the rung the native data plane exists for.
    Skips cleanly (no rows) when the toolchain is absent — the fallback
    environments serve through the Python front and the plain
    serving-closed rows already cover them."""
    import threading

    import numpy as np

    from oryx_tpu import native as native_mod
    from oryx_tpu.common import config as C
    from oryx_tpu.serving.layer import ServingLayer
    from tools.load_benchmark import build_model
    from tools.traffic import worker

    lib = native_mod.get_library()
    if lib is None or not hasattr(lib, "hf_create"):
        print("bench[serving-native]: skipped (native toolchain unavailable)",
              file=sys.stderr)
        return

    items = int(os.environ.get("ORYX_BENCH_ITEMS", 1_000_000))
    features = int(os.environ.get("ORYX_BENCH_FEATURES", 50))
    users = int(os.environ.get("ORYX_BENCH_CL_USERS", 10_000))
    seconds = float(os.environ.get("ORYX_BENCH_CL_SECONDS", 6.0))
    backend, _, _ = _device_info()
    if backend != "tpu":
        items = min(items, int(os.environ.get("ORYX_BENCH_CL_CPU_ITEMS", 200_000)))
        seconds = min(seconds, 4.0)

    def make_layer(arm: str, enabled: str) -> ServingLayer:
        cfg = C.get_default().with_overlay(
            f"""
            oryx {{
              id = "BenchNativeFront"
              input-topic.broker = "inproc://benchnf-{arm}"
              update-topic.broker = "inproc://benchnf-{arm}"
              serving {{
                api.port = 0
                api.read-only = true
                model-manager-class = "tools.load_benchmark:LoadTestModelManager"
                application-resources = "oryx_tpu.app.als.endpoints"
                native.enabled = "{enabled}"
              }}
            }}
            """
        )
        return ServingLayer(cfg)

    t0 = time.perf_counter()
    model = build_model(users, items, features)
    arms = {"native": make_layer("native", "true"),
            "python": make_layer("python", "false")}
    label_m = f"{items // 1_000_000}M" if items >= 1_000_000 else f"{items // 1000}K"
    try:
        for name, layer in arms.items():
            layer.start()
            layer.model_manager.model = model
        if arms["native"]._native_front is None:
            print("bench[serving-native]: skipped (native front declined)",
                  file=sys.stderr)
            return
        from oryx_tpu.loadgen.engine import KeepAliveClient

        warm = KeepAliveClient(timeout_s=300)
        for layer in arms.values():
            status, _, _, _ = warm.request(
                f"http://127.0.0.1:{layer.port}/recommend/u0")
            assert status == 200, status
        warm.close()
        print(
            f"bench[serving-native]: model+2 layers+warm in "
            f"{time.perf_counter() - t0:.1f}s ({users}u x {items}i x "
            f"{features}f), arms: native :{arms['native'].port} / "
            f"python :{arms['python'].port}",
            file=sys.stderr,
        )

        def one_trial(layer, clients: int, n_users: int = users) -> tuple[float, list]:
            base = f"http://127.0.0.1:{layer.port}"
            lats: list = []
            errs: list = []
            stop = threading.Event()
            deadline = time.perf_counter() + seconds
            threads = [
                threading.Thread(
                    target=worker,
                    args=(base, "/recommend/u%d", n_users, deadline, lats,
                          errs, stop),
                    daemon=True,
                )
                for _ in range(clients)
            ]
            t1 = time.perf_counter()
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            elapsed = time.perf_counter() - t1
            if errs:
                raise RuntimeError(
                    f"serving-native trial errors ({clients} clients): "
                    f"{errs[:5]}"
                )
            return len(lats) / max(elapsed, 1e-9), lats

        floor = 1.5
        for clients, order in ((1, 91), (2, 92), (3, 93)):
            qps: dict = {"native": [], "python": []}
            lats: dict = {"native": [], "python": []}
            for trial in range(_TRIALS):
                # alternate which arm runs first so thermal / scheduler
                # drift lands on both arms equally
                order_names = (
                    ("native", "python") if trial % 2 == 0
                    else ("python", "native")
                )
                for name in order_names:
                    rate, trial_lats = one_trial(arms[name], clients)
                    qps[name].append(rate)
                    lats[name].extend(trial_lats)
            med_py = max(statistics.median(qps["python"]), 1e-9)
            ratios = [r / med_py for r in qps["native"]]
            p50n, p99n = np.percentile(np.array(lats["native"]) * 1000, [50, 99])
            p50p, p99p = np.percentile(np.array(lats["python"]) * 1000, [50, 99])
            value, vs, tf = _rate_row(qps["native"], 437.0)
            ratio = statistics.median(ratios)
            detail = (
                f"paired closed-loop arms, {clients} sync keep-alive "
                f"client(s): native {value:.0f} qps p50 {p50n:.1f} / "
                f"p99 {p99n:.1f} ms vs python {med_py:.0f} qps p50 "
                f"{p50p:.1f} / p99 {p99p:.1f} ms ({tf['trials']} x "
                f"{seconds:.0f}s trials per arm, interleaved); "
                f"native/python {ratio:.2f}x; reference 437 qps / ~7 ms"
            )
            print(f"bench[serving-native {clients} client(s)]: {detail}",
                  file=sys.stderr)
            _emit(
                f"native-front closed-loop, {clients} sync client(s), "
                f"{features}f x {label_m} items, vs 437 qps published",
                value,
                "queries/sec",
                vs,
                order=order,
                detail=detail,
                p50_ms=float(p50n),
                p99_ms=float(p99n),
                python_qps=round(med_py, 2),
                python_p50_ms=float(p50p),
                python_p99_ms=float(p99p),
                front_ratio=round(ratio, 3),
                clients=clients,
                **tf,
            )
        # --- the acceptance row: stale answer-cache rung, paired arms -------
        # Pin admission at STAGE_STALE over a primed cache so every
        # /recommend is a champion-gated cache hit: C++ template on the
        # native arm, Python ladder + AnswerCache on the other. Same 200
        # bytes (byte-parity suite), very different data planes.
        hot_users = 64
        prime = KeepAliveClient(timeout_s=300)
        for layer in arms.values():
            layer.health.live_generation = "bench-gen"
            adm = layer.admission
            # freeze the ladder: evaluate() keeps returning the pinned stage
            adm.evaluate = (lambda a: (lambda *x, **k: a._stage))(adm)
            for u in range(hot_users):
                status, _, _, _ = prime.request(
                    f"http://127.0.0.1:{layer.port}/recommend/u{u}")
                assert status == 200, status
        prime.close()
        for layer in arms.values():
            layer.admission._stage = 2  # STAGE_STALE
        arms["native"]._native_front.push_control()  # mirror cache + stage

        clients = 3
        qps = {"native": [], "python": []}
        lats = {"native": [], "python": []}
        for trial in range(_TRIALS):
            order_names = (
                ("native", "python") if trial % 2 == 0
                else ("python", "native")
            )
            for name in order_names:
                rate, trial_lats = one_trial(arms[name], clients,
                                             n_users=hot_users)
                qps[name].append(rate)
                lats[name].extend(trial_lats)
        med_py = max(statistics.median(qps["python"]), 1e-9)
        ratios = [r / med_py for r in qps["native"]]
        ratio_med = statistics.median(ratios)
        p50n, p99n = np.percentile(np.array(lats["native"]) * 1000, [50, 99])
        p50p, p99p = np.percentile(np.array(lats["python"]) * 1000, [50, 99])
        tf = _trial_fields(ratios, [r / floor for r in ratios])
        detail = (
            f"stale answer-cache rung (admission pinned at stage stale, "
            f"{hot_users} hot keys primed), {clients} sync keep-alive "
            f"clients: native {statistics.median(qps['native']):.0f} qps "
            f"p50 {p50n:.2f} / p99 {p99n:.2f} ms vs python {med_py:.0f} "
            f"qps p50 {p50p:.2f} / p99 {p99p:.2f} ms; ratio {ratio_med:.2f}x "
            f"(floor {floor}x; per-trial {[round(r, 2) for r in ratios]})"
        )
        print(f"bench[serving-native ratio]: {detail}", file=sys.stderr)
        _emit(
            "native-front vs python-front paired qps, stale-rung "
            f"/recommend, 3 clients (vs_baseline = ratio/{floor} floor)",
            ratio_med,
            "x python-front qps",
            ratio_med / floor,
            order=89,
            detail=detail,
            native_qps=round(statistics.median(qps["native"]), 2),
            python_qps=round(med_py, 2),
            p50_ms=float(p50n),
            p99_ms=float(p99n),
            python_p50_ms=float(p50p),
            python_p99_ms=float(p99p),
            **tf,
        )
    finally:
        for layer in arms.values():
            layer.close()


def bench_serving_open_loop() -> None:
    """OPEN-loop serving rows: arrivals fire on their own Poisson clock
    regardless of outstanding responses, so offered vs achieved rate and
    queue-inclusive p99 are measured the way production traffic would
    experience them (closed-loop rows above can never show queueing —
    the generator slows down with the server). Three rows: steady state
    at 1 and 3 replicas, then the rotation row — a scripted generation
    publish + chaos window + rollback mid-run at a held offered rate,
    with the failed-request count in the row (0 = zero-downtime held)."""
    import tempfile

    from oryx_tpu.loadgen import OpenLoopEngine, PoissonProcess, PowerLawUsers
    from tools.fleet import FleetHarness, default_scenario, run_scenario

    rate = float(os.environ.get("ORYX_BENCH_OL_RATE", 150.0))
    seconds = float(os.environ.get("ORYX_BENCH_OL_SECONDS", 6.0))
    n_users = int(os.environ.get("ORYX_BENCH_OL_USERS", 2_000_000))

    for replicas, order in ((1, 96), (3, 97)):
        with tempfile.TemporaryDirectory() as tmp:
            with FleetHarness(replicas, tmp, bus_name=f"benchol{replicas}") as fleet:
                first = fleet.publish(metric=0.90)
                if not fleet.wait_converged(first, timeout=30.0):
                    raise RuntimeError("open-loop bench: fleet never converged")
                engine = OpenLoopEngine(fleet.targets, template="/probe/recommend/u%d")
                result = engine.run(
                    PoissonProcess(rate=rate, seed=7),
                    PowerLawUsers(n_users, exponent=1.1, hot_count=16,
                                  hot_weight=0.2, seed=7),
                    seconds,
                )
        s = result.summary()
        detail = (
            f"open-loop Poisson {s['offered_rate']:.0f} rps offered over "
            f"{seconds:.0f}s, {replicas} replica(s): achieved "
            f"{s['achieved_rate']:.0f} rps, p50 {s['p50_ms']:.1f} ms / "
            f"queue-inclusive p99 {s['p99_ms']:.1f} ms (service p99 "
            f"{s['service_p99_ms']:.1f} ms), {s['failed']} failed, "
            f"{s['queued_arrivals']} queued arrivals"
        )
        print(f"bench[serving-open {replicas}r]: {detail}", file=sys.stderr)
        _emit(
            f"open-loop serving, {replicas} replica(s), Poisson "
            f"{rate:.0f} rps offered, power-law users (achieved rate; "
            f"vs_baseline = achieved/offered, 1.0 = kept up)",
            s["achieved_rate"],
            "requests/sec",
            s["achieved_rate"] / max(s["offered_rate"], 1e-9),
            order=order,
            detail=detail,
            p50_ms=s["p50_ms"],
            p99_ms=s["p99_ms"],
            service_p99_ms=s["service_p99_ms"],
            offered_rate=s["offered_rate"],
            failed=s["failed"],
            queued_arrivals=s["queued_arrivals"],
            replicas=replicas,
        )

    # rotation under load: publish + chaos + rollback mid-run, 3 replicas
    with tempfile.TemporaryDirectory() as tmp:
        with FleetHarness(3, tmp, bus_name="bencholrot") as fleet:
            first = fleet.publish(metric=0.90)
            if not fleet.wait_converged(first, timeout=30.0):
                raise RuntimeError("open-loop bench: fleet never converged")
            scenario = default_scenario(rate=rate, seconds=max(seconds, 8.0))
            result, verdict, _runner = run_scenario(fleet, scenario)
            converged = fleet.wait_converged(fleet.generations[-1], timeout=15.0)
    s = result.summary()
    detail = (
        f"generation rotation under load (publish + chaos window + "
        f"rollback mid-run, 3 replicas, {s['offered_rate']:.0f} rps "
        f"offered): achieved {s['achieved_rate']:.0f} rps, p99 "
        f"{s['p99_ms']:.1f} ms, {s['failed']} failed request(s), SLO "
        f"{'PASS' if verdict.passed else 'FAIL ' + '; '.join(verdict.violations)}, "
        f"fleet {'re-converged' if converged else 'DID NOT re-converge'}"
    )
    print(f"bench[serving-open rotation]: {detail}", file=sys.stderr)
    _emit(
        "open-loop rotation-under-load, 3 replicas: publish + chaos + "
        "rollback mid-run at held offered rate (achieved rate; "
        "vs_baseline = achieved/offered with zero failures required)",
        s["achieved_rate"],
        "requests/sec",
        (s["achieved_rate"] / max(s["offered_rate"], 1e-9))
        if s["failed"] == 0 and verdict.passed
        else 0.0,
        order=98,
        detail=detail,
        p99_ms=s["p99_ms"],
        offered_rate=s["offered_rate"],
        failed=s["failed"],
        slo_passed=verdict.passed,
        converged=converged,
        replicas=3,
    )


def bench_overload() -> None:
    """Overload-control acceptance rows (docs/overload.md). Two halves:

    - idle admission overhead: closed-loop serving qps through one warm
      layer with the admission controller wired vs bypassed — the
      per-request decide() cost at calm pressure must stay <= 2%
      (median AND best below the 0.98 envelope hard-fails; median-only
      misses are flagged `noise-suspect` per the repo's noise protocol);
    - 10x Poisson spike over a 3-replica fleet with 60 ms scripted probe
      work (saturation is then a function of offered rate alone —
      Little's law — deterministic on a single-core host): offered vs
      answered rate, queue-inclusive p99, per-stage shed fractions, zero
      failed requests and zero 5xx required, plus the seconds until every
      replica answers at full quality again after the spike ends."""
    import tempfile
    import threading
    import urllib.error
    import urllib.request

    from oryx_tpu.common import config as C
    from oryx_tpu.loadgen import OpenLoopEngine, PoissonProcess, PowerLawUsers
    from oryx_tpu.serving.layer import ServingLayer
    from oryx_tpu.serving.overload import SHED_HEADER
    from tools.fleet import FleetHarness
    from tools.load_benchmark import build_model
    from tools.traffic import worker

    envelope = float(os.environ.get("ORYX_BENCH_OVERLOAD_ENVELOPE", 0.98))
    failures: list[str] = []

    # --- idle overhead: admission wired vs bypassed, one warm layer -------
    items = int(os.environ.get("ORYX_BENCH_OVERLOAD_ITEMS", 200_000))
    users = 10_000
    seconds = float(os.environ.get("ORYX_BENCH_OVERLOAD_SECONDS", 4.0))
    cfg = C.get_default().with_overlay(
        """
        oryx {
          id = "BenchOverload"
          input-topic.broker = "inproc://benchovl"
          update-topic.broker = "inproc://benchovl"
          serving {
            api.port = 0
            api.read-only = true
            model-manager-class = "tools.load_benchmark:LoadTestModelManager"
            application-resources = "oryx_tpu.app.als.endpoints"
          }
        }
        """
    )
    layer = ServingLayer(cfg)
    layer.start()
    layer.model_manager.model = build_model(users, items, 50)
    base = f"http://127.0.0.1:{layer.port}"
    admission = layer.admission
    if admission is None:
        raise RuntimeError("bench overload: admission controller not enabled")
    try:
        urllib.request.urlopen(f"{base}/recommend/u0", timeout=300).read()

        def one_trial(wired: bool) -> float:
            # _admit_and_route reads layer.admission per request, so this
            # is the exact operator toggle (oryx.serving.overload.enabled)
            layer.admission = admission if wired else None
            lats: list = []
            stop = threading.Event()
            deadline = time.perf_counter() + seconds
            t1 = time.perf_counter()
            worker(base, "/recommend/u%d", users, deadline, lats, [], stop)
            if not lats:
                raise RuntimeError("bench overload: no requests completed")
            return len(lats) / (time.perf_counter() - t1)

        # interleave wired/bypassed pairs, alternating order, so the slow
        # single-core throughput drift over a long run cancels instead of
        # landing entirely on one arm
        on: list = []
        off: list = []
        for i in range(_TRIALS):
            if i % 2 == 0:
                on.append(one_trial(True))
                off.append(one_trial(False))
            else:
                off.append(one_trial(False))
                on.append(one_trial(True))
    finally:
        layer.admission = admission
        layer.close()

    med_on = statistics.median(on)
    med_off = max(statistics.median(off), 1e-9)
    ratio = med_on / med_off
    best = max(on) / med_off
    detail = (
        f"admission wired {med_on:.0f} vs bypassed {med_off:.0f} queries/sec "
        f"(medians of {len(on)}/{len(off)} trials), overhead "
        f"{100 * (1 - ratio):.2f}%, envelope <= {100 * (1 - envelope):.0f}%"
    )
    print(f"bench[overload idle]: {detail}", file=sys.stderr)
    _emit(
        "overload admission idle overhead, closed-loop serving, controller "
        f"wired vs bypassed (vs_baseline = wired/bypassed ratio, floor "
        f"{envelope})",
        med_on,
        "queries/sec",
        ratio,
        order=43,
        detail=detail,
        off_value=round(med_off, 2),
        overhead_pct=round(100 * (1 - ratio), 3),
        noise_suspect=ratio < envelope <= best,
        spread=[round(float(min(on)), 2), round(float(max(on)), 2)],
        trials=len(on),
    )
    if ratio < envelope and best < envelope:
        failures.append(f"idle overhead: wired/bypassed {ratio:.4f} < {envelope}")

    # --- 10x spike over 3 replicas, scripted 60 ms probe work -------------
    base_rate = float(os.environ.get("ORYX_BENCH_OVERLOAD_BASE_RATE", 25.0))
    spike_rate = 10.0 * base_rate
    recovery_cap_s = 20.0
    recovery_budget_s = 10.0
    # same tuning as test_spike_absorbed_by_staged_shedding_zero_5xx: the
    # tightened ladder knobs let the controller walk rungs within the
    # few-second phases of one trial
    overlay = """
        oryx {
          serving.overload {
            inflight-target = 4
            hold-s = 0.2
            control-interval-ms = 25
            alpha = 0.5
          }
          test.probe-work-ms = 60
        }
        """

    def fivexx_total(fleet) -> float:
        total = 0.0
        for replica in fleet.replicas:
            snap = replica.instance_metrics.snapshot()
            entry = snap.get("serving.responses.5xx") or {}
            total += float(entry.get("value") or 0.0)
        return total

    trials: list[dict] = []
    for t in range(_TRIALS):
        with tempfile.TemporaryDirectory() as tmp:
            with FleetHarness(
                3, tmp, bus_name=f"benchovl{t}", overlay=overlay
            ) as fleet:
                gen = fleet.publish(metric=0.90)
                if not fleet.wait_converged(gen, timeout=30.0):
                    raise RuntimeError("bench overload: fleet never converged")

                def run_phase(rate, secs, seed):
                    engine = OpenLoopEngine(
                        fleet.targets,
                        template="/probe/recommend/u%d",
                        readiness_poll_s=0.1,
                    )
                    return engine.run(
                        PoissonProcess(rate=rate, seed=seed),
                        PowerLawUsers(100_000, seed=seed),
                        secs,
                    )

                baseline = run_phase(base_rate, 2.0, seed=31 + t)
                spike = run_phase(spike_rate, 2.5, seed=47 + t)

                # recovery: seconds from spike end until every replica
                # answers 3 straight probes at full quality (no shed
                # header, no 429) — the probes themselves drive the
                # controllers' release evaluations
                t0 = time.perf_counter()
                recovery_s = recovery_cap_s
                streak = 0
                while time.perf_counter() - t0 < recovery_cap_s:
                    full = True
                    for target in fleet.targets:
                        try:
                            with urllib.request.urlopen(
                                target.base_url + "/probe/recommend/u1",
                                timeout=10,
                            ) as resp:
                                resp.read()
                                if resp.headers.get(SHED_HEADER):
                                    full = False
                        except urllib.error.HTTPError:
                            full = False
                    streak = streak + 1 if full else 0
                    if streak >= 3:
                        recovery_s = time.perf_counter() - t0
                        break
                    time.sleep(0.05)

                q = spike.quality()
                trials.append(
                    {
                        "answered_qps": (spike.ok + spike.shed)
                        / max(spike.duration_s, 1e-9),
                        "offered_qps": spike.offered_rate,
                        "p99_ms": spike.latency_quantile(0.99) * 1000.0,
                        "failed": baseline.failed + spike.failed,
                        "fivexx": fivexx_total(fleet),
                        "q_full": q["full"],
                        "q_reduced": q["reduced-probe"],
                        "q_stale": q["stale"],
                        "q_shed": q["shed"],
                        "recovery_s": recovery_s,
                    }
                )

    med = _median_run(trials, "answered_qps")
    answered = [r["answered_qps"] for r in trials]
    clean = med["failed"] == 0 and med["fivexx"] == 0
    detail = (
        f"10x Poisson spike over 3 replicas ({med['offered_qps']:.0f} rps "
        f"offered, 60 ms scripted probe work): answered "
        f"{med['answered_qps']:.0f} rps (ok + deliberate 429 sheds), "
        f"queue-inclusive p99 {med['p99_ms']:.0f} ms, quality "
        f"full/reduced/stale/shed = {med['q_full']:.2f}/{med['q_reduced']:.2f}"
        f"/{med['q_stale']:.2f}/{med['q_shed']:.2f}, "
        f"{int(med['failed'])} failed, {int(med['fivexx'])} 5xx"
    )
    print(f"bench[overload spike]: {detail}", file=sys.stderr)
    _emit(
        "overload 10x spike, 3 replicas: answered rate under staged "
        "shedding (vs_baseline = answered/offered with zero failures and "
        "zero 5xx required)",
        med["answered_qps"],
        "responses/sec",
        (med["answered_qps"] / max(med["offered_qps"], 1e-9)) if clean else 0.0,
        order=44,
        detail=detail,
        offered_rate=med["offered_qps"],
        p99_ms=med["p99_ms"],
        quality_full=med["q_full"],
        quality_reduced_probe=med["q_reduced"],
        quality_stale=med["q_stale"],
        quality_shed=med["q_shed"],
        failed=int(med["failed"]),
        responses_5xx=int(med["fivexx"]),
        replicas=3,
        spread=[round(min(answered), 2), round(max(answered), 2)],
        trials=len(trials),
    )
    for r in trials:
        if r["failed"] or r["fivexx"]:
            failures.append(
                f"spike trial: {int(r['failed'])} failed, "
                f"{int(r['fivexx'])} 5xx (both must be 0)"
            )
    if med["q_full"] >= 1.0:
        failures.append("spike: shed ladder never engaged (quality full = 1.0)")

    recs = [r["recovery_s"] for r in trials]
    med_rec = statistics.median(recs)
    detail = (
        f"seconds from spike end until all 3 replicas answer 3 straight "
        f"probes at full quality: median {med_rec:.2f}s over {len(recs)} "
        f"trials (budget {recovery_budget_s:.0f}s, poll cap {recovery_cap_s:.0f}s)"
    )
    print(f"bench[overload recovery]: {detail}", file=sys.stderr)
    _emit(
        "overload recovery after 10x spike: seconds until every replica "
        f"answers at full quality again (vs_baseline = {recovery_budget_s:.0f}s "
        "budget / measured, >= 1.0 = inside budget)",
        med_rec,
        "seconds",
        recovery_budget_s / max(med_rec, 1e-9),
        order=45,
        detail=detail,
        spread=[round(min(recs), 2), round(max(recs), 2)],
        trials=len(recs),
    )
    if med_rec > recovery_budget_s:
        failures.append(f"recovery {med_rec:.2f}s > {recovery_budget_s:.0f}s budget")

    if failures:
        raise RuntimeError("overload bench failed: " + "; ".join(failures))


def bench_crash_recovery() -> None:
    """Crash-recovery row: 3 subprocess replicas under open-loop load, one
    SIGKILLed mid-run (no drain). Value = SIGKILL->/readyz recovery time
    of the killed slot (respawn + restage-cache repair + update-topic
    replay); vs_baseline = budget/recovery (>1.0 = inside budget), gated
    to 0.0 unless the surviving fleet held the SLO with zero failed
    requests — the zero-downtime claim is part of the metric."""
    import tempfile

    from tools.fleet import run_crash_campaign

    rate = float(os.environ.get("ORYX_BENCH_CRASH_RATE", 150.0))
    seconds = float(os.environ.get("ORYX_BENCH_CRASH_SECONDS", 8.0))
    budget_s = float(os.environ.get("ORYX_BENCH_CRASH_BUDGET_S", 30.0))

    with tempfile.TemporaryDirectory() as tmp:
        report = run_crash_campaign(
            3, rate, seconds, tmp, recovery_budget_s=budget_s
        )
    recovery_s = max(report["recovery_seconds"], default=float("nan"))
    clean = report["failed"] == 0 and report["slo"]["passed"]
    detail = (
        f"one SIGKILL at 35% of a {seconds:.0f}s open-loop run, "
        f"{report['offered_rate']:.0f} rps offered over 3 replicas: "
        f"recovery {recovery_s:.2f}s (budget {budget_s:.0f}s), "
        f"{report['failed']} failed request(s), {report['retried']} "
        f"failed over to survivors, p99 {report['p99_ms']:.1f} ms, SLO "
        f"{'PASS' if report['slo']['passed'] else 'FAIL ' + '; '.join(report['slo']['violations'])}"
    )
    print(f"bench[crash-recovery]: {detail}", file=sys.stderr)
    _emit(
        "crash-recovery, 3 replicas open-loop, one SIGKILL mid-run: "
        "killed-slot SIGKILL->/readyz seconds, vs 30s budget "
        "(vs_baseline = budget/recovery, 0.0 unless zero failed + SLO held)",
        recovery_s,
        "sec",
        (budget_s / recovery_s) if clean and recovery_s > 0 else 0.0,
        order=99,
        detail=detail,
        p99_ms=report["p99_ms"],
        offered_rate=report["offered_rate"],
        failed=report["failed"],
        retried=report["retried"],
        slo_passed=report["slo"]["passed"],
        recovery_budget_s=budget_s,
        replicas=3,
        backend=f"host/{report['replica_platform']}",
    )


def bench_tenancy_overhead() -> None:
    """Multi-tenancy cost acceptance rows (docs/multi-tenancy.md).

    Row 1 — single-tenant overhead: the tenancy plumbing (tenant
    resolution from the /t/ prefix, the request-scoped ContextVar, the
    TenantServingMux attribute forwarding, per-tenant metric twins) must
    cost <= 2% on the serving hot path when only ONE tenant exists —
    the price of *being able* to multi-tenant, paid by deployments that
    don't. Protocol: two live layers in one process (one with a
    single-tenant `oryx.tenancy` block, one with tenancy absent),
    >= 3 closed-loop trial PAIRS in alternating order; the statistic is
    the median of per-pair on/off ratios — host drift on this class of
    machine is +-10% between trials but near-zero within an adjacent
    pair, so pairing cancels it (server-side handler timing puts the
    true plumbing cost at ~8us on a ~2ms request). A median-AND-best
    pair-ratio miss below 0.98 hard-fails, a median-only miss flags
    `noise-suspect`.

    Row 2 — noisy-neighbour fairness: deterministic arrivals through the
    batcher's DRR queue. An attacker tenant parks a deep backlog, a
    victim tenant's entries arrive steadily, one consumer drains at a
    fixed per-entry service time. With DRR on (tenanted entries, equal
    weights) the victim's queue-wait p99 is bounded by one quantum
    rotation; with DRR off (untenanted entries, FIFO-equivalent path
    through the SAME queue class) every victim entry waits behind the
    whole backlog. vs_baseline = fifo_p99/drr_p99 (improvement factor);
    < 5x hard-fails — the fairness mechanism, not the scheduler, must
    be doing the work."""
    import shutil
    import tempfile
    import threading
    import urllib.request

    from oryx_tpu.common import config as C
    from oryx_tpu.serving.layer import ServingLayer
    from tools.load_benchmark import build_model
    from tools.traffic import worker

    envelope = float(os.environ.get("ORYX_BENCH_TENANCY_ENVELOPE", 0.98))
    failures: list[str] = []

    items = int(os.environ.get("ORYX_BENCH_TENANCY_ITEMS", 200_000))
    users = 10_000
    seconds = float(os.environ.get("ORYX_BENCH_TENANCY_SECONDS", 4.0))
    model_dir = tempfile.mkdtemp(prefix="oryx-bench-tenancy-")

    def overlay(tenanted: bool) -> object:
        tenancy = (
            """
              tenancy {
                enabled = true
                default-tenant = t0
                tenants.t0 = {
                  app = als
                  serving-manager = "tools.load_benchmark:LoadTestModelManager"
                }
              }
            """
            if tenanted
            else """
              serving.model-manager-class = "tools.load_benchmark:LoadTestModelManager"
              serving.application-resources = "oryx_tpu.app.als.endpoints"
            """
        )
        return C.get_default().with_overlay(
            f"""
            oryx {{
              id = "BenchTenancyOverhead"
              update-topic.broker = "inproc://benchtenancy"
              batch.storage.model-dir = "{model_dir}"
              serving {{
                api.port = 0
                api.read-only = true
              }}
              {tenancy}
            }}
            """
        )

    def make_layer(tenanted: bool) -> tuple:
        layer = ServingLayer(overlay(tenanted))
        layer.start()
        if tenanted:
            manager = layer.tenant_mux.runtime("t0").manager
        else:
            manager = layer.model_manager
        manager.model = build_model(users, items, 50)
        base = f"http://127.0.0.1:{layer.port}"
        template = "/t/t0/recommend/u%d" if tenanted else "/recommend/u%d"
        urllib.request.urlopen(base + template % 0, timeout=300).read()
        return layer, base, template

    def serving_trial(base: str, template: str) -> float:
        lats: list = []
        stop = threading.Event()
        deadline = time.perf_counter() + seconds
        t1 = time.perf_counter()
        worker(base, template, users, deadline, lats, [], stop)
        if not lats:
            raise RuntimeError("tenancy-overhead serving: no requests")
        return len(lats) / (time.perf_counter() - t1)

    off_layer, off_base, off_tmpl = make_layer(tenanted=False)
    try:
        on_layer, on_base, on_tmpl = make_layer(tenanted=True)
        try:
            if on_layer.tenant_mux is None or on_layer.tenant_mux.ids() != ["t0"]:
                raise RuntimeError("tenancy-overhead: tenancy failed to activate")
            srv_on: list = []
            srv_off: list = []
            pair_ratios: list = []
            for pair in range(_TRIALS):
                rates = {}
                for mode_on in (True, False) if pair % 2 == 0 else (False, True):
                    rates[mode_on] = serving_trial(
                        on_base if mode_on else off_base,
                        on_tmpl if mode_on else off_tmpl,
                    )
                srv_on.append(rates[True])
                srv_off.append(rates[False])
                pair_ratios.append(rates[True] / max(rates[False], 1e-9))
        finally:
            on_layer.close()
    finally:
        off_layer.close()
        shutil.rmtree(model_dir, ignore_errors=True)

    med_on = statistics.median(srv_on)
    med_off = max(statistics.median(srv_off), 1e-9)
    ratio = statistics.median(pair_ratios)
    best = max(pair_ratios)
    detail = (
        f"single tenant wired {med_on:.0f} vs tenancy absent {med_off:.0f} "
        f"queries/sec, per-pair on/off ratios "
        f"{[round(r, 4) for r in pair_ratios]} (median {ratio:.4f}), "
        f"overhead {100 * (1 - ratio):.2f}%, envelope <= "
        f"{100 * (1 - envelope):.0f}%"
    )
    print(f"bench[tenancy-overhead serving]: {detail}", file=sys.stderr)
    _emit(
        "multi-tenancy overhead, serving closed-loop, single tenant wired "
        f"(/t/ prefix + mux + per-tenant metrics) vs tenancy absent "
        f"(vs_baseline = median per-pair on/off ratio, floor {envelope})",
        med_on,
        "queries/sec",
        ratio,
        order=48,
        detail=detail,
        off_value=round(med_off, 2),
        overhead_pct=round(100 * (1 - ratio), 3),
        noise_suspect=ratio < envelope <= best,
        spread=[round(float(min(srv_on)), 2), round(float(max(srv_on)), 2)],
        trials=len(srv_on),
    )
    if ratio < envelope and best < envelope:
        failures.append(f"serving closed-loop: on/off {ratio:.4f} < {envelope}")

    # -- row 2: noisy-neighbour victim queue-wait p99, DRR on vs off ------
    from oryx_tpu.serving.batcher import _Entry, _FairQueue

    backlog = int(os.environ.get("ORYX_BENCH_TENANCY_BACKLOG", 2000))
    victims = 200
    service_s = 50e-6  # fixed per-entry service time (busy-wait, not sleep)
    arrival_s = 0.002  # one victim entry every 2 ms

    def victim_wait_p99(drr: bool) -> float:
        q = _FairQueue({"attacker": 1.0, "victim": 1.0} if drr else None)
        waits: dict[str, list[float]] = {"attacker": [], "victim": []}
        drained = threading.Event()

        def enq(tenant: str) -> None:
            e = _Entry(None, None, 1, False)
            e.tenant = tenant if drr else None
            e.t_q = time.perf_counter()
            # label rides the entry even when untenanted so the drain
            # loop attributes the wait to the right victim/attacker list
            e.trace_ctx = tenant
            q.put(e)

        def drain() -> None:
            served = 0
            while served < backlog + victims:
                e = q.get()
                waits[e.trace_ctx].append(time.perf_counter() - e.t_q)
                served += 1
                t_end = time.perf_counter() + service_s
                while time.perf_counter() < t_end:
                    pass
            drained.set()

        for _ in range(backlog):
            enq("attacker")
        consumer = threading.Thread(target=drain, daemon=True)
        consumer.start()
        for i in range(victims):
            enq("victim")
            time.sleep(arrival_s)
        if not drained.wait(timeout=60.0):
            raise RuntimeError("tenancy-overhead DRR drain did not finish")
        consumer.join()
        v = sorted(waits["victim"])
        return v[min(len(v) - 1, int(0.99 * len(v)))] * 1000.0

    drr_p99_ms = victim_wait_p99(drr=True)
    fifo_p99_ms = victim_wait_p99(drr=False)
    improvement = fifo_p99_ms / max(drr_p99_ms, 1e-9)
    detail = (
        f"victim queue-wait p99 {drr_p99_ms:.2f} ms with DRR vs "
        f"{fifo_p99_ms:.2f} ms FIFO ({backlog}-entry attacker backlog, "
        f"{victims} victim arrivals @ {1 / arrival_s:.0f}/s, "
        f"{service_s * 1e6:.0f}us service): {improvement:.0f}x better"
    )
    print(f"bench[tenancy-overhead drr]: {detail}", file=sys.stderr)
    _emit(
        "noisy-neighbour victim queue-wait p99, DRR fair queue vs FIFO "
        f"under a {backlog}-entry attacker backlog "
        "(vs_baseline = fifo_p99/drr_p99 improvement, floor 5x)",
        drr_p99_ms,
        "ms",
        improvement,
        order=49,
        detail=detail,
        fifo_p99_ms=round(fifo_p99_ms, 2),
        attacker_backlog=backlog,
        victim_arrivals=victims,
    )
    if improvement < 5.0:
        failures.append(
            f"DRR victim p99 {drr_p99_ms:.2f} ms only {improvement:.1f}x "
            f"better than FIFO {fifo_p99_ms:.2f} ms"
        )

    if failures:
        raise RuntimeError(
            "tenancy acceptance failed: " + "; ".join(failures)
        )


def bench_serving_maintain() -> None:
    """Always-fresh ANN maintenance acceptance rows at the >=10M-item
    shape: steady-state qps + per-dispatch p99 of the probed IVF scan
    while a continuous fold-in stream AND the background IndexMaintainer
    (snapshot -> compact_ivf -> install) run against the same index,
    next to a no-maintenance baseline measured first on the same
    catalog. Acceptance: p99 under maintenance within 1.5x the baseline
    p99 (median AND best of >= 3 trials must miss before the row
    hard-fails; a median-only miss is `noise-suspect` per the repo's
    noise protocol), ZERO full re-clusters on any path (build_ivf is
    wrapped and counted for the whole measured window), plus a
    freshness-seconds row (fold-in -> clustered-visibility lag the
    maintainer observed) and a recall@10 row against the exact f32
    ranking over the union catalog after the final drain."""
    import threading

    import numpy as np

    from oryx_tpu.common import metrics
    from oryx_tpu.ops import ivf as ivf_ops
    from oryx_tpu.serving import maintain as maintain_mod

    items = int(os.environ.get("ORYX_BENCH_MAINTAIN_ITEMS", 10_000_000))
    features = int(os.environ.get("ORYX_BENCH_MAINTAIN_FEATURES", 50))
    batch = int(os.environ.get("ORYX_BENCH_ANN_BATCH", 256))
    seconds = float(os.environ.get("ORYX_BENCH_MAINTAIN_SECONDS", 6.0))
    interval = float(os.environ.get("ORYX_BENCH_MAINTAIN_INTERVAL", 1.0))
    fold_rate = float(os.environ.get("ORYX_BENCH_MAINTAIN_RATE", 1000.0))
    fresh_budget = float(os.environ.get("ORYX_BENCH_MAINTAIN_FRESH_BUDGET", 10.0))
    how_many = 10
    cells = max(64, int(round(items**0.5 / 8)) * 8)
    nprobe = max(8, int(round(0.0025 * cells)))
    label_m = f"{items // 1_000_000}M" if items >= 1_000_000 else f"{items // 1000}K"

    mat, queries = _ann_mixture(items, features, cells, 7117, batch)
    old_qb = ivf_ops.QUERY_BLOCK
    ivf_ops.configure_ann(query_block=4)
    t0 = time.perf_counter()
    index = ivf_ops.build_ivf(mat, n_cells=cells, seed=7, overlay_capacity=2048)
    build_sec = time.perf_counter() - t0
    print(
        f"bench[serving-maintain {features}f x {label_m}]: build_ivf "
        f"{build_sec:.0f}s ({index.n_cells} cells, nprobe {nprobe})",
        file=sys.stderr,
    )

    lock = threading.Lock()
    holder = {"index": index}

    class _OpsModel:
        """ops-level maintenance protocol (the serving-model half of
        serving/maintain.py's contract) over a plain index holder."""

        def set_index_pressure_callback(self, cb):
            self._cb = cb

        def maintenance_snapshot(self, watermark, force=False):
            with lock:
                idx = holder["index"]
                if not force and not ivf_ops.needs_maintenance(idx, watermark=watermark):
                    return None
                return idx, ivf_ops.snapshot_pending(idx)

        def install_compacted(self, new_index, stats):
            with lock:
                cur = holder["index"]
                snap_born = stats.get("born") or {}
                feat = new_index.features
                rids, raws = [], []
                for item, slot in (cur.ov_map or {}).items():
                    b = (cur.ov_born or {}).get(item, 0.0)
                    if item not in snap_born or b > snap_born[item]:
                        rids.append(item)
                        raws.append(np.asarray(cur.ov_raw_host[slot][:feat], np.float32))
                for item, (raw, b) in (cur.pending_spill or {}).items():
                    if item not in snap_born or b > snap_born[item]:
                        rids.append(item)
                        raws.append(np.asarray(raw[:feat], np.float32))
                if rids:
                    new_index = ivf_ops.update_rows(
                        new_index, np.asarray(rids, np.int64), np.stack(raws)
                    )
                    stats["replayed"] = len(rids)
                holder["index"] = new_index
                return True

    def run_trials(tag: str) -> tuple[list, list, list]:
        """(per-trial qps, per-trial p99 ms, all walls) over _TRIALS
        `seconds`-long passes of batch dispatches on the live index."""
        qps_t, p99_t, walls_all = [], [], []
        ivf_ops.top_k(holder["index"], queries, how_many, nprobe=nprobe)  # warm
        for _ in range(_TRIALS):
            walls = []
            start = time.perf_counter()
            deadline = start + seconds
            served = 0
            while time.perf_counter() < deadline:
                td = time.perf_counter()
                ivf_ops.top_k(holder["index"], queries, how_many, nprobe=nprobe)
                walls.append(time.perf_counter() - td)
                served += batch
            qps_t.append(served / (time.perf_counter() - start))
            p99_t.append(float(np.percentile(np.array(walls) * 1000.0, 99)))
            walls_all.extend(walls)
        print(
            f"bench[serving-maintain]: {tag} qps {statistics.median(qps_t):.0f}, "
            f"p99 {statistics.median(p99_t):.1f} ms",
            file=sys.stderr,
        )
        return qps_t, p99_t, walls_all

    # phase A: no fold-ins, no maintainer — the baseline the 1.5x bound frames
    base_qps_t, base_p99_t, _ = run_trials("baseline")
    base_qps = statistics.median(base_qps_t)
    base_p99 = statistics.median(base_p99_t)

    # full-re-cluster tripwire: the request path and the maintenance loop
    # must never call build_ivf during the measured window
    real_build = ivf_ops.build_ivf
    recluster = [0]

    def counting_build(*a, **k):
        recluster[0] += 1
        return real_build(*a, **k)

    ivf_ops.build_ivf = counting_build
    folded_log: dict[int, np.ndarray] = {}
    fresh_samples: list[float] = []
    stop = threading.Event()
    model = _OpsModel()
    maint = maintain_mod.IndexMaintainer(
        lambda: model, interval_sec=interval, watermark=0.5, seed=11
    )

    def fold_loop():
        gen = np.random.default_rng(99)
        next_id = len(mat)
        seen = maint.compactions
        while not stop.is_set():
            vals = (
                mat[gen.integers(0, len(mat), 64)]
                + 0.1 * gen.standard_normal((64, features)).astype(np.float32)
            ).astype(np.float32)
            ids = np.arange(next_id, next_id + 64, dtype=np.int64)
            next_id += 64
            with lock:
                holder["index"] = ivf_ops.update_rows(holder["index"], ids, vals)
            for i, v in zip(ids.tolist(), vals):
                folded_log[i] = v
            if maint.compactions != seen:
                seen = maint.compactions
                fresh_samples.append(
                    metrics.registry.gauge(maintain_mod.FRESHNESS_GAUGE).value
                )
            stop.wait(64.0 / fold_rate)

    folder = threading.Thread(target=fold_loop, daemon=True)
    maint.start()
    folder.start()
    try:
        m_qps_t, m_p99_t, _ = run_trials("under maintenance")
    finally:
        stop.set()
        folder.join(timeout=10)
        maint.close()
        ivf_ops.build_ivf = real_build
    # final forced drain so the recall row sees every fold-in clustered
    maint.run_once(force=True)
    if maint.last_stats and maint.last_stats.get("born"):
        fresh_samples.append(metrics.registry.gauge(maintain_mod.FRESHNESS_GAUGE).value)
    ivf_ops.configure_ann(query_block=old_qb)

    m_p99 = statistics.median(m_p99_t)
    ratio = m_p99 / max(base_p99, 1e-9)
    best_ratio = min(m_p99_t) / max(base_p99, 1e-9)
    # the 1.5x bound presumes a spare core for the background compaction
    # (the design's deployment shape); on a single-core host the OS
    # time-slices compaction against the scan, so the row records the
    # honest ratio but only multi-core hosts hard-fail on it
    cores = os.cpu_count() or 1
    detail = (
        f"p99 {m_p99:.1f} ms under maintenance vs {base_p99:.1f} ms baseline "
        f"({ratio:.2f}x, bound 1.5x"
        f"{' — advisory: single-core host' if cores < 2 else ''}), "
        f"{maint.compactions} compactions, ~{fold_rate:.0f} items/s folded "
        f"({len(folded_log)} total), {recluster[0]} full re-clusters "
        f"(must be 0), {_TRIALS} x {seconds:.0f}s trials"
    )
    print(f"bench[serving-maintain]: {detail}", file=sys.stderr)
    _emit(
        f"ALS /recommend ANN p99 under live maintenance, {features}f x "
        f"{label_m} items, vs 1.5x no-maintenance p99",
        m_p99,
        "ms",
        1.5 * base_p99 / max(m_p99, 1e-9),
        order=84,
        detail=detail,
        base_p99_ms=round(base_p99, 2),
        compactions=maint.compactions,
        folded=len(folded_log),
        recluster_calls=recluster[0],
        noise_suspect=ratio > 1.5 >= best_ratio,
        trials=_TRIALS,
        spread=[round(min(m_p99_t), 2), round(max(m_p99_t), 2)],
    )
    qps, vs, tf = _rate_row(m_qps_t, base_qps)
    _emit(
        f"ALS /recommend ANN steady-state qps under live maintenance, "
        f"{features}f x {label_m} items, vs no-maintenance qps",
        qps,
        "queries/sec",
        vs,
        order=85,
        detail=f"baseline {base_qps:.0f} qps on the same catalog",
        base_qps=round(base_qps, 1),
        **tf,
    )
    if fresh_samples:
        fr = statistics.median(fresh_samples)
        _emit(
            f"ANN freshness under continuous fold-ins, {features}f x {label_m} "
            f"items, vs {fresh_budget:.0f}s budget",
            fr,
            "seconds",
            fresh_budget / max(fr, 1e-9),
            order=85,
            detail=f"fold-in -> clustered-visibility lag at each of "
            f"{len(fresh_samples)} compactions, maintain interval {interval}s",
            trials=len(fresh_samples),
            spread=[round(min(fresh_samples), 3), round(max(fresh_samples), 3)],
        )
    # recall@10 vs the exact f32 ranking over the union catalog (truth
    # computed per-probe: base-matrix scores + folded-row scores merged)
    probes = min(16, batch)
    fids = np.asarray(sorted(folded_log), np.int64)
    fvals = np.stack([folded_log[i] for i in fids.tolist()]) if len(fids) else None
    final = holder["index"]
    aidx, _ = ivf_ops.top_k(final, queries[:probes], how_many, nprobe=nprobe)
    hits = 0
    for r in range(probes):
        q = queries[r]
        t_base = mat @ q
        scores = np.concatenate([t_base, fvals @ q]) if fvals is not None else t_base
        ids_all = (
            np.concatenate([np.arange(len(mat), dtype=np.int64), fids])
            if fvals is not None
            else np.arange(len(mat), dtype=np.int64)
        )
        kth = np.partition(scores, -how_many)[-how_many]
        truth = dict(zip(ids_all.tolist(), scores.tolist()))
        got = [int(i) for i in np.asarray(aidx[r]) if int(i) >= 0]
        hits += sum(1 for i in got if truth.get(i, -np.inf) >= kth - 1e-4)
    recall = hits / (probes * how_many)
    _emit(
        f"ALS /recommend ANN recall after maintenance drain, {features}f x "
        f"{label_m} items, vs 0.95 floor",
        recall,
        "recall@10",
        recall / 0.95,
        order=85,
        detail=f"{probes} probes, nprobe {nprobe} of {final.n_cells} cells, "
        f"union catalog = {len(mat)} built + {len(folded_log)} folded live, "
        "tie-tolerant at 1e-4",
        folded=len(folded_log),
    )
    if ratio > 1.5 and best_ratio > 1.5 and cores >= 2:
        raise RuntimeError(
            f"maintenance p99 {m_p99:.1f} ms breaches 1.5x baseline "
            f"{base_p99:.1f} ms in every trial"
        )
    if recluster[0]:
        raise RuntimeError(
            f"{recluster[0]} full re-cluster(s) during the maintenance window"
        )


def bench_store_tier_cold() -> None:
    """The 100M-item cold-tier capacity row, sized to free disk: the
    tiered cell store holds a catalog far past host RAM as mmap'd disk
    cells (int8-plane bytes per item), and the row measures sequential
    cold-scan bandwidth through `read_cell` — disk -> pinned-RAM
    promotion under a RAM budget that forces continuous LRU eviction, so
    every pass stays cold like a worst-case probe storm."""
    import shutil as _sh

    import numpy as np

    from oryx_tpu.native.store import make_tier_store

    features = int(os.environ.get("ORYX_BENCH_MAINTAIN_FEATURES", 50))
    target = int(os.environ.get("ORYX_BENCH_COLD_ITEMS", 100_000_000))
    ram_budget = int(os.environ.get("ORYX_BENCH_COLD_RAM_MB", 256)) << 20
    items_per_cell = 65_536
    import tempfile

    spill = tempfile.mkdtemp(prefix="oryx-bench-cold-")
    free = _sh.disk_usage(spill).free
    items = min(target, int(free * 0.4 / features))
    n_cells = max(1, (items + items_per_cell - 1) // items_per_cell)
    items = n_cells * items_per_cell
    label_m = f"{items // 1_000_000}M" if items >= 1_000_000 else f"{items // 1000}K"
    sized_down = items < target

    st = make_tier_store(n_cells, ram_budget, spill)
    try:
        gen = np.random.default_rng(31)
        # one random payload reused per cell: content is irrelevant to the
        # mmap/LRU data path and generating the full catalog would bench
        # the RNG, not the store
        block = gen.integers(-127, 128, (items_per_cell, features)).astype(np.int8)
        t0 = time.perf_counter()
        for c in range(n_cells):
            st.put_cell(c, block)
        write_sec = time.perf_counter() - t0
        total_bytes = n_cells * block.nbytes
        print(
            f"bench[store-tier]: {label_m} items / {n_cells} cells / "
            f"{total_bytes / 1e9:.1f} GB written in {write_sec:.0f}s "
            f"({'sized to disk' if sized_down else 'full target'})",
            file=sys.stderr,
        )
        rates = []
        for _ in range(_TRIALS):
            t1 = time.perf_counter()
            for c in range(n_cells):
                buf = st.read_cell(c)
                assert buf is not None
            rates.append(total_bytes / (time.perf_counter() - t1) / 1e9)
        gbps, vs, tf = _rate_row(rates, 0.5)
        s = st.stats()
        detail = (
            f"{n_cells} cells x {items_per_cell} items x {features} B "
            f"(int8 plane), RAM budget {ram_budget >> 20} MB "
            f"({s['ram_cells']} cells resident), {s['demotions']} LRU "
            f"demotions, {tf['trials']} sequential cold passes; "
            f"{items / max(statistics.median(rates), 1e-9) / 1e9 * features:.1f}s "
            "per full-catalog pass"
        )
        print(f"bench[store-tier]: {detail}", file=sys.stderr)
        _emit(
            f"tiered item store cold-tier scan, {label_m} items mmap'd on "
            f"disk{' (sized to free disk)' if sized_down else ''}, "
            "vs 0.5 GB/s floor",
            gbps,
            "GB/s",
            vs,
            order=83,
            detail=detail,
            items=items,
            cells=n_cells,
            disk_gb=round(total_bytes / 1e9, 2),
            ram_cells=s["ram_cells"],
            backend=f"host/{os.cpu_count()}-core",
            **tf,
        )
    finally:
        st.close()
        _sh.rmtree(spill, ignore_errors=True)


BENCHES = [
    ("kmeans", bench_kmeans),
    ("als", bench_als),
    ("als-scale", bench_als_scale),
    ("speed", bench_speed),
    ("tracing-overhead", bench_tracing_overhead),
    ("lock-watchdog", bench_lock_watchdog_overhead),
    ("experiment-overhead", bench_experiment_overhead),
    ("resource-ledger", bench_ledger_overhead),
    ("overload", bench_overload),
    ("tenancy", bench_tenancy_overhead),
    ("rdf", bench_rdf),
    ("serving-large", bench_serving_large),
    ("serving-ann", bench_serving_ann),
    ("serving-maintain", bench_serving_maintain),
    ("store-tier", bench_store_tier_cold),
    ("serving-closed", bench_serving_closed_loop),
    ("serving-native", bench_native_front),
    ("serving-open", bench_serving_open_loop),
    ("crash-recovery", bench_crash_recovery),
    ("serving-250", bench_serving_250),
    ("serving", bench_serving),
]


def run_bench() -> None:
    only = os.environ.get("ORYX_BENCH_ONLY")
    selected = {s.strip() for s in only.split(",")} if only else None
    shapes = os.environ.get("ORYX_BENCH_SHAPES", "all")

    import logging

    logging.getLogger("jax._src.xla_bridge").setLevel(logging.ERROR)

    import jax

    from oryx_tpu.parallel.distributed import enable_compile_cache

    enable_compile_cache()
    backend, kind, _ = _device_info()
    print(
        f"bench: backend={backend} device={kind} n={len(jax.devices())}",
        file=sys.stderr,
    )
    try:
        with open(EVIDENCE_PATH, "a", encoding="utf-8") as f:
            ts = time.strftime("%Y-%m-%d %H:%M:%S UTC", time.gmtime())
            f.write(f"=== bench run @ {ts} backend={backend} device={kind} ===\n")
    except OSError:
        pass
    failed = []
    for name, fn in BENCHES:
        if selected is not None and name not in selected:
            continue
        if name == "serving-large" and shapes != "all":
            continue
        t0 = time.perf_counter()
        try:
            fn()
        except Exception as e:  # noqa: BLE001 - later rows still run
            failed.append(name)
            print(f"bench[{name}]: FAILED: {e!r}", file=sys.stderr)
        print(
            f"bench[{name}]: done in {time.perf_counter() - t0:.0f}s",
            file=sys.stderr,
        )
    if failed:
        print(f"bench: failed rows: {', '.join(failed)}", file=sys.stderr)
        sys.exit(3)


# --------------------------------------------------------------------------
# Parent: runs the child once and prints the summary last.
# --------------------------------------------------------------------------

# Only strip lines positively identified as known spam sources — a real
# crash report (which may mention SIGILL or external/xla paths) must
# survive into the operator-visible excerpt.
_NOISE_MARKERS = (
    "cpu_aot_loader",
    "TfrtCpuClient created",
    "absl::InitializeLog",
)


def _filter_stderr(err: str) -> str:
    kept = [
        ln
        for ln in err.splitlines()
        if ln.strip() and not any(m in ln for m in _NOISE_MARKERS)
    ]
    return "\n".join(kept)[-3000:]


def _print_summary(json_lines: list[str]) -> None:
    """The LAST thing this process writes: every metric row, compact,
    sorted so the headline serving row is the final line. The driver
    records a bounded tail of merged output and parses the last JSON
    line, so nothing may print after this."""
    rows = []
    for ln in json_lines:
        try:
            rows.append(json.loads(ln))
        except json.JSONDecodeError:
            continue
    # de-dup by metric (later wins), stable order field
    by_metric = {}
    for r in rows:
        by_metric[r["metric"]] = r
    final = sorted(by_metric.values(), key=lambda r: r.get("order", 50))
    sys.stderr.flush()
    print("=== BENCH SUMMARY ===", flush=True)
    for r in final:
        # keep summary rows compact — the driver records a bounded tail;
        # the full rows (latencies, detail) live in tools/bench_evidence.txt.
        # Closed-loop rows keep p50/p99: true latency is their whole point.
        drop = (
            ("order",)
            if "closed-loop" in r.get("metric", "")
            else ("order", "p50_ms", "p99_ms")
        )
        for k in drop:
            r.pop(k, None)
        print(json.dumps(r), flush=True)
    sys.stdout.flush()


def _run_child(env: dict, timeout: float) -> tuple[int, list[str], str]:
    """Stream child stdout, forwarding metric JSON lines immediately so
    completed metrics survive a mid-run kill."""
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__)],
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
    )
    json_lines: list[str] = []

    import threading

    # hard watchdog: a child hung in backend init prints nothing, so the
    # readline loop alone would block forever — kill unconditionally at
    # the deadline
    timed_out = threading.Event()

    def _watchdog() -> None:
        if proc.poll() is None:
            timed_out.set()
            proc.kill()

    killer = threading.Timer(timeout, _watchdog)
    killer.daemon = True
    killer.start()

    err_chunks: list[str] = []
    t = threading.Thread(
        target=lambda: err_chunks.append(proc.stderr.read()), daemon=True
    )
    t.start()
    try:
        for line in proc.stdout:
            line = line.strip()
            if line.startswith("{") and '"metric"' in line:
                json_lines.append(line)
                print(line, flush=True)
        rc = proc.wait(timeout=30)
    except subprocess.TimeoutExpired:
        proc.kill()
        rc = -9
    finally:
        killer.cancel()
    t.join(timeout=5)
    err = err_chunks[0] if err_chunks else ""
    if timed_out.is_set():
        rc = -9
        err += "\n[parent] child timed out"
    return rc, json_lines, err


def main() -> None:
    init_timeout = float(os.environ.get("ORYX_BENCH_INIT_TIMEOUT", 150))
    env = dict(os.environ)
    env["ORYX_BENCH_CHILD"] = "1"
    rc, json_lines, err = _run_child(env, timeout=init_timeout + 4500)
    sys.stderr.write(_filter_stderr(err) + "\n")
    print(
        f"bench[parent]: {len(json_lines)} metric(s) recorded (rc={rc})",
        file=sys.stderr,
    )
    if json_lines:
        _print_summary(json_lines)
    sys.exit(0 if rc == 0 else max(rc, 1))


if __name__ == "__main__":
    if os.environ.get("ORYX_BENCH_CHILD"):
        run_bench()
    else:
        main()
