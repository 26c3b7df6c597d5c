"""Real-dataset quality parity (VERDICT r3 #6): held-out RMSE on the
real MovieLens-100K and held-out accuracy on the real UCI covtype,
through the SAME training paths the framework's apps use.

Requires `python tools/fetch_datasets.py` first (needs network; this
build sandbox has none, so tools/train_benchmark.py's quality numbers
come from dataset-shaped synthetics).

Parity bars (the MLlib-trained reference's ballpark at comparable
settings): ML-100K held-out RMSE ~0.90-0.95 (rank 25, lam 0.1,
time-ordered 90/10); covtype held-out accuracy ~0.72-0.75 at 20 trees
depth 10 (deeper forests reach higher; this matches rdf-example scale).

Usage:
    python tools/real_data_eval.py [--data data/real] [--out FILE]

Prints one JSON line per dataset.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def eval_ml100k(data_dir: Path) -> dict:
    from oryx_tpu.ops import als as als_ops

    raw = np.loadtxt(data_dir / "ml-100k" / "u.data", dtype=np.int64)  # u i r ts
    order = np.argsort(raw[:, 3], kind="stable")  # time-ordered split
    raw = raw[order]
    uq, u = np.unique(raw[:, 0], return_inverse=True)
    iq, i = np.unique(raw[:, 1], return_inverse=True)
    v = raw[:, 2].astype(np.float32)
    split = int(len(v) * 0.9)
    t0 = time.perf_counter()
    model = als_ops.train_als(
        u[:split].astype(np.int32),
        i[:split].astype(np.int32),
        v[:split],
        len(uq),
        len(iq),
        features=25,
        lam=0.1,
        implicit=False,
        iterations=10,
        seed=42,
    )
    wall = time.perf_counter() - t0
    rmse = als_ops.rmse(
        model.x, model.y, u[split:].astype(np.int32), i[split:].astype(np.int32), v[split:]
    )
    return {
        "metric": "ALS held-out RMSE, REAL MovieLens-100K (rank 25, lam 0.1, "
        "time-ordered 90/10, 10 sweeps)",
        "value": round(float(rmse), 4),
        "unit": "rmse",
        "vs_baseline": round(0.93 / float(rmse), 2),  # MLlib ballpark ~0.93
        "wall_sec": round(wall, 1),
    }


def eval_covtype(data_dir: Path) -> dict:
    from oryx_tpu.ops import forest as forest_ops

    raw = np.loadtxt(data_dir / "covtype.data", delimiter=",", dtype=np.float32)
    x, y = raw[:, :-1], raw[:, -1].astype(np.int32) - 1  # classes 1..7 -> 0..6
    gen = np.random.default_rng(13)
    perm = gen.permutation(len(y))
    x, y = x[perm], y[perm]
    n_test = 50_000
    xtr, ytr, xte, yte = x[:-n_test], y[:-n_test], x[-n_test:], y[-n_test:]
    num_bins = 32
    cuts = [
        np.quantile(xtr[:, j], np.linspace(0, 1, num_bins)[1:-1]) for j in range(10)
    ]

    def binize(m):
        out = np.zeros(m.shape, np.int32)
        for j in range(10):
            out[:, j] = np.searchsorted(cuts[j], m[:, j], side="left")
        out[:, 10:] = m[:, 10:].astype(np.int32)
        return out

    t0 = time.perf_counter()
    forest = forest_ops.train_forest(
        binize(xtr), ytr, num_bins=num_bins, num_classes=7,
        num_trees=20, max_depth=10, impurity="entropy", seed=77,
    )
    wall = time.perf_counter() - t0
    votes = forest_ops.predict_forest_binned(forest, binize(xte))
    acc = float((votes.argmax(axis=1) == yte).mean())
    return {
        "metric": "RDF held-out accuracy, REAL UCI covtype (581K rows, 20 trees "
        "depth 10)",
        "value": round(acc, 4),
        "unit": "accuracy",
        "vs_baseline": round(acc / 0.73, 2),  # MLlib RF ballpark at this depth
        "wall_sec": round(wall, 1),
    }


def eval_bundled_iris() -> dict:
    """REAL Iris through our k-means vs sklearn's KMeans as an
    independent reference implementation on the identical data — the
    kmeans-example.conf quality bar (BASELINE.json row) with no network."""
    from sklearn.cluster import KMeans
    from sklearn.datasets import load_iris

    from oryx_tpu.ops import kmeans as km

    x = load_iris().data.astype(np.float32)
    t0 = time.perf_counter()
    centers, cost = None, np.inf
    for restart in range(5):  # KMeansUpdate-style restarts, best SSE wins
        cen, _counts, c = km.train_kmeans(x, 3, iterations=50, seed=5 + restart)
        if c < cost:
            centers, cost = cen, c
    wall = time.perf_counter() - t0
    ours_sse = float(km.sum_squared_error(x, centers))
    ours_sil = float(km.silhouette_coefficient(x, centers))
    ref = KMeans(n_clusters=3, n_init=5, random_state=5).fit(x)
    ref_sse = float(
        km.sum_squared_error(x, ref.cluster_centers_.astype(np.float32))
    )
    return {
        "metric": "k-means SSE, REAL Iris (k=3, 5 restarts) vs sklearn KMeans "
        f"SSE {ref_sse:.2f} on identical data",
        "value": round(ours_sse, 2),
        "unit": "sse (lower better)",
        "vs_baseline": round(ref_sse / ours_sse, 4),
        "silhouette": round(ours_sil, 3),
        "wall_sec": round(wall, 2),
    }


def eval_bundled_digits() -> dict:
    """REAL handwritten digits (1797x64, 10 classes) through our
    histogram forest vs sklearn's RandomForest at matched size on the
    identical split — an independent-implementation accuracy bar (the
    covtype row's stand-in while the sandbox has no network)."""
    from sklearn.datasets import load_digits
    from sklearn.ensemble import RandomForestClassifier

    from oryx_tpu.ops import forest as forest_ops

    d = load_digits()
    x = d.data.astype(np.float32)
    y = d.target.astype(np.int32)
    gen = np.random.default_rng(13)
    perm = gen.permutation(len(y))
    x, y = x[perm], y[perm]
    n_test = 400
    xtr, ytr, xte, yte = x[:-n_test], y[:-n_test], x[-n_test:], y[-n_test:]
    xb_tr = np.clip(xtr, 0, 16).astype(np.int32)  # pixel values are 0..16
    xb_te = np.clip(xte, 0, 16).astype(np.int32)
    t0 = time.perf_counter()
    forest = forest_ops.train_forest(
        xb_tr, ytr, num_bins=17, num_classes=10,
        num_trees=50, max_depth=10, impurity="entropy", seed=77,
    )
    wall = time.perf_counter() - t0
    votes = forest_ops.predict_forest_binned(forest, xb_te)
    acc = float((votes.argmax(axis=1) == yte).mean())
    ref = RandomForestClassifier(
        n_estimators=50, max_depth=10, random_state=77
    ).fit(xtr, ytr)
    ref_acc = float(ref.score(xte, yte))
    return {
        "metric": "RDF held-out accuracy, REAL digits (1797x64, 50 trees depth "
        f"10) vs sklearn RandomForest {ref_acc:.4f} on the identical split",
        "value": round(acc, 4),
        "unit": "accuracy",
        "vs_baseline": round(acc / ref_acc, 4),
        "wall_sec": round(wall, 1),
    }


def skip_row(metric: str, dataset_path: str) -> dict:
    """Explicit evidence that a real-dataset row was NOT measured, and
    why — a sandbox with no egress cannot fetch the dataset. A skip row
    in the evidence file is auditable; a silent stderr line is not."""
    return {
        "metric": metric,
        "status": "SKIPPED: no-egress",
        "reason": f"{dataset_path} absent; this sandbox has no network. "
        "Run `python tools/fetch_datasets.py` where egress is allowed, "
        "then re-run tools/real_data_eval.py — the eval path runs "
        "unchanged once the files exist.",
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--data", default="data/real")
    ap.add_argument("--out", default=None)
    ap.add_argument(
        "--bundled",
        action="store_true",
        help="also evaluate on sklearn's BUNDLED real datasets (Iris, "
        "digits) against sklearn's own estimators — the no-network "
        "quality-parity path",
    )
    args = ap.parse_args()
    data_dir = Path(args.data)
    results = []
    measured = 0
    if (data_dir / "ml-100k" / "u.data").exists():
        results.append(eval_ml100k(data_dir))
        measured += 1
    else:
        results.append(
            skip_row(
                "ALS held-out RMSE, REAL MovieLens-100K (rank 25, lam 0.1, "
                "time-ordered 90/10, 10 sweeps)",
                str(data_dir / "ml-100k" / "u.data"),
            )
        )
    if (data_dir / "covtype.data").exists():
        results.append(eval_covtype(data_dir))
        measured += 1
    else:
        results.append(
            skip_row(
                "RDF held-out accuracy, REAL UCI covtype (581K rows, 20 trees "
                "depth 10)",
                str(data_dir / "covtype.data"),
            )
        )
    if args.bundled:
        results.append(eval_bundled_iris())
        results.append(eval_bundled_digits())
        measured += 2
    for r in results:
        print(json.dumps(r), flush=True)
    if args.out and results:
        with open(args.out, "a", encoding="utf-8") as f:
            for r in results:
                f.write(json.dumps(r) + "\n")
    if not measured and not any(r.get("status") for r in results):
        sys.exit(2)


if __name__ == "__main__":
    main()
