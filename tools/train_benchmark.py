"""Batch-training benchmark harness: quality + wall-clock for the three
packaged apps (BASELINE.json rows; VERDICT r1 #3).

The reference publishes no batch wall-clocks ("just that of the
underlying MLlib implementations", src/site/markdown/docs/
performance.md:19-27), so the bars here are the BASELINE.json targets:
ALS MovieLens-100K-shape RMSE + wall-clock, k-means synthetic SSE/
silhouette, RDF covtype-shape accuracy, plus an ALS power-law scale run
exercising the sharded-factor mode. This environment has no network
egress, so dataset-shaped synthetics stand in for MovieLens/covtype:
same row/column/nnz counts and value ranges, generative structure
(low-rank + noise, Gaussian mixture, axis-aligned rule target) chosen so
the quality number is meaningful and reproducible (fixed seeds).

Usage:
  python tools/train_benchmark.py [als|als-scale|kmeans|rdf|all]

Env knobs: ORYX_TB_SCALE_NNZ (als-scale ratings, default 2e6),
ORYX_TB_SCALE_RANK (default 32), ORYX_TB_SCALE_SHARDED (0/1),
ORYX_TB_RDF_ROWS (default 100000), ORYX_TB_KMEANS_N (default 200000),
ORYX_TB_KMEANS_MINIBATCH (points per mini-batch Lloyd step; unset =
full-batch).

Each result carries "phase_sec" {init, iterate, eval}: trainer setup/
initialization wall vs sweep wall (from the ops module's
last_phase_seconds) vs the held-out metric wall.

Each benchmark prints one JSON line; `all` prints one per app.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _emit(d: dict) -> None:
    print(json.dumps(d), flush=True)


def _phase_sec(ops_mod, eval_sec: float) -> dict:
    """{"init": s, "iterate": s, "eval": s} for the trainer that just ran:
    init/iterate come from the ops module's last_phase_seconds, eval is
    the harness's own held-out metric wall."""
    ph = dict(getattr(ops_mod, "last_phase_seconds", {}) or {})
    ph["eval"] = eval_sec
    return {p: round(float(s), 3) for p, s in ph.items()}


# -- ALS: MovieLens-100K shape ----------------------------------------------


def movielens_100k_shape(seed=17):
    """943 users x 1682 items, 100k explicit ratings 1..5 with power-law
    item popularity and a rank-8 taste structure."""
    gen = np.random.default_rng(seed)
    num_users, num_items, nnz, r = 943, 1682, 100_000, 8
    xt = gen.standard_normal((num_users, r)) / np.sqrt(r)
    yt = gen.standard_normal((num_items, r)) / np.sqrt(r)
    pop = gen.zipf(1.3, size=nnz * 2) % num_items  # power-law item draw
    u = gen.integers(0, num_users, nnz * 2).astype(np.int32)
    ui = np.stack([u, pop.astype(np.int32)], axis=1)
    ui = np.unique(ui, axis=0)
    gen.shuffle(ui)
    ui = ui[:nnz]
    u, i = ui[:, 0], ui[:, 1]
    raw = np.einsum("nk,nk->n", xt[u], yt[i]) + 0.35 * gen.standard_normal(len(u))
    # map to 1..5 stars by quantile (marginals like real ratings data)
    qs = np.quantile(raw, [0.1, 0.3, 0.6, 0.85])
    v = (1.0 + np.digitize(raw, qs)).astype(np.float32)
    return u.astype(np.int32), i.astype(np.int32), v, num_users, num_items


def bench_als() -> dict:
    from oryx_tpu.ops import als as als_ops

    u, i, v, num_users, num_items = movielens_100k_shape()
    # 90/10 split (time-ordered in the app; random here — synthetic has no time)
    gen = np.random.default_rng(5)
    test = gen.random(len(u)) < 0.1
    t0 = time.perf_counter()
    model = als_ops.train_als(
        u[~test], i[~test], v[~test], num_users, num_items,
        features=25, lam=0.1, implicit=False, iterations=10, seed=42,
    )
    wall = time.perf_counter() - t0
    test_rmse = als_ops.rmse(model.x, model.y, u[test], i[test], v[test])
    eval_sec = time.perf_counter() - t0 - wall
    return {
        "bench": "als-ml100k-shape",
        "config": "943x1682, 100k explicit 1-5, rank 25, lam 0.1, 10 sweeps",
        "wall_sec": round(wall, 2),
        "held_out_rmse": round(test_rmse, 4),
        "phase_sec": _phase_sec(als_ops, eval_sec),
        "backend": _backend(),
    }


# -- ALS: power-law scale run ------------------------------------------------


def bench_als_scale() -> dict:
    from oryx_tpu.ops import als as als_ops
    from oryx_tpu.parallel.mesh import get_mesh

    import jax

    nnz = int(float(os.environ.get("ORYX_TB_SCALE_NNZ", 2e6)))
    rank = int(os.environ.get("ORYX_TB_SCALE_RANK", 32))
    sharded = os.environ.get("ORYX_TB_SCALE_SHARDED", "0") == "1"
    num_users = max(1000, nnz // 40)
    num_items = max(500, nnz // 200)
    gen = np.random.default_rng(99)
    # power-law users AND items: zipf-ish degree via pareto weights
    uw = (1.0 / (np.arange(num_users) + 10.0)) ** 0.8
    iw = (1.0 / (np.arange(num_items) + 10.0)) ** 0.9
    u = gen.choice(num_users, size=nnz, p=uw / uw.sum()).astype(np.int32)
    i = gen.choice(num_items, size=nnz, p=iw / iw.sum()).astype(np.int32)
    v = (1.0 + gen.random(nnz)).astype(np.float32)

    mesh = get_mesh() if (sharded or len(jax.devices()) > 1) else None
    t0 = time.perf_counter()
    model = als_ops.train_als(
        u, i, v, num_users, num_items, features=rank, lam=0.01, alpha=1.0,
        implicit=True, iterations=3, mesh=mesh, seed=7, shard_factors=sharded,
        matmul_dtype=os.environ.get("ORYX_TB_MATMUL_DTYPE"),
    )
    wall = time.perf_counter() - t0
    t1 = time.perf_counter()
    assert np.isfinite(model.x).all()
    eval_sec = time.perf_counter() - t1
    max_deg_u = int(np.bincount(u).max())
    return {
        "bench": "als-powerlaw-scale",
        "config": (
            f"{nnz} implicit ratings, {num_users}x{num_items}, rank {rank}, "
            f"max user degree {max_deg_u}, 3 sweeps, "
            f"{'sharded factors' if sharded else 'replicated factors'}, "
            f"{len(jax.devices())} device(s)"
        ),
        "wall_sec": round(wall, 2),
        "ratings_per_sec": int(nnz * 3 / wall),
        "phase_sec": _phase_sec(als_ops, eval_sec),
        "backend": _backend(),
    }


# -- k-means -----------------------------------------------------------------


def bench_kmeans() -> dict:
    from oryx_tpu.ops import kmeans as km

    n = int(os.environ.get("ORYX_TB_KMEANS_N", 200_000))
    d, k = 20, 10
    gen = np.random.default_rng(31)
    centers_true = 6.0 * gen.standard_normal((k, d))
    labels = gen.integers(0, k, n)
    pts = centers_true[labels] + gen.standard_normal((n, d))
    minibatch = os.environ.get("ORYX_TB_KMEANS_MINIBATCH")
    t0 = time.perf_counter()
    centers, counts, cost = km.train_kmeans(
        pts.astype(np.float32), k, iterations=20, seed=3,
        minibatch_size=int(minibatch) if minibatch else None,
    )
    wall = time.perf_counter() - t0
    t1 = time.perf_counter()
    sse = km.sum_squared_error(pts.astype(np.float32), centers)
    sil = km.silhouette_coefficient(pts[:2000].astype(np.float32), centers)
    eval_sec = time.perf_counter() - t1
    return {
        "bench": "kmeans-gaussians",
        "config": (
            f"{n}x{d}, k={k}, 20 "
            + (f"mini-batch({minibatch}) iters" if minibatch else "Lloyd iters")
            + ", k-means|| init"
        ),
        "wall_sec": round(wall, 2),
        "sse_per_point": round(sse / n, 3),
        "silhouette_2k_sample": round(float(sil), 3),
        "phase_sec": _phase_sec(km, eval_sec),
        "backend": _backend(),
    }


# -- RDF: covtype shape ------------------------------------------------------


def covtype_shape(n, seed=23):
    """54 features (10 numeric + 44 binary like covtype's one-hots),
    7 classes from axis-aligned rules + noise."""
    gen = np.random.default_rng(seed)
    num = gen.standard_normal((n, 10)).astype(np.float32)
    binary = (gen.random((n, 44)) < 0.15).astype(np.float32)
    x = np.concatenate([num, binary], axis=1)
    # axis-aligned rule target (trees can learn it) + 10% label noise
    yc = (
        (num[:, 0] > 0).astype(int)
        + 2 * (num[:, 1] > 0.5).astype(int)
        + (binary[:, 3] > 0).astype(int)
        + 2 * ((num[:, 2] + num[:, 3]) > 0).astype(int)
    ) % 7
    flip = gen.random(n) < 0.1
    yc[flip] = gen.integers(0, 7, flip.sum())
    return x, yc.astype(np.int32)


def bench_rdf() -> dict:
    from oryx_tpu.ops import forest as forest_ops

    n = int(os.environ.get("ORYX_TB_RDF_ROWS", 100_000))
    x, y = covtype_shape(n + 20_000)
    xtr, ytr = x[:n], y[:n]
    xte, yte = x[n:], y[n:]
    # quantile-bin numerics to 32 bins; binaries already 0/1
    num_bins = 32
    cuts = [np.quantile(xtr[:, j], np.linspace(0, 1, num_bins)[1:-1]) for j in range(10)]

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
    t1 = time.perf_counter()
    votes = forest_ops.predict_forest_binned(forest, binize(xte))  # [n, 7]
    acc = float((votes.argmax(axis=1) == yte).mean())
    eval_sec = time.perf_counter() - t1
    return {
        "bench": "rdf-covtype-shape",
        "config": f"{n}x54 (10 numeric + 44 binary), 7 classes, 20 trees depth 10",
        "wall_sec": round(wall, 2),
        "held_out_accuracy": round(acc, 4),
        "phase_sec": _phase_sec(forest_ops, eval_sec),
        "backend": _backend(),
    }


def _backend() -> str:
    import jax

    return f"{jax.default_backend()}x{len(jax.devices())}"


BENCHES = {
    "als": bench_als,
    "als-scale": bench_als_scale,
    "kmeans": bench_kmeans,
    "rdf": bench_rdf,
}


def main() -> None:
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    names = list(BENCHES) if which == "all" else [which]
    for name in names:
        _emit(BENCHES[name]())


if __name__ == "__main__":
    main()
