"""Builder `loadtest_als`: the reference's LoadTestALSModelFactory.

Random factors for `users` x `items` x `features` from the seed, a few
known items per user, filled into the program's ALSServingModel through
its bulk setters. The arrays are made by the benchmark (NumPy's PCG64),
handed to the program as a deployment's update topic would hand them, and
kept for the plain reference: the reference never sees anything the
program made."""

from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import numpy as np

_FILL_CHUNK = 1 << 20
_GEN_THREADS = 8


@dataclass
class Built:
    model: object
    x: np.ndarray  # [users, features] float32
    y: np.ndarray  # [items, features] float32
    known: np.ndarray  # [users, c] int32 item rows
    timings: dict  # seconds per set-up step, for the line before the result

    @staticmethod
    def item_row(item_id: str) -> int:
        return int(item_id[1:])


def _normal(seed: int, stream: int, rows: int, cols: int) -> np.ndarray:
    """[rows, cols] standard normal float32, the same for the same seed:
    one PCG64 stream per row band, filled by a few threads."""
    out = np.empty((rows, cols), dtype=np.float32)
    bands = [(i, min(rows, i + (1 << 18))) for i in range(0, rows, 1 << 18)]

    def fill(args):
        band, (lo, hi) = args
        rng = np.random.Generator(np.random.PCG64([seed, stream, band]))
        rng.standard_normal(out=out[lo:hi], dtype=np.float32)

    with ThreadPoolExecutor(max_workers=_GEN_THREADS) as pool:
        list(pool.map(fill, enumerate(bands)))
    return out


def make_arrays(config: dict, seed: int):
    users, items, f = int(config["users"]), int(config["items"]), int(config["features"])
    x = _normal(seed, 1, users, f)
    y = _normal(seed, 2, items, f)
    rng = np.random.Generator(np.random.PCG64([seed, 3]))
    known = rng.integers(
        0, items, size=(users, int(config["known_items_per_user"])), dtype=np.int32
    )
    return x, y, known


def build(config: dict, seed: int, score_dtype: str | None = None) -> Built:
    """`score_dtype` overrides the configuration's dtype: the control run
    serves the same factors from the program's lower-precision matrix."""
    from oryx_tpu.app.als.serving_model import ALSServingModel

    t = {}
    t0 = time.perf_counter()
    x, y, known = make_arrays(config, seed)
    t["factors_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    users, items = x.shape[0], y.shape[0]
    item_ids = list(map("i%d".__mod__, range(items)))
    user_ids = list(map("u%d".__mod__, range(users)))
    t["ids_s"] = time.perf_counter() - t0

    model = ALSServingModel(
        features=int(config["features"]),
        implicit=bool(config["implicit"]),
        sample_rate=float(config.get("sample_rate", 1.0)),
        score_dtype=score_dtype or config["dtype"],
    )
    t0 = time.perf_counter()
    for lo in range(0, items, _FILL_CHUNK):
        model.set_item_vectors(item_ids[lo : lo + _FILL_CHUNK], y[lo : lo + _FILL_CHUNK])
    for lo in range(0, users, _FILL_CHUNK):
        model.set_user_vectors(user_ids[lo : lo + _FILL_CHUNK], x[lo : lo + _FILL_CHUNK])
    t["store_fill_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    getter = item_ids.__getitem__
    model.add_known_items_many(
        (u, list(map(getter, row))) for u, row in zip(user_ids, known.tolist())
    )
    t["known_items_s"] = time.perf_counter() - t0
    return Built(model=model, x=x, y=y, known=known, timings=t)


def staged(model) -> bool:
    """True once the item matrix is on the device and the user matrix is
    staged beside it, so that /recommend for a known user goes by row
    index (the path the cells time)."""
    return (
        model._y_matrix is not None
        and model._x_matrix is not None
        and not model._x_building
        and not model._x_dirty
    )


def warm_scan_programs(model, batch_buckets, how_many: int, known_per_user: int) -> int:
    """Compile (or load from the cache) the indexed-submit scan program of
    each batch bucket the traffic can meet, at the k bucket of its
    requests, by calling the program's own submit path with the model's
    own device arrays. Returns the number of programs run."""
    from oryx_tpu.ops import topn as topn_ops
    from oryx_tpu.serving.batcher import TopNBatcher, _b_bucket, _k_bucket

    _ids, _index, y_mat, _h, _p = model._ensure_y_matrix()
    x_mat = model._x_matrix
    kk = _k_bucket(how_many + known_per_user)
    n = 0
    for b in batch_buckets:
        rows = np.zeros(_b_bucket(int(b)), dtype=np.int32)
        topn_ops.submit_top_k_multi_indexed(
            y_mat, x_mat, rows, kk, scan_batch=TopNBatcher.MULTI_THRESHOLD
        ).result()
        n += 1
    return n
