"""Builder `loadtest_als_sharded`: the arrays of `loadtest_als`, served by
the program's shard-items layout (`ALSServingModel(shard_items=True)`):
the item matrix row-sharded over every chip of the host, the user matrix
staged on each, every pass through the batcher like any other handle.

Its first act, on import, is to resolve that path's entry point in the
program. A program without it (the parent of the PR that brought this
cell) would spend minutes building 20 GB of factors and then put all of
them on chip 0 before it failed: here it fails in seconds, with the
reason, before anything is made."""

from __future__ import annotations

import resource
import time

try:
    from oryx_tpu.ops.topn import sharded_layout
except ImportError as e:
    raise ImportError(
        "this program has no batched shard-items serving path (oryx_tpu.ops.topn has no "
        "`sharded_layout`): the configuration needs a row-sharded item matrix behind the "
        "batcher and cannot run on it"
    ) from e

from benchmark.builders.loadtest_als import _FILL_CHUNK, Built, make_arrays, staged  # noqa: F401


def build(config: dict, seed: int, score_dtype: str | None = None) -> Built:
    """As `loadtest_als.build`, the model made with `shard_items=True`.
    Nothing is cut: where the host cannot take a step, the step and its
    size are in the error."""
    from oryx_tpu.app.als.serving_model import ALSServingModel

    t = {}
    t0 = time.perf_counter()
    try:
        x, y, known = make_arrays(config, seed)
    except MemoryError as e:
        raise MemoryError(
            "factors: %d x %d + %d x %d float32 did not fit the host"
            % (config["users"], config["features"], config["items"], config["features"])
        ) from e
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
        shard_items=True,
    )
    t0 = time.perf_counter()
    for lo in range(0, items, _FILL_CHUNK):
        try:
            model.set_item_vectors(item_ids[lo : lo + _FILL_CHUNK], y[lo : lo + _FILL_CHUNK])
        except MemoryError as e:
            raise MemoryError(f"store fill stopped at item {lo} of {items}") from e
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


def warm_scan_programs(model, batch_buckets, how_many: int, known_per_user: int) -> int:
    """Compile (or load from the cache) the sharded indexed-submit program
    of each batch bucket, through the program's own submit path with the
    model's own device arrays, and print where the data is."""
    import numpy as np

    from oryx_tpu.ops import topn as topn_ops
    from oryx_tpu.serving.batcher import TopNBatcher, _b_bucket, _k_bucket

    _ids, _index, y_mat, _h, _p = model._ensure_y_matrix()
    if not isinstance(y_mat, topn_ops.ShardedItemMatrix):
        raise RuntimeError(f"the item matrix is not sharded: {type(y_mat).__name__}")
    x_mat = model._x_matrix
    print(
        "shard layout: items %s; users %s a chip on %d chips; host peak resident %.1f GB"
        % (sharded_layout(y_mat), tuple(x_mat.shape), len(x_mat.sharding.device_set),
           resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6),
        flush=True,
    )
    kk = _k_bucket(how_many + known_per_user)
    n = 0
    for b in batch_buckets:
        rows = np.zeros(_b_bucket(int(b)), dtype=np.int32)
        topn_ops.submit_top_k_multi_indexed(
            y_mat, x_mat, rows, kk, scan_batch=TopNBatcher.MULTI_THRESHOLD
        ).result()
        n += 1
    return n
