"""Builder `loadtest_als_similarity`: the factors, ids and store fill of
`loadtest_als` (the reference's LoadTestALSModelFactory), judged as
`/similarity/i<id>`: the items of highest cosine to ONE item, that item
left out.

The harness draws ids in [0, `users`) and judges through four names of
what the builder returns (benchmark/check.py): `x[u]`, `y`, `known[u]`
and `item_row`. Cosine to one item is a dot product of unit rows with the
item itself as the exclusion list, so the builder hands the check
`y` = the unit item rows (float32, normalised in float64 by
benchmark/reference/als_similarity.py), `x` = the first `users` of them,
`known[u] = [u]`: the accepted comparison, limits and control, unchanged.
The program is given the factors as they were drawn; it normalises (or
not) by its own code.

Nothing here stages anything: the query of this endpoint is a float32
vector the handler reads from the store and the batcher uploads with the
pass (`_submit_vectors`), whether or not the program stages the users."""

from __future__ import annotations

import time

import numpy as np

from benchmark.builders.loadtest_als import Built
from benchmark.builders.loadtest_als import build as _build
from benchmark.reference.als_similarity import unit_rows

# items a request names beside `howMany` in the program's candidate window:
# the queried item is asked for on top (endpoints.similarity) and is the one
# entry of the exclusion list (ALSServingModel._select_loop)
_WINDOW_EXTRA = 2


def build(config: dict, seed: int, score_dtype: str | None = None) -> Built:
    """`loadtest_als.build`, then the judged view: unit item rows."""
    users, items = int(config["users"]), int(config["items"])
    if users > items:  # before anything is built
        raise ValueError(f"ids are drawn below {users}: the catalog holds {items} items")
    built = _build(config, seed, score_dtype=score_dtype)
    t = dict(built.timings)
    t0 = time.perf_counter()
    y_unit = unit_rows(built.y)
    known = np.arange(users, dtype=np.int32)[:, None]
    t["unit_rows_s"] = time.perf_counter() - t0
    return Built(model=built.model, x=y_unit[:users], y=y_unit, known=known, timings=t)


def staged(model) -> bool:
    """True once the item matrix is on the device: this endpoint waits for
    nothing else."""
    return model._y_matrix is not None


def warm_scan_programs(model, batch_buckets, how_many: int, known_per_user: int) -> int:
    """Compile (or load from the cache) the VECTOR-submit cosine program of
    each batch bucket the traffic can meet, at the k bucket of its
    requests, through the program's own submit. Returns the number of
    programs run."""
    from oryx_tpu.ops import topn as topn_ops
    from oryx_tpu.serving.batcher import _b_bucket, _k_bucket

    _ids, _index, y_mat, _h, _p = model._ensure_y_matrix()
    kk = _k_bucket(how_many + _WINDOW_EXTRA)
    for b in batch_buckets:
        block = np.zeros((_b_bucket(int(b)), model.features), dtype=np.float32)
        topn_ops.submit_top_k(y_mat, block, kk, cosine=True).result()
    return len(batch_buckets)
