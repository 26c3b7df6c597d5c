"""Builder `loadtest_als_anonymous`: the factors, ids and store fill of
`loadtest_als` (the reference's LoadTestALSModelFactory), judged as
`/recommendToAnonymous/i<h>/i<o_1>/...`: the items of highest dot product
with a temporary user vector folded in from a basket, the basket left out.

The harness names a request by ONE integer and judges through four names
of what the builder returns (benchmark/check.py): `x[u]`, `y`, `known[u]`
and `item_row`. Here the integer is a basket's index, `(k - 1) * users +
head` (benchmark/reference/als_foldin.py `basket`: index `head` is the
one-item basket `path % head`, what run.py asks by itself), so the builder
hands the check `y` as drawn, `x` an object whose `x[indices]` is the
plain reference's folded vector of each of those baskets (float64, against
a `YtY` summed in float64 blocks once in set-up), and `known` an object
whose `known[indices]` is the baskets' item rows, `[m, 8]`, padded by
repeating the head: the accepted comparison and limits, unchanged.

Nothing here stages anything and nothing asks the program for a solver:
the first request folds in, so the program builds `YtY` by its own path
(the device's Gram pass, or a host loop on a program without one), and
`staged` waits for that, so that it lies in `setup_s` and not in the
window."""

from __future__ import annotations

import time
import weakref

import numpy as np

from benchmark.builders.loadtest_als import Built
from benchmark.builders.loadtest_als import build as _build
from benchmark.reference import als_foldin

# the candidate windows of the mix: `howMany` + the basket (1 to 8 items,
# all of them in the exclusion list, ALSServingModel._select_loop)
_BASKETS = (1, als_foldin.MAX_BASKET)
# model -> the reference's YtY, for the one line `warm_scan_programs` prints
_reference_yty: weakref.WeakKeyDictionary = weakref.WeakKeyDictionary()


class _Baskets:
    """`known[indices]`: [m, 8] int32 item rows of the baskets of those
    indices, padded by repeating the head."""

    def __init__(self, config: dict) -> None:
        sessions = config["sessions"]
        self._args = (
            int(config["users"]), int(config["items"]),
            float(sessions["exponent"]), int(sessions["basket_seed"]),
        )

    def rows(self, index: int) -> list[int]:
        return als_foldin.basket(index, *self._args)

    def __getitem__(self, indices) -> np.ndarray:
        indices = np.atleast_1d(np.asarray(indices, dtype=np.int64))
        out = np.empty((len(indices), als_foldin.MAX_BASKET), dtype=np.int32)
        for at, index in enumerate(indices.tolist()):
            rows = self.rows(index)
            out[at] = rows + rows[:1] * (als_foldin.MAX_BASKET - len(rows))
        return out


class _FoldedVectors:
    """`x[indices]`: [m, f] float64, the plain reference's temporary user
    vector of each of those baskets (strengths 1.0)."""

    def __init__(self, y: np.ndarray, yty: np.ndarray, baskets: _Baskets, implicit: bool) -> None:
        self._y, self._yty, self._baskets, self._implicit = y, yty, baskets, implicit

    def __getitem__(self, indices) -> np.ndarray:
        indices = np.atleast_1d(np.asarray(indices, dtype=np.int64))
        rows = [self._baskets.rows(i) for i in indices.tolist()]
        return als_foldin.fold_in(
            self._y, self._yty, rows, [[1.0] * len(r) for r in rows], self._implicit
        )


def build(config: dict, seed: int, score_dtype: str | None = None) -> Built:
    """`loadtest_als.build`, then the judged view: the reference's `YtY`
    (timed on the `setup:` line) and the two objects above."""
    users, items = int(config["users"]), int(config["items"])
    if users > items:  # before anything is built
        raise ValueError(f"heads are drawn below {users}: the catalog holds {items} items")
    built = _build(config, seed, score_dtype=score_dtype)
    t = dict(built.timings)
    t0 = time.perf_counter()
    yty = als_foldin.yty(built.y)
    t["reference_yty_s"] = time.perf_counter() - t0
    baskets = _Baskets(config)
    folded = _FoldedVectors(built.y, yty, baskets, bool(config["implicit"]))
    _reference_yty[built.model] = yty
    return Built(model=built.model, x=folded, y=built.y, known=baskets, timings=t)


def staged(model) -> bool:
    """True once the item matrix is on the device AND the program holds its
    solver over `YtY`: the first request builds both."""
    return model._y_matrix is not None and model._yty_solver is not None


def _yty_line(model) -> str:
    """What the program's `YtY` is against the reference's, and what its
    build took against the least a chip could take (one pass over the item
    matrix: bytes at the HBM's peak, products at the MXU's)."""
    from oryx_tpu.common import metrics

    want = _reference_yty[model]
    got = model.get_yty_solver().matrix
    err = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    snap = metrics.registry.snapshot()
    took = snap.get("serving.yty.build.seconds") or {}
    where = [w for w in ("device", "host")
             if (snap.get(f"serving.yty.builds.{w}") or {}).get("value")]
    n, f = model.y.size(), model.features
    least_ms = max(n * f * 4 / 819e9, 2.0 * n * f * f / 197e12) * 1000.0
    return (
        "yty: largest |program - float64 reference| = %.3g of the largest entry (limit 1e-6); "
        "built %s, %s; least for one pass over %d x %d float32 on a v5e: %.2f ms "
        "(%.2f GB at 819 GB/s, %.0f GFLOP at 197 TFLOP/s)"
        % (
            err,
            "on the " + "/".join(where) if where else "by a program that does not say where",
            "%.3f s in %d build(s)" % (took["sum"], took["count"]) if took.get("count")
            else "seconds not reported by this program (they are in first_request)",
            n, f, least_ms, n * f * 4 / 1e9, 2.0 * n * f * f / 1e9,
        )
    )


def warm_scan_programs(model, batch_buckets, how_many: int, known_per_user: int) -> int:
    """Compile (or load from the cache) the VECTOR-submit dot program of
    each batch bucket the traffic can meet, at BOTH k buckets its baskets
    ask for (`howMany` + 1..6 items: 16; + 7..8 items: 32), through the
    program's own submit. Returns the number of programs run."""
    from oryx_tpu.ops import topn as topn_ops
    from oryx_tpu.serving.batcher import _b_bucket, _k_bucket

    print(_yty_line(model), flush=True)
    _ids, _index, y_mat, _h, _p = model._ensure_y_matrix()
    k_buckets = sorted({_k_bucket(how_many + k) for k in _BASKETS})
    for kk in k_buckets:
        for b in batch_buckets:
            block = np.zeros((_b_bucket(int(b)), model.features), dtype=np.float32)
            topn_ops.submit_top_k(y_mat, block, kk, cosine=False).result()
    return len(k_buckets) * len(batch_buckets)
