"""Plain reference for ALS `/similarity`: NumPy float64 on the host, no
program code.

Semantics (the reference's Similarity.java:60 and CosineAverageFunction.java):
an item's score is the MEAN, over the queried items, of its cosine to each
of them; the queried items themselves are left out; the `how_many` best
come back with their scores, best first. With unit rows `u_i = y_i / |y_i|`
that mean is `u_i . mean_q(u_q)`: one dot product an item.

`most_similar` is that, written straight, for the sizes the tests run. A
cell of millions of items is judged by `als_topn.judge`, which knows dot
products and an exclusion list only, over the UNIT rows: for ONE queried
item q the score of item i is `u_i . u_q` and the exclusion list is `[q]`,
which is what `unit_rows` hands it (benchmark/builders/
loadtest_als_similarity.py); tests/benchmark/test_bench_similarity.py ties
the two."""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np


def unit_rows(y: np.ndarray, block: int = 1 << 12, threads: int = 8) -> np.ndarray:
    """[n, f] float32 rows of `y` over their own norms, the division in
    float64; a zero row stays zero. A small block of rows at a time, a few
    blocks at once: millions of rows are set-up time of a run, and a block
    that stays in the cache takes a fiftieth of what a large one takes."""
    out = np.empty(y.shape, dtype=np.float32)

    def fill(lo: int) -> None:
        rows = y[lo : lo + block].astype(np.float64)
        norms = np.sqrt(np.einsum("ij,ij->i", rows, rows))
        rows /= np.where(norms > 0, norms, 1.0)[:, None]
        out[lo : lo + block] = rows

    with ThreadPoolExecutor(max_workers=threads) as pool:
        list(pool.map(fill, range(0, y.shape[0], block)))
    return out


def most_similar(y: np.ndarray, rows, how_many: int):
    """(item rows, float64 scores) of the `how_many` items of highest mean
    cosine to the items `rows` of `y` [n, f], those rows removed, best
    first; ties go to the lower row."""
    y64 = np.asarray(y, dtype=np.float64)
    rows = np.asarray(rows, dtype=np.int64)
    norms = np.linalg.norm(y64, axis=1)
    safe = np.where(norms > 0, norms, 1.0)
    mean_unit = (y64[rows] / safe[rows, None]).mean(axis=0)
    scores = (y64 @ mean_unit) / safe
    scores[rows] = -np.inf
    order = np.argsort(-scores, kind="stable")[:how_many]
    return order, scores[order]
