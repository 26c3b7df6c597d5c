"""Plain reference for ALS `/recommend`: NumPy on the host, no program code.

Semantics (the reference's Recommend.java / ALSServingModel.topN): score
every item by the dot product with the user's vector, drop the user's
known items, return the `how_many` best with their scores, best first.

`top_n` is that, written straight. `judge` answers the question the check
asks of a served answer at 5M-20M items without sorting 20M scores a user:
how far does each served score lie from the reference's, and how far does
any served item lie below the best item that was left out? It walks the
item matrix once in blocks (float32 BLAS for the pass over all items, then
float64 for every score that is compared)."""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np


def top_n(x_u: np.ndarray, y: np.ndarray, known: np.ndarray, how_many: int):
    """(item rows, float64 scores) of the best `how_many` items for one
    user vector, known item rows removed, best first."""
    scores = y.astype(np.float64) @ x_u.astype(np.float64)
    scores[np.asarray(known, dtype=np.int64)] = -np.inf
    order = np.argsort(-scores, kind="stable")[:how_many]
    return order, scores[order]


def _block_best_left_out(y_block, base, xs, masked_rows):
    """Best score of each user among this block's items that are neither
    known nor served: (max [m], argmax row [m])."""
    scores = xs @ y_block.T  # [m, rows] float32
    for u, rows in masked_rows:
        scores[u, rows - base] = -np.inf
    arg = scores.argmax(axis=1)
    return scores[np.arange(scores.shape[0]), arg], arg + base


def judge(
    xs: np.ndarray,
    y: np.ndarray,
    known: np.ndarray,
    served_rows: list[np.ndarray],
    served_scores: list[np.ndarray],
    block: int = 1 << 18,
    threads: int = 8,
) -> dict:
    """Compare m served answers with the reference.

    xs [m, f] user vectors; y [n, f] item matrix; known [m, c] known item
    rows; served_rows[u] / served_scores[u] the item rows and scores of
    answer u in the order served. Per answer, as shares of that answer's
    score scale (the largest |reference score| among its served items and
    the best left-out item):

      score_err   largest |served score - float64 reference score of the
                  same item|
      left_out    how far the worst served item lies below the best item
                  left out (0 if none is better)
      order       how far a served item lies above the one served before
                  it (0 if the list is in order)
      known       served items that are in the user's known set (a count)
    """
    m, n = xs.shape[0], y.shape[0]
    xs64 = xs.astype(np.float64)
    xs32 = np.ascontiguousarray(xs, dtype=np.float32)
    best = np.full(m, -np.inf, dtype=np.float32)
    best_row = np.zeros(m, dtype=np.int64)
    starts = list(range(0, n, block))

    def work(base: int):
        stop = min(n, base + block)
        masked = []
        for u in range(m):
            rows = np.concatenate([known[u], served_rows[u]]).astype(np.int64)
            rows = rows[(rows >= base) & (rows < stop)]
            if rows.size:
                masked.append((u, rows))
        return _block_best_left_out(y[base:stop], base, xs32, masked)

    with ThreadPoolExecutor(max_workers=threads) as pool:
        for vals, rows in pool.map(work, starts):
            better = vals > best
            best[better] = vals[better]
            best_row[better] = rows[better]

    out = {"score_err": [], "left_out": [], "order": [], "known": []}
    for u in range(m):
        rows = np.asarray(served_rows[u], dtype=np.int64)
        ref = y[rows].astype(np.float64) @ xs64[u]
        left = float(y[best_row[u]].astype(np.float64) @ xs64[u])
        scale = max(float(np.max(np.abs(ref), initial=0.0)), abs(left), 1e-30)
        got = np.asarray(served_scores[u], dtype=np.float64)
        out["score_err"].append(float(np.max(np.abs(got - ref), initial=0.0)) / scale)
        out["left_out"].append(max(0.0, left - float(ref.min(initial=np.inf))) / scale)
        out["order"].append(max(0.0, float(np.max(np.diff(ref), initial=0.0))) / scale)
        out["known"].append(int(np.isin(rows, known[u]).sum()))
    return out
