"""Plain reference for the ALS fold-in of an anonymous visitor
(`/recommendToAnonymous`): NumPy float64 on the host, no program code.

Semantics (the reference's RecommendToAnonymous.java:59 through
EstimateForAnonymous.buildTemporaryUserVector:73-87, ALSUtils.
computeTargetQui:37-59 and computeUpdatedXu:74-106 against the solver over
`YtY` of ALSServingModel.java:357-373): the visitor has no row in X. Each
item of the basket, in the URL's order, moves a temporary vector `Xu`:

    Qui       = Xu . Yi                 (0 while there is no Xu yet)
    targetQui = computeTargetQui(implicit, value, 0.5 if no Xu else Qui)
    dXu       = (YtY)^-1 ((targetQui - Qui) * Yi)
    Xu        = Xu + dXu                (dXu itself for the first item)

and an item whose target is "no change" (NaN) is passed over. The answer
is then the plain top-N by dot product of `Xu` over every item, the basket
left out: `als_topn.judge` judges it unchanged.

Departures from upstream, each on purpose: everything here is float64
(upstream keeps `Xu`, `Yi` and `dXu` in Java floats and solves in double);
`YtY` is summed in float64 over blocks of rows, where upstream's
VectorMath.transposeTimesSelf adds rank-one products in double one row at
a time (the same sum in another order); the solve is LAPACK's on the full
matrix for each step's right-hand sides, where upstream applies a cached
QR decomposition.

`session` / `basket` / `url` are the traffic's side of the same cell, kept
here so that the load generator's child process and the builder name one
basket by one integer (benchmark/traffic/anonymous-open.json)."""

from __future__ import annotations

import math

import numpy as np

from benchmark.drivers.httpclient import power_law_users  # the traffic's law, for `session`

# -- the fold-in ----------------------------------------------------------------------------


def yty(y: np.ndarray, block: int = 1 << 14) -> np.ndarray:
    """`Y^T Y` of [n, f] item rows, [f, f] float64: every product and every
    sum in float64, a block of rows at a time (one after another: the BLAS
    under NumPy spreads a block over the cores itself, and a few blocks at
    once took twenty times as long)."""
    total = np.zeros((y.shape[1], y.shape[1]), dtype=np.float64)
    for lo in range(0, y.shape[0], block):
        rows = y[lo : lo + block].astype(np.float64)
        total += rows.T @ rows
    return total


def target_qui(implicit: bool, value: float, current: float) -> float:
    """ALSUtils.computeTargetQui: the estimate the interaction asks for, or
    NaN for "no change"."""
    if not implicit:
        return value
    if value > 0.0 and current < 1.0:
        return current + (value / (1.0 + value)) * (1.0 - max(0.0, current))
    if value < 0.0 and current > 0.0:
        return current + (value / (value - 1.0)) * -min(1.0, current)
    return math.nan


def fold_in(y: np.ndarray, yty_: np.ndarray, baskets, values, implicit: bool) -> np.ndarray:
    """[m, f] float64: the temporary user vector of each basket. `baskets[b]`
    are item rows of `y` in the URL's order, `values[b]` their strengths; a
    basket that moves nothing keeps a zero vector (the endpoint answers 400
    there, and no cell sends one)."""
    f = y.shape[1]
    out = np.zeros((len(baskets), f), dtype=np.float64)
    for b, (rows, strengths) in enumerate(zip(baskets, values)):
        xu = None
        for row, value in zip(rows, strengths):
            yi = y[int(row)].astype(np.float64)
            qui = 0.0 if xu is None else float(xu @ yi)
            target = target_qui(implicit, float(value), 0.5 if xu is None else qui)
            if math.isnan(target):
                continue
            d_xu = np.linalg.solve(yty_, (target - qui) * yi)
            xu = d_xu if xu is None else xu + d_xu
        if xu is not None:
            out[b] = xu
    return out


# -- the traffic's baskets ------------------------------------------------------------------

MAX_BASKET = 8  # a client caps its URL


def session(head: int, n_items: int, exponent: float, basket_seed: int) -> list[int]:
    """The fixed session of head item `head`: `[head, o_1, ..., o_7]`, the
    seven further items drawn by the power law over ALL `n_items` from
    `PCG64([basket_seed, head])`, distinct and not the head. A catalog of
    fewer than eight items gives a shorter session."""
    rng = np.random.Generator(np.random.PCG64([int(basket_seed), int(head)]))
    items, seen = [int(head)], {int(head)}
    want = min(MAX_BASKET, n_items)
    while len(items) < want:
        for row in power_law_users(rng, n_items, exponent, 32).tolist():
            if row not in seen:
                seen.add(row)
                items.append(row)
                if len(items) == want:
                    break
    return items


def basket(index: int, heads: int, n_items: int, exponent: float, basket_seed: int) -> list[int]:
    """Item rows of request `index = (k - 1) * heads + head`: the first k
    items of the head's session. Index `head` itself is the one-item
    basket, the first click of a session."""
    k_less_one, head = divmod(int(index), int(heads))
    if k_less_one >= MAX_BASKET:
        raise ValueError(f"index {index} names a basket of more than {MAX_BASKET} items")
    return session(head, n_items, exponent, basket_seed)[: k_less_one + 1]


def url(path: str, rows) -> str:
    """The request of a basket: `path` is the one-item template
    (`/recommendToAnonymous/i%d?howMany=10`); further items follow the
    first as path segments, bare ids, strength 1.0."""
    first, rest = path.split("i%d")
    return first + "/".join("i%d" % r for r in rows) + rest


def basket_size_law(ratio: float, largest: int = MAX_BASKET) -> np.ndarray:
    """P(k), k = 1..largest, proportional to ratio^(k - 1)."""
    p = np.power(float(ratio), np.arange(largest))
    return p / p.sum()
