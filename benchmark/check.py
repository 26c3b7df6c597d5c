"""The comparison that decides `correct`.

Answers of the timed path (the real HTTP front, batcher and scan, at the
cell's own sizes) against benchmark/reference/als_topn.py: a seeded set of
users asked before the window, and a seeded sample of the window's own
answers, judged once the window has closed. Every number compared is
printed beside its limit. How the limits were set: PERF.md section 2."""

from __future__ import annotations

from benchmark.drivers import httpclient as hc
from benchmark.reference import als_topn

# limit of each number compared; the readings they were set from: PERF.md section 2
LIMITS = {
    "score_err_of_scale": 1e-5,
    "left_out_gap_of_scale": 1e-5,
    "order_gap_of_scale": 1e-5,
    "known_items_served": 0,
    "malformed_answers": 0,
    "answers_compared_min": 1,  # a run that compared nothing is not correct
}


def judge_answers(built, answers: list[dict], how_many: int) -> dict:
    """`answers`: [{"user": row, "body": JSON text}]. Returns the numbers
    compared (worst over the answers)."""
    import numpy as np

    users, rows, scores = [], [], []
    malformed = 0
    for a in answers:
        try:
            pairs = hc.parse_answer(a["body"])
            r = np.asarray([built.item_row(i) for i, _ in pairs], dtype=np.int64)
        except (ValueError, KeyError, TypeError):
            malformed += 1
            continue
        if len(pairs) != how_many or len(set(r.tolist())) != len(pairs):
            malformed += 1
            continue
        users.append(int(a["user"]))
        rows.append(r)
        scores.append(np.asarray([s for _, s in pairs], dtype=np.float64))
    numbers = {
        "score_err_of_scale": 0.0,
        "left_out_gap_of_scale": 0.0,
        "order_gap_of_scale": 0.0,
        "known_items_served": 0,
        "malformed_answers": malformed,
        "answers_compared_min": len(users),
    }
    if users:
        u = np.asarray(users, dtype=np.int64)
        j = als_topn.judge(built.x[u], built.y, built.known[u], rows, scores)
        numbers["score_err_of_scale"] = max(j["score_err"])
        numbers["left_out_gap_of_scale"] = max(j["left_out"])
        numbers["order_gap_of_scale"] = max(j["order"])
        numbers["known_items_served"] = int(sum(j["known"]))
    return numbers


def verdict(numbers: dict) -> tuple[bool, list[str]]:
    """(correct, one line per number with its limit)."""
    ok, lines = True, []
    for name, limit in LIMITS.items():
        value = numbers[name]
        good = value >= limit if name.endswith("_min") else value <= limit
        ok = ok and good
        lines.append(
            "check: %s = %.6g (limit %s %.6g) %s"
            % (name, value, ">=" if name.endswith("_min") else "<=", limit, "ok" if good else "FAIL")
        )
    return ok, lines
