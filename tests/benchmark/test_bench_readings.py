"""The benchmark's own arithmetic: the tail reading, the seeded schedules, the roofline counts and the plain reference (tier-1,
no JAX)."""

import numpy as np
import pytest

from benchmark import roofline, stats
from benchmark.drivers import closed_http, httpclient, open_http
from benchmark.reference import als_topn


def _latencies(seed=0, n=6000, seconds=30.0):
    rng = np.random.default_rng(seed)
    due = np.sort(rng.random(n)) * seconds
    # a lattice of pass times, as the serving scan gives: 3 to 5 passes of 15 ms
    lat = 15.0 * rng.choice([3, 4, 5], size=n, p=[0.5, 0.4, 0.1]) + rng.random(n)
    return due, lat


def test_percentile_is_a_latency_some_request_had():
    vals = [5.0, 1.0, 3.0, 2.0, 4.0]
    assert stats.percentile(vals, 0.5) == 3.0
    assert stats.percentile(vals, 0.95) == 5.0
    with pytest.raises(ValueError):
        stats.percentile([], 0.5)


def test_the_judged_tail_is_the_tail_of_all_requests_and_shows_a_stall():
    """Why the judged tail is the whole window's: a stall that covers a
    tenth of the window moves it (a median of per-slice tails would not:
    PR 23 tried that reading and took it out)."""
    due, lat = _latencies()
    stalled = lat.copy()
    stalled[(due >= 12.0) & (due < 15.0)] += 400.0
    assert stats.percentile(stalled, 0.95) > stats.percentile(lat, 0.95) + 100.0
    assert stats.percentile(lat + 7.0, 0.95) == pytest.approx(stats.percentile(lat, 0.95) + 7.0)


def _open_params(seed):
    return {"seed": seed, "seconds": 4.0, "warm_seconds": 1.0, "rate_per_s": 250.0,
            "n_users": 1_000_000, "exponent": 1.1, "sample_every": 100, "sample_max": 128}


@pytest.mark.parametrize("seed", [1, 2**31 + 12345])
def test_open_schedule_is_the_seeds_and_offers_the_same_work(seed):
    due, users, n_warm, n_win, sampled = open_http.schedule(_open_params(seed))
    due2, users2, _, _, sampled2 = open_http.schedule(_open_params(seed))
    assert np.array_equal(due, due2) and np.array_equal(users, users2) and sampled == sampled2
    assert n_warm == 250 and n_win == 1000 and len(due) == 1250  # every seed: rate x seconds
    assert np.all(np.diff(due) >= 0) and due[n_warm] >= 1.0 and due[-1] < 5.0
    assert users.min() >= 0 and users.max() < 1_000_000
    assert len(sampled) == 10 and all(n_warm <= i < len(due) for i in sampled)
    other = open_http.schedule(_open_params(seed + 1))
    assert len(other[0]) == len(due) and not np.array_equal(other[0], due)
    # a traced run offers the same load for a tail after the window, outside it
    traced = open_http.schedule(dict(_open_params(seed), tail_seconds=2.0))
    assert len(traced[0]) == 1750 and traced[3] == 1000
    assert traced[0][1250:].min() >= 5.0 and traced[0][1250:].max() < 7.0


def test_power_law_users_follow_the_law():
    rng = np.random.default_rng(3)
    rows = httpclient.power_law_users(rng, 1_000_000, 1.1, 200_000)
    head = np.mean(rows < 10)
    assert 0.25 < head < 0.45  # exponent 1.1 over 1M users: about a third on ten users
    assert rows.max() > 100_000


def test_closed_loop_counts_answers_completed_inside_the_window():
    result = {"window": [1.0, 3.0], "sent": [0.5, 1.1, 2.0, 2.9, 2.95],
              "done": [0.9, 1.2, 2.1, 3.1, 2.99], "ok": [True, True, False, True, True],
              "kinds": {}, "sampled": []}
    got = closed_http.reduce(result, {"clients": 4})
    assert got["attempted"] == 3 and got["failed"] == 1
    assert got["values"]["recommend_qps"] == pytest.approx(1.0)  # 2 good answers / 2 s


def _open_result(n, failed):
    due = np.linspace(1.0, 5.0, n, endpoint=False)
    ok = np.ones(n, dtype=bool)
    ok[np.linspace(0, n - 1, failed).astype(int)] = False
    return {"window": [1.0, 5.0], "due": due.tolist(), "sent": (due + 0.001).tolist(),
            # a shed answer comes back sooner than a served one
            "done": np.where(ok, due + 0.050, due + 0.002).tolist(), "ok": ok.tolist(),
            "warm_ok": 5, "warm_sent": 5, "kinds": {"shed-stale": failed}, "sampled": []}


def test_open_reduce_counts_a_shed_answer_as_failed_and_reads_latency_from_due():
    got = open_http.reduce(_open_result(1000, 1), {"timeout_s": 10})
    assert got["attempted"] == 1000 and got["failed"] == 1
    assert got["values"]["recommend_p95_ms"] == pytest.approx(50.0, abs=1e-6)
    assert got["values"]["recommend_p50_ms"] == pytest.approx(50.0, abs=1e-6)
    assert got["values"]["generator_late_p99_ms"] == pytest.approx(1.0, abs=1e-6)


@pytest.mark.parametrize("failed, p95_ms", [(9, 50.0), (60, 10_000.0)])
def test_a_failed_request_is_charged_the_timeout_so_shedding_cannot_shorten_the_tail(failed, p95_ms):
    """The tail is the tail of ALL requests: under 5 % failed the p95 is
    still a served request's, over 5 % it is the client's timeout."""
    got = open_http.reduce(_open_result(1000, failed), {"timeout_s": 10})
    assert got["failed"] == failed
    assert got["values"]["recommend_p95_ms"] == pytest.approx(p95_ms, abs=1e-6)
    assert got["values"]["recommend_p99_ms"] == pytest.approx(10_000.0 if failed > 10 else 50.0)


def test_pause_watch_sees_a_stall_of_its_process():
    import time

    clock = httpclient.Clock(time.time())
    calm = httpclient.PauseWatch(clock, 0.0, 0.1, period_s=0.005).reading()
    assert set(calm) == {"max_ms", "at_s", "over_20ms"} and calm["max_ms"] >= 0.0
    watch = httpclient.PauseWatch(httpclient.Clock(time.time()), 0.0, 0.4, period_s=0.005)
    time.sleep(0.02)
    # one long call that keeps the GIL, as a full collection would
    t0 = time.perf_counter()
    sum(range(8_000_000))
    held_ms = (time.perf_counter() - t0) * 1000.0
    got = watch.reading()
    assert held_ms > 40.0 and got["max_ms"] > held_ms / 2 and got["over_20ms"] >= 1
    assert 0.0 <= got["at_s"] < 0.4


def test_roofline_counts_bytes_and_operations_from_the_shapes():
    cfg = {"items": 20_000_000, "features": 50, "dtype": "float32"}
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    assert roofline.scan_bytes(20_000_000, 50, "float32", 4, 32) == pytest.approx(
        20e6 * 50 * 4 + 20e6 * 4 + 4 * 50 * 4 + 4 * 32 * 8
    )
    assert roofline.scan_flops(20_000_000, 50, 4) == 2 * 4 * 20e6 * 50
    least, bound = roofline.scan_least_seconds(cfg, 4, 32, peaks)
    assert bound == "bytes" and least == pytest.approx(4.08e9 / 819e9, rel=1e-3)
    # hundreds of rows a pass at 250 features would be bound by the MXU
    wide = {"items": 5_000_000, "features": 250, "dtype": "float32"}
    assert roofline.scan_least_seconds(wide, 1024, 32, peaks)[1] == "operations"


def _model(seed=0, users=40, items=5000, f=12):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((users, f)).astype(np.float32)
    y = rng.standard_normal((items, f)).astype(np.float32)
    known = rng.integers(0, items, size=(users, 10)).astype(np.int32)
    return x, y, known


def test_judge_agrees_with_the_plain_top_n():
    x, y, known = _model()
    rows, scores = zip(*(als_topn.top_n(x[u], y, known[u], 10) for u in range(len(x))))
    j = als_topn.judge(x, y, known, list(rows), list(scores), block=700, threads=3)
    assert max(j["score_err"]) < 1e-12 and max(j["left_out"]) == 0.0
    assert max(j["order"]) == 0.0 and sum(j["known"]) == 0


def test_judge_sees_a_left_out_item_a_wrong_order_a_known_item_and_a_score():
    x, y, known = _model(1)
    rows, scores = als_topn.top_n(x[0], y, known[0], 11)
    xs, kn = x[:1], known[:1]
    missing_best = als_topn.judge(xs, y, kn, [rows[1:]], [scores[1:]])
    assert missing_best["left_out"][0] > 1e-3
    swapped = rows[:10].copy()
    swapped[[2, 3]] = swapped[[3, 2]]
    sw_scores = scores[:10].copy()
    sw_scores[[2, 3]] = sw_scores[[3, 2]]
    assert als_topn.judge(xs, y, kn, [swapped], [sw_scores])["order"][0] > 0
    with_known = rows[:10].copy()
    with_known[9] = known[0][0]
    ref = float(y[known[0][0]].astype(np.float64) @ x[0].astype(np.float64))
    out = als_topn.judge(xs, y, kn, [with_known], [np.append(scores[:9], ref)])
    assert out["known"][0] == 1
    off = scores[:10] * (1 + 4e-3)  # what a bfloat16 score looks like
    assert als_topn.judge(xs, y, kn, [rows[:10]], [off])["score_err"][0] > 1e-3
