"""ALS shared-math tests (reference: ALSUtilsTest, FeatureVectorsTest)."""

import math

import numpy as np
import pytest

from oryx_tpu.app.als import data as als_data
from oryx_tpu.app.als.common import (
    FeatureVectors,
    compute_target_qui,
    compute_updated_xu,
    compute_updated_xu_basket,
)
from oryx_tpu.common.vectormath import Solver


def test_compute_target_qui_explicit_is_value():
    assert compute_target_qui(False, 3.5, 0.2) == 3.5


def test_compute_target_qui_implicit_moves_toward_one():
    t = compute_target_qui(True, 1.0, 0.0)
    assert t == pytest.approx(0.5)  # 0 + (1/2) * 1
    t2 = compute_target_qui(True, 1.0, t)
    assert t < t2 < 1.0
    # already >= 1: no change
    assert math.isnan(compute_target_qui(True, 1.0, 1.0))


def test_compute_target_qui_implicit_negative_moves_toward_zero():
    t = compute_target_qui(True, -1.0, 1.0)
    assert t == pytest.approx(0.5)  # 1 + (-1/-2) * -1
    assert math.isnan(compute_target_qui(True, -1.0, 0.0))


def test_compute_updated_xu_hand_computed():
    # Y^T Y for Y = identity-ish gives simple solver
    yty = np.array([[2.0, 0.0], [0.0, 2.0]])
    solver = Solver(yty)
    yi = np.array([1.0, 0.0], dtype=np.float32)
    # new user, implicit, value=1: target = 0.5 + (1/2)*0.5 = 0.75; dQui=0.75
    xu = compute_updated_xu(solver, 1.0, None, yi, True)
    np.testing.assert_allclose(xu, [0.375, 0.0], atol=1e-6)  # (yty)^-1 * 0.75*yi
    # explicit existing user: target = value
    xu2 = compute_updated_xu(solver, 2.0, np.array([1.0, 1.0], dtype=np.float32), yi, False)
    # Qui = 1.0, dQui = 1.0, dXu = [0.5, 0]
    np.testing.assert_allclose(xu2, [1.5, 1.0], atol=1e-6)


def test_compute_updated_xu_no_item_vector():
    solver = Solver(np.eye(2))
    assert compute_updated_xu(solver, 1.0, None, None, True) is None


def test_feature_vectors_rotation_keeps_recent():
    fv = FeatureVectors()
    fv.set_vector("a", [1, 2])
    fv.set_vector("b", [3, 4])
    # rotation: new model has only "b"; "a" was not recently written after
    fv.retain_recent_and_ids({"b"})
    # both survive: a and b were both recent since last rotation
    assert set(fv.ids()) == {"a", "b"}
    # next rotation without new writes: only model ids survive
    fv.retain_recent_and_ids({"b"})
    assert set(fv.ids()) == {"b"}
    # recent write survives rotation that drops it from the model
    fv.set_vector("c", [5, 6])
    fv.retain_recent_and_ids({"b"})
    assert set(fv.ids()) == {"b", "c"}


def test_feature_vectors_vtv():
    fv = FeatureVectors()
    fv.set_vector("a", [1.0, 2.0])
    fv.set_vector("b", [3.0, 4.0])
    np.testing.assert_allclose(fv.get_vtv(), [[10.0, 14.0], [14.0, 20.0]])
    ids, mat = fv.to_matrix()
    assert set(ids) == {"a", "b"}
    assert mat.shape == (2, 2)


def test_parse_and_aggregate_implicit_sum_and_delete():
    lines = [
        "u1,i1,1.0,100",
        "u1,i1,2.5,200",
        "u2,i1,1.0,100",
        "u2,i1,,300",  # delete marker
        '["u3","i2",4.0,50]',
    ]
    inter = als_data.parse_interactions(lines)
    agg = als_data.aggregate(inter, implicit=True)
    assert agg == {("u1", "i1"): pytest.approx(3.5), ("u3", "i2"): pytest.approx(4.0)}


def test_aggregate_explicit_last_wins():
    lines = ["u1,i1,5.0,100", "u1,i1,2.0,300", "u1,i1,3.0,200"]
    agg = als_data.aggregate(als_data.parse_interactions(lines), implicit=False)
    assert agg == {("u1", "i1"): pytest.approx(2.0)}  # ts=300 last


def test_decay():
    day_ms = 86_400_000
    inter = als_data.parse_interactions([f"u,i,8.0,0"])
    out = als_data.decay_interactions(inter, factor=0.5, zero_threshold=0.0, now_ms=3 * day_ms)
    assert out[0].value == pytest.approx(1.0)  # 8 * 0.5^3
    out2 = als_data.decay_interactions(inter, factor=0.5, zero_threshold=1.5, now_ms=3 * day_ms)
    assert out2 == []


def test_to_rating_matrix_and_known_items():
    agg = {("u1", "i1"): 1.0, ("u1", "i2"): 2.0, ("u2", "i1"): 3.0}
    rm = als_data.to_rating_matrix(agg)
    assert rm.user_ids == ["u1", "u2"]
    assert rm.item_ids == ["i1", "i2"]
    assert rm.known_items == {"u1": {"i1", "i2"}, "u2": {"i1"}}


@pytest.mark.parametrize("implicit", [True, False])
@pytest.mark.parametrize("known_user", [False, True])
def test_the_basket_form_is_the_recurrence_item_by_item(implicit, known_user):
    """One pass of matrix products for a whole basket against
    `compute_updated_xu` applied to each item in turn: strengths other than
    1, a negative one, one that asks no change (implicit 0), and a start
    from a known user's vector or from none."""
    rng = np.random.default_rng(7)
    y = rng.standard_normal((4000, 24)).astype(np.float32)
    solver = Solver(y.astype(np.float64).T @ y.astype(np.float64))
    x0 = (1e-3 * rng.standard_normal(24)).astype(np.float32) if known_user else None
    rows, values = [3, 77, 1500, 9, 2000, 77], [1.0, 2.5, -0.5, 0.0, 1.0, 3.0]
    xu = x0
    for row, value in zip(rows, values):
        updated = compute_updated_xu(solver, value, xu, y[row], implicit)
        xu = xu if updated is None else updated
    got = compute_updated_xu_basket(solver, values, x0, y[rows], implicit)
    assert got.dtype == np.float32
    np.testing.assert_allclose(got, xu, rtol=0, atol=5e-7 * np.abs(xu).max())
    for k in range(1, len(rows)):  # and every prefix of it
        want = x0
        for row, value in zip(rows[:k], values[:k]):
            updated = compute_updated_xu(solver, value, want, y[row], implicit)
            want = want if updated is None else updated
        got = compute_updated_xu_basket(solver, values[:k], x0, y[rows[:k]], implicit)
        np.testing.assert_allclose(got, want, rtol=0, atol=5e-7 * np.abs(want).max())


def test_a_basket_that_asks_no_change_gives_none():
    solver = Solver(np.array([[2.0, 0.0], [0.0, 2.0]]))
    ys = np.array([[1.0, 0.0], [0.0, 1.0]], dtype=np.float32)
    assert compute_updated_xu_basket(solver, [0.0, 0.0], None, ys, True) is None
    assert compute_updated_xu_basket(solver, [], None, ys[:0], True) is None
    # a first item that asks nothing leaves the new user's prior to the next
    got = compute_updated_xu_basket(solver, [0.0, 1.0], None, ys, True)
    np.testing.assert_allclose(got, compute_updated_xu(solver, 1.0, None, ys[1], True))


@pytest.mark.parametrize("implicit", [True, False])
def test_both_stores_fold_a_basket_in_alike(implicit):
    """`fold_in` of the store that serves (the native one where the library
    is built: look-ups and recurrence in one call) against the Python
    store's (`compute_updated_xu_basket`), an unknown id among the ids, from
    a known user's vector and from none; and against the recurrence item by
    item."""
    from oryx_tpu.native.store import make_feature_vectors

    rng = np.random.default_rng(11)
    y = rng.standard_normal((3000, 50)).astype(np.float32)
    ids = [f"i{i}" for i in range(len(y))]
    serving, plain = make_feature_vectors(), FeatureVectors()
    serving.set_batch(ids, y)
    plain.set_batch(ids, y)
    solver = Solver(y.astype(np.float64).T @ y.astype(np.float64))
    asked = ["i3", "nope", "i77", "i1500", "i9", "i2000", "i77"]
    values = [1.0, 9.0, 2.5, -0.5, 0.0, 1.0, 3.0]
    for x0 in (None, (1e-3 * rng.standard_normal(50)).astype(np.float32)):
        got = serving.fold_in(asked, values, solver, x0, implicit)
        want = plain.fold_in(asked, values, solver, x0, implicit)
        assert got.dtype == want.dtype == np.float32 and got.shape == (50,)
        np.testing.assert_allclose(got, want, rtol=0, atol=2e-7 * np.abs(want).max())
        xu = x0
        for item, value in zip(asked, values):
            updated = compute_updated_xu(solver, value, xu, plain.get_vector(item), implicit)
            xu = xu if updated is None else updated
        np.testing.assert_allclose(got, xu, rtol=0, atol=5e-7 * np.abs(xu).max())
    for store in (serving, plain):
        assert store.fold_in(["nope"], [1.0], solver, None, implicit) is None
        assert store.fold_in([], [], solver, None, implicit) is None
    if implicit:  # a strength of 0 asks for no change
        assert serving.fold_in(["i3"], [0.0], solver, None, True) is None
    assert make_feature_vectors().fold_in(["i3"], [1.0], solver, None, implicit) is None  # empty
