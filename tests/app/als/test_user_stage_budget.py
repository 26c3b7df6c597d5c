"""The rule that decides whether the user matrix is staged on the device
(`serving_model.user_stage_budget`), on stubbed memory statistics, and
the model's two outcomes under it."""

import numpy as np
import pytest

from oryx_tpu.app.als import serving_model as sm
from oryx_tpu.common import metrics

GB = 10**9


class _Device:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


def _chip(limit, in_use):
    return _Device({"bytes_limit": limit, "bytes_in_use": in_use, "peak_bytes_in_use": in_use})


def test_the_budget_is_the_limit_less_what_is_in_use_less_the_reserve():
    # a v5e chip holding the 1M x 250 item matrix: 5M users x 250 (6.25 GB padded) fit
    budget = sm.user_stage_budget([_chip(16 * GB, 1 * GB)])
    assert budget == 15 * GB - sm.USER_STAGE_RESERVE_BYTES
    assert 6_250_000 * 250 * 4 <= budget
    # the same users beside a 12 GB item matrix do not
    assert sm.user_stage_budget([_chip(16 * GB, 12 * GB)]) < 6_250_000 * 250 * 4
    # 2.5 GB of users, which the 2 GiB constant refused whatever the chip held
    assert 2_000_000 * 250 * 4 * 1.25 > 2 << 30
    assert 2_000_000 * 250 * 4 * 1.25 <= sm.user_stage_budget([_chip(16 * GB, 5 * GB)])


def test_a_full_device_has_a_budget_of_zero_not_a_negative_one():
    assert sm.user_stage_budget([_chip(16 * GB, 16 * GB - 1000)]) == 0


def test_over_a_mesh_the_fullest_device_decides():
    chips = [_chip(16 * GB, 5 * GB), _chip(16 * GB, 9 * GB), _chip(16 * GB, 5 * GB)]
    assert sm.user_stage_budget(chips) == 7 * GB - sm.USER_STAGE_RESERVE_BYTES


@pytest.mark.parametrize("stats", [None, {}, {"bytes_in_use": 5}, {"bytes_limit": 5}])
def test_a_backend_that_reports_nothing_keeps_the_former_bound(stats):
    """The CPU of tests and development: its "device" is the host's own
    memory, a staged copy doubles what the store holds, and there is no
    limit to read, so the bound the rule replaced (2 GiB) stands."""
    assert sm.user_stage_budget([_Device(stats)]) == 2 << 30
    assert sm.user_stage_budget([_chip(16 * GB, GB), _Device(stats)]) == 2 << 30
    assert sm.user_stage_budget([]) == 2 << 30


def test_the_real_backend_here_gives_the_default():
    import jax

    assert sm.user_stage_budget(jax.local_devices()[:1]) == sm.USER_STAGE_DEFAULT_BUDGET_BYTES


def _model(users=40, features=4):
    gen = np.random.default_rng(5)
    m = sm.ALSServingModel(features, True, refresh_sec=0.0)
    m.set_item_vectors([f"i{i}" for i in range(30)],
                       gen.standard_normal((30, features)).astype(np.float32))
    m.set_user_vectors([f"u{i}" for i in range(users)],
                       gen.standard_normal((users, features)).astype(np.float32))
    return m


def _value(name):
    return metrics.registry.snapshot()[name]["value"]


def test_a_model_that_fits_is_staged_in_chunks_and_says_so(monkeypatch):
    from oryx_tpu.ops import topn as topn_ops

    monkeypatch.setattr(topn_ops, "QUERY_CHUNK_BYTES", 7 * 4 * 4)  # 7 rows a chunk: 6 chunks
    monkeypatch.setattr(sm, "user_stage_budget", lambda devices: 64 * 4 * 4)
    m = _model()
    stagings = metrics.registry.histogram("serving.users.stage.seconds").count
    unstaged = _value("serving.users.unstaged-requests")
    assert m.top_n_for_user("u3", 5)  # trips the restage, served by vector meanwhile
    m._x_restage_thread.join(30)
    assert _value("serving.users.unstaged-requests") == unstaged + 1
    assert m._x_matrix.shape == (64, 4) and m._x_capacity == 64 and m._x_staging
    ids, mat = m.x.to_matrix()
    assert m._x_ids == ids and m._x_index == {u: i for i, u in enumerate(ids)}
    np.testing.assert_array_equal(np.asarray(m._x_matrix)[:40], mat)
    assert not np.asarray(m._x_matrix)[40:].any()
    assert _value("serving.users.stage.refused") == 0
    assert _value("serving.users.staged-rows") == 40
    assert _value("serving.users.staged-bytes") == 64 * 4 * 4
    assert _value("serving.users.stage-budget-bytes") == 64 * 4 * 4  # exactly fits
    assert metrics.registry.histogram("serving.users.stage.seconds").count == stagings + 1
    assert m.top_n_for_user("u3", 5)  # by row now
    assert m.top_n_for_user("nobody", 5) is None  # unknown: not a known user's fall
    assert _value("serving.users.unstaged-requests") == unstaged + 1


def test_a_model_one_byte_past_the_budget_is_refused_whole(monkeypatch, caplog):
    monkeypatch.setattr(sm, "user_stage_budget", lambda devices: 64 * 4 * 4 - 1)
    m = _model()
    unstaged = _value("serving.users.unstaged-requests")
    with caplog.at_level("WARNING", logger=sm.__name__):
        first = m.top_n_for_user("u3", 5)
        m._x_restage_thread.join(30)
    assert m._x_matrix is None and not m._x_staging and m._x_stage_refused
    assert not m._x_dirty and not m._x_dirty_ids and not m._x_building
    assert _value("serving.users.stage.refused") == 1
    assert _value("serving.users.staged-rows") == 0 and _value("serving.users.staged-bytes") == 0
    assert _value("serving.users.stage-budget-bytes") == 64 * 4 * 4 - 1
    said = "\n".join(r.getMessage() for r in caplog.records)
    assert "40 users x 4 features ask 1024 bytes" in said and "the budget is 1023" in said
    # still served, by the vector path, and counted as such; writes keep no dirty set
    assert m.top_n_for_user("u3", 5) == first
    assert _value("serving.users.unstaged-requests") == unstaged + 2
    m.set_user_vector("u3", np.ones(4, np.float32))
    assert not m._x_dirty_ids


def test_after_a_rotation_the_old_matrix_is_let_go_before_the_budget_is_read(monkeypatch):
    """A new generation restages every user. The old matrix serves no row
    from the rotation on, so it must not stand in the budget's way: at 5M
    users it is 6 GB of a 16 GB chip."""
    m = _model()
    assert m.top_n_for_user("u3", 5)
    m._x_restage_thread.join(30)
    old = m._x_matrix
    assert old is not None
    held_at_budget = []

    def budget(devices):
        held_at_budget.append(m._x_matrix)
        return sm.USER_STAGE_DEFAULT_BUDGET_BYTES

    monkeypatch.setattr(sm, "user_stage_budget", budget)
    keep = {f"u{i}" for i in range(30)}
    m.retain_recent_and_user_ids(keep)
    m.retain_recent_and_user_ids(keep)  # two rounds: the first keeps recent writes
    assert m.top_n_for_user("u3", 5)  # by vector, and trips the restage
    m._x_restage_thread.join(30)
    assert held_at_budget == [None]
    assert m._x_matrix is not None and m._x_matrix is not old and len(m._x_ids) == 30
    assert m.top_n_for_user("u35", 5) is None  # rotated out


@pytest.mark.parametrize(
    "room, reads", [(2 << 30, [False]), (40 * 4 * 4 * 2, [False, True])],
    ids=["roomy: beside the item upload", "tight: the item matrix first"],
)
def test_the_item_matrix_is_counted_before_a_tight_budget_decides(monkeypatch, room, reads):
    """The request that trips the first staging goes on to upload the item
    matrix itself. Users that fit even with the whole item matrix still to
    come are staged beside that upload; where they do not clearly fit, the
    thread makes the item matrix first and reads its budget again."""
    m = _model()  # 30 items x 4 features: 528 bytes still to come, 1024 asked
    seen = []
    monkeypatch.setattr(
        sm, "user_stage_budget", lambda devices: seen.append(m._y_matrix is not None) or room
    )
    assert m._y_matrix is None
    m._x_building = True
    m._rebuild_x_staging(set(m._x_dirty_ids), m._x_epoch)  # the thread's body, alone
    assert seen == reads and m._x_matrix is not None and not m._x_building
