"""ALS batch trainer tests (reference: ALSUpdateIT, ALSModelContentIT)."""

import json

import numpy as np
import pytest

from oryx_tpu import bus
from oryx_tpu.app import pmml as app_pmml
from oryx_tpu.app.als.update import ALSUpdate, _load_features
from oryx_tpu.bus.core import KeyMessage
from oryx_tpu.common import config as C


def make_config(implicit=True, candidates=1, features=5, test_fraction=0.0):
    return C.get_default().with_overlay(
        f"""
        oryx {{
          ml.eval {{ candidates = {candidates}, test-fraction = {test_fraction} }}
          als {{
            implicit = {str(implicit).lower()}
            iterations = 8
            hyperparams {{ features = {features}, lambda = 0.01, alpha = 2.0 }}
          }}
        }}
        """
    )


def synthetic_data(num_users=30, num_items=20, per_user=6, seed=5):
    gen = np.random.default_rng(seed)
    group_u = gen.integers(0, 2, num_users)
    group_i = gen.integers(0, 2, num_items)
    recs = []
    ts = 0
    for u in range(num_users):
        liked = np.nonzero(group_i == group_u[u])[0]
        for i in gen.choice(liked, size=min(per_user, len(liked)), replace=False):
            ts += 1
            recs.append(KeyMessage(None, f"U{u},I{i},1.0,{ts}"))
    return recs, group_u, group_i


def test_build_model_and_artifacts(tmp_path):
    data, _, _ = synthetic_data()
    update = ALSUpdate(make_config())
    pmml = update.build_model(data, [5, 0.01, 2.0], tmp_path)
    # artifacts
    ids_x, x = _load_features(tmp_path / "X")
    ids_y, y = _load_features(tmp_path / "Y")
    assert x.shape[1] == 5 and y.shape[1] == 5
    assert all(i.startswith("U") for i in ids_x)
    assert all(i.startswith("I") for i in ids_y)
    # pmml extensions
    assert app_pmml.get_extension_value(pmml, "features") == "5"
    assert app_pmml.get_extension_value(pmml, "implicit") == "true"
    assert set(app_pmml.get_extension_content(pmml, "XIDs")) == set(ids_x)
    assert set(app_pmml.get_extension_content(pmml, "YIDs")) == set(ids_y)


def test_full_run_update_publishes_model_and_factors(tmp_path):
    data, _, _ = synthetic_data()
    update = ALSUpdate(make_config(test_fraction=0.2))
    broker = bus.get_broker("inproc://als-batch")
    broker.create_topic("OryxUpdate", 1)
    tail = broker.consumer("OryxUpdate", from_beginning=True)
    with broker.producer("OryxUpdate") as producer:
        update.run_update(1000, data, [], str(tmp_path / "model"), producer)
    from oryx_tpu.common import tracing

    # skip the `@trc` trace/freshness control record (stripped by block
    # consumers; a raw poll sees it)
    msgs = [
        m
        for m in tail.poll(max_records=10_000, timeout=2.0)
        if m.key != tracing.TRACE_KEY
    ]
    assert msgs[0].key == "MODEL"
    ups = [m for m in msgs if m.key == "UP"]
    # Y rows come before X rows (ALSUpdate.java:194-230 ordering)
    kinds = [json.loads(m.message)[0] for m in ups]
    assert "X" in kinds and "Y" in kinds
    assert kinds.index("X") > kinds.index("Y")
    first_y = kinds.index("Y")
    assert all(k == "Y" for k in kinds[: kinds.index("X")])
    # X rows carry known items
    x_up = json.loads(next(m.message for m in ups if json.loads(m.message)[0] == "X"))
    assert len(x_up) == 4 and isinstance(x_up[3], list) and x_up[3]
    # model promoted
    assert (tmp_path / "model" / "1000" / "model.pmml").exists()


def test_implicit_eval_auc_above_chance(tmp_path):
    data, _, _ = synthetic_data(per_user=8)
    update = ALSUpdate(make_config())
    pmml = update.build_model(data, [5, 0.01, 2.0], tmp_path)
    score = update.evaluate(pmml, tmp_path, data[:40], data)
    assert 0.5 < score <= 1.0


def test_explicit_eval_negative_rmse(tmp_path):
    gen = np.random.default_rng(1)
    data = [
        KeyMessage(None, f"U{u},I{i},{(u % 3) + 1}.0,{u * 100 + i}")
        for u in range(20)
        for i in gen.choice(15, 5, replace=False)
    ]
    update = ALSUpdate(make_config(implicit=False))
    pmml = update.build_model(data, [4, 0.05, 1.0], tmp_path)
    score = update.evaluate(pmml, tmp_path, data[:30], data)
    assert score <= 0.0  # negated RMSE
    assert score > -1.0  # trained model fits decently


def test_time_ordered_split():
    update = ALSUpdate(make_config(test_fraction=0.25))
    update.test_fraction = 0.25
    data = [KeyMessage(None, f"u,i,1.0,{ts}") for ts in [30, 10, 40, 20]]
    train, test = update.split_new_data_to_train_test(data)
    assert [r.message for r in train] == ["u,i,1.0,10", "u,i,1.0,20", "u,i,1.0,30"]
    assert [r.message for r in test] == ["u,i,1.0,40"]


def test_published_known_items_survive_the_serving_columnar_parse(tmp_path):
    """The batch layer's X rows and the serving layer's columnar UP parse
    must agree on the wire form of the known-items list. They did not:
    the rows were written '["a", "b"]' and the byte-level parse splits on
    '","', so every known list of two or more ids past the first consumed
    block became ONE garbage id and known items stopped being excluded.
    The first block went through the per-record JSON path, which is why
    small tests never saw it: here the rows are applied as a later block."""
    from oryx_tpu.app.als.serving_model import ALSServingModelManager

    data, _, _ = synthetic_data()
    update = ALSUpdate(make_config())
    broker = bus.get_broker("inproc://als-batch-known")
    broker.create_topic("OryxUpdate", 1)
    tail = broker.consumer("OryxUpdate", from_beginning=True)
    with broker.producer("OryxUpdate") as producer:
        update.run_update(1000, data, [], str(tmp_path / "model"), producer)
    msgs = tail.poll(max_records=10_000, timeout=2.0)
    expected: dict[str, set] = {}
    for rec in data:
        user, item = rec.message.split(",")[:2]
        expected.setdefault(user, set()).add(item)
    assert max(len(v) for v in expected.values()) >= 2

    manager = ALSServingModelManager(C.get_default())
    manager.consume(iter(m for m in msgs if m.key == "MODEL"))
    ups = [m.message.encode("utf-8") for m in msgs if m.key == "UP"]
    manager._apply_up_batch(ups)  # the columnar path of every later block
    for user, items in expected.items():
        assert manager.model.get_known_items(user) == items, user
    # a spaced list from another producer still parses (json path)
    manager._apply_up_batch([b'["X","U0",[0.0,0.0,0.0,0.0,0.0],["I1", "I2"]]'])
    assert {"I1", "I2"} <= manager.model.get_known_items("U0")
