"""Chunked staging of the query (user) matrix: a device buffer of the final
capacity filled chunk by chunk equals the one-shot upload of the padded
matrix bit for bit, whatever the chunk size and the headroom."""

import jax.numpy as jnp
import numpy as np
import pytest

from oryx_tpu.ops import topn as topn_ops

ROWS, FEATURES = 1003, 24


@pytest.fixture(scope="module")
def rows():
    return np.random.default_rng(31).standard_normal((ROWS, FEATURES)).astype(np.float32)


def _one_shot(rows, capacity):
    pad = np.zeros((capacity - len(rows), rows.shape[1]), np.float32)
    return np.asarray(jnp.asarray(np.concatenate([rows, pad])))


@pytest.mark.parametrize("capacity", [ROWS, int(ROWS * 1.25)], ids=["no-headroom", "headroom"])
@pytest.mark.parametrize("step", [17, 59, 1003, 5000], ids=lambda s: f"chunk{s}")
def test_chunked_staging_equals_one_shot_staging(rows, step, capacity):
    # 17 x 59 = 1003: both divide the row count's factors, 5000 is one short chunk
    chunks = [rows[lo : lo + step] for lo in range(0, ROWS, step)]
    staged = topn_ops.stage_queries(iter(chunks), capacity, FEATURES)
    assert staged.shape == (capacity, FEATURES) and staged.dtype == jnp.float32
    assert np.asarray(staged).tobytes() == _one_shot(rows, capacity).tobytes()


@pytest.mark.parametrize("step", [64, 100, 333])
def test_a_chunk_size_that_does_not_divide_the_rows_leaves_a_short_last_chunk(rows, step):
    assert ROWS % step
    staged = topn_ops.stage_queries(
        (rows[lo : lo + step] for lo in range(0, ROWS, step)), ROWS + 7, FEATURES
    )
    assert np.asarray(staged).tobytes() == _one_shot(rows, ROWS + 7).tobytes()


def test_upload_queries_goes_through_the_same_chunks(rows, monkeypatch):
    monkeypatch.setattr(topn_ops, "QUERY_CHUNK_BYTES", 100 * FEATURES * 4)
    assert topn_ops.query_chunk_rows(FEATURES) == 100
    writes = []
    sound = topn_ops._write_query_rows

    def noted(buf, chunk, start):
        writes.append((int(start), len(chunk)))
        return sound(buf, chunk, start)

    monkeypatch.setattr(topn_ops, "_write_query_rows", noted)
    whole = topn_ops.upload_queries(rows)
    assert np.asarray(whole).tobytes() == rows.tobytes()
    assert writes == [(lo, min(100, ROWS - lo)) for lo in range(0, ROWS, 100)]


def test_rows_past_the_capacity_are_an_error_not_a_silent_clamp(rows):
    with pytest.raises(ValueError, match="exceed the capacity"):
        topn_ops.stage_queries([rows[:600], rows[600:]], 1000, FEATURES)


def test_staged_on_a_mesh_every_device_holds_the_whole_matrix(rows):
    import jax

    from oryx_tpu.parallel.mesh import get_mesh

    mesh = get_mesh()
    staged = topn_ops.stage_queries(
        (rows[lo : lo + 250] for lo in range(0, ROWS, 250)), 1254, FEATURES, mesh=mesh
    )
    assert len(staged.sharding.device_set) == jax.device_count()
    want = _one_shot(rows, 1254).tobytes()
    for shard in staged.addressable_shards:
        assert np.asarray(shard.data).tobytes() == want
