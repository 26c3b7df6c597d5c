"""`ops/gram.py`: the Gram matrix of an uploaded item matrix against
float64 NumPy, for every handle kind that holds the item rows as they are
(the plain pair, the streaming layout with and without a tail plane, in
float32 and bfloat16, the sharded layout), at sizes that are not a
multiple of the block, with padding columns that hold garbage, to 1e-6 of
the largest entry. (The program at the benchmark's size is compiled for a
described v5e in tests/ops/test_scan_compile_v5e.py.)"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oryx_tpu.ops import gram as gram_ops
from oryx_tpu.ops import topn as topn_ops
from oryx_tpu.ops.pallas_topn import BLOCK_N, upload_streaming

LIMIT = 1e-6  # of the largest entry: what the fold-in's scores can bear (benchmark/check.py)


def _items(n, f, seed=0):
    return np.random.default_rng(seed).standard_normal((n, f)).astype(np.float32)


def _float64(y):
    y = y.astype(np.float64)
    return y.T @ y


def _err(got, want):
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


# (items, features): one block and a part, blocks and a part, fewer rows than
# a block; a tail plane of 2 rows (50, 250), of 4 rows holding 3 (51), none (48)
SIZES = [(BLOCK_N + 1234, 50), (2 * BLOCK_N + 77, 250), (3000, 51), (BLOCK_N, 48), (5, 16)]


@pytest.mark.parametrize("n, f", SIZES)
def test_streaming_handle_against_float64(n, f):
    y = _items(n, f, seed=n)
    up = upload_streaming(y)
    assert gram_ops.supported(up)
    assert (up.tail is not None) == (f % 8 != 0)
    got = gram_ops.gram(up)
    assert got.shape == (f, f) and got.dtype == np.float64
    assert _err(got, _float64(y)) < LIMIT
    np.testing.assert_array_equal(got, got.T)
    stats = gram_ops.pass_stats(up)
    assert stats["rows"] == n and stats["blocks"] == up.mat_t.shape[1] // gram_ops.GRAM_BLOCK
    assert stats["bytes"] == up.mat_t.nbytes + (up.tail.nbytes if up.tail is not None else 0)


@pytest.mark.parametrize("n, f", SIZES)
def test_plain_pair_against_float64(n, f):
    y = _items(n, f, seed=n + 1)
    up = topn_ops.upload(y, streaming=False)
    assert isinstance(up, tuple) and gram_ops.supported(up)
    got = gram_ops.gram(up)
    assert got.shape == (f, f) and _err(got, _float64(y)) < LIMIT
    assert gram_ops.pass_stats(up)["rows"] == n


def test_padding_columns_contribute_nothing_whatever_they_hold():
    """The columns past `n_items` are zeros as uploaded; the pass masks them
    all the same: a handle whose padding holds garbage reads the same."""
    y = _items(BLOCK_N + 100, 50, seed=3)
    up = upload_streaming(y)
    clean = gram_ops.gram(up)
    n_pad = up.mat_t.shape[1]
    dirty = dataclasses.replace(
        up,
        mat_t=up.mat_t.at[:, up.n_items :].set(7.0),
        tail=up.tail.at[:, up.n_items :].set(-3.0),
    )
    assert n_pad > up.n_items
    np.testing.assert_array_equal(gram_ops.gram(dirty), clean)


def test_rows_appended_into_the_padding_are_counted():
    """`update_rows` grows `n_items` into the padded capacity: the Gram
    matrix follows the handle's count, and a rewritten row its new value."""
    y = _items(3000, 50, seed=4)
    up = upload_streaming(y)
    more = _items(3, 50, seed=5)
    grown = topn_ops.update_rows(up, np.asarray([3000, 3001, 7]), more, n_items=3002)
    want = np.vstack([y, more[:2]])
    want[7] = more[2]
    assert _err(gram_ops.gram(grown), _float64(want)) < LIMIT


def test_bfloat16_handle_is_the_gram_matrix_of_what_it_holds():
    y = _items(BLOCK_N + 10, 64, seed=6)
    up = upload_streaming(y, dtype=jnp.bfloat16)
    assert up.mat_t.dtype == jnp.bfloat16 and gram_ops.supported(up)
    held = np.asarray(up.mat_t.astype(jnp.float32)).T[: up.n_items]
    # (the CPU backend sums a dot of converted bfloat16 operands less evenly than a
    # float32 one: 1.1e-6 over one block, where the float32 handles read 3e-7 at most)
    assert _err(gram_ops.gram(up), _float64(held)) < 10 * LIMIT
    # and within the format's rounding of the float32 rows' own
    assert _err(gram_ops.gram(up), _float64(y)) < 1e-3


def test_quantized_and_ivf_handles_are_not_supported():
    y = _items(2000, 32, seed=7)
    assert not gram_ops.supported(upload_streaming(y, dtype=jnp.int8))
    assert not gram_ops.supported(object())


def test_sharded_handle_against_float64():
    """Every shard's own columns under `shard_map`, each masked by its own
    count (here on the CPU's virtual devices; on four chips: tools/chip_kernels.py
    `--only gram`)."""
    from oryx_tpu.parallel.mesh import get_mesh

    if len(jax.devices()) < 2:
        pytest.skip("one device: nothing to shard over")
    mesh = get_mesh()
    d = len(mesh.devices.flat)
    n = 3 * d * 500 + 3  # uneven shards: the first `n % d` hold one more
    for f in (50, 48):
        y = _items(n, f, seed=8 + f)
        up = topn_ops.upload_sharded(y, mesh)
        assert gram_ops.supported(up) and len(set(up.counts)) == 2
        got = gram_ops.gram(up)
        assert got.shape == (f, f) and _err(got, _float64(y)) < LIMIT
        assert gram_ops.pass_stats(up)["rows"] == n


def test_one_float32_running_sum_would_not_do():
    """Why the partials are summed in float64: the same per-block partials
    added up in float32 drift past the limit at a few hundred blocks, where
    the float64 sum stays three orders under it."""
    y = np.abs(_items(64 * 1024, 8, seed=9)) + 1.0  # every product positive: the worst case
    blocks = y.reshape(512, 128, 8)
    partials = np.einsum("bnk,bnj->bkj", blocks, blocks).astype(np.float32)
    want = _float64(y)
    running = np.zeros((8, 8), np.float32)
    for p in partials:
        running += p
    assert _err(running.astype(np.float64), want) > 10 * _err(partials.sum(0, dtype=np.float64), want)
    assert _err(partials.sum(0, dtype=np.float64), want) < LIMIT


@pytest.mark.parametrize("features", [250, 50])
def test_the_chip_tool_checks_the_gram_pass_rehearsed_tiny(features):
    """tools/chip_kernels.py `--only gram`: the checks a chip call runs (one
    chip: the streaming handles; four: the sharded one across them), here
    at the rehearsal's size on the CPU's devices."""
    import importlib.util
    from pathlib import Path
    from types import SimpleNamespace

    path = Path(__file__).resolve().parents[2] / "tools" / "chip_kernels.py"
    spec = importlib.util.spec_from_file_location("chip_kernels_tool", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    checks = tool.Checks(
        SimpleNamespace(tiny=True, interpret=True, seed=features, only="gram", check_timeout=240.0)
    )
    checks.gram_checks(features)
    names = [f"gram/{features}f/float32", f"gram/{features}f/float32/sharded"]
    if features == 250:
        names.insert(1, "gram/250f/bfloat16")
    assert [r["name"] for r in checks.rows] == names
    assert all(r["ok"] for r in checks.rows), checks.rows
    for r in checks.rows:
        assert r["rows"] == 21_234 and r["err_of_largest"] < tool.GRAM_TOL["bfloat16"]
    assert checks.rows[-1]["devices"] == len(jax.devices())
    # a run that asks for other checks draws no matrix and runs none of these
    checks = tool.Checks(
        SimpleNamespace(tiny=True, interpret=True, seed=0, only="scan/50f,fold-in", check_timeout=240.0)
    )
    checks.gram_checks(features)
    assert checks.rows == []
