"""ALS kernel tests: reconstruction quality, implicit ranking, sharded run
on the 8-device CPU mesh."""

import numpy as np
import pytest

from oryx_tpu.ops import als as als_ops
from oryx_tpu.parallel.mesh import get_mesh


def low_rank_ratings(num_users=60, num_items=40, k=4, density=0.5, seed=7, noise=0.01):
    gen = np.random.default_rng(seed)
    xt = gen.standard_normal((num_users, k))
    yt = gen.standard_normal((num_items, k))
    full = xt @ yt.T
    mask = gen.random((num_users, num_items)) < density
    u, i = np.nonzero(mask)
    v = full[u, i] + noise * gen.standard_normal(len(u))
    return (
        u.astype(np.int32),
        i.astype(np.int32),
        v.astype(np.float32),
        full,
    )


def test_build_neighbor_block_pads_and_groups():
    u = np.array([2, 0, 2, 1], dtype=np.int32)
    i = np.array([5, 3, 1, 4], dtype=np.int32)
    v = np.array([1.0, 2.0, 3.0, 4.0], dtype=np.float32)
    blk = als_ops.build_neighbor_block(u, i, v, num_rows=4)
    assert blk.idx.shape == (4, 2)
    assert blk.mask.sum() == 4
    # row 2 has two entries (5, 1)
    assert sorted(blk.idx[2][blk.mask[2] > 0].tolist()) == [1, 5]
    # row 3 empty
    assert blk.mask[3].sum() == 0


def test_explicit_als_reconstructs_low_rank_matrix():
    u, i, v, full = low_rank_ratings()
    model = als_ops.train_als(
        u, i, v, 60, 40, features=8, lam=0.01, implicit=False, iterations=15, seed=42
    )
    pred = als_ops.predict_pairs(model.x, model.y, u, i)
    err = np.sqrt(np.mean((pred - v) ** 2))
    assert err < 0.15, f"train rmse too high: {err}"
    # held-out reconstruction decent too
    gen = np.random.default_rng(0)
    uu = gen.integers(0, 60, 200).astype(np.int32)
    ii = gen.integers(0, 40, 200).astype(np.int32)
    pred_all = als_ops.predict_pairs(model.x, model.y, uu, ii)
    corr = np.corrcoef(pred_all, full[uu, ii])[0, 1]
    assert corr > 0.95


def test_implicit_als_ranks_positives_above_negatives():
    gen = np.random.default_rng(3)
    num_users, num_items = 50, 30
    # two latent groups: users prefer items in their own group
    group_u = gen.integers(0, 2, num_users)
    group_i = gen.integers(0, 2, num_items)
    us, its, vs = [], [], []
    for u in range(num_users):
        liked = np.nonzero(group_i == group_u[u])[0]
        pick = gen.choice(liked, size=min(6, len(liked)), replace=False)
        for i in pick:
            us.append(u)
            its.append(i)
            vs.append(1.0 + gen.random())
    u = np.asarray(us, dtype=np.int32)
    i = np.asarray(its, dtype=np.int32)
    v = np.asarray(vs, dtype=np.float32)
    model = als_ops.train_als(
        u, i, v, num_users, num_items, features=6, lam=0.01, alpha=10.0,
        implicit=True, iterations=10, seed=11,
    )
    auc = als_ops.mean_auc(model.x, model.y, u, i, np.random.default_rng(5))
    assert auc > 0.8, f"implicit AUC too low: {auc}"


def test_rmse_and_empty():
    x = np.ones((2, 2), dtype=np.float32)
    y = np.ones((2, 2), dtype=np.float32)
    u = np.array([0, 1], dtype=np.int32)
    i = np.array([0, 1], dtype=np.int32)
    v = np.array([2.0, 2.0], dtype=np.float32)
    assert als_ops.rmse(x, y, u, i, v) == pytest.approx(0.0)
    assert np.isnan(als_ops.rmse(x, y, u[:0], i[:0], v[:0]))


def test_sharded_training_matches_single_device():
    u, i, v, _ = low_rank_ratings(num_users=48, num_items=32)
    kwargs = dict(features=4, lam=0.05, implicit=False, iterations=5, seed=123)
    single = als_ops.train_als(u, i, v, 48, 32, **kwargs)
    mesh = get_mesh()  # 8 virtual CPU devices from conftest
    assert mesh.devices.size == 8
    sharded = als_ops.train_als(u, i, v, 48, 32, mesh=mesh, **kwargs)
    pred_s = als_ops.predict_pairs(single.x, single.y, u, i)
    pred_m = als_ops.predict_pairs(sharded.x, sharded.y, u, i)
    np.testing.assert_allclose(pred_s, pred_m, atol=1e-2)


def test_chunked_solve_matches_unchunked():
    u, i, v, _ = low_rank_ratings(num_users=50, num_items=20)
    a = als_ops.train_als(u, i, v, 50, 20, features=4, lam=0.05, implicit=False,
                          iterations=3, seed=9)
    # tiny workspace forces chunk=1 lax.map sweeps in every bucket
    b = als_ops.train_als(u, i, v, 50, 20, features=4, lam=0.05, implicit=False,
                          iterations=3, seed=9, workspace_elems=64)
    np.testing.assert_allclose(a.x, b.x, atol=1e-4)


# ---------------------------------------------------------------------------
# batched fold-in vs the scalar reference semantics
# ---------------------------------------------------------------------------


def _scalar_fold(yty_mat, xtx_mat, events, xvecs, yvecs, implicit):
    from oryx_tpu.app.als.common import compute_updated_xu
    from oryx_tpu.common.vectormath import Solver

    yty, xtx = Solver(yty_mat), Solver(xtx_mat)
    out = []
    for (u, i), v in events:
        xu, yi = xvecs.get(u), yvecs.get(i)
        out.append(
            (
                compute_updated_xu(yty, v, xu, yi, implicit),
                compute_updated_xu(xtx, v, yi, xu, implicit),
            )
        )
    return out


@pytest.mark.parametrize("backend", ["host", "device"])
@pytest.mark.parametrize("implicit", [True, False])
def test_fold_in_batch_matches_scalar(implicit, backend):
    from oryx_tpu.ops import als as als_ops

    gen = np.random.default_rng(42)
    k = 4
    xvecs = {f"U{j}": gen.standard_normal(k).astype(np.float32) for j in range(6)}
    yvecs = {f"I{j}": gen.standard_normal(k).astype(np.float32) for j in range(6)}
    xmat = np.stack(list(xvecs.values()))
    ymat = np.stack(list(yvecs.values()))
    yty_mat = ymat.T @ ymat
    xtx_mat = xmat.T @ xmat
    events = [
        (("U0", "I0"), 1.0),
        (("U1", "I1"), -0.5),  # negative strength
        (("U2", "Inew"), 2.0),  # unknown item: no X update, no Y update
        (("Unew", "I3"), 1.0),  # unknown user: fresh vector from 0.5 prior
        (("U4", "I4"), 0.0),  # zero strength: implicit -> NaN target
    ]
    expected = _scalar_fold(yty_mat, xtx_mat, events, xvecs, yvecs, implicit)

    n = len(events)
    xu = np.zeros((n, k), np.float32)
    yi = np.zeros((n, k), np.float32)
    xu_valid = np.zeros(n, bool)
    yi_valid = np.zeros(n, bool)
    values = np.array([v for _, v in events], np.float32)
    for j, ((u, i), _) in enumerate(events):
        if u in xvecs:
            xu[j], xu_valid[j] = xvecs[u], True
        if i in yvecs:
            yi[j], yi_valid[j] = yvecs[i], True

    new_xu, x_upd, new_yi, y_upd = als_ops.fold_in_batch(
        yty_mat, xtx_mat, xu, xu_valid, yi, yi_valid, values, implicit,
        backend=backend,
    )
    for j, (exp_xu, exp_yi) in enumerate(expected):
        assert bool(x_upd[j]) == (exp_xu is not None), f"event {j} X"
        assert bool(y_upd[j]) == (exp_yi is not None), f"event {j} Y"
        if exp_xu is not None:
            np.testing.assert_allclose(new_xu[j], exp_xu, rtol=1e-4, atol=1e-5)
        if exp_yi is not None:
            np.testing.assert_allclose(new_yi[j], exp_yi, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("backend", ["host", "device"])
def test_fold_in_singular_gramian_never_emits_nonfinite(backend):
    """A rank-deficient Gramian must fall back to a pseudo-inverse solve
    (reference: LinearSystemSolver's QR threshold + Solver semantics),
    never publish NaN/huge vectors."""
    from oryx_tpu.ops import als as als_ops

    k = 4
    gen = np.random.default_rng(5)
    y = np.zeros((3, k), np.float32)
    y[:, 0] = 1.0  # rank-1 -> exactly singular YtY
    x = gen.standard_normal((3, k)).astype(np.float32)
    yty = y.T @ y
    xtx = x.T @ x + 0.1 * np.eye(k, dtype=np.float32)
    values = np.array([1.0, 2.0, 0.5], np.float32)
    valid = np.ones(3, bool)
    new_xu, x_upd, new_yi, y_upd = als_ops.fold_in_batch(
        yty, xtx, x, valid, y, valid, values, True, backend=backend
    )
    assert np.isfinite(new_xu).all() and np.isfinite(new_yi).all()
    # the well-conditioned side still updates
    assert y_upd.any()


# ---------------------------------------------------------------------------
# degree buckets + sharded factors
# ---------------------------------------------------------------------------


def test_build_neighbor_buckets_power_law():
    """A power-law degree distribution must not inflate narrow rows."""
    gen = np.random.default_rng(3)
    # 100 rows of degree <= 4, one super-row of degree 300
    rows, cols, vals = [], [], []
    for r in range(100):
        deg = int(gen.integers(1, 5))
        rows += [r] * deg
        cols += gen.integers(0, 500, deg).tolist()
        vals += [1.0] * deg
    rows += [100] * 300
    cols += gen.integers(0, 500, 300).tolist()
    vals += [1.0] * 300
    buckets = als_ops.build_neighbor_buckets(
        np.array(rows, np.int32), np.array(cols, np.int32),
        np.array(vals, np.float32), num_rows=101,
    )
    widths = sorted(b.width for b in buckets)
    assert widths[0] == 8  # min width holds the small rows
    assert widths[-1] == 512  # super-row rounds up to 512, alone
    wide = [b for b in buckets if b.width == 512][0]
    assert (wide.rows >= 0).sum() == 1
    # every entry lands exactly once
    assert sum(int(b.deg.sum()) for b in buckets) == len(rows)
    # zero-degree rows excluded entirely
    covered = np.concatenate([b.rows[b.rows >= 0] for b in buckets])
    assert len(covered) == 101


def test_bucketed_matches_on_skewed_degrees():
    """Rows with wildly different degrees still solve correctly."""
    gen = np.random.default_rng(11)
    k = 3
    xt = gen.standard_normal((30, k))
    yt = gen.standard_normal((25, k))
    rows, cols = [], []
    for r in range(30):
        deg = 24 if r == 0 else int(gen.integers(1, 4))
        cs = gen.choice(25, size=deg, replace=False)
        rows += [r] * deg
        cols += cs.tolist()
    u = np.array(rows, np.int32)
    i = np.array(cols, np.int32)
    v = (xt @ yt.T)[u, i].astype(np.float32)
    model = als_ops.train_als(u, i, v, 30, 25, features=k, lam=0.005,
                              implicit=False, iterations=12, seed=5)
    pred = als_ops.predict_pairs(model.x, model.y, u, i)
    assert np.sqrt(np.mean((pred - v) ** 2)) < 0.1


def test_shard_factors_matches_replicated():
    mesh = get_mesh()
    u, i, v, _ = low_rank_ratings(num_users=48, num_items=32)
    kwargs = dict(features=6, lam=0.01, implicit=False, iterations=8, seed=21)
    repl = als_ops.train_als(u, i, v, 48, 32, **kwargs)
    shard = als_ops.train_als(u, i, v, 48, 32, mesh=mesh, shard_factors=True, **kwargs)
    pred_r = als_ops.predict_pairs(repl.x, repl.y, u, i)
    pred_s = als_ops.predict_pairs(shard.x, shard.y, u, i)
    np.testing.assert_allclose(pred_r, pred_s, atol=1e-2)


def test_shard_factors_implicit():
    mesh = get_mesh()
    gen = np.random.default_rng(13)
    u = gen.integers(0, 40, 600).astype(np.int32)
    i = gen.integers(0, 30, 600).astype(np.int32)
    v = np.abs(gen.standard_normal(600)).astype(np.float32) + 0.1
    kwargs = dict(features=5, lam=0.1, alpha=1.0, implicit=True, iterations=6, seed=33)
    repl = als_ops.train_als(u, i, v, 40, 30, **kwargs)
    shard = als_ops.train_als(u, i, v, 40, 30, mesh=mesh, shard_factors=True, **kwargs)
    pred_r = als_ops.predict_pairs(repl.x, repl.y, u, i)
    pred_s = als_ops.predict_pairs(shard.x, shard.y, u, i)
    np.testing.assert_allclose(pred_r, pred_s, atol=5e-2, rtol=5e-2)


def test_matmul_dtype_bfloat16_quality_parity():
    """oryx.batch.compute.matmul-dtype=bfloat16 runs the Gramian einsums
    with bf16 operands + f32 accumulation; the factorization must stay
    within noise of the f32 path (solves are f32 either way)."""
    import numpy as np

    from oryx_tpu.ops import als as als_ops

    gen = np.random.default_rng(13)
    nu, ni, nnz = 300, 120, 4000
    u = gen.integers(0, nu, nnz).astype(np.int32)
    i = gen.integers(0, ni, nnz).astype(np.int32)
    v = (1.0 + 4.0 * gen.random(nnz)).astype(np.float32)
    kw = dict(num_users=nu, num_items=ni, features=8, lam=0.1, alpha=1.0,
              iterations=4, seed=3)
    for implicit in (False, True):
        m32 = als_ops.train_als(u, i, v, implicit=implicit, **kw)
        mbf = als_ops.train_als(u, i, v, implicit=implicit,
                                matmul_dtype="bfloat16", **kw)
        for a, b in ((m32.x, mbf.x), (m32.y, mbf.y)):
            cos = float(np.sum(a * b) / (np.linalg.norm(a) * np.linalg.norm(b) + 1e-12))
            assert cos > 0.99, (implicit, cos)
        r32 = als_ops.rmse(m32.x, m32.y, u, i, v)
        rbf = als_ops.rmse(mbf.x, mbf.y, u, i, v)
        assert abs(r32 - rbf) < 0.05, (implicit, r32, rbf)


def test_train_als_matches_naive_reference_solver():
    """Independent-implementation parity: a from-scratch per-row numpy
    ALS (explicit ALS-WR and implicit Hu/Koren/Volinsky normal equations
    solved row by row with np.linalg.solve) must land the same factors as
    train_als on identical data, init, and sweep schedule — the solver-
    correctness half of 'equal held-out quality' that real-dataset runs
    (tools/real_data_eval.py) demonstrate end to end."""
    import numpy as np

    from oryx_tpu.ops import als as als_ops

    gen = np.random.default_rng(21)
    num_users, num_items, nnz, k = 60, 40, 600, 5
    u = gen.integers(0, num_users, nnz).astype(np.int32)
    i = gen.integers(0, num_items, nnz).astype(np.int32)

    def naive_als(u, i, v, implicit, lam, alpha, iterations, seed):
        y = 0.1 * np.random.default_rng(seed).standard_normal(
            (num_items, k)
        ).astype(np.float32)
        x = np.zeros((num_users, k), np.float32)

        def half(own_n, own_idx, oth_idx, oth, v):
            out = np.zeros((own_n, k), np.float32)
            if implicit:
                yty = oth.T @ oth
            for r in range(own_n):
                sel = own_idx == r
                if not sel.any():
                    continue  # degree-0 rows stay zero
                ys = oth[oth_idx[sel]]
                vs = v[sel]
                if implicit:
                    c_m1 = alpha * np.abs(vs)
                    p = (vs > 0).astype(np.float32)
                    a = yty + (ys.T * c_m1) @ ys + lam * np.eye(k)
                    b = ((1.0 + c_m1) * p) @ ys
                else:
                    a = ys.T @ ys + lam * len(vs) * np.eye(k)
                    b = vs @ ys
                out[r] = np.linalg.solve(a, b)
            return out

        for _ in range(iterations):
            x = half(num_users, u, i, y, v)
            y = half(num_items, i, u, x, v)
        return x, y

    for implicit in (False, True):
        v = (
            (1.0 + gen.random(nnz)).astype(np.float32)
            if implicit
            else gen.integers(1, 6, nnz).astype(np.float32)
        )
        # aggregate duplicates the way the app tier would (sum/last-wins
        # nuances don't matter here: make pairs unique)
        pair = u.astype(np.int64) * num_items + i
        _, first = np.unique(pair, return_index=True)
        uu, ii, vv = u[first], i[first], v[first]
        model = als_ops.train_als(
            uu, ii, vv, num_users, num_items, features=k,
            lam=0.05, alpha=1.0, implicit=implicit, iterations=3, seed=9,
        )
        nx, ny = naive_als(uu, ii, vv, implicit, 0.05, 1.0, 3, 9)
        np.testing.assert_allclose(model.x, nx, rtol=2e-2, atol=2e-3)
        np.testing.assert_allclose(model.y, ny, rtol=2e-2, atol=2e-3)


# ---------------------------------------------------------------------------
# partitioned fold-in sessions (sharded speed pipeline)
# ---------------------------------------------------------------------------


def _fold_inputs(gen, n, k):
    xu = gen.standard_normal((n, k)).astype(np.float32)
    yi = gen.standard_normal((n, k)).astype(np.float32)
    xu_valid = gen.random(n) < 0.9
    yi_valid = gen.random(n) < 0.9
    values = gen.standard_normal(n).astype(np.float32)
    return xu, xu_valid, yi, yi_valid, values


@pytest.mark.parametrize("backend", ["host", "device"])
@pytest.mark.parametrize("implicit", [True, False])
def test_partitioned_fold_merge_bit_identical_to_single_session(implicit, backend):
    """Distributing a micro-batch's rows over K shard slices and merging
    (solve, shard order) yields EXACTLY the f32 bits one FoldInSession fed
    the same rows would — the fold math is row-wise independent."""
    from oryx_tpu.ops import als as als_ops

    gen = np.random.default_rng(7)
    k, n, shards = 4, 96, 4
    g = gen.standard_normal((6, k)).astype(np.float32)
    yty = (g.T @ g).astype(np.float64)
    xtx = (g.T @ g * 0.5).astype(np.float64)
    xu, xu_valid, yi, yi_valid, values = _fold_inputs(gen, n, k)

    owner = np.arange(n) % shards  # round-robin rows -> shards
    part = als_ops.PartitionedFoldInSession(yty, xtx, implicit, shards, backend=backend)
    for s in range(shards):
        sel = owner == s
        part.add_block(s, xu[sel], xu_valid[sel], yi[sel], yi_valid[sel], values[sel])
    assert part.pending == n
    got = part.solve()
    assert part.pending == 0

    # single-session reference, rows in the merged (shard-major) order
    order = np.concatenate([np.flatnonzero(owner == s) for s in range(shards)])
    single = als_ops.FoldInSession(yty, xtx, implicit, backend=backend)
    single.add_block(
        xu[order], xu_valid[order], yi[order], yi_valid[order], values[order]
    )
    want = single.solve()
    for g_arr, w_arr in zip(got, want):
        g_arr, w_arr = np.asarray(g_arr), np.asarray(w_arr)
        if g_arr.dtype == np.float32:
            np.testing.assert_array_equal(
                g_arr.view(np.uint32), w_arr.view(np.uint32)
            )
        else:
            np.testing.assert_array_equal(g_arr, w_arr)


@pytest.mark.parametrize("backend", ["host", "device"])
def test_partitioned_solve_shard_matches_private_session(backend):
    """solve_shard folds ONLY that shard's slice, bit-identical to a
    private session over the same rows; other slices stay pending."""
    from oryx_tpu.ops import als as als_ops

    gen = np.random.default_rng(11)
    k, n = 4, 32
    g = gen.standard_normal((5, k)).astype(np.float32)
    yty = (g.T @ g).astype(np.float64)
    xtx = (g.T @ g * 0.25).astype(np.float64)
    a = _fold_inputs(gen, n, k)
    b = _fold_inputs(gen, n, k)

    part = als_ops.PartitionedFoldInSession(yty, xtx, True, 2, backend=backend)
    part.add_block(0, *a)
    part.add_block(1, *b)
    single = als_ops.FoldInSession(yty, xtx, True, backend=backend)
    single.add_block(*a)

    got = part.solve_shard(0)
    want = single.solve()
    for g_arr, w_arr in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g_arr), np.asarray(w_arr))
    # shard 1 untouched by shard 0's micro-batch boundary
    assert part.pending == n
    assert part.session(1).pending == n
    assert part.solve_shard(1) is not None
    assert part.solve_shard(1) is None  # drained


def test_partitioned_set_gramians_swaps_every_slice():
    from oryx_tpu.ops import als as als_ops

    part = als_ops.PartitionedFoldInSession(
        np.eye(3), np.eye(3), False, 3, backend="host"
    )
    yty2, xtx2 = np.eye(3) * 2.0, np.eye(3) * 3.0
    part.set_gramians(yty2, xtx2)
    for s in range(3):
        assert part.session(s).yty is yty2
        assert part.session(s).xtx is xtx2
    with pytest.raises(ValueError):
        als_ops.PartitionedFoldInSession(np.eye(3), np.eye(3), False, 0)


def test_auto_fold_backend_propagates_a_device_failure(monkeypatch):
    """The auto calibration times host against device on the first large
    batch. A device error there used to elect the host silently; it must
    surface, or a layer that lost its device looks healthy."""
    from oryx_tpu.ops import als as als_ops

    gen = np.random.default_rng(0)
    n, k = 4096, 128  # n*k >= 500_000: large enough to calibrate
    y = gen.standard_normal((400, k))
    yty = y.T @ y
    xu = gen.standard_normal((n, k)).astype(np.float32)
    yi = gen.standard_normal((n, k)).astype(np.float32)
    valid = np.ones(n, bool)
    values = np.ones(n, np.float32)

    def boom(*a, **kw):
        raise RuntimeError("device lost")

    monkeypatch.setattr(als_ops, "_auto_fold_choice", None)
    monkeypatch.setattr(als_ops, "_fold_in_batch_jit", boom)
    with pytest.raises(RuntimeError, match="device lost"):
        als_ops.fold_in_batch(yty, yty, xu, valid, yi, valid, values, True, backend="auto")
    assert als_ops._auto_fold_choice is None
    with pytest.raises(RuntimeError, match="device lost"):
        als_ops.fold_in_batch(yty, yty, xu, valid, yi, valid, values, True, backend="device")


def test_fold_session_says_which_side_ran(monkeypatch):
    from oryx_tpu.ops import als as als_ops

    monkeypatch.setattr(als_ops, "_auto_fold_choice", None)

    gen = np.random.default_rng(1)
    n, k = 300, 8
    y = gen.standard_normal((50, k))
    yty = y.T @ y
    xu = gen.standard_normal((n, k)).astype(np.float32)
    yi = gen.standard_normal((n, k)).astype(np.float32)
    valid = np.ones(n, bool)
    values = np.ones(n, np.float32)
    for backend, ran in (("device", "device"), ("host", "host"), ("auto", "host")):
        # auto below the calibration size resolves to the host
        session = als_ops.FoldInSession(yty, yty, True, backend=backend)
        assert session.ran is None
        session.add_block(xu, valid, yi, valid, values)
        session.solve()
        assert session.ran == ran
