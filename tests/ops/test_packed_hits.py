"""A pass is one dispatch and one download (ops/topn.py `_submit`,
pallas_topn.py `pack_hits`): the packed result is the pair it replaces bit
for bit on every float32 handle kind, the bfloat16 wire and the IVF index
keep their pair, and the submit makes no host-side `device_put`, runs one
program, starts one copy and is fetched once."""

import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oryx_tpu.common import metrics
from oryx_tpu.ops import ivf as ivf_ops
from oryx_tpu.ops import pallas_topn as ptn
from oryx_tpu.ops import topn
from oryx_tpu.serving.batcher import TopNBatcher

N_ITEMS, N_USERS = 3000, 300


@functools.lru_cache(maxsize=None)
def _factors(features: int):
    """Small whole numbers, so that scores tie exactly. Users 7 and 8 are
    zero (every item ties at 0); users 9 and 10 point against the items'
    first feature, which is 1 to 3: all their scores are negative."""
    gen = np.random.default_rng(features)
    y = gen.integers(-2, 3, (N_ITEMS, features)).astype(np.float32)
    y[:, 0] = gen.integers(1, 4, N_ITEMS)
    x = gen.integers(-2, 3, (N_USERS, features)).astype(np.float32)
    x[7:11] = 0.0
    x[9:11, 0] = -1.0, -2.0
    return y, x


@functools.lru_cache(maxsize=None)
def _mesh():
    return jax.sharding.Mesh(np.asarray(jax.devices()[:4]), ("data",))


@functools.lru_cache(maxsize=None)
def _handle(kind: str):
    """(handle, staged users, the users' rows on the host) of one kind."""
    features = 50 if kind == "streaming-50" else 250
    y, x = _factors(features)
    if kind == "sharded":
        return topn.upload_sharded(y, _mesh()), topn.upload_queries(x, mesh=_mesh()), x
    up = topn.upload(y, streaming=kind != "plain")
    assert kind == "plain" or up.tail is not None  # 250 = 248 + 2, 50 = 48 + 2
    return up, topn.upload_queries(x), x


@functools.partial(jax.jit, static_argnums=(3, 4))
def _plain_pair(mat, norms, q_kb, k, cosine):
    return jax.lax.map(lambda q: topn._dot_topk_batch(mat, norms, q, k, cosine), q_kb)


@functools.partial(jax.jit, static_argnames=("k", "n_items", "cosine"))
def _streaming_pair(mat_t, norms, q_kb, tail, *, k, n_items, cosine):
    return ptn._xla_streaming_topk_multi_impl(
        mat_t, norms, None, None, None, q_kb, k=k, n_items=n_items, cosine=cosine, tail=tail
    )


def _parent_pair(kind, up, x_dev, groups, indexed, k, cosine):
    """(vals, idxs) [K, b, k] as the parent's program of this kind made
    them: the same scan, its two results not yet joined."""
    if kind == "sharded":
        from oryx_tpu.parallel.mesh import replicated

        fn = topn._sharded_scan_fn(up.mesh, k, cosine, False, indexed, None, tailed=True)
        return fn(
            up.mat_t, up.norms, (up.tail,), up.base, up.valid,
            jax.device_put(groups, replicated(up.mesh)), x_dev if indexed else (),
        )
    q_kb = x_dev[groups] if indexed else jnp.asarray(groups)
    if kind == "plain":
        mat, norms = up
        return _plain_pair(mat, norms, q_kb, k, cosine)
    return _streaming_pair(
        up.mat_t, up.norms, q_kb, up.tail, k=k, n_items=up.n_items, cosine=cosine
    )


@pytest.mark.parametrize("k", [16, 32])
@pytest.mark.parametrize("metric", ["dot", "cosine"])
@pytest.mark.parametrize("submit", ["rows", "vectors"])
@pytest.mark.parametrize("kind", ["streaming-250", "streaming-50", "plain", "sharded"])
def test_the_packed_result_is_the_pair_bit_for_bit(kind, submit, metric, k):
    up, x_dev, x = _handle(kind)
    cosine = metric == "cosine"
    if submit == "rows":  # 11 rows in groups of 8: five zero rows pad the second
        rows = np.asarray([0, 7, 8, 9, 10, 3, 3, 21, 22, 23, 24], np.int32)
        handle = topn.submit_top_k_multi_indexed(up, x_dev, rows, k, cosine=cosine, scan_batch=8)
        groups = ptn.group_rows(rows, 8)
    else:  # 260 vectors in groups of 256: the second is all but four rows padding
        rows = x[np.arange(260) % N_USERS]
        handle = topn.submit_top_k(up, rows, k, cosine=cosine)
        groups = ptn.group_rows(rows)
    assert handle.packed and handle._vals.dtype == jnp.int32
    assert handle._vals.shape == groups.shape[:2] + (2 * k,)
    want_vals, want_idxs = (
        np.asarray(a) for a in _parent_pair(kind, up, x_dev, groups, submit == "rows", k, cosine)
    )
    assert want_vals.dtype == np.float32 and want_idxs.dtype == np.int32
    # the whole array, the padding rows' answers too
    got = np.asarray(handle._vals)
    np.testing.assert_array_equal(got[..., :k], want_vals.view(np.int32))
    np.testing.assert_array_equal(got[..., k:], want_idxs)
    # and what a caller is given: the rows it asked for, scores as float32
    idxs, vals = handle.result()
    n = len(rows)
    assert idxs.dtype == np.int32 and vals.dtype == np.float32 and idxs.shape == vals.shape == (n, k)
    np.testing.assert_array_equal(idxs, want_idxs.reshape(-1, k)[:n])
    np.testing.assert_array_equal(vals.view(np.int32), want_vals.reshape(-1, k)[:n].view(np.int32))
    assert (vals < 0).any() and (vals > 0).any()
    ties = vals[:, 1:] == vals[:, :-1]
    assert ties.any() and (np.diff(idxs, axis=1)[ties] > 0).all()  # equal scores: lowest id first


@pytest.mark.parametrize("submit", ["rows", "vectors"])
def test_a_bfloat16_wire_keeps_its_pair(submit):
    y, x = _factors(50)
    up = topn.upload(y, dtype=jnp.bfloat16, streaming=True)
    rows = np.arange(5, dtype=np.int32)
    if submit == "rows":
        x_dev = topn.upload_queries(x)
        handle = topn.submit_top_k_multi_indexed(up, x_dev, rows, 16)
        pair = ptn.scan_groups(up, rows[None, :], 16, download_dtype=jnp.bfloat16, x_dev=x_dev)
    else:
        handle = topn.submit_top_k(up, x[rows], 16)
        pair = ptn.scan_groups(up, x[rows][None, :], 16, download_dtype=jnp.bfloat16)
    assert not handle.packed and handle._vals.dtype == jnp.bfloat16
    idxs, vals = handle.result()
    assert vals.dtype == np.float32
    np.testing.assert_array_equal(idxs, np.asarray(pair[1])[0])
    np.testing.assert_array_equal(vals, np.asarray(pair[0])[0].astype(np.float32))
    # with the wire off the same handle's scores are float32, and packed
    assert topn._submit(up, x[rows], 16, False, wire_dtype=False).packed


@pytest.mark.parametrize("submit", ["rows", "vectors"])
def test_an_ivf_index_keeps_its_pair(submit):
    y, x = _factors(50)
    index = ivf_ops.build_ivf(y, n_cells=16)
    rows = np.arange(5, dtype=np.int32)
    if submit == "rows":
        x_dev = topn.upload_queries(x)
        handle = topn.submit_top_k_multi_indexed(index, x_dev, rows, 16)
        want_vals, want_idxs = ivf_ops.top_k_device_indexed(index, x_dev, rows, 16)
    else:
        handle = topn.submit_top_k(index, x[rows], 16)
        want_vals, want_idxs = ivf_ops.top_k_device(index, x[rows], 16)
    assert not handle.packed
    idxs, vals = handle.result()
    np.testing.assert_array_equal(idxs, np.asarray(want_idxs).reshape(-1, 16)[:5])
    np.testing.assert_array_equal(vals, np.asarray(want_vals, np.float32).reshape(-1, 16)[:5])


# -- the mechanism: one trip in, one trip out --------------------------------------------


@pytest.fixture
def trips(monkeypatch):
    """Counts, while it is armed, what a submit may do once or not at all."""
    from jax._src.array import ArrayImpl

    seen = {"device_put": 0, "asarray": 0, "copies": 0, "fetches": 0}

    def counting(name, real, when=lambda *args: True):
        def wrapped(*args, **kwargs):
            seen[name] += bool(when(*args))
            return real(*args, **kwargs)

        return wrapped

    monkeypatch.setattr(jax, "device_put", counting("device_put", jax.device_put))
    monkeypatch.setattr(jnp, "asarray", counting("asarray", jnp.asarray))
    monkeypatch.setattr(
        ArrayImpl, "copy_to_host_async", counting("copies", ArrayImpl.copy_to_host_async)
    )
    # a device array made into a NumPy one (on the CPU by the buffer protocol,
    # on a chip by a transfer: either way by this call)
    of_device = lambda a, *rest: isinstance(a, jax.Array)  # noqa: E731
    monkeypatch.setattr(np, "asarray", counting("fetches", np.asarray, of_device))
    return seen


@pytest.mark.parametrize("submit", ["rows", "vectors"])
@pytest.mark.parametrize("kind", ["streaming-250", "plain", "sharded"])
def test_a_float32_submit_is_one_dispatch_and_one_download(kind, submit, trips, monkeypatch):
    up, x_dev, x = _handle(kind)
    rows = np.arange(8, dtype=np.int32)

    def once():
        if submit == "rows":
            return topn.submit_top_k_multi_indexed(up, x_dev, rows, 16)
        return topn.submit_top_k(up, x[rows], 16)

    want = once().result()  # traced and compiled before anything is counted
    programs = []  # (the groups as the one program was given them, what it handed back)

    def recording(real, groups_at):
        def wrapped(*args, **kwargs):
            out = real(*args, **kwargs)
            programs.append((args[groups_at], out))
            return out

        return wrapped

    if kind == "sharded":
        real_packed = topn._packed
        monkeypatch.setattr(topn, "_packed", lambda scan: recording(real_packed(scan), 5))
    elif kind == "plain":
        monkeypatch.setattr(topn, "_plain_topk_groups", recording(topn._plain_topk_groups, 3))
    else:
        for name, at in (("_xla_streaming_topk_multi", 5), ("_xla_streaming_topk_multi_indexed", 6)):
            monkeypatch.setattr(ptn, name, recording(getattr(ptn, name), at))
    for key in trips:
        trips[key] = 0
    handle = once()
    assert trips == {"device_put": 0, "asarray": 0, "copies": 1, "fetches": 0}
    (groups, out), = programs  # one program ran
    assert type(groups) is np.ndarray  # and took the row groups as the NumPy array they are
    assert handle._vals is out and handle._idxs is None  # nothing ran on its result
    got = handle.result()
    assert trips["fetches"] == 1 and trips["copies"] == 1
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1].view(np.int32), want[1].view(np.int32))


def test_every_pass_of_a_float32_handle_counts_as_packed():
    up, x_dev, x = _handle("streaming-50")
    index = ivf_ops.build_ivf(_factors(50)[0], n_cells=16)

    def counts():
        snap = metrics.registry.snapshot()
        return tuple(
            snap.get(f"serving.batcher.{name}", {}).get("value", 0) for name in ("passes", "pass.packed")
        )

    b = TopNBatcher()
    try:
        before = counts()
        threads = [
            threading.Thread(target=b.score, args=(up, x[j], 10)) for j in range(6)
        ] + [threading.Thread(target=b.score_indexed, args=(up, x_dev, j, 10)) for j in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        passes, packed = (a - b0 for a, b0 in zip(counts(), before))
        assert passes >= 2 and packed == passes  # a vector pass and an indexed pass at the least
        b.score(index, x[0], 10)  # an IVF pass hands back its pair: counted, not as packed
        assert tuple(a - b0 for a, b0 in zip(counts(), before)) == (passes + 1, packed)
    finally:
        b.close()
