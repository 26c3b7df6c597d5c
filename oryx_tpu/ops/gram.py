"""Gram matrix of the device-resident item matrix: ``YtY`` for the ALS
fold-in (``ALSServingModel.get_yty_solver``).

The reference keeps ``YtY`` by a double-precision loop over its in-heap
vectors (``FeatureVectors.getVTV``; here ``fs_vtv`` over the host store,
one thread: minutes at 5M x 250). The same matrix lies on the device in
the layout the scan reads, where its Gram matrix is one pass over it:
``oryx_gram``.

Numerics. One float32 running sum over millions of rows drifts by about
1e-4 of a diagonal entry, which the fold-in hands on to every score. So
the sum is cut where the matrix is: one ``[f, f]`` float32 partial per
block of ``GRAM_BLOCK`` columns (products at ``Precision.HIGHEST``, the
MXU's float32 accumulation inside a block), all partials downloaded
(306 x 250 KB at 5M x 250) and added in float64 on the host. A block's
rounding is then relative to that block's own sums, and the blocks'
errors do not accumulate: every entry comes within 1e-6 of the largest
against a float64 reference (tests/ops/test_gram.py holds it so at small
sizes; on the chip the benchmark's builder prints the reading).

Columns past a handle's item count are padding (zeros as uploaded) and
are masked, whatever they hold.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from oryx_tpu.ops.pallas_topn import BLOCK_N, StreamingItemMatrix
from oryx_tpu.ops.topn import ShardedItemMatrix

# columns a partial sum: the kernel's own block, which every streaming
# handle's column count (a shard's too) is a multiple of
GRAM_BLOCK = BLOCK_N


def supported(uploaded) -> bool:
    """True for the handles whose planes ARE the item rows: the plain
    pair, and the streaming and sharded layouts unless quantized (int8
    codes are not the rows; an IVF index holds cells). The others keep the
    host store's ``get_vtv``."""
    if isinstance(uploaded, (StreamingItemMatrix, ShardedItemMatrix)):
        return uploaded.scales is None
    return isinstance(uploaded, tuple) and len(uploaded) == 2


def _block_partials(mat_t, tail, count, n_blocks: int):
    """[n_blocks, f, f] float32: the Gram matrix of each block of
    ``GRAM_BLOCK`` columns of the feature-major planes, the columns from
    ``count`` on left out. ``f`` counts the stored rows of both planes."""
    k_main = mat_t.shape[0]

    def one(i):
        lo = i * GRAM_BLOCK
        blk = jax.lax.dynamic_slice(mat_t, (0, lo), (k_main, GRAM_BLOCK)).astype(jnp.float32)
        if tail is not None:
            t_blk = jax.lax.dynamic_slice(tail, (0, lo), (tail.shape[0], GRAM_BLOCK))
            blk = jnp.concatenate([blk, t_blk.astype(jnp.float32)], axis=0)
        col = lo + jax.lax.broadcasted_iota(jnp.int32, (1, GRAM_BLOCK), 1)
        blk = jnp.where(col < count, blk, 0.0)
        return jax.lax.dot_general(
            blk, blk, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
        )

    return jax.lax.map(one, jnp.arange(n_blocks, dtype=jnp.int32))


@functools.partial(jax.jit, static_argnames=("n_blocks",))
def oryx_gram(mat_t, tail, count, *, n_blocks: int):
    """The one-device program: per-block partials of ``[features, n]``
    planes (``tail`` None where the handle has one plane)."""
    return _block_partials(mat_t, tail, count, n_blocks)


@functools.lru_cache(maxsize=None)
def _sharded_gram_fn(mesh, n_blocks: int, tailed: bool):
    """``oryx_gram`` on every shard's own columns under ``shard_map``, each
    masked by its own count: [d * n_blocks, f, f], nothing crosses a chip."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from oryx_tpu.parallel.mesh import DATA_AXIS

    def local(mat_t, beside, valid):
        return _block_partials(mat_t, beside[0] if tailed else None, valid[0], n_blocks)

    cols_spec = P(None, DATA_AXIS)
    return jax.jit(
        shard_map(
            local, mesh=mesh,
            in_specs=(cols_spec, (cols_spec,) if tailed else (), P(DATA_AXIS)),
            out_specs=P(DATA_AXIS),
        )
    )


@functools.partial(jax.jit, static_argnames=("n_blocks", "block"))
def _row_major_partials(mat, *, n_blocks: int, block: int):
    """The plain pair's twin: [n_blocks, f, f] partials of a row-major
    ``[n, f]`` matrix whose rows are a whole number of blocks."""
    blocks = mat.astype(jnp.float32).reshape(n_blocks, block, mat.shape[1])
    return jnp.einsum(
        "bnk,bnj->bkj", blocks, blocks,
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )


def _plain_partials(mat) -> list:
    """Partials of the plain pair's ``[n, f]`` matrix: whole blocks, then
    the rows that are left as one block of their own."""
    n = mat.shape[0]
    block = min(GRAM_BLOCK, n)
    whole = n // block
    parts = [_row_major_partials(mat[: whole * block], n_blocks=whole, block=block)]
    if n > whole * block:
        parts.append(_row_major_partials(mat[whole * block :], n_blocks=1, block=n - whole * block))
    return parts


def _planes(uploaded) -> tuple:
    if isinstance(uploaded, tuple):
        return (uploaded[0],)
    return (uploaded.mat_t,) if uploaded.tail is None else (uploaded.mat_t, uploaded.tail)


def wait_ready(uploaded) -> None:
    """Block until the handle's planes are on the device: an upload returns
    before its transfer has ended, and whoever times the Gram pass wants
    the pass, not the 5 GB on their way."""
    jax.block_until_ready(_planes(uploaded))


def pass_stats(uploaded) -> dict:
    """What one Gram pass over ``uploaded`` reads: ``rows`` (items),
    ``bytes`` (the planes as stored) and ``blocks`` (partial sums
    downloaded); the attributes of the ``serving.yty.build`` annotation."""
    planes = _planes(uploaded)
    if isinstance(uploaded, tuple):
        rows = int(planes[0].shape[0])
        blocks = -(-rows // min(GRAM_BLOCK, max(rows, 1)))
    else:
        rows, blocks = int(uploaded.n_items), planes[0].shape[1] // GRAM_BLOCK
    return {
        "rows": rows,
        "bytes": int(sum(p.size * p.dtype.itemsize for p in planes)),
        "blocks": int(blocks),
    }


def gram(uploaded) -> np.ndarray:
    """``YtY`` of an uploaded item matrix (``supported``): ``[features,
    features]`` float64. Blocks until the partials are on the host."""
    features = None
    if isinstance(uploaded, ShardedItemMatrix):
        fn = _sharded_gram_fn(
            uploaded.mesh, uploaded.cols // GRAM_BLOCK, uploaded.tail is not None
        )
        beside = () if uploaded.tail is None else (uploaded.tail,)
        parts = [fn(uploaded.mat_t, beside, uploaded.valid)]
        features = uploaded.features
    elif isinstance(uploaded, StreamingItemMatrix):
        parts = [
            oryx_gram(
                uploaded.mat_t, uploaded.tail, np.int32(uploaded.n_items),
                n_blocks=uploaded.mat_t.shape[1] // GRAM_BLOCK,
            )
        ]
        features = uploaded.features
    else:
        parts = _plain_partials(uploaded[0])
    total = sum(np.asarray(p).sum(axis=0, dtype=np.float64) for p in parts)
    if features is not None:  # a tail plane stores 3 features in 4 rows
        total = total[:features, :features]
    return np.ascontiguousarray(total)
