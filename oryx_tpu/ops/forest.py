"""Random-forest training on TPU: histogram-based level-wise growth.

The TPU-native replacement for Spark MLlib's RandomForest.trainClassifier/
trainRegressor used by the reference's RDFUpdate (app/oryx-app-mllib/...
/rdf/RDFUpdate.java:143-165). Decision-tree induction is branchy and
pointer-chasing in its classic form; the TPU formulation (XGBoost-style,
SURVEY.md §7 step 5) grows all nodes of one depth at once:

- inputs are pre-binned feature matrices ([n, p] small-int bin ids, the
  binning/bin-edge mapping lives in the app tier),
- one level = ONE fused pass producing the full [p, nodes, bins, stats]
  histogram tensor, from which cumulative sums over bins give every
  candidate split's left/right statistics, impurity gains are evaluated
  for all (node, feature, bin) candidates simultaneously, and argmax
  picks each node's split,
- per-node feature subsampling (mtry) is a random mask over the gain
  tensor, bootstrap resampling is Poisson(1) example weights,
- trees come out as flat heap arrays (node i's children at 2i+1/2i+2)
  that the app tier converts to portable DecisionTree objects.

Histogram formulations (docs/batch-trainers.md):

- **matmul** — one dense contraction ``A.T @ onehot(bins)`` with
  ``A[n, L*S] = onehot(node) ⊗ (w * chan)``: all features × nodes × bins
  batched through the MXU. Used when the level's FLOP/one-hot footprint
  fits a budget (shallow levels, where nodes are few).
- **scalar** — classification folds the class channel INTO the segment
  id (``seg = (node*B + bin)*C + class``) so the scatter moves one
  scalar weight per (row, feature) instead of a C-wide stat vector.
- **reference** — the original per-feature vector segment-sum scan,
  kept as the equivalence baseline for tests.

All formulations produce the same [p, L, B, S] tensor and stay
psum-compatible under the existing shard_map: each device computes local
histograms and a single psum produces the global ones; split selection is
then replicated math and example routing stays local.

On the CPU backend with no mesh, ``train_forest`` takes a host fast path:
per-(tree, level) ``np.bincount`` histograms (5-10x the throughput of
XLA:CPU scatter) over only the **live** nodes of the level — children of
the previous level's splits — with the split selection running through
the same jitted gain kernel the device path uses, so both paths pick
identical splits. Stats channels: per-class weighted counts for
classification, (w, w*y, w*y^2) for regression.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np


# cap on one tree chunk's per-tree device-resident rows (weights+routing)
_TREE_CHUNK_BUDGET_BYTES = 1 << 30

# dense-matmul histogram budget: FLOPs of the one contraction, and
# elements of the materialized one-hots (the [n, p*B] bin one-hot is the
# big one). Above either bound the scalar/vector segment path takes over.
_MM_FLOP_BUDGET = float(1 << 32)
_MM_ELEM_BUDGET = float(1 << 28)

# wall seconds of the most recent train_forest call, split by phase
# ({"init": s, "iterate": s}); read by tools/train_benchmark.py.
# Overwritten per call, never merged.
last_phase_seconds: dict[str, float] = {}


@dataclass
class ForestArrays:
    """Flat heap-layout forest. -1 split_feature = leaf."""

    split_feature: np.ndarray  # [T, max_nodes] int32
    split_bin: np.ndarray  # [T, max_nodes] int32 (negative branch: bin <= split_bin)
    node_stats: np.ndarray  # [T, max_nodes, S] per-node class counts / (w, wy, wyy)
    node_counts: np.ndarray  # [T, max_nodes] weighted example counts
    gains: np.ndarray  # [T, max_nodes] impurity decrease of each split
    num_classes: int | None  # None = regression

    @property
    def num_trees(self) -> int:
        return self.split_feature.shape[0]


def _impurity(stats: jnp.ndarray, total: jnp.ndarray, kind: str) -> jnp.ndarray:
    """stats [..., S], total [...] -> impurity [...]."""
    if kind == "variance":
        w, wy, wyy = stats[..., 0], stats[..., 1], stats[..., 2]
        mean = wy / jnp.maximum(w, 1e-12)
        return jnp.maximum(wyy / jnp.maximum(w, 1e-12) - mean * mean, 0.0)
    p = stats / jnp.maximum(total[..., None], 1e-12)
    if kind == "gini":
        return 1.0 - jnp.sum(p * p, axis=-1)
    # entropy in nats (reference: min-info-gain-nats)
    return -jnp.sum(jnp.where(p > 0, p * jnp.log(p), 0.0), axis=-1)


def _level_histograms(
    binned,  # [n, p] int32
    w_act,  # [n] float32 example weights, 0 for inactive rows
    y_cls,  # [n] int32 class ids (zeros for regression)
    chan,  # [n, S] float32 per-example stat basis (onehot(y) / (1, y, y^2))
    pos_c,  # [n] int32 clamped node position within the level
    num_level_nodes: int,
    num_bins: int,
    impurity: str,
    hist_mode: str,
):
    """All (feature, node, bin, stat) sums for one level: [p, L, B, S]."""
    n, p = binned.shape
    s = chan.shape[1]
    L, B = num_level_nodes, num_bins

    if hist_mode != "reference":
        mm_flops = 2.0 * n * L * s * p * B
        mm_elems = float(n) * (p * B + L * s)
        if hist_mode == "matmul" or (
            hist_mode == "auto"
            and mm_flops <= _MM_FLOP_BUDGET
            and mm_elems <= _MM_ELEM_BUDGET
        ):
            # ONE dense contraction on the MXU: A[n, L*S] carries each
            # row's weighted stat channels at its node's slot, the bin
            # one-hot [n, p*B] carries its bin slot per feature, and
            # A.T @ onehot yields every (node, stat, feature, bin) sum.
            nh = jax.nn.one_hot(pos_c, L, dtype=jnp.float32) * w_act[:, None]
            a = (nh[:, :, None] * chan[:, None, :]).reshape(n, L * s)
            ohb = jax.nn.one_hot(binned, B, dtype=jnp.float32).reshape(n, p * B)
            h = jnp.dot(a.T, ohb, preferred_element_type=jnp.float32)
            return h.reshape(L, s, p, B).transpose(2, 0, 3, 1)  # [p, L, B, S]
        if impurity != "variance":
            # classification: fold the class channel into the segment id
            # so each (row, feature) scatters ONE scalar, not an S-vector
            base = (pos_c * B) * s + y_cls

            def hist_scalar(carry, f):
                seg = base + binned[:, f] * s
                h = jax.ops.segment_sum(w_act, seg, num_segments=L * B * s)
                return carry, h.reshape(L, B, s)

            _, hists = jax.lax.scan(hist_scalar, 0, jnp.arange(p))
            return hists

    w_stats = chan * w_act[:, None]  # [n, S]

    def hist_vector(carry, f):
        seg = pos_c * B + binned[:, f]
        h = jax.ops.segment_sum(w_stats, seg, num_segments=L * B)
        return carry, h.reshape(L, B, s)

    _, hists = jax.lax.scan(hist_vector, 0, jnp.arange(p))
    return hists


def _candidate_gains(
    hists,  # [p, L, B, S] histograms; B may be trimmed below num_bins_total
    node_tot,  # [L, S] per-node totals (shared across feature groups)
    impurity: str,
    min_node_size,  # float32
    num_bins_total: int,  # GLOBAL bin count: candidate bin num_bins-1 is
    # "everything left" and never a real split, even when B is trimmed
):
    """Impurity gain of every (feature, node, bin) candidate: [p, L, B],
    -inf where the candidate is invalid (child below min_node_size, or
    the all-left last bin)."""
    num_bins = hists.shape[2]

    # weighted example count: regression carries it in channel 0; for
    # classification it is the sum of the per-class channels
    def _count(stats):
        if impurity == "variance":
            return stats[..., 0]
        return stats.sum(axis=-1)

    left = jnp.cumsum(hists, axis=2)  # [p, L, B, S] stats for bin <= b
    right = node_tot[None, :, None, :] - left
    tot_cnt = _count(node_tot)  # [L]
    l_cnt = _count(left)
    r_cnt = _count(right)

    parent_imp = _impurity(node_tot, tot_cnt, impurity)  # [L]
    l_imp = _impurity(left, l_cnt, impurity)
    r_imp = _impurity(right, r_cnt, impurity)
    tot_safe = jnp.maximum(tot_cnt, 1e-12)
    gain = parent_imp[None, :, None] - (l_cnt * l_imp + r_cnt * r_imp) / tot_safe[None, :, None]

    valid = (l_cnt >= min_node_size) & (r_cnt >= min_node_size)
    # last candidate bin (B-1) sends everything left: never a real split
    valid = valid & (jnp.arange(num_bins)[None, None, :] < num_bins_total - 1)
    return jnp.where(valid, gain, -jnp.inf)


def _best_of(g):
    """argmax over the (feature, bin) candidate axes: g [p, L, B] ->
    (flat index [L], gain [L]); flat = f_local * B + bin."""
    p, num_level_nodes, num_bins = g.shape
    flat = g.transpose(1, 0, 2).reshape(num_level_nodes, p * num_bins)
    best = jnp.argmax(flat, axis=1)
    return best, jnp.take_along_axis(flat, best[:, None], axis=1)[:, 0]


def _level_splits_from_hists(
    hists,  # [p, L, B, S] histogram tensor (already psum'd if sharded)
    feat_mask,  # [L, p] float32 1/0 mtry mask
    allowed_mask,  # [p] float32 1/0: features splits may EVER use
    impurity: str,
    min_node_size,  # float32
    min_info_gain,  # float32
    is_last_level: bool,
):
    """Split selection for one level from its histograms: returns
    (split_feature [L], split_bin [L], gain [L], node_tot [L, S])."""
    p, num_level_nodes, num_bins, _s = hists.shape
    node_tot = hists[0].sum(axis=1)  # [L, S] (same for every feature)

    gain = _candidate_gains(hists, node_tot, impurity, min_node_size, num_bins)
    # excluded features (id/ignored/target columns) are out of bounds for
    # the mtry-widening fallback too, not just for the sampled mask
    gain_all = jnp.where(allowed_mask[:, None, None] > 0, gain, -jnp.inf)
    gain_masked = jnp.where(feat_mask.T[:, :, None] > 0, gain_all, -jnp.inf)

    # prefer the mtry-sampled features; when none of them admits a valid
    # split, keep looking among all features (sklearn max_features
    # semantics: the search widens until a valid partition is found)
    best_m, gain_m = _best_of(gain_masked)
    best_a, gain_a = _best_of(gain_all)
    use_masked = gain_m > min_info_gain
    best = jnp.where(use_masked, best_m, best_a)
    best_gain = jnp.where(use_masked, gain_m, gain_a)
    best_feat = (best // num_bins).astype(jnp.int32)
    best_bin = (best % num_bins).astype(jnp.int32)

    do_split = (best_gain > min_info_gain) & jnp.isfinite(best_gain)
    if is_last_level:
        do_split = jnp.zeros_like(do_split)
    split_feature = jnp.where(do_split, best_feat, -1)
    split_bin = jnp.where(do_split, best_bin, -1)
    return split_feature, split_bin, jnp.where(do_split, best_gain, 0.0), node_tot


def _grow_level_impl(
    binned,  # [n, p] int32 (local rows under shard_map)
    y_cls,  # [n] int32 class ids (zeros for regression)
    chan,  # [n, S] float32 per-example stat basis (shared by every tree)
    w_ex,  # [n] float32 per-tree example weights
    node_of,  # [n] int32 heap index or -1 (inactive)
    feat_mask,  # [L, p] float32 1/0 mtry mask for this level
    allowed_mask,  # [p] float32 1/0: features splits may EVER use
    level_start: int,  # heap index of first node at this depth (2^d - 1)
    num_level_nodes: int,  # L = 2^d
    num_bins: int,  # B
    impurity: str,
    min_node_size,  # float32
    min_info_gain,  # float32
    is_last_level: bool,
    hist_mode: str = "auto",
    axis_name: str | None = None,  # psum histograms over this mesh axis
):
    """Returns (split_feature [L], split_bin [L], gain [L], node_tot [L,S],
    new_node_of [n])."""
    n, p = binned.shape
    pos = node_of - level_start  # position within level; <0 or >=L = inactive
    active = (pos >= 0) & (pos < num_level_nodes)
    pos_c = jnp.where(active, pos, 0)
    w_act = jnp.where(active, w_ex, 0.0)

    hists = _level_histograms(
        binned, w_act, y_cls, chan, pos_c, num_level_nodes, num_bins,
        impurity, hist_mode,
    )
    if axis_name is not None:
        # rows are sharded over the mesh: local histograms psum into the
        # global ones; everything after this line is replicated math
        hists = jax.lax.psum(hists, axis_name)

    split_feature, split_bin, gains, node_tot = _level_splits_from_hists(
        hists, feat_mask, allowed_mask, impurity,
        min_node_size, min_info_gain, is_last_level,
    )
    do_split = split_feature >= 0

    # route examples: children heap indices; leaves freeze at -1
    node_heap = pos_c + level_start
    ex_feat = split_feature[pos_c]
    ex_bin = split_bin[pos_c]
    ex_split = do_split[pos_c] & active
    goes_pos = binned[jnp.arange(n), jnp.maximum(ex_feat, 0)] > ex_bin
    child = 2 * node_heap + 1 + goes_pos.astype(jnp.int32)
    new_node_of = jnp.where(ex_split, child, jnp.where(active, -node_heap - 2, node_of))
    # inactive-but-was-active encode as -(heap+2) so final leaf is recoverable
    return split_feature, split_bin, gains, node_tot, new_node_of


def _grow_level_trees_impl(
    binned,  # [n, p] int32 (shared by every tree)
    y_cls,  # [n] int32 (shared)
    chan,  # [n, S] float32 (shared)
    w_t,  # [T, n] per-tree example weights
    node_t,  # [T, n] per-tree heap index or -1
    mask_t,  # [T, L, p] per-tree mtry masks for this level
    allowed_mask,  # [p] float32, shared by every tree
    level_start: int,
    num_level_nodes: int,
    num_bins: int,
    impurity: str,
    min_node_size,
    min_info_gain,
    is_last_level: bool,
    hist_mode: str = "auto",
    axis_name: str | None = None,
):
    """Whole-forest level pass: lax.scan over the tree axis around the
    single-tree level kernel, so ALL trees advance one depth in ONE
    device dispatch (the per-(tree, level) dispatch grid — 20 trees x 11
    levels of ~round-trip latency each — dominated wall-clock on remote
    devices). The scan keeps peak histogram memory at one tree's
    [p, L, B, S] tensor; the [T, n] weights, [T, n] routing, and [T, L]
    split results are resident for the whole call — train_forest bounds
    T per call so they stay under a fixed budget."""

    def one_tree(carry, args):
        w, no, fm = args
        out = _grow_level_impl(
            binned, y_cls, chan, w, no, fm, allowed_mask, level_start,
            num_level_nodes, num_bins, impurity, min_node_size,
            min_info_gain, is_last_level, hist_mode, axis_name,
        )
        return carry, out

    _, outs = jax.lax.scan(one_tree, 0, (w_t, node_t, mask_t))
    return outs  # (sf [T,L], sb [T,L], gain [T,L], node_tot [T,L,S], node_of [T,n])


_grow_level_trees = functools.partial(jax.jit, static_argnums=(7, 8, 9, 10, 13, 14))(
    _grow_level_trees_impl
)


# jitted candidate scoring for the host-histogram fast path: the SAME
# gain kernel the device path runs, so both paths pick identical splits
# (host log/argmax would differ from XLA by ulps and flip near-tie
# candidates). Evaluated per feature GROUP — features of equal bin width
# share a trimmed [pg, L, width, S] tensor, so a mostly-binary feature
# set (e.g. one-hot categoricals next to a few 32-bin numerics) skips
# the ~75% of the dense candidate grid that is structurally empty.
@functools.partial(jax.jit, static_argnums=(5, 6))
def _eval_group_hists(hists, node_tot, feat_mask, allowed_mask, mins,
                      impurity, num_bins_total):
    gain = _candidate_gains(hists, node_tot, impurity, mins[0], num_bins_total)
    gain_all = jnp.where(allowed_mask[:, None, None] > 0, gain, -jnp.inf)
    gain_masked = jnp.where(feat_mask.T[:, :, None] > 0, gain_all, -jnp.inf)
    best_m, gain_m = _best_of(gain_masked)
    best_a, gain_a = _best_of(gain_all)
    return best_m, gain_m, best_a, gain_a


@functools.lru_cache(maxsize=8)
def _grow_level_trees_mesh(mesh, axis_name: str):
    """shard_map'd whole-forest level pass: rows sharded over ``axis_name``
    (tree axis replicated in layout, scanned in compute), histograms
    psum'd per tree inside the scan."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    rows = P(axis_name, None)
    row1 = P(axis_name)
    trow1 = P(None, axis_name)
    repl = P()

    def wrapped(binned, y_cls, chan, w_t, node_t, mask_t, allowed_mask,
                level_start, num_level_nodes, num_bins, impurity,
                min_node_size, min_info_gain, is_last_level, hist_mode):
        fn = functools.partial(
            _grow_level_trees_impl,
            level_start=level_start,
            num_level_nodes=num_level_nodes,
            num_bins=num_bins,
            impurity=impurity,
            min_node_size=min_node_size,
            min_info_gain=min_info_gain,
            is_last_level=is_last_level,
            hist_mode=hist_mode,
            axis_name=axis_name,
        )
        return shard_map(
            fn,
            mesh=mesh,
            in_specs=(rows, row1, rows, trow1, trow1, repl, repl),
            out_specs=(repl, repl, repl, repl, trow1),
        )(binned, y_cls, chan, w_t, node_t, mask_t, allowed_mask)

    return functools.partial(
        jax.jit, static_argnums=(7, 8, 9, 10, 13, 14)
    )(wrapped)


def _host_level_hists(
    binned_T,  # [p, n] int32 (row-major per feature)
    w,  # [n] float32 weights with inactive rows zeroed is NOT required:
    #   inactive rows are routed to the trash slot below instead
    y_cls,  # [n] int32 (classification) — class folded into the bin id
    ybase,  # (y, y*y) float arrays for regression, else None
    compact,  # [n] int64 live-node slot per row; == trash for dead rows
    num_slots: int,  # live slots incl. pow2 padding (trash slot excluded)
    num_bins: int,
    s: int,
) -> np.ndarray:
    """np.bincount histograms [p, num_slots, B, S] for one (tree, level).

    One weighted bincount per (feature, channel): 5-10x the throughput of
    an XLA:CPU scatter for the same sums, and exact for classification
    (integer Poisson weights accumulate exactly in float64)."""
    p = binned_T.shape[0]
    b = num_bins
    size = (num_slots + 1) * b  # +1 = trash slot for dead/frozen rows
    if ybase is None:
        base = compact * (b * s) + y_cls
        out = np.empty((p, num_slots + 1, b, s), np.float32)
        for f in range(p):
            seg = base + binned_T[f] * s
            out[f] = np.bincount(seg, weights=w, minlength=size * s).reshape(
                num_slots + 1, b, s
            )
    else:
        base = compact * b
        out = np.empty((p, num_slots + 1, b, s), np.float32)
        chans = (w, w * ybase[0], w * ybase[1])
        for f in range(p):
            seg = base + binned_T[f]
            for c in range(3):
                out[f, :, :, c] = np.bincount(
                    seg, weights=chans[c], minlength=size
                ).reshape(num_slots + 1, b)
    return out[:, :num_slots]


def _pow2_at_least(n: int) -> int:
    return 1 << max(0, int(n - 1).bit_length())


def train_forest(
    binned: np.ndarray,
    targets: np.ndarray,
    num_bins: int,
    num_classes: int | None,
    num_trees: int = 20,
    max_depth: int = 8,
    min_node_size: float = 1.0,
    min_info_gain: float = 0.0,
    impurity: str = "entropy",
    mtry: int | None = None,
    seed: int | None = None,
    exclude_features: set[int] | None = None,
    mesh=None,
    hist_mode: str = "auto",
    host_hist: bool | None = None,
) -> ForestArrays:
    """Train `num_trees` trees over pre-binned features. Columns in
    `exclude_features` (e.g. the target's predictor slot) are never
    sampled for splitting. With ``mesh``, example rows shard over the
    'data' axis and per-level histograms psum across devices.

    ``hist_mode`` picks the device histogram formulation: "auto" (dense
    one-hot matmul when the level fits the FLOP budget, else the scalar/
    vector segment path), "matmul", "scalar", or "reference" (the
    original per-feature vector scan, kept for equivalence tests).
    ``host_hist`` forces the host np.bincount fast path on or off;
    default None enables it on the CPU backend with no mesh. Both paths
    consume the identical RNG stream and run split selection through the
    same jitted gain kernel, so they grow identical forests."""
    import time as _time

    from oryx_tpu.common import rng as rng_mod

    t_init = _time.perf_counter()
    binned = np.asarray(binned, dtype=np.int32)
    n, p = binned.shape
    allowed = np.asarray(
        sorted(set(range(p)) - (exclude_features or set())), dtype=np.int64
    )
    if len(allowed) == 0:
        raise ValueError("no usable features")
    allowed_vec = np.zeros(p, dtype=np.float32)
    allowed_vec[allowed] = 1.0
    if num_classes is None:
        y = np.asarray(targets, dtype=np.float32)
        chan_base = np.stack([np.ones(n, np.float32), y, y * y], axis=1)
        y_cls = np.zeros(n, dtype=np.int32)
        imp_kind = "variance"
    else:
        y_cls = np.asarray(targets, dtype=np.int32)
        chan_base = np.eye(num_classes, dtype=np.float32)[y_cls]
        imp_kind = impurity
    s_chan = chan_base.shape[1]
    pa = len(allowed)
    if mtry is None:
        mtry = max(1, int(np.sqrt(pa)) if num_classes is not None else max(1, pa // 3))

    max_nodes = 2 ** (max_depth + 1) - 1
    gen = np.random.default_rng(rng_mod.next_seed() if seed is None else seed)

    t_feat = np.full((num_trees, max_nodes), -1, dtype=np.int32)
    t_bin = np.full((num_trees, max_nodes), -1, dtype=np.int32)
    t_stats = np.zeros((num_trees, max_nodes, s_chan), dtype=np.float64)
    t_counts = np.zeros((num_trees, max_nodes), dtype=np.float64)
    t_gains = np.zeros((num_trees, max_nodes), dtype=np.float64)

    if host_hist is None:
        host_hist = mesh is None and jax.default_backend() == "cpu"

    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from oryx_tpu.parallel.mesh import DATA_AXIS, pad_to_multiple

        num_shards = int(np.prod(mesh.devices.shape))
        n_pad = pad_to_multiple(n, num_shards)
        if n_pad != n:  # pad rows arrive inactive (node_of = -1, weight 0)
            binned = np.concatenate([binned, np.zeros((n_pad - n, p), np.int32)])
            chan_base = np.concatenate(
                [chan_base, np.zeros((n_pad - n, s_chan), np.float32)]
            )
            y_cls = np.concatenate([y_cls, np.zeros(n_pad - n, np.int32)])
        rows_sh = NamedSharding(mesh, P(DATA_AXIS, None))
        row1_sh = NamedSharding(mesh, P(DATA_AXIS))
        trow1_sh = NamedSharding(mesh, P(None, DATA_AXIS))
        grow = _grow_level_trees_mesh(mesh, DATA_AXIS)
        binned_dev = jax.device_put(binned, rows_sh)
        y_dev = jax.device_put(y_cls, row1_sh)
        chan_dev = jax.device_put(chan_base, rows_sh)
    elif not host_hist:
        grow = _grow_level_trees
        binned_dev = jnp.asarray(binned)  # uploaded once, reused every level
        y_dev = jnp.asarray(y_cls)
        chan_dev = jnp.asarray(chan_base)

    n_rows = binned.shape[0]  # == n unless mesh-padded

    # Trees batch into chunks whose per-tree [tc, n_rows] weight/routing
    # tensors stay under a fixed budget. One chunk covers every packaged
    # config.
    budget = int(_TREE_CHUNK_BUDGET_BYTES)
    tc = max(1, min(num_trees, budget // max(1, n_rows * 8)))

    def chunk_weights(t0: int, t1: int) -> np.ndarray:
        # drawn per chunk (in order, so the sequence matches an up-front
        # [num_trees, n] draw) to keep peak weight memory chunk-bounded
        if num_trees > 1:
            w = gen.poisson(1.0, (t1 - t0, n)).astype(np.float32)
        else:
            w = np.ones((1, n), np.float32)
        if n_rows != n:  # pad rows arrive inactive (node_of = -1, weight 0)
            w = np.concatenate(
                [w, np.zeros((t1 - t0, n_rows - n), np.float32)], axis=1
            )
        return w

    def level_masks(t0: int, t1: int, num_level: int) -> np.ndarray:
        # per-node mtry masks, vectorized: one uniform key per allowed
        # column, smallest-m keys win — a uniform random m-subset per
        # node in one pass (the per-node gen.choice loop was ~0.5s of
        # host time for a 20-tree depth-10 training)
        m = min(mtry, pa)
        mask_t = np.zeros((t1 - t0, num_level, p), dtype=np.float32)
        if m >= pa:
            mask_t[:, :, allowed] = 1.0
        else:
            keys = gen.random((t1 - t0, num_level, pa), dtype=np.float32)
            pick = np.argpartition(keys, m, axis=2)[:, :, :m]
            np.put_along_axis(
                mask_t.reshape((t1 - t0) * num_level, p),
                allowed[pick].reshape((t1 - t0) * num_level, m),
                1.0,
                axis=1,
            )
        return mask_t

    t_iter = _time.perf_counter()
    if host_hist:
        _train_host_chunks(
            binned, y_cls, chan_base, num_classes, allowed_vec, num_bins,
            imp_kind, min_node_size, min_info_gain, max_depth, num_trees, tc,
            chunk_weights, level_masks,
            t_feat, t_bin, t_stats, t_counts, t_gains,
        )
        last_phase_seconds.clear()
        last_phase_seconds.update(
            init=t_iter - t_init, iterate=_time.perf_counter() - t_iter
        )
        return ForestArrays(t_feat, t_bin, t_stats, t_counts, t_gains, num_classes)

    # The chunk's whole forest advances one depth per dispatch (lax.scan
    # over trees inside the level kernel). The level loop syncs each
    # level's splits (one small [T, L] array) and exits as soon as no
    # node anywhere can still split — an all-leaf level is never
    # dispatched.
    for t0 in range(0, num_trees, tc):
        t1 = min(t0 + tc, num_trees)
        w_c = chunk_weights(t0, t1)
        node_c = np.where(w_c > 0, 0, -1).astype(np.int32)  # [tc, n_rows]
        if mesh is not None:
            w_dev = jax.device_put(w_c, trow1_sh)
            node_dev = jax.device_put(node_c, trow1_sh)
        else:
            w_dev = jnp.asarray(w_c)
            node_dev = jnp.asarray(node_c)
        level_out = []
        for depth in range(max_depth + 1):
            level_start = 2**depth - 1
            num_level = 2**depth
            mask_t = level_masks(t0, t1, num_level)
            sf, sb, gains, node_tot, node_dev = grow(
                binned_dev,
                y_dev,
                chan_dev,
                w_dev,
                node_dev,
                jnp.asarray(mask_t),
                allowed_vec,
                level_start,
                num_level,
                num_bins,
                imp_kind,
                np.float32(min_node_size),
                np.float32(min_info_gain),
                depth == max_depth,
                hist_mode,
            )
            for a in (sb, gains, node_tot):
                a.copy_to_host_async()
            level_out.append((level_start, num_level, sf, sb, gains, node_tot))
            # exact level-wise early exit: no split at this level means
            # every deeper level is all-leaf — don't dispatch it
            if np.all(np.asarray(sf) < 0):
                break
        for level_start, num_level, sf, sb, gains, node_tot in level_out:
            sl = slice(level_start, level_start + num_level)
            node_tot = np.asarray(node_tot)  # [tc, L, S]
            t_feat[t0:t1, sl] = np.asarray(sf)
            t_bin[t0:t1, sl] = np.asarray(sb)
            t_stats[t0:t1, sl] = node_tot
            t_counts[t0:t1, sl] = (
                node_tot[..., 0] if num_classes is None else node_tot.sum(axis=2)
            )
            t_gains[t0:t1, sl] = np.asarray(gains)
    last_phase_seconds.clear()
    last_phase_seconds.update(
        init=t_iter - t_init, iterate=_time.perf_counter() - t_iter
    )
    return ForestArrays(t_feat, t_bin, t_stats, t_counts, t_gains, num_classes)


def _train_host_chunks(
    binned, y_cls, chan_base, num_classes, allowed_vec, num_bins,
    imp_kind, min_node_size, min_info_gain, max_depth, num_trees, tc,
    chunk_weights, level_masks,
    t_feat, t_bin, t_stats, t_counts, t_gains,
):
    """Host fast-path level loop (CPU backend, no mesh): np.bincount
    histograms restricted to each tree's LIVE nodes — the children of the
    previous level's splits — with split selection through the same
    jitted gain kernel as the device path. Mirrors the device path's RNG
    consumption exactly (same weight/mask draw schedule, same chunk-wide
    level-loop exit), so both paths grow identical forests on a seed."""
    n, p = binned.shape
    s = chan_base.shape[1]
    if num_classes is None:
        ybase = (chan_base[:, 1].astype(np.float64), chan_base[:, 2].astype(np.float64))
        y64 = None
    else:
        ybase = None
        y64 = y_cls.astype(np.int64)
    mins = (np.float32(min_node_size), np.float32(min_info_gain))

    # group features by occupied bin width (rounded up to a power of two
    # to bound the jit shape set): binary/one-hot columns score over a
    # 2-bin candidate axis instead of the full num_bins one
    nb_f = binned.max(axis=0).astype(np.int64) + 1
    pow2 = 2 ** np.ceil(np.log2(np.maximum(nb_f, 2))).astype(np.int64)
    widths = np.minimum(pow2, num_bins)
    groups = []  # (width, feats ascending, [pg, n] binned.T slice)
    for width in sorted(set(widths.tolist())):
        feats = np.nonzero(widths == width)[0]
        groups.append((int(width), feats, np.ascontiguousarray(binned[:, feats].T)))
    # node totals come from feature 0's histogram (first slot of its group:
    # feats are ascending, so feature 0 is slot 0 when present)
    g0 = next(i for i, (wd, _, _) in enumerate(groups) if wd == widths[0])

    for t0 in range(0, num_trees, tc):
        t1 = min(t0 + tc, num_trees)
        w_c = chunk_weights(t0, t1)
        node_c = np.where(w_c > 0, 0, -1).astype(np.int32)  # [tc, n]
        w64 = w_c.astype(np.float64)
        # per-tree live-node heap positions for the CURRENT level
        alive = [np.array([0], dtype=np.int64) for _ in range(t1 - t0)]
        for depth in range(max_depth + 1):
            level_start = 2**depth - 1
            num_level = 2**depth
            mask_t = level_masks(t0, t1, num_level)
            any_split = False
            for ti in range(t1 - t0):
                alive_pos = alive[ti]
                la = len(alive_pos)
                if la == 0:
                    continue
                lp = _pow2_at_least(la)  # pad slots: bounded compile set
                inv = np.full(num_level, lp, dtype=np.int64)
                inv[alive_pos] = np.arange(la)
                pos = node_c[ti].astype(np.int64) - level_start
                in_level = (pos >= 0) & (pos < num_level)
                compact = np.where(in_level, inv[np.where(in_level, pos, 0)], lp)
                group_hists = [
                    _host_level_hists(bt, w64[ti], y64, ybase, compact, lp, wd, s)
                    for wd, _, bt in groups
                ]
                node_tot = group_hists[g0][0].sum(axis=1)  # [lp, S]
                # score each group's trimmed candidate grid on the shared
                # gain kernel, then merge: max gain wins, ties go to the
                # lowest (feature * num_bins + bin) flat index — exactly
                # the device kernel's single flat argmax
                cand = []  # (gain_m, flat_m, gain_a, flat_a) per group
                for (wd, feats, _), gh in zip(groups, group_hists):
                    fm = np.zeros((lp, len(feats)), np.float32)
                    fm[:la] = mask_t[ti, alive_pos][:, feats]
                    bm, gm, ba, ga = _eval_group_hists(
                        gh, node_tot, fm, allowed_vec[feats], mins,
                        imp_kind, num_bins,
                    )
                    bm, gm, ba, ga = (np.asarray(a) for a in (bm, gm, ba, ga))
                    flat_m = feats[bm // wd] * num_bins + bm % wd
                    flat_a = feats[ba // wd] * num_bins + ba % wd
                    cand.append((gm, flat_m, ga, flat_a))

                def _merge(gs, flats):
                    g = np.stack(gs)  # [G, lp]
                    f = np.stack(flats)
                    top = g.max(axis=0)
                    return top, np.where(g == top, f, np.iinfo(np.int64).max).min(axis=0)

                gain_m, flat_m = _merge([c[0] for c in cand], [c[1] for c in cand])
                gain_a, flat_a = _merge([c[2] for c in cand], [c[3] for c in cand])
                use_masked = gain_m > mins[1]
                best_gain = np.where(use_masked, gain_m, gain_a)
                best_flat = np.where(use_masked, flat_m, flat_a)
                do_split = (best_gain > mins[1]) & np.isfinite(best_gain)
                if depth == max_depth:  # device kernel's is_last_level
                    do_split[:] = False
                sf = np.where(do_split, best_flat // num_bins, -1).astype(np.int32)
                sb = np.where(do_split, best_flat % num_bins, -1).astype(np.int32)
                gains = np.where(do_split, best_gain, 0.0)
                heap = level_start + alive_pos
                t_feat[t0 + ti, heap] = sf[:la]
                t_bin[t0 + ti, heap] = sb[:la]
                t_stats[t0 + ti, heap] = node_tot[:la]
                t_counts[t0 + ti, heap] = (
                    node_tot[:la, 0] if num_classes is None else node_tot[:la].sum(axis=1)
                )
                t_gains[t0 + ti, heap] = gains[:la]
                # route rows: split rows descend, the rest freeze
                full_sf = np.full(num_level, -1, np.int32)
                full_sf[alive_pos] = sf[:la]
                full_sb = np.full(num_level, -1, np.int32)
                full_sb[alive_pos] = sb[:la]
                pos_c = np.where(in_level, pos, 0)
                ex_feat = full_sf[pos_c]
                ex_bin = full_sb[pos_c]
                ex_split = (ex_feat >= 0) & in_level
                node_heap = (pos_c + level_start).astype(np.int32)
                goes_pos = binned[np.arange(n), np.maximum(ex_feat, 0)] > ex_bin
                child = 2 * node_heap + 1 + goes_pos.astype(np.int32)
                node_c[ti] = np.where(
                    ex_split, child, np.where(in_level, -node_heap - 2, node_c[ti])
                )
                split_heap = heap[sf[:la] >= 0]
                if len(split_heap):
                    any_split = True
                    alive[ti] = np.sort(
                        np.concatenate([2 * split_heap + 1, 2 * split_heap + 2])
                    ) - (2 ** (depth + 1) - 1)
                else:
                    alive[ti] = np.empty(0, dtype=np.int64)
            if not any_split:
                break


def feature_importances(forest: ForestArrays, num_features: int) -> np.ndarray:
    """Total weighted impurity decrease per feature, normalized to max 1
    (DecisionForest feature-importance semantics)."""
    imp = np.zeros(num_features)
    feat = forest.split_feature
    weight = forest.node_counts * forest.gains
    for t in range(forest.num_trees):
        mask = feat[t] >= 0
        np.add.at(imp, feat[t][mask], weight[t][mask])
    m = imp.max()
    return imp / m if m > 0 else imp


def predict_forest_binned(forest: ForestArrays, binned: np.ndarray) -> np.ndarray:
    """Vectorized inference over the flat heap arrays (device-friendly):
    returns [n, C] summed class counts or [n, 2] (sum, count) pooled."""
    binned = jnp.asarray(binned, dtype=jnp.int32)
    sf = jnp.asarray(forest.split_feature)
    sb = jnp.asarray(forest.split_bin)
    stats = jnp.asarray(forest.node_stats, dtype=jnp.float32)
    max_depth = int(np.log2(forest.split_feature.shape[1] + 1)) - 1

    @jax.jit
    def run(x):
        n = x.shape[0]

        def one_tree(carry, ti):
            node = jnp.zeros(n, dtype=jnp.int32)

            def step(_, node_):
                f = sf[ti][node_]
                b = sb[ti][node_]
                is_split = f >= 0
                goes_pos = x[jnp.arange(n), jnp.maximum(f, 0)] > b
                child = 2 * node_ + 1 + goes_pos.astype(jnp.int32)
                return jnp.where(is_split, child, node_)

            node = jax.lax.fori_loop(0, max_depth + 1, step, node)
            return carry + stats[ti][node], None

        acc, _ = jax.lax.scan(one_tree, jnp.zeros((n, stats.shape[2])), jnp.arange(sf.shape[0]))
        return acc

    return np.asarray(run(binned))
