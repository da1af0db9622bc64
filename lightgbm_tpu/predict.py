"""Jitted batch prediction: level-synchronous tree walks on device.

Reference analogs: the fork's batch path ``GBDT::PredictRawBatch``
(src/boosting/gbdt_prediction.cpp:60) -> ``PredictTreeBatchAVX512``
(include/LightGBM/tree_avx512.hpp:41) — 8-row level-synchronous walks; and the
scalar ``Tree::Predict`` (include/LightGBM/tree.h:596).

TPU-native formulation: ALL rows x ALL trees advance one level per step of a
``lax.while_loop`` — the AVX512 kernel's ``nodes[8]`` array becomes a
``[rows, trees]`` node-index matrix, every step is a pair of gathers plus a
compare (vectorized over the full batch), and the loop exits when every walk
has reached a leaf.  Two variants:

  * bin space (exact, used when BinMappers are available): decisions are
    ``bin <= split_bin`` with the NaN-bin default-direction rule — bit-for-bit
    the same decisions the trainer made;
  * real-value space (used for models loaded from text without mappers):
    ``NumericalDecision`` semantics (tree.h:346) in f32.
"""

from __future__ import annotations

import functools
import time
from collections import deque
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from .obs.device import sample_device_memory
from .obs.jit import instrumented_jit, note_executable
from .obs.registry import get_session
from .ops.score_lookup import lookup_form, tree_values
from .ops.tensor_forest import (
    _tensor_bins_leaves_impl,
    _tensor_bins_pertree_impl,
    build_tensor_forest,
    parity_probe_reason,
    tensor_reject_reason,
)
from .tree import (
    K_CATEGORICAL_MASK,
    K_DEFAULT_LEFT_MASK,
    K_ZERO_THRESHOLD,
    MISSING_NAN,
    MISSING_ZERO,
    Tree,
)


class BinTreeBatch(NamedTuple):
    """Stacked bin-space trees [T, ...]; bin-space mirrors the trainer."""

    split_feature: jnp.ndarray  # [T, M] used-feature column index
    split_bin: jnp.ndarray  # [T, M] int32
    default_left: jnp.ndarray  # [T, M] bool
    left_child: jnp.ndarray  # [T, M] int32 (neg = ~leaf)
    right_child: jnp.ndarray  # [T, M] int32
    leaf_value: jnp.ndarray  # [T, L] f32
    split_is_cat: jnp.ndarray  # [T, M] bool
    cat_mask: jnp.ndarray  # [T, M, Bm] bool — bin goes left (Bm=1 if no cat)


class RealTreeBatch(NamedTuple):
    """Stacked real-value trees (categoricals as per-node value bitsets)."""

    split_feature: jnp.ndarray  # [T, M] original feature index
    threshold: jnp.ndarray  # [T, M] f32
    decision_type: jnp.ndarray  # [T, M] int32
    left_child: jnp.ndarray  # [T, M] int32
    right_child: jnp.ndarray  # [T, M] int32
    leaf_value: jnp.ndarray  # [T, L] f32
    cat_words: jnp.ndarray  # [T, M, W] uint32 bitset over category VALUES
    cat_nwords: jnp.ndarray  # [T, M] int32 valid word count per node


def stack_bin_trees(records: List[dict], num_leaves_cap: int) -> BinTreeBatch:
    """Pad per-tree bin-space arrays (host dicts) into one [T, ...] batch."""
    t = len(records)
    m = max(1, max(len(r["split_feature"]) for r in records))
    # merged init-model trees may exceed the current config's num_leaves
    L = max(1, num_leaves_cap, max(len(r["leaf_value"]) for r in records))

    def padded(key, fill, dtype):
        out = np.full((t, m), fill, dtype=dtype)
        for i, r in enumerate(records):
            arr = np.asarray(r[key])
            out[i, : len(arr)] = arr
        return out

    leaf = np.zeros((t, L), dtype=np.float32)
    for i, r in enumerate(records):
        lv = np.asarray(r["leaf_value"], dtype=np.float32)
        leaf[i, : len(lv)] = lv
    left = padded("left_child", -1, np.int32)
    # single-leaf trees: route node 0 to leaf 0
    for i, r in enumerate(records):
        if len(r["split_feature"]) == 0:
            left[i, 0] = -1
    # categorical masks: width = max over trees (1 when no tree has any)
    bm = max(
        [1]
        + [
            np.asarray(r["cat_mask"]).shape[1]
            for r in records
            if r.get("cat_mask") is not None and np.size(r.get("cat_mask"))
        ]
    )
    is_cat = np.zeros((t, m), dtype=bool)
    cmask = np.zeros((t, m, bm), dtype=bool)
    for i, r in enumerate(records):
        sic = r.get("split_is_cat")
        cm = r.get("cat_mask")
        if sic is not None and len(sic):
            is_cat[i, : len(sic)] = sic
        if cm is not None and np.size(cm):
            cm = np.asarray(cm)
            cmask[i, : cm.shape[0], : cm.shape[1]] = cm
    return BinTreeBatch(
        split_feature=jnp.asarray(padded("split_feature", 0, np.int32)),
        split_bin=jnp.asarray(padded("split_bin", 0, np.int32)),
        default_left=jnp.asarray(padded("default_left", False, bool)),
        left_child=jnp.asarray(left),
        right_child=jnp.asarray(padded("right_child", -1, np.int32)),
        leaf_value=jnp.asarray(leaf),
        split_is_cat=jnp.asarray(is_cat),
        cat_mask=jnp.asarray(cmask),
    )


def stack_real_trees(trees: List[Tree]) -> RealTreeBatch:
    t = len(trees)
    m = max(1, max(tr.num_leaves - 1 for tr in trees))
    L = max(1, max(tr.num_leaves for tr in trees))
    sf = np.zeros((t, m), dtype=np.int32)
    th = np.zeros((t, m), dtype=np.float32)
    dt = np.zeros((t, m), dtype=np.int32)
    lc = np.full((t, m), -1, dtype=np.int32)
    rc = np.full((t, m), -1, dtype=np.int32)
    lv = np.zeros((t, L), dtype=np.float32)
    # per-node category-value bitsets (reference cat_threshold_ words,
    # tree.h:283): W = widest bitset across all cat nodes, 1 if none
    w = 1
    for tr in trees:
        if tr.cat_boundaries is not None:
            for ci in range(len(tr.cat_boundaries) - 1):
                w = max(w, int(tr.cat_boundaries[ci + 1] - tr.cat_boundaries[ci]))
    cw = np.zeros((t, m, w), dtype=np.uint32)
    cn = np.zeros((t, m), dtype=np.int32)
    for i, tr in enumerate(trees):
        nn = tr.num_leaves - 1
        sf[i, :nn] = tr.split_feature
        th[i, :nn] = tr.threshold
        dt[i, :nn] = tr.decision_type
        lc[i, :nn] = tr.left_child
        rc[i, :nn] = tr.right_child
        lv[i, : tr.num_leaves] = tr.leaf_value
        if tr.cat_boundaries is not None:
            for node in range(nn):
                if tr.decision_type[node] & 1:
                    ci = int(tr.threshold[node])
                    b0 = int(tr.cat_boundaries[ci])
                    b1 = int(tr.cat_boundaries[ci + 1])
                    cw[i, node, : b1 - b0] = tr.cat_threshold[b0:b1]
                    cn[i, node] = b1 - b0
    return RealTreeBatch(
        split_feature=jnp.asarray(sf),
        threshold=jnp.asarray(th),
        decision_type=jnp.asarray(dt),
        left_child=jnp.asarray(lc),
        right_child=jnp.asarray(rc),
        leaf_value=jnp.asarray(lv),
        cat_words=jnp.asarray(cw),
        cat_nwords=jnp.asarray(cn),
    )


def _walk(gather_decide, left, right, n_rows: int, n_trees: int):
    """Shared level-synchronous loop: advance [rows, trees] node indices."""
    tree_ids = jnp.arange(n_trees, dtype=jnp.int32)[None, :]

    def cond(nodes):
        return jnp.any(nodes >= 0)

    def body(nodes):
        cur = jnp.maximum(nodes, 0)
        go_left = gather_decide(cur, tree_ids)
        nxt = jnp.where(
            go_left, left[tree_ids, cur], right[tree_ids, cur]
        )
        return jnp.where(nodes >= 0, nxt, nodes)

    nodes0 = jnp.zeros((n_rows, n_trees), dtype=jnp.int32)
    return lax.while_loop(cond, body, nodes0)


def _predict_bins_leaves_impl(batch: BinTreeBatch, bins: jnp.ndarray, nan_bins: jnp.ndarray) -> jnp.ndarray:
    """Leaf index per (row, tree). bins: [N, F_used] int32; nan_bins: [F_used]."""
    n = bins.shape[0]
    t = batch.split_feature.shape[0]

    def decide(cur, tree_ids):
        feat = batch.split_feature[tree_ids, cur]  # [N, T]
        tbin = batch.split_bin[tree_ids, cur]
        dl = batch.default_left[tree_ids, cur]
        # GL012: jax 0.9's take_along_axis converts its indices through the
        # default int dtype, int32 with x64 off, which is how every entry runs
        fval = jnp.take_along_axis(bins, feat, axis=1)  # graftlint: disable=GL012
        nb = nan_bins[feat]
        gl = (fval <= tbin) | (dl & (nb >= 0) & (fval == nb))
        bm = batch.cat_mask.shape[-1]
        if bm > 1:
            # one joint gather to [N, T] — a two-step index would materialize
            # an [N, T, Bm] intermediate inside every walk iteration
            gl_cat = batch.cat_mask[tree_ids, cur, jnp.minimum(fval, bm - 1)]
            # out-of-range bins (unseen-category sentinel) are never in the
            # left subset (reference CategoricalDecision, tree.h:382)
            gl_cat = gl_cat & (fval < bm)
            gl = jnp.where(batch.split_is_cat[tree_ids, cur], gl_cat, gl)
        return gl

    nodes = _walk(decide, batch.left_child, batch.right_child, n, t)
    return ~nodes  # [N, T] leaf indices


predict_bins_leaves = instrumented_jit(_predict_bins_leaves_impl, label="predict/bins_leaves")


def _predict_bins_raw_impl(batch: BinTreeBatch, bins: jnp.ndarray, nan_bins: jnp.ndarray) -> jnp.ndarray:
    """Sum of per-tree outputs [N, T] (caller groups by class and sums)."""
    leaves = _predict_bins_leaves_impl(batch, bins, nan_bins)
    t = batch.split_feature.shape[0]
    tree_ids = jnp.arange(t, dtype=jnp.int32)[None, :]
    return batch.leaf_value[tree_ids, leaves]  # [N, T]


predict_bins_raw = instrumented_jit(_predict_bins_raw_impl, label="predict/bins_raw")


def _predict_real_leaves_impl(batch: RealTreeBatch, X: jnp.ndarray) -> jnp.ndarray:
    """Leaf index per (row, tree) with NumericalDecision semantics (f32)."""
    n = X.shape[0]
    t = batch.split_feature.shape[0]

    def decide(cur, tree_ids):
        feat = batch.split_feature[tree_ids, cur]
        thr = batch.threshold[tree_ids, cur]
        dt = batch.decision_type[tree_ids, cur]
        fval = jnp.take_along_axis(X, feat, axis=1)
        missing = (dt >> 2) & 3
        is_nan = jnp.isnan(fval)
        fv = jnp.where(is_nan & (missing != MISSING_NAN), 0.0, fval)
        is_missing = ((missing == MISSING_ZERO) & (jnp.abs(fv) <= K_ZERO_THRESHOLD)) | (
            (missing == MISSING_NAN) & jnp.isnan(fv)
        )
        dl = (dt & K_DEFAULT_LEFT_MASK) != 0
        gl = jnp.where(is_missing, dl, fv <= thr)
        # categorical: bit test in the node's value bitset; NaN/negative/
        # out-of-range values go right (CategoricalDecision, tree.h:346)
        wmax = batch.cat_words.shape[-1]
        is_cat = (dt & 1) != 0
        iv = jnp.where(is_nan | (fval < 0), -1, fval).astype(jnp.int32)
        word_idx = jnp.clip(iv // 32, 0, wmax - 1)
        words = batch.cat_words[tree_ids, cur]  # [N, T, W]
        word = jnp.take_along_axis(words, word_idx[..., None], axis=2)[..., 0]
        in_range = (iv >= 0) & ((iv // 32) < batch.cat_nwords[tree_ids, cur])
        bit = (word >> (iv % 32).astype(jnp.uint32)) & 1
        return jnp.where(is_cat, in_range & (bit == 1), gl)

    nodes = _walk(decide, batch.left_child, batch.right_child, n, t)
    return ~nodes


predict_real_leaves = instrumented_jit(_predict_real_leaves_impl, label="predict/real_leaves")


def _predict_real_raw_impl(batch: RealTreeBatch, X: jnp.ndarray) -> jnp.ndarray:
    leaves = _predict_real_leaves_impl(batch, X)
    t = batch.split_feature.shape[0]
    tree_ids = jnp.arange(t, dtype=jnp.int32)[None, :]
    return batch.leaf_value[tree_ids, leaves]


predict_real_raw = instrumented_jit(_predict_real_raw_impl, label="predict/real_raw")


def _stacked_bins_value_impl(batch: BinTreeBatch, nan_bins: jnp.ndarray, bins: jnp.ndarray):
    """Engine-facing order: tables first, data chunk LAST (the streaming
    executables all take the chunk as their final argument)."""
    return _predict_bins_raw_impl(batch, bins, nan_bins)


def _stacked_bins_leaves_impl(batch: BinTreeBatch, nan_bins: jnp.ndarray, bins: jnp.ndarray):
    return _predict_bins_leaves_impl(batch, bins, nan_bins)


def _cat_width(cat_mask) -> int:
    """Bins a tree's categorical bitmaps span: 1 (or no mask) = none."""
    return 1 if cat_mask is None else cat_mask.shape[-1]


def valid_walk_form(num_leaves: int, cat_width: int) -> str:
    """Which form scores one bin-space tree over a binned matrix, from the two
    static shapes the choice rests on: ``"contract"`` (ops/score_lookup.py:
    node decisions and a path match on the MXU) for a numeric tree,
    ``"walk"`` (the ``while_loop`` of per-row gathers below) for a tree that
    may hold categorical splits — their predicate indexes a per-node bitmap
    by the row's bin, a gather by nature — or more leaves than the
    contraction's [L, L] tables are worth."""
    if cat_width > 1 or lookup_form(num_leaves) == "gather":
        return "walk"
    return "contract"


def _walk_tree_values(
    bins, nan_bins, split_feature, split_bin, default_left, left_child,
    right_child, leaf_value, split_is_cat=None, cat_mask=None,
) -> jnp.ndarray:
    """[N] leaf values by a level-synchronous walk: seven gathers a level."""
    n = bins.shape[0]
    use_cat = cat_mask is not None and cat_mask.shape[-1] > 1

    def cond(nodes):
        return jnp.any(nodes >= 0)

    def body(nodes):
        cur = jnp.maximum(nodes, 0)
        feat = split_feature[cur]
        tbin = split_bin[cur]
        dl = default_left[cur]
        # GL012: as in _predict_bins_leaves_impl, the int64 is jax's own
        # index conversion under enable_x64
        fval = jnp.take_along_axis(bins, feat[:, None], axis=1)[:, 0]  # graftlint: disable=GL012
        nb = nan_bins[feat]
        go_left = (fval <= tbin) | (dl & (nb >= 0) & (fval == nb))
        if use_cat:
            bm = cat_mask.shape[-1]
            gl_cat = cat_mask[cur, jnp.minimum(fval, bm - 1)] & (fval < bm)
            go_left = jnp.where(split_is_cat[cur], gl_cat, go_left)
        nxt = jnp.where(go_left, left_child[cur], right_child[cur])
        return jnp.where(nodes >= 0, nxt, nodes)

    nodes = lax.while_loop(cond, body, jnp.zeros((n,), jnp.int32))
    return leaf_value[~nodes]


def _add_tree_to_score_impl(
    score_k: jnp.ndarray,  # [N] f32 (donated in the jitted wrappers)
    bins: jnp.ndarray,  # [N, F_used]
    nan_bins: jnp.ndarray,  # [F_used]
    split_feature: jnp.ndarray,  # [L-1]
    split_bin: jnp.ndarray,
    default_left: jnp.ndarray,
    left_child: jnp.ndarray,
    right_child: jnp.ndarray,
    leaf_value: jnp.ndarray,  # [L] ALREADY shrunk
    split_is_cat: Optional[jnp.ndarray] = None,  # [L-1] bool
    cat_mask: Optional[jnp.ndarray] = None,  # [L-1, Bm] bool
    row_mesh: Optional[Tuple[Any, Any]] = None,  # static (mesh, row axis)
) -> jnp.ndarray:
    """Score one bin-space tree over a dataset and add leaf outputs to score —
    the valid-set ScoreUpdater::AddScore (src/boosting/score_updater.hpp:54).
    The form follows the tree's static shapes (``valid_walk_form``); both
    give the same leaf and so the same bits.  ``row_mesh`` (``row_mesh_of``
    the binned matrix) makes the work local to each shard of the rows: a
    row's leaf depends on that row alone, so no collective is needed, and the
    contraction's row blocks stay blocks of the local rows."""
    tree = (split_feature, split_bin, default_left, left_child, right_child,
            leaf_value)
    if valid_walk_form(leaf_value.shape[0], _cat_width(cat_mask)) == "contract":
        values = tree_values
    else:
        values = _walk_tree_values
        if cat_mask is not None:
            tree += (split_is_cat, cat_mask)

    with jax.named_scope("score_update"):
        if row_mesh is not None:
            from .parallel import _shard_map

            mesh, axis = row_mesh
            values = _shard_map(
                values, mesh=mesh,
                in_specs=(P(axis, None),) + (P(),) * (1 + len(tree)),
                out_specs=P(axis),
            )
        return score_k + values(bins, nan_bins, *tree)


def row_mesh_of(bins) -> Optional[Tuple[Any, Any]]:
    """``(mesh, axis)`` where the rows of a placed array are sharded over a
    mesh axis (``tree_learner=data``), else None: what the jitted entries of
    the validation score take as their static ``row_mesh``."""
    sh = getattr(bins, "sharding", None)
    if not isinstance(sh, NamedSharding) or not len(sh.spec):
        return None
    axis = sh.spec[0]
    if axis is None or sh.mesh.size == 1:
        return None
    return sh.mesh, axis


_add_tree_to_score_jit = instrumented_jit(
    _add_tree_to_score_impl, label="add_tree_to_score", donate_argnums=(0,),
    static_argnames=("row_mesh",),
)


def count_valid_tree(leaf_value, cat_mask) -> None:
    """One tree scored over one binned matrix: the session counter of its
    form (``score/valid_contract_trees`` / ``score/valid_walk_trees``)."""
    form = valid_walk_form(leaf_value.shape[0], _cat_width(cat_mask))
    get_session().inc(f"score/valid_{form}_trees")


def add_tree_to_score(
    score_k, bins, nan_bins, split_feature, split_bin, default_left,
    left_child, right_child, leaf_value, split_is_cat=None, cat_mask=None,
):
    """Standalone entry (valid-score updates call it once per tree with a
    dead score row: the old buffer is donated back to the allocator)."""
    count_valid_tree(leaf_value, cat_mask)
    return _add_tree_to_score_jit(
        score_k, bins, nan_bins, split_feature, split_bin, default_left,
        left_child, right_child, leaf_value, split_is_cat, cat_mask,
        row_mesh=row_mesh_of(bins),
    )


# ---------------------------------------------------------------------------
# Streaming batch-prediction engine (the fork's PredictRawBatch pipeline,
# original.md / SURVEY §2.9): fixed-size chunks padded to a power-of-two
# bucket ladder so every chunk hits a cached compiled executable, with
# double-buffered host prep (binning chunk k+1 while chunk k walks the
# forest) and optional row-sharding over a local device mesh.
# ---------------------------------------------------------------------------

LADDER_MIN = 256  # smallest bucket: tiny requests pad here, not per-size


def bucket_rows(rows: int, chunk: int) -> int:
    """Smallest ladder bucket >= rows: powers of two from LADDER_MIN up,
    capped at the full chunk size (chunk itself need not be a power of two).
    Full chunks always map to `chunk`, so a stream of any length touches at
    most ceil(log2(chunk / LADDER_MIN)) + 1 executables per model."""
    if rows >= chunk:
        return chunk
    b = LADDER_MIN
    while b < rows:
        b <<= 1
    return min(b, chunk)


def ladder_buckets(chunk: int) -> List[int]:
    """Every bucket `bucket_rows` can produce for this chunk size."""
    out = []
    b = LADDER_MIN
    while b < chunk:
        out.append(b)
        b <<= 1
    out.append(chunk)
    return out


class PackedBinForest(NamedTuple):
    """Bin-space forest with all per-node scalars bit-packed into ONE i32
    table (the forest-walk kernel's pk1/pk2 layout, XLA-shaped): a walk
    level costs one node gather + one bin gather + one child gather instead
    of the five separate table gathers of the BinTreeBatch walker."""

    pk1: jnp.ndarray  # [T, M] i32: thr(9) | feat(9)<<9 | dl<<18 | (nanb+1)(10)<<19
    pk2: jnp.ndarray  # [T, M] i32: (left+base)(16) | (right+base)<<16 (neg = ~leaf)
    leaf: jnp.ndarray  # [T, L] f32 leaf values


_PACK_THR = 512  # split/NaN bins must fit 9/10-bit fields
_PACK_F = 512  # feature index field is 9 bits
_PACK_BASE = 32768  # children are offset by base in 16-bit halves


def packed_reject_reason(records, nan_bins: np.ndarray, num_features: int):
    """None when the packed walker covers this model exactly, else why not
    (categorical splits, wide bins, or wide trees keep the general walker)."""
    if num_features > _PACK_F:
        return f"{num_features} bin columns > {_PACK_F}"
    if len(nan_bins) and int(np.max(nan_bins)) >= _PACK_THR:
        return f"a NaN bin >= {_PACK_THR}"
    base = 1
    for r in records:
        sf = r.get("split_feature")
        if sf is None:
            return "a tree has no bin-space record"
        sic = r.get("split_is_cat")
        if sic is not None and np.any(np.asarray(sic)):
            return "categorical splits"
        if len(sf) and int(np.max(np.asarray(r["split_bin"]))) >= _PACK_THR:
            return f"a split threshold bin >= {_PACK_THR}"
        base = max(base, len(sf) + 1, len(r["leaf_value"]))
    if base >= _PACK_BASE:
        return f"{base} leaves >= {_PACK_BASE}"
    return None


def build_packed_bin_tables(records, nan_bins: np.ndarray) -> Tuple[PackedBinForest, int]:
    """Stack bin-space records into packed tables; caller checked
    `packed_reject_reason`.  Returns (tables, base) — base is the child
    offset (max of node/leaf counts) the walker subtracts back out."""
    t = len(records)
    m = max(1, max(len(r["split_feature"]) for r in records))
    L = max(1, max(len(r["leaf_value"]) for r in records))
    base = max(m, L)
    pk1 = np.zeros((t, m), np.int32)
    pk2 = np.zeros((t, m), np.int32)
    leaf = np.zeros((t, L), np.float32)
    nan_bins = np.asarray(nan_bins, np.int64)
    for i, r in enumerate(records):
        sf = np.asarray(r["split_feature"], np.int64)
        nn = len(sf)
        lv = np.asarray(r["leaf_value"], np.float32)
        leaf[i, : len(lv)] = lv
        if nn == 0:
            # single-leaf tree: node 0 routes every row to leaf 0
            pk2[i, 0] = (~0 + base) | ((~0 + base) << 16)
            continue
        thr = np.asarray(r["split_bin"], np.int64)
        dl = np.asarray(r["default_left"], np.int64)
        lc = np.asarray(r["left_child"], np.int64)
        rc = np.asarray(r["right_child"], np.int64)
        nb = nan_bins[sf] + 1  # 0 = no NaN bin
        pk1[i, :nn] = (thr | (sf << 9) | (dl << 18) | (nb << 19)).astype(np.int32)
        pk2[i, :nn] = ((lc + base) | ((rc + base) << 16)).astype(np.int32)
    return (
        PackedBinForest(
            pk1=jnp.asarray(pk1), pk2=jnp.asarray(pk2), leaf=jnp.asarray(leaf)
        ),
        base,
    )


def _packed_walk_nodes(forest: PackedBinForest, bins: jnp.ndarray, base: int):
    """Level-synchronous walk over packed tables -> final [N, T] node state
    (negative = ~leaf).  Decision rule identical to the BinTreeBatch walker:
    go left iff fval <= thr, or the feature's NaN bin matches under
    default_left."""
    n = bins.shape[0]
    t = forest.pk1.shape[0]
    tree_ids = jnp.arange(t, dtype=jnp.int32)[None, :]

    def cond(nodes):
        return jnp.any(nodes >= 0)

    def body(nodes):
        cur = jnp.maximum(nodes, 0)
        p1 = forest.pk1[tree_ids, cur]
        thr = p1 & 0x1FF
        feat = (p1 >> 9) & 0x1FF
        dl = (p1 >> 18) & 1
        nb = ((p1 >> 19) & 0x3FF) - 1
        fval = jnp.take_along_axis(bins, feat, axis=1)
        gl = (fval <= thr) | ((dl != 0) & (nb >= 0) & (fval == nb))
        p2 = forest.pk2[tree_ids, cur]
        child = jnp.where(gl, p2 & 0xFFFF, (p2 >> 16) & 0xFFFF) - base
        return jnp.where(nodes >= 0, child, nodes)

    return lax.while_loop(cond, body, jnp.zeros((n, t), jnp.int32))


def _packed_bins_pertree_impl(forest: PackedBinForest, bins: jnp.ndarray, *, base: int):
    """Per-tree leaf outputs [N, T] f32 via the packed walker."""
    nodes = _packed_walk_nodes(forest, bins, base)
    t = forest.pk1.shape[0]
    tree_ids = jnp.arange(t, dtype=jnp.int32)[None, :]
    return forest.leaf[tree_ids, ~nodes]


def _packed_bins_leaves_impl(forest: PackedBinForest, bins: jnp.ndarray, *, base: int):
    """Leaf index per (row, tree) [N, T] i32 via the packed walker."""
    return ~_packed_walk_nodes(forest, bins, base)


# executables are shared ACROSS boosters (like jit's global cache): the key
# is shapes + statics only, tables arrive as call arguments.  A scoped
# engine (serving registry) prepends its scope string so two co-resident
# models never collide on a key even at identical table shapes, and so a
# retired model's executables can be evicted without touching its
# neighbours' (`evict_exec_scope`).
_EXEC_CACHE: Dict[Any, Any] = {}
_COMPILE_COUNT = 0


def streaming_compile_count() -> int:
    """Total bucket executables compiled this process (test hook: asserting
    this stays flat across varying batch sizes proves zero recompiles)."""
    return _COMPILE_COUNT


def evict_exec_scope(scope: str) -> int:
    """Drop every cached executable compiled under `scope` (serving registry
    retirement after drain).  Returns how many entries were evicted.  The
    unscoped (scope=None) shared cache is never touched."""
    if not scope:
        return 0
    dead = [k for k in _EXEC_CACHE if k[0] == scope]
    for k in dead:
        del _EXEC_CACHE[k]
    return len(dead)


# streaming-engine executable bodies by (variant, kind) — the lint IR
# matrix traces the tensor entries straight out of this table so the
# audited callable IS the one the engine AOT-compiles
_STREAM_IMPLS = {
    ("packed", "value"): _packed_bins_pertree_impl,
    ("packed", "leaf"): _packed_bins_leaves_impl,
    ("stacked", "value"): _stacked_bins_value_impl,
    ("stacked", "leaf"): _stacked_bins_leaves_impl,
    ("real", "value"): _predict_real_raw_impl,
    ("real", "leaf"): _predict_real_leaves_impl,
    ("tensor", "value"): _tensor_bins_pertree_impl,
    ("tensor", "leaf"): _tensor_bins_leaves_impl,
}


def _shape_key(tree):
    return tuple(
        (a.shape, str(a.dtype)) for a in jax.tree_util.tree_leaves(tree)
    )


def _clamp_pow2(x: int) -> int:
    p = 1
    while p * 2 <= x:
        p *= 2
    return p


class StreamingPredictor:
    """Chunked, bucket-padded, double-buffered prediction engine.

    The scheduler splits the input into `pred_chunk_rows`-sized chunks, pads
    each to a `bucket_rows` ladder bucket, and feeds an AOT-compiled
    executable per (model shape x bucket x output kind) — so varying batch
    sizes never recompile.  While chunk k walks the forest on device, chunk
    k+1 is binned on host (native `_binning.so` fast path via the
    BinMapper) — jax's async dispatch overlaps the two; `pred_num_buffers`
    bounds how many device outputs may be in flight.  With
    `pred_shard_devices` > 1 each chunk's rows are sharded over a local
    device mesh (pjit data axis), tables replicated.
    """

    def __init__(self, booster, scope: Optional[str] = None):
        self._b = booster
        # scope=None (default) keeps the process-global shared cache and the
        # frozen `predict/stream/{variant}` labels; a registry-owned engine
        # passes its model identity so cache keys and retrace labels become
        # per-model (`predict/stream/{scope}/{variant}`)
        self._scope = scope
        self.last_stats: Dict[str, Any] = {}

    # ------------------------------------------------------------- tables
    def _tables(self, space: str, t0: int, t1: int, engine: str = "walk"):
        """(variant, table_pytree, static_kwargs) for this tree range,
        cached in the booster's _stack_cache (same invalidation discipline
        as the other stacks: any models_ mutation bumps _model_version).
        ``engine`` is the RESOLVED engine ("walk"/"matmul"): the caller
        already ran `resolve_engine`, so "matmul" implies eligibility."""
        b = self._b
        if space == "real":
            return "real", (b._stacked_real(t0, t1),), {}
        recs = b._bin_records[t0:t1]
        nanb = np.asarray(b._nan_bins)
        width = b._bin_matrix_width()
        if engine == "matmul":
            key = ("tf", t0, t1, b._model_version)
            if key not in b._stack_cache:
                b._stack_cache = {
                    kk: v
                    for kk, v in b._stack_cache.items()
                    if kk[0] != "tf"
                }
                b._stack_cache[key] = build_tensor_forest(recs, nanb, width)
            return "tensor", (b._stack_cache[key],), {}
        if packed_reject_reason(recs, nanb, width) is None:
            key = ("pkbin", t0, t1, b._model_version)
            if key not in b._stack_cache:
                b._stack_cache = {
                    kk: v
                    for kk, v in b._stack_cache.items()
                    if kk[0] != "pkbin"
                }
                b._stack_cache[key] = build_packed_bin_tables(recs, nanb)
            forest, base = b._stack_cache[key]
            return "packed", (forest,), {"base": base}
        return "stacked", (b._stacked_bins(t0, t1), b._nan_bins), {}

    # ------------------------------------------------------------- engine
    def resolve_engine(self, engine: str, space: str, t0: int, t1: int):
        """Resolve a ``pred_engine`` request to the engine that will run.

        Returns ``(resolved, reject_reason)`` with resolved in
        {"walk", "matmul"}.  "matmul"/"auto" requests check tensor-forest
        eligibility (cached per model version); "auto" additionally runs
        the host-side byte-parity probe vs the walker.  A fallback emits
        ONE telemetry event + the `pred/engine_selected` gauge per model
        version so the silent walker downgrade is visible in obs_top and
        /metrics."""
        if engine in (None, "", "walk"):
            return "walk", None
        b = self._b
        key = ("tfrej", t0, t1, b._model_version, engine)
        if key not in b._stack_cache:
            b._stack_cache = {
                kk: v for kk, v in b._stack_cache.items() if kk[0] != "tfrej"
            }
            b._stack_cache[key] = self._tensor_reject(engine, space, t0, t1)
        reason = b._stack_cache[key]
        ses = get_session()
        if reason is None:
            if ses.enabled:
                ses.set_gauge("pred/engine_selected", 1.0)
            return "matmul", None
        warn_key = ("tfwarn", t0, t1, b._model_version, engine)
        if warn_key not in b._stack_cache:
            b._stack_cache[warn_key] = True
            if ses.enabled:
                ses.set_gauge("pred/engine_selected", 0.0)
                ses.inc("pred/engine_fallback_total")
                ses.record(
                    {
                        "event": "pred_engine_fallback",
                        "requested": engine,
                        "reason": reason,
                        "trees": t1 - t0,
                    }
                )
        return "walk", reason

    def _tensor_reject(self, engine, space, t0, t1):
        """Eligibility (+ auto's parity probe) — None or the reject reason."""
        b = self._b
        if space != "bin":
            return "real-space model (no bin mappers)"
        recs = b._bin_records[t0:t1]
        nanb = np.asarray(b._nan_bins)
        width = b._bin_matrix_width()
        max_bin = getattr(b, "_max_bin_padded", None)
        reason = tensor_reject_reason(recs, nanb, width, max_bin=max_bin)
        if reason is not None or engine != "auto":
            return reason
        # auto: compile-time byte-parity probe against a reference walk
        # (host numpy on both sides — no device executables, so warmed
        # ladders stay flat)
        _, (forest,), _ = self._tables(space, t0, t1, engine="matmul")
        return parity_probe_reason(
            recs, nanb, forest, width, max_bin or _PACK_THR
        )

    # -------------------------------------------------------- executables
    def _get_exec(self, variant, kind, tables, statics, bucket, width, dtype, ndev):
        global _COMPILE_COUNT
        key = (
            self._scope,
            variant,
            kind,
            bucket,
            width,
            dtype,
            ndev,
            tuple(sorted(statics.items())),
            _shape_key(tables),
        )
        label = (
            f"predict/stream/{self._scope}/{variant}"
            if self._scope
            else f"predict/stream/{variant}"
        )
        hit = _EXEC_CACHE.get(key)
        if hit is not None:
            # device_accounting may have turned on after the miss that
            # compiled this bucket; note_executable dedups per object
            note_executable(label, hit)
            return hit
        impl = _STREAM_IMPLS[(variant, kind)]
        if statics:
            # bind statics up front: pjit rejects kwargs when in_shardings
            # is set, and the cache key already carries their values
            impl = functools.partial(impl, **statics)
        jit_kwargs: Dict[str, Any] = {}
        if ndev > 1:
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

            mesh = Mesh(np.array(jax.local_devices()[:ndev]), ("data",))
            repl = NamedSharding(mesh, P())
            rows = NamedSharding(mesh, P("data"))
            in_sh = tuple(
                jax.tree_util.tree_map(lambda _: repl, t) for t in tables
            ) + (rows,)
            jit_kwargs["in_shardings"] = in_sh
            jit_kwargs["out_shardings"] = NamedSharding(mesh, P("data", None))
        elif jax.default_backend() == "tpu":
            # donate the chunk buffer: the walk never reuses it, and
            # donation lets XLA recycle the H2D staging allocation
            jit_kwargs["donate_argnums"] = (len(tables),)
        # labeled per table variant so suspect re-walk ("real") compiles are
        # separable in compile_counts_by_label(); the lower().compile() below
        # traces exactly once, which instrumented_jit counts at trace time
        fn = instrumented_jit(impl, label=label, **jit_kwargs)
        avals = tuple(
            jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), t
            )
            for t in tables
        ) + (jax.ShapeDtypeStruct((bucket, width), dtype),)
        compiled = fn.lower(*avals).compile()
        _EXEC_CACHE[key] = compiled
        _COMPILE_COUNT += 1
        note_executable(label, compiled)
        return compiled

    def warmup(
        self,
        t0: int,
        t1: int,
        *,
        space: str,
        chunk: int,
        shard_devices: int = 1,
        width: Optional[int] = None,
        kinds=("value",),
        engine: str = "walk",
    ) -> int:
        """AOT-lower and cache every ladder bucket executable for this model
        so the first request pays no compile.  Returns how many executables
        this call actually compiled (0 = everything was already cached).

        ``engine`` is the pred_engine request: it is resolved first, so an
        ineligible forest never AOT-compiles the matmul ladder (warm time
        and HBM would double for executables the model can't use).  When
        matmul DOES resolve, the walker ladder is warmed alongside it —
        the runtime fallback path stays compile-free through serving."""
        resolved, _ = self.resolve_engine(engine, space, t0, t1)
        if width is None:
            width = (
                self._b.max_feature_idx + 1
                if space == "real"
                else self._b._bin_matrix_width()
            )
        dtype = np.float32 if space == "real" else np.int32
        ndev = self._shard_count(shard_devices)
        before = _COMPILE_COUNT
        engines = ("matmul", "walk") if resolved == "matmul" else ("walk",)
        for eng in engines:
            variant, tables, statics = self._tables(space, t0, t1, engine=eng)
            for bucket in ladder_buckets(chunk):
                for kind in kinds:
                    self._get_exec(
                        variant, kind, tables, statics, bucket, width,
                        dtype, ndev,
                    )
        return _COMPILE_COUNT - before

    @staticmethod
    def _shard_count(shard_devices: int) -> int:
        """Usable mesh size: clamped to a power of two (buckets are powers
        of two, so the row axis always divides) and the local device count;
        -1 means all local devices."""
        avail = jax.local_device_count()
        if shard_devices in (0, 1):
            return 1
        if shard_devices < 0:
            shard_devices = avail
        return _clamp_pow2(min(shard_devices, avail))

    # ---------------------------------------------------------- scheduler
    def run(
        self,
        X,
        t0: int,
        t1: int,
        *,
        space: str,
        kind: str = "value",
        chunk: int,
        num_buffers: int = 2,
        shard_devices: int = 1,
        reduce_fn: Optional[Callable[[np.ndarray, int], np.ndarray]] = None,
        engine: str = "walk",
    ) -> np.ndarray:
        """Stream X through the engine.  kind="value" yields per-tree leaf
        outputs as float64 [rows, T] blocks (bit-identical to the legacy
        single-shot walk + float64 cast), kind="leaf" int32 leaf indices;
        `reduce_fn(block, rows)` maps each chunk's block before
        concatenation (e.g. the per-class sum), running on host while the
        next chunk computes on device."""
        b = self._b
        ses = get_session()
        n = int(X.shape[0])
        t_count = t1 - t0
        chunk = max(LADDER_MIN, int(chunk))
        num_buffers = max(1, int(num_buffers))
        ndev = self._shard_count(shard_devices)
        resolved, _ = self.resolve_engine(engine, space, t0, t1)
        stats = {
            "path": "stream_" + space,
            "engine": resolved,
            "rows": n,
            "chunks": 0,
            "buckets": [],
            "shard_devices": ndev,
            "bin_ms": 0.0,
            "transfer_ms": 0.0,
            "walk_ms": 0.0,
            "host_ms": 0.0,
            "compiles": 0,
        }
        variant, tables, statics = self._tables(space, t0, t1, engine=resolved)
        suspects = kind == "value" and space == "real"
        if n == 0:
            # empty-input edge: no device work, correctly shaped output
            empty = np.zeros(
                (0, t_count), np.int32 if kind == "leaf" else np.float64
            )
            out = reduce_fn(empty, 0) if reduce_fn is not None else empty
            self.last_stats = stats
            return out

        if space == "real":
            width = int(X.shape[1])
            dtype = np.float32

            def host_rows(lo: int, rows: int):
                xo = X[lo : lo + rows]
                return np.ascontiguousarray(xo, dtype=np.float32), xo

        else:
            width = b._bin_matrix_width()
            dtype = np.int32
            sparse = hasattr(X, "tocsc") and hasattr(X, "nnz")
            if sparse:
                # scipy input: bin once from CSC (column-sliced), then
                # stream the int32 matrix — row-slicing sparse per chunk
                # would re-walk indptr per feature per chunk
                t_b = time.perf_counter()
                full_bins = b._bin_input_host(X)
                stats["bin_ms"] += (time.perf_counter() - t_b) * 1e3
            else:
                full_bins = None
            # dense host binning runs in blocks of >= _HOST_BIN_BLOCK rows:
            # per-chunk mapper calls at small chunks would pay the
            # per-feature dispatch overhead ~n_chunks times
            block_rows = max(chunk, _HOST_BIN_BLOCK)
            block_cache = {"lo": -1, "mat": None}

            def host_rows(lo: int, rows: int):
                if full_bins is not None:
                    return full_bins[lo : lo + rows], None
                blo = (lo // block_rows) * block_rows
                if block_cache["lo"] != blo:
                    block_cache["lo"] = blo
                    block_cache["mat"] = b._bin_input_host(
                        X[blo : blo + block_rows]
                    )
                mat = block_cache["mat"]
                return mat[lo - blo : lo - blo + rows], None

        compiles_before = _COMPILE_COUNT
        blocks: List[np.ndarray] = []
        inflight: deque = deque()

        def drain_one():
            dev, rows, patch = inflight.popleft()
            t_w = time.perf_counter()
            with jax.profiler.TraceAnnotation("predict/walk"):
                host = np.asarray(dev)
            stats["walk_ms"] += (time.perf_counter() - t_w) * 1e3
            t_h = time.perf_counter()
            blk = host[:rows]
            if kind == "value":
                blk = blk.astype(np.float64)
            if patch is not None:
                sidx, pvals = patch
                blk[sidx] = pvals
            if reduce_fn is not None:
                blk = reduce_fn(blk, rows)
            blocks.append(blk)
            stats["host_ms"] += (time.perf_counter() - t_h) * 1e3

        for lo in range(0, n, chunk):
            rows = min(chunk, n - lo)
            bucket = bucket_rows(rows, chunk)
            t_b = time.perf_counter()
            with jax.profiler.TraceAnnotation("predict/bin"):
                mat, x_orig = host_rows(lo, rows)
            if bucket > rows:
                padded = np.zeros((bucket, width), dtype)
                padded[:rows] = mat
            else:
                padded = np.ascontiguousarray(mat, dtype=dtype)
            patch = None
            if suspects:
                # f64 suspect re-walk (rows within f32 rounding of a
                # threshold) is per-row, so per-chunk patching is
                # bit-identical to the legacy full-batch patch — and runs
                # on host while earlier chunks walk on device
                sidx = b._real_walk_suspects(
                    np.asarray(x_orig, np.float64), t0, t1
                )
                if sidx.size:
                    patch = (
                        sidx,
                        np.stack(
                            [
                                tr.predict(x_orig[sidx])
                                for tr in b.models_[t0:t1]
                            ],
                            axis=1,
                        ),
                    )
            stats["bin_ms"] += (time.perf_counter() - t_b) * 1e3
            compiled = self._get_exec(
                variant, kind, tables, statics, bucket, width, dtype, ndev
            )
            t_t = time.perf_counter()
            with jax.profiler.TraceAnnotation("predict/transfer"):
                dev = compiled(*tables, padded)
            stats["transfer_ms"] += (time.perf_counter() - t_t) * 1e3
            inflight.append((dev, rows, patch))
            stats["chunks"] += 1
            if ses.enabled:
                ses.record({
                    "event": "predict_chunk",
                    "chunk": stats["chunks"] - 1,
                    "rows": rows,
                    "bucket": bucket,
                })
            if bucket not in stats["buckets"]:
                stats["buckets"].append(bucket)
            while len(inflight) >= num_buffers:
                drain_one()
        while inflight:
            drain_one()
        t_h = time.perf_counter()
        out = blocks[0] if len(blocks) == 1 else np.concatenate(blocks, axis=0)
        stats["host_ms"] += (time.perf_counter() - t_h) * 1e3
        stats["compiles"] = _COMPILE_COUNT - compiles_before
        self.last_stats = stats
        sample_device_memory("predict")
        if ses.enabled:
            ses.inc("predict_chunks", stats["chunks"])
            ses.set_gauge(
                "pred/engine", 1.0 if resolved == "matmul" else 0.0
            )
            ses.record({
                "event": "predict",
                "path": stats["path"],
                "engine": resolved,
                "rows": n,
                "chunks": stats["chunks"],
                "shard_devices": ndev,
                "phases": {
                    "bin_ms": stats["bin_ms"],
                    "transfer_ms": stats["transfer_ms"],
                    "walk_ms": stats["walk_ms"],
                    "host_ms": stats["host_ms"],
                },
                "compiles": stats["compiles"],
            })
        return out


_HOST_BIN_BLOCK = 65536  # dense host-binning block size (rows)
