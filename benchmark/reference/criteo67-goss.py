"""Plain reference of the ``criteo67-goss`` configuration: gradient-based
one-side sampling (Ke et al., "LightGBM: A Highly Efficient Gradient Boosting
Decision Tree", NeurIPS 2017, section 3, Algorithm 2; upstream
``src/boosting/goss.hpp``; ``data_sample_strategy=goss``) on the Criteo-shaped
table.

Float64 NumPy; the tree arithmetic is ``gbdt.py``'s, imported and not edited;
nothing of the program is imported.  For tree k of the model the timed path
grew:

* true g, h from the scores of the program's own trees before it;
* for k >= 1 / learning_rate the metric ``|g h|``, its ``top_k``-th largest,
  the rest draws ``u`` of every row from the configuration's mix, written out
  again here (``rest_draws``): MurmurHash3's ``fmix32`` over ``row ^
  fmix32(bagging_seed ^ (0x80000000 | k) * 0x9E3779B9)``, top 24 bits; the bag
  = the rows at or above the threshold and the rest with ``u < other_k / (N -
  top_k)``; the rest's statistics amplified by ``(N - top_k) / other_k``;
* ``gbdt.judge_tree`` on the IN-BAG rows with the amplified statistics for
  counts, gains, regret and the hessian floor — on tree 0 (every row), on the
  first sampled tree and on the last tree given (the timed window's first);
* every tree's leaf values and leaf counts against the sums over its bag, and
  every tree applied to the scores of ALL rows: the out-of-bag rows' too.

Program and reference compute the metric in float32 and float64: a row whose
metric lies within ``BAND`` of the threshold, relatively, may fall on either
side.  Such a row is in the bag anyway when its draw keeps it; otherwise it
may or may not be, and a node's count may differ from the reference's by as
many such rows as reach it.  ``count_mismatch`` is what lies beyond that.

*Control* (``run.py --control bfloat16``): ``gbdt.py``'s own, at the same
bag — the per-row (amplified) statistics rounded to bfloat16 before they are
summed, at the same nodes of the same trees.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from benchmark.reference import gbdt

_M32 = 0xFFFFFFFF
GOSS_STREAM = 0x80000000
# relative half-width of the threshold's band: the program's float32 metric
# (a float32 score summed over up to 16 trees, a float32 sigmoid) lies within
# a few 1e-6 of the float64 one
BAND = 1e-5


def fmix32(x: np.ndarray) -> np.ndarray:
    """MurmurHash3's 32-bit finalizer on uint32 arrays (products wrap)."""
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> np.uint32(13))
    x = x * np.uint32(0xC2B2AE35)
    return x ^ (x >> np.uint32(16))


def rest_draws(bagging_seed: int, iteration: int, n: int) -> np.ndarray:
    """[n] float64 in [0, 1), 24 bits each: the draw of rows 0..n-1 in
    iteration ``iteration``."""
    word = (((GOSS_STREAM | int(iteration)) & _M32) * 0x9E3779B9) & _M32
    key = fmix32(np.array([(int(bagging_seed) & _M32) ^ word], np.uint32))
    x = fmix32(np.arange(n, dtype=np.uint32) ^ key)
    return (x >> np.uint32(8)).astype(np.float64) * 2.0**-24


def warmup_iterations(params: Dict[str, Any]) -> int:
    return int(1.0 / max(float(params.get("learning_rate", 0.1)), 1e-12))


def goss_bag(g: np.ndarray, h: np.ndarray, params: Dict[str, Any], iteration: int):
    """(in_bag [N] bool, weight [N], open [N] bool) of one sampled iteration:
    the bag, every row's amplification, and the rows whose membership the
    threshold's band leaves open."""
    n = len(g)
    top_k = max(1, int(n * float(params.get("top_rate", 0.2))))
    other_k = max(1, int(n * float(params.get("other_rate", 0.1))))
    metric = np.abs(g * h)
    threshold = np.partition(metric, n - top_k)[n - top_k]
    is_top = metric >= threshold
    # the program compares float32 draws with the float32 quotient
    rest_prob = float(np.float32(other_k / max(1, n - top_k)))
    drawn = rest_draws(int(params.get("bagging_seed", 3)), iteration, n) < rest_prob
    weight = np.where(is_top, 1.0, (n - top_k) / other_k)
    open_rows = (np.abs(metric - threshold) <= BAND * threshold) & ~drawn
    return is_top | drawn, weight, open_rows


def _leaf_values(leaf_of_row, g, h, n_leaves: int, params) -> np.ndarray:
    G = np.bincount(leaf_of_row, weights=g, minlength=n_leaves)
    H = np.bincount(leaf_of_row, weights=h, minlength=n_leaves)
    return np.array([gbdt.leaf_output(G[i], H[i], params) for i in range(n_leaves)])


def _up_the_tree(tree: gbdt.Tree, per_leaf: np.ndarray) -> np.ndarray:
    """Internal nodes' totals of a per-leaf count."""
    n_int = len(tree.feature)
    out = np.zeros(n_int)

    def total(c: int) -> float:
        if c < 0:
            return float(per_leaf[~c])
        out[c] = total(int(tree.left[c])) + total(int(tree.right[c]))
        return out[c]

    if n_int:
        total(0)
    return out


def count_mismatch(tree: gbdt.Tree, leaf_in_bag, leaf_open) -> float:
    """Worst |model's count - in-bag rows the raw walk sends there| over
    leaves and internal nodes, beyond the open rows that reach the node."""
    n = tree.n_leaves
    ref = np.bincount(leaf_in_bag, minlength=n).astype(np.float64)
    slack = np.bincount(leaf_open, minlength=n).astype(np.float64)
    over = np.abs(ref - tree.leaf_count) - slack
    over_int = (np.abs(_up_the_tree(tree, ref) - tree.internal_count)
                - _up_the_tree(tree, slack))
    return float(max(np.max(over, initial=0.0), np.max(over_int, initial=0.0), 0.0))


def follow_model(tree_dumps, *, blocks, y, params, recipe, valid_blocks=None,
                 valid_y=None, valid_metric=None, control=None, detail=None):
    """The numbers ``correct`` is decided by (the names of ``gbdt.follow``),
    worst over the first trees of the model the timed path produced."""
    if valid_blocks is not None:
        raise ValueError("the criteo67-goss reference follows training alone")
    trees = [gbdt.tree_from_dump(t) for t in tree_dumps]
    pool = gbdt.RowPool.for_table(sum(b.shape[0] for b in blocks), blocks[0].shape[1])
    try:
        return _follow(trees, blocks, y, params, recipe, control, detail, pool)
    finally:
        if pool is not None:
            pool.close()


def _follow(trees, blocks, y, params, recipe, control, detail, pool) -> Dict[str, float]:
    cols, values = gbdt.levels_of(blocks, recipe, pool)
    y = np.asarray(y, np.float64)
    bias = gbdt.init_score(y)
    score = np.full(len(y), bias)
    warm = warmup_iterations(params)
    judged = {0, warm, len(trees) - 1}
    worst: Dict[str, float] = {}
    for k, tree in enumerate(trees):
        g, h = gbdt.gradients(score, y, pool)
        leaf_of_row = gbdt.walk(tree, blocks, pool)
        b = bias if k == 0 else 0.0  # the first tree carries the bias in its leaves
        if k >= warm:
            in_bag, weight, open_rows = goss_bag(g, h, params, k)
            rows = np.flatnonzero(in_bag)
            leaf_b, g_b, h_b = leaf_of_row[rows], (g * weight)[rows], (h * weight)[rows]
            leaf_open = leaf_of_row[open_rows]
        else:
            rows, leaf_b, g_b, h_b = None, leaf_of_row, g, h
            leaf_open = leaf_of_row[:0]
        if control is not None:
            g_low, h_low = gbdt.round_bfloat16(g_b), gbdt.round_bfloat16(h_b)
        if k in judged:
            cols_b = cols if rows is None else [c[rows] for c in cols]
            nums = gbdt.judge_tree(tree, leaf_b, cols_b, values, g_b, h_b, params, b,
                                   control=control, detail=detail, pool=pool)
            if detail is not None:
                detail[-1].update(tree=k, in_bag_rows=len(leaf_b), open_rows=len(leaf_open))
        else:
            # the other trees: their leaves against the sums over their bag
            ref = _leaf_values(leaf_b, g_b, h_b, tree.n_leaves, params)
            got = (tree.leaf_value - b if control is None
                   else _leaf_values(leaf_b, g_low, h_low, tree.n_leaves, params))
            nums = {"leaf_value_rms_gap": gbdt._rms(gbdt._rel_gaps(got, ref))}
        if control is None:
            nums["count_mismatch"] = count_mismatch(tree, leaf_b, leaf_open)
        score += tree.leaf_value[leaf_of_row] - b
        for name, v in nums.items():
            worst[name] = max(worst.get(name, 0.0), float(v))
    return worst
