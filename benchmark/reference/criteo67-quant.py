"""Plain reference of the ``criteo67-quant`` configuration: LightGBM 4's
quantized training (``use_quantized_grad``; Shi et al., "Quantized Training
of Gradient Boosting Decision Trees", NeurIPS 2022; upstream
``src/treelearner/gradient_discretizer.cpp``) on the Criteo-shaped table.

Float64 NumPy; the tree arithmetic is ``gbdt.py``'s, imported and not
edited; nothing of the program is imported.  For tree k of the model the
timed path grew:

* true g, h from the scores of the program's own trees before it;
* the two scales from their maxima, ``s_g = max|g| / (bins // 2)``,
  ``s_h = max h / bins``;
* the rounding offsets ``u`` of every row from the configuration's mix,
  written out again here (``rounding_uniforms``): MurmurHash3's ``fmix32``
  over ``row ^ fmix32(seed ^ (2 k + stream) * 0x9E3779B9)``, top 24 bits;
* the levels by the published rule, truncation toward zero of
  ``g / s_g +- u`` (the sign's side) and of ``h / s_h + u``;
* ``gbdt.judge_tree`` on the discretized statistics ``(k_g s_g, k_h s_h)``
  for counts, gains, regret and the hessian floor, and the leaf values from
  the TRUE statistics (``quant_train_renew_leaf``: the program renews every
  leaf from the true gradients after the tree is grown).

Program and reference compute ``g / s + u`` in float32 and float64: a row
whose sum lies within float32 rounding of a whole number lands one level
apart (a few rows of 8M), which is the floor of the gain numbers.

*Control* (any ``control`` string; ``run.py --control coarser``): the
nearest precision below the stated one is the grid one bit coarser
(``num_grad_quant_bins // 2`` levels) at the same offsets: the gains and the
split it puts first at the same nodes of the same trees, held against the
stated grid's.  The renewed leaves do not depend on the grid; their control
is ``gbdt.py``'s own (true gradients rounded to bfloat16 before they are
summed).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np

from benchmark.reference import gbdt

_M32 = 0xFFFFFFFF


def fmix32(x: np.ndarray) -> np.ndarray:
    """MurmurHash3's 32-bit finalizer on uint32 arrays (products wrap)."""
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> np.uint32(13))
    x = x * np.uint32(0xC2B2AE35)
    return x ^ (x >> np.uint32(16))


def rounding_uniforms(seed: int, tree_index: int, n: int, stream: int) -> np.ndarray:
    """[n] float64 in [0, 1), 24 bits each: the rounding offset of rows
    0..n-1 for tree ``tree_index``, stream 0 (gradients) or 1 (hessians)."""
    word = ((2 * int(tree_index) + int(stream)) * 0x9E3779B9) & _M32
    key = fmix32(np.array([(int(seed) & _M32) ^ word], np.uint32))
    x = fmix32(np.arange(n, dtype=np.uint32) ^ key)
    return (x >> np.uint32(8)).astype(np.float64) * 2.0**-24


def discretize(g: np.ndarray, h: np.ndarray, params: Dict[str, Any], tree_index: int,
               bins: Optional[int] = None):
    """(k_g, k_h, s_g, s_h): the integer levels of every row and the two
    scales (DiscretizeGradients, gradient_discretizer.cpp:70-160)."""
    bins = int(params.get("num_grad_quant_bins", 4) if bins is None else bins)
    s_g = max(float(np.max(np.abs(g))) / (bins // 2), 1e-30)
    s_h = max(float(np.max(np.abs(h))) / bins, 1e-30)
    n = len(g)
    if params.get("stochastic_rounding", True):
        seed = int(params.get("seed") or 0)
        u_g = rounding_uniforms(seed, tree_index, n, 0)
        u_h = rounding_uniforms(seed, tree_index, n, 1)
    else:
        u_g = u_h = 0.5
    gi, hi = g / s_g, h / s_h
    k_g = np.trunc(np.where(gi >= 0, gi + u_g, gi - u_g))
    k_h = np.trunc(hi + u_h)
    return k_g, k_h, s_g, s_h


def _leaf_values(leaf_of_row, g, h, n_leaves: int, params) -> np.ndarray:
    """The float64 optimum of every leaf from the sums of (g, h) over the
    rows that reach it (no bias)."""
    G = np.bincount(leaf_of_row, weights=g, minlength=n_leaves)
    H = np.bincount(leaf_of_row, weights=h, minlength=n_leaves)
    return np.array([gbdt.leaf_output(G[i], H[i], params) for i in range(n_leaves)])


def _leaf_gap(got: np.ndarray, ref: np.ndarray) -> float:
    """gbdt.judge_tree's rule and scale for ``leaf_value_rms_gap``."""
    return gbdt._rms(gbdt._rel_gaps(got, ref))


def _coarser_numbers(tree: gbdt.Tree, leaf_of_row, cols, values, stated, coarse,
                     params) -> Dict[str, float]:
    """The control's gains and regret: what the coarser grid's sums give at
    the same nodes, held against the stated grid's (gbdt.judge_tree's
    control branch, with the two sets of per-row statistics handed in)."""
    n_levels = len(values)
    GL, HL, CL = gbdt.leaf_sums(cols, leaf_of_row, *stated, tree.n_leaves, n_levels)
    GN, HN, CN = gbdt.node_sums(tree, GL, HL, CL)
    GLq, HLq, _ = gbdt.leaf_sums(cols, leaf_of_row, *coarse, tree.n_leaves, n_levels,
                                 counts=False)
    GNq, HNq, _ = gbdt.node_sums(tree, GLq, HLq, CL)
    n_int = len(tree.feature)
    got = np.empty(n_int); ref = np.empty(n_int); regret = np.zeros(n_int)
    for i in range(n_int):
        gq = gbdt.split_gains(GNq[i], HNq[i], CN[i], params)
        j, t = (int(a) for a in np.unravel_index(int(np.argmax(gq)), gq.shape))
        got[i] = gq[j, t]
        ref[i] = gbdt.split_gains(GN[i], HN[i], CN[i], params, margin=-1e-4)[j, t]
        if not np.isfinite(ref[i]):
            ref[i] = 0.0
        best = float(np.max(gbdt.split_gains(GN[i], HN[i], CN[i], params, margin=1e-4)))
        if np.isfinite(best) and best > 0:
            regret[i] = max(0.0, best - ref[i]) / best
    return {
        "count_mismatch": 0.0,
        "split_gain_rms_gap": gbdt._rms(gbdt._rel_gaps(got, ref)),
        "split_regret": float(np.max(regret, initial=0.0)),
        "floor_violation": 0.0,
    }


def follow_model(tree_dumps, *, blocks, y, params, recipe, valid_blocks=None,
                 valid_y=None, valid_metric=None, control=None, detail=None):
    """The numbers ``correct`` is decided by (the names of ``gbdt.follow``),
    worst over the first trees of the model the timed path produced."""
    trees = [gbdt.tree_from_dump(t) for t in tree_dumps]
    cols, values = gbdt.levels_of(blocks, recipe)
    y = np.asarray(y, np.float64)
    bias = gbdt.init_score(y)
    score = np.full(len(y), bias)
    renew = bool(params.get("quant_train_renew_leaf", False))
    vscore = None
    if valid_blocks is not None:
        vy = np.asarray(valid_y, np.float64)
        vscore = np.full(len(vy), bias)
        vscore_low = gbdt.round_bfloat16(vscore)
    worst: Dict[str, float] = {}
    for k, tree in enumerate(trees):
        g, h = gbdt.gradients(score, y)
        k_g, k_h, s_g, s_h = discretize(g, h, params, k)
        leaf_of_row = gbdt.walk(tree, blocks)
        b = bias if k == 0 else 0.0  # the first tree carries the bias in its leaves
        stated = (k_g * s_g, k_h * s_h)
        # the leaves are renewed from the TRUE statistics after the tree is grown
        leaf_stats = (g, h) if renew else stated
        leaf_ref = _leaf_values(leaf_of_row, *leaf_stats, tree.n_leaves, params)
        if control is None:
            nums = gbdt.judge_tree(tree, leaf_of_row, cols, values, *stated, params, b,
                                   detail=detail)
            nums["leaf_value_rms_gap"] = _leaf_gap(tree.leaf_value - b, leaf_ref)
            if detail is not None:
                # judge_tree read the leaves against the statistics it was given
                for key in ("worst_leaf_rows", "worst_leaf_got", "worst_leaf_ref"):
                    del detail[-1][key]
                detail[-1]["leaf_value_worst"] = float(np.max(
                    gbdt._rel_gaps(tree.leaf_value - b, leaf_ref)))
                # the largest per-bin sum of the tree's root, in grid units:
                # the f32 multiples of the scale are exact below 2**24 of them
                detail[-1]["largest_bin_sum_units"] = float(max(
                    max(np.max(np.abs(np.bincount(c, weights=k_g))),
                        np.max(np.bincount(c, weights=k_h))) for c in cols))
        else:
            bins = int(params.get("num_grad_quant_bins", 4)) // 2
            c_g, c_h, t_g, t_h = discretize(g, h, params, k, bins=bins)
            coarse = (c_g * t_g, c_h * t_h)
            nums = _coarser_numbers(tree, leaf_of_row, cols, values, stated, coarse, params)
            low = (gbdt.round_bfloat16(g), gbdt.round_bfloat16(h)) if renew else coarse
            nums["leaf_value_rms_gap"] = _leaf_gap(
                _leaf_values(leaf_of_row, *low, tree.n_leaves, params), leaf_ref)
        score += tree.leaf_value[leaf_of_row] - b
        if vscore is not None:
            step = gbdt.predict(tree, valid_blocks) - b
            vscore += step
            ref = gbdt.logloss(vscore, vy)
            if control is not None:
                vscore_low = gbdt.round_bfloat16(vscore_low + step)
                nums["valid_logloss_gap"] = abs(gbdt.logloss(vscore_low, vy) - ref) / ref
            elif valid_metric is not None and k < len(valid_metric):
                nums["valid_logloss_gap"] = abs(float(valid_metric[k]) - ref) / ref
        for name, v in nums.items():
            worst[name] = max(worst.get(name, 0.0), float(v))
    return worst
