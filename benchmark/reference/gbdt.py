"""Plain reference for binary GBDT training: float64 NumPy, no kernels.

Follows the published algorithm (LightGBM, Ke et al. 2017; the exact greedy
split search of Friedman 2001 over the candidates the data itself offers),
and imports nothing of the program.  It is given the same inputs the program
was given (raw float32 rows, labels, the configuration's parameters) and
the program's OUTPUT — the trees of the model a user gets — and re-derives
from the raw rows what each tree should have been:

* which rows reach which node (``walk``: ``x <= threshold`` on raw values),
* every node's gradient/hessian/count sums (``node_sums``: one
  ``bincount`` per feature over (leaf, level) keys, then sums up the tree),
* the best split any node could have made (``split_gains``), the gain of
  the split it did make, and every leaf's value.

It "teacher-forces" like a served model's reference run over the served
tokens: tree k is judged with the scores of the program's own trees
0..k-1, so one near-tie flip does not cascade into the later trees.

``grow_tree`` is the same arithmetic run forward (leaf-wise, best first):
the independent grower the CPU tests and the fault tests put in the
program's place.  ``round_stats="bfloat16"`` is the control: per-row
gradients and hessians rounded to bfloat16 before they are summed.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from benchmark.reference.rows import RowPool, row_blocks, sums_worth_processes


@dataclasses.dataclass
class Tree:
    """One tree as arrays.  Child >= 0 is an internal node's index; child
    < 0 is leaf ``~child`` (LightGBM's own convention)."""

    feature: np.ndarray  # [n_int] int
    threshold: np.ndarray  # [n_int] float64, go left iff x <= threshold
    left: np.ndarray  # [n_int] int
    right: np.ndarray  # [n_int] int
    gain: np.ndarray  # [n_int]
    internal_count: np.ndarray  # [n_int]
    leaf_value: np.ndarray  # [n_leaves]
    leaf_count: np.ndarray  # [n_leaves]

    @property
    def n_leaves(self) -> int:
        return len(self.leaf_value)


def tree_from_dump(tree_structure: Dict[str, Any]) -> Tree:
    """A ``dump_model()['tree_info'][k]['tree_structure']`` dict (the
    model's public form) as arrays."""
    feats, thrs, lefts, rights, gains, icnt = [], [], [], [], [], []
    lval: Dict[int, float] = {}
    lcnt: Dict[int, int] = {}

    def visit(node) -> int:
        if "leaf_index" in node or "leaf_value" in node and "split_index" not in node:
            li = int(node.get("leaf_index", 0))
            lval[li] = float(node["leaf_value"])
            lcnt[li] = int(node.get("leaf_count", 0))
            return ~li
        i = int(node["split_index"])
        while len(feats) <= i:
            for a in (feats, thrs, lefts, rights, gains, icnt):
                a.append(0)
        if node.get("decision_type", "<=") != "<=":
            raise ValueError(f"reference handles numerical '<=' splits only, "
                             f"got {node.get('decision_type')!r}")
        feats[i] = int(node["split_feature"])
        thrs[i] = float(node["threshold"])
        gains[i] = float(node["split_gain"])
        icnt[i] = int(node["internal_count"])
        lefts[i] = visit(node["left_child"])
        rights[i] = visit(node["right_child"])
        return i

    visit(tree_structure)
    n_leaves = len(lval)
    return Tree(
        feature=np.asarray(feats, np.int64),
        threshold=np.asarray(thrs, np.float64),
        left=np.asarray(lefts, np.int64),
        right=np.asarray(rights, np.int64),
        gain=np.asarray(gains, np.float64),
        internal_count=np.asarray(icnt, np.int64),
        leaf_value=np.asarray([lval[i] for i in range(n_leaves)], np.float64),
        leaf_count=np.asarray([lcnt[i] for i in range(n_leaves)], np.int64),
    )


# ------------------------------------------------------------ objective


def init_score(y: np.ndarray) -> float:
    """boost_from_average for binary log loss: the log odds of the mean."""
    p = float(np.mean(y, dtype=np.float64))
    p = min(max(p, 1e-15), 1 - 1e-15)
    return float(np.log(p / (1.0 - p)))


def gradients(score: np.ndarray, y: np.ndarray,
              pool: Optional[RowPool] = None) -> Tuple[np.ndarray, np.ndarray]:
    """Binary log loss's g and h of every row; with ``pool`` a block of rows
    a thread (elementwise: the same numbers)."""
    if pool is None:
        p = 1.0 / (1.0 + np.exp(-score))
        return p - y, p * (1.0 - p)
    g, h = np.empty_like(score), np.empty_like(score)

    def one(s):
        g[s], h[s] = gradients(score[s], y[s])

    pool.map_blocks(one, row_blocks(len(score)))
    return g, h


def logloss(score: np.ndarray, y: np.ndarray) -> float:
    # log(1 + exp(-s)) for y = 1, log(1 + exp(s)) for y = 0, stably
    z = np.where(y > 0, -score, score)
    return float(np.mean(np.logaddexp(0.0, z)))


def round_bfloat16(a: np.ndarray) -> np.ndarray:
    """float64 -> nearest bfloat16 (round to nearest even) -> float64."""
    u = a.astype(np.float32).view(np.uint32)
    u = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))) & np.uint32(
        0xFFFF0000
    )
    return u.view(np.float32).astype(np.float64)


# ------------------------------------------------------------- the walk


def _walk_block(tree: Tree, x: np.ndarray) -> np.ndarray:
    n = x.shape[0]
    if len(tree.feature) == 0:
        return np.zeros(n, np.int32)
    node = np.zeros(n, np.int64)  # internal index, or ~leaf once < 0
    live = np.arange(n)
    while live.size:
        nd = node[live]
        v = x[live, tree.feature[nd]].astype(np.float64)
        nxt = np.where(v <= tree.threshold[nd], tree.left[nd], tree.right[nd])
        node[live] = nxt
        live = live[nxt >= 0]
    return (~node).astype(np.int32)


def walk(tree: Tree, blocks: Sequence[np.ndarray], pool: Optional[RowPool] = None) -> np.ndarray:
    """Leaf index of every row, from the raw values: left iff
    ``float64(x) <= threshold``; a block a thread of ``pool``."""
    if pool is None:
        return np.concatenate([_walk_block(tree, x) for x in blocks])
    return np.concatenate(pool.map_blocks(lambda x: _walk_block(tree, x), blocks))


def predict(tree: Tree, blocks: Sequence[np.ndarray], pool: Optional[RowPool] = None) -> np.ndarray:
    return tree.leaf_value[walk(tree, blocks, pool)]


# ------------------------------------------------------- sums per node


def leaf_sums(level_cols: Sequence[np.ndarray], leaf_of_row: np.ndarray,
              g: np.ndarray, h: np.ndarray, n_leaves: int, n_levels: int,
              counts: bool = True, pool: Optional[RowPool] = None):
    """[n_leaves, F, n_levels] sums of g, h and (unless ``counts`` is off)
    row counts: one pass per feature over (leaf, level) keys; with ``pool``
    (where the rows outnumber the bins) a group of features a worker
    process, the same sums to the last bit."""
    if pool is not None and sums_worth_processes(len(leaf_of_row), n_leaves, n_levels):
        return pool.leaf_sums(level_cols, leaf_of_row, g, h, n_leaves, n_levels, counts)
    f = len(level_cols)
    G = np.empty((n_leaves, f, n_levels))
    H = np.empty((n_leaves, f, n_levels))
    C = np.empty((n_leaves, f, n_levels)) if counts else None
    base = leaf_of_row.astype(np.int64) * n_levels
    size = n_leaves * n_levels
    for j, col in enumerate(level_cols):
        key = base + col
        G[:, j, :] = np.bincount(key, weights=g, minlength=size).reshape(n_leaves, -1)
        H[:, j, :] = np.bincount(key, weights=h, minlength=size).reshape(n_leaves, -1)
        if counts:
            C[:, j, :] = np.bincount(key, minlength=size).reshape(n_leaves, -1)
    return G, H, C


def node_sums(tree: Tree, GL, HL, CL):
    """Internal nodes' [n_int, F, n_levels] sums from their leaves'."""
    n_int = len(tree.feature)
    shape = (n_int,) + GL.shape[1:]
    GN, HN, CN = np.zeros(shape), np.zeros(shape), np.zeros(shape)

    # children have larger split_index than parents in a leaf-wise tree, but
    # do not count on it: post-order by recursion depth
    order: List[int] = []
    stack = [0] if n_int else []
    while stack:
        i = stack.pop()
        order.append(i)
        for c in (tree.left[i], tree.right[i]):
            if c >= 0:
                stack.append(int(c))
    for i in reversed(order):
        for c in (tree.left[i], tree.right[i]):
            if c >= 0:
                GN[i] += GN[c]; HN[i] += HN[c]; CN[i] += CN[c]
            else:
                GN[i] += GL[~c]; HN[i] += HL[~c]; CN[i] += CL[~c]
    return GN, HN, CN


# ------------------------------------------------------------- splits


def _leaf_gain(G, H, lam):
    return G * G / (H + lam)


def split_gains(Gn, Hn, Cn, params: Dict[str, Any], margin: float = 0.0):
    """Gain of every candidate (feature, level: left = levels <= it) of one
    node's [F, n_levels] sums; invalid candidates are -inf.  ``margin``
    widens (+) or narrows (-) the hessian and count floors relatively."""
    lam = float(params.get("lambda_l2", 0.0))
    min_h = float(params.get("min_sum_hessian_in_leaf", 1e-3)) * (1.0 + margin)
    min_n = float(params.get("min_data_in_leaf", 20))
    gl = np.cumsum(Gn, axis=1)
    hl = np.cumsum(Hn, axis=1)
    cl = np.cumsum(Cn, axis=1)
    gt, ht, ct = gl[:, -1:], hl[:, -1:], cl[:, -1:]
    gr, hr, cr = gt - gl, ht - hl, ct - cl
    with np.errstate(divide="ignore", invalid="ignore"):
        gain = _leaf_gain(gl, hl, lam) + _leaf_gain(gr, hr, lam) - _leaf_gain(gt, ht, lam)
    # a candidate must separate two occupied levels: the last occupied level
    # and everything after it send no row right
    ok = (cl >= min_n) & (cr >= min_n) & (hl >= min_h) & (hr >= min_h) & (Cn > 0)
    return np.where(ok, gain, -np.inf)


def level_of_threshold(values: np.ndarray, thr: float) -> int:
    """Index of the last grid value <= thr."""
    return int(np.searchsorted(values, thr, side="right")) - 1


def leaf_output(G: float, H: float, params: Dict[str, Any]) -> float:
    lam = float(params.get("lambda_l2", 0.0))
    return -G / (H + lam) * float(params.get("learning_rate", 0.1))


# ---------------------------------------------------------- the grower


def grow_tree(level_cols: Sequence[np.ndarray], values: np.ndarray, g: np.ndarray,
              h: np.ndarray, params: Dict[str, Any], bias: float = 0.0,
              round_stats: Optional[str] = None) -> Tree:
    """Leaf-wise (best-first) growth to ``num_leaves`` leaves over the
    levels of ``level_cols`` (one uint8/int16 column per feature)."""
    if round_stats == "bfloat16":
        g, h = round_bfloat16(g), round_bfloat16(h)
    elif round_stats is not None:
        raise ValueError(round_stats)
    n_levels = len(values)
    num_leaves = int(params.get("num_leaves", 31))
    f = len(level_cols)

    def hist(rows):
        G = np.empty((f, n_levels)); H = np.empty((f, n_levels)); C = np.empty((f, n_levels))
        gr, hr = g[rows], h[rows]
        for j, col in enumerate(level_cols):
            k = col[rows]
            G[j] = np.bincount(k, weights=gr, minlength=n_levels)
            H[j] = np.bincount(k, weights=hr, minlength=n_levels)
            C[j] = np.bincount(k, minlength=n_levels)
        return G, H, C

    def candidate(hs):
        gains = split_gains(*hs, params)
        j, t = np.unravel_index(int(np.argmax(gains)), gains.shape)
        return float(gains[j, t]), int(j), int(t)

    rows0 = np.arange(len(g))
    leaves = {0: {"rows": rows0, "hist": hist(rows0)}}
    leaves[0]["cand"] = candidate(leaves[0]["hist"])
    feature, threshold, left, right, gain, icount = [], [], [], [], [], []
    parent_slot: Dict[int, Tuple[int, str]] = {}
    while len(leaves) < num_leaves:
        li = max(leaves, key=lambda k: leaves[k]["cand"][0])
        bg, bj, bt = leaves[li]["cand"]
        if not np.isfinite(bg) or bg <= float(params.get("min_gain_to_split", 0.0)):
            break
        leaf = leaves[li]
        go_left = level_cols[bj][leaf["rows"]] <= bt
        rl, rr = leaf["rows"][go_left], leaf["rows"][~go_left]
        small, big = (rl, rr) if len(rl) <= len(rr) else (rr, rl)
        hs = hist(small)
        hb = tuple(p - s for p, s in zip(leaf["hist"], hs))
        hl, hr_ = (hs, hb) if small is rl else (hb, hs)
        ni = len(feature)
        feature.append(bj)
        # the program writes the midpoint of the two levels; any value in
        # [values[bt], values[bt+1]) routes the same rows
        occupied = np.flatnonzero(leaf["hist"][2][bj, bt + 1:] > 0)
        nxt = bt + 1 + (int(occupied[0]) if occupied.size else 0)
        threshold.append(0.5 * (values[bt] + values[min(nxt, n_levels - 1)]))
        gain.append(bg); icount.append(len(leaf["rows"]))
        left.append(~li); right.append(~len(leaves))
        if li in parent_slot:
            pi, side = parent_slot[li]
            (left if side == "l" else right)[pi] = ni
        new = len(leaves)
        leaves[li] = {"rows": rl, "hist": hl}
        leaves[new] = {"rows": rr, "hist": hr_}
        for k in (li, new):
            leaves[k]["cand"] = candidate(leaves[k]["hist"])
        parent_slot[li] = (ni, "l")
        parent_slot[new] = (ni, "r")
    n_leaves = len(leaves)
    lv = np.empty(n_leaves); lc = np.empty(n_leaves, np.int64)
    for k, leaf in leaves.items():
        G, H, C = (float(a[0].sum()) for a in leaf["hist"])
        lv[k] = leaf_output(G, H, params) + bias
        lc[k] = len(leaf["rows"])
    return Tree(
        feature=np.asarray(feature, np.int64), threshold=np.asarray(threshold),
        left=np.asarray(left, np.int64), right=np.asarray(right, np.int64),
        gain=np.asarray(gain), internal_count=np.asarray(icount, np.int64),
        leaf_value=lv, leaf_count=lc,
    )


# ----------------------------------------------------- following trees


def _rel_gaps(got: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """|got - ref| of every leaf or node against the larger of its own |ref|
    and the median |ref| (some sums are all but zero)."""
    if len(ref) == 0:
        return np.zeros(1)
    scale = np.maximum(np.abs(ref), np.median(np.abs(ref)))
    return np.abs(got - ref) / np.maximum(scale, 1e-300)


def _rms(a: np.ndarray) -> float:
    return float(np.sqrt(np.mean(np.square(a))))


def judge_tree(tree: Tree, leaf_of_row: np.ndarray, level_cols, values, g, h,
               params: Dict[str, Any], bias: float,
               control: Optional[str] = None, detail: Optional[list] = None,
               pool: Optional[RowPool] = None) -> Dict[str, float]:
    """The numbers of one tree, judged against float64 sums over the rows
    that reach each node.  With ``control`` the judged values are not the
    tree's own but those the same arithmetic gives from rounded per-row
    stats at the same nodes (it need not grow a tree of its own)."""
    n_levels = len(values)
    GL, HL, CL = leaf_sums(level_cols, leaf_of_row, g, h, tree.n_leaves, n_levels, pool=pool)
    GN, HN, CN = node_sums(tree, GL, HL, CL)
    if control is not None:
        if control != "bfloat16":
            raise ValueError(control)
        gq, hq = round_bfloat16(g), round_bfloat16(h)
        GLq, HLq, _ = leaf_sums(level_cols, leaf_of_row, gq, hq, tree.n_leaves, n_levels,
                                counts=False, pool=pool)
        GNq, HNq, _ = node_sums(tree, GLq, HLq, CL)

    # every row passes feature 0's levels exactly once: its row of sums is the node's total
    ref_leaf_cnt = CL[:, 0, :].sum(axis=1)
    ref_int_cnt = CN[:, 0, :].sum(axis=1)
    count_mismatch = 0.0
    if control is None:
        count_mismatch = float(max(
            np.max(np.abs(ref_leaf_cnt - tree.leaf_count), initial=0.0),
            np.max(np.abs(ref_int_cnt - tree.internal_count), initial=0.0),
        ))

    def leaf_values(G, H):
        return np.array([leaf_output(G[i, 0].sum(), H[i, 0].sum(), params) + bias
                         for i in range(tree.n_leaves)])

    ref_leaf_val = leaf_values(GL, HL)
    got_leaf_val = tree.leaf_value if control is None else leaf_values(GLq, HLq)
    # the bias is common to both sides and is no part of what the tree learnt
    leaf_gaps = _rel_gaps(got_leaf_val - bias, ref_leaf_val - bias)

    n_int = len(tree.feature)
    ref_gain = np.empty(n_int); got_gain = np.empty(n_int)
    regret = np.zeros(n_int); violation = np.zeros(n_int)
    min_h = float(params.get("min_sum_hessian_in_leaf", 1e-3))
    for i in range(n_int):
        # the split judged: the tree's own, or the one rounded stats put first
        if control is None:
            j = int(tree.feature[i])
            t = level_of_threshold(values, float(tree.threshold[i]))
            got_gain[i] = tree.gain[i]
        else:
            gq = split_gains(GNq[i], HNq[i], CN[i], params)
            j, t = (int(a) for a in np.unravel_index(int(np.argmax(gq)), gq.shape))
            got_gain[i] = gq[j, t]
        # its gain in float64; the floors are read a hair loosely for the
        # split judged and a hair strictly for the best it is held against,
        # so a sum that sits on a floor to rounding fails nobody
        ref_gain[i] = split_gains(GN[i], HN[i], CN[i], params, margin=-1e-4)[j, t]
        if not np.isfinite(ref_gain[i]):
            hl = HN[i][j, : t + 1].sum(); hr = HN[i][j, t + 1:].sum()
            violation[i] = max(1e-3, 1.0 - min(hl, hr) / min_h) if min_h > 0 else 1.0
            ref_gain[i] = 0.0
        best = float(np.max(split_gains(GN[i], HN[i], CN[i], params, margin=1e-4)))
        if np.isfinite(best) and best > 0:
            regret[i] = max(0.0, best - ref_gain[i]) / best
    gain_gaps = _rel_gaps(got_gain, ref_gain)
    if detail is not None:
        # the widest single gaps swing by their nature (one leaf, one node, a
        # difference of large terms): read beside the numbers, not compared
        w = int(np.argmax(leaf_gaps))
        detail.append({
            "leaf_value_worst": float(leaf_gaps[w]), "worst_leaf_rows": float(ref_leaf_cnt[w]),
            "worst_leaf_got": float(got_leaf_val[w] - bias),
            "worst_leaf_ref": float(ref_leaf_val[w] - bias),
            "split_gain_worst": float(np.max(gain_gaps)),
        })
    return {
        "count_mismatch": count_mismatch,
        "leaf_value_rms_gap": _rms(leaf_gaps),
        "split_gain_rms_gap": _rms(gain_gaps),
        "split_regret": float(np.max(regret, initial=0.0)),
        "floor_violation": float(np.max(violation, initial=0.0)),
    }


def follow(trees: Sequence[Tree], blocks: Sequence[np.ndarray], y: np.ndarray,
           level_cols, values: np.ndarray, params: Dict[str, Any], *,
           valid_blocks: Optional[Sequence[np.ndarray]] = None,
           valid_y: Optional[np.ndarray] = None,
           valid_metric: Optional[Sequence[float]] = None,
           control: Optional[str] = None, detail: Optional[list] = None,
           pool: Optional[RowPool] = None) -> Dict[str, float]:
    """Worst numbers over ``trees`` (the first trees the timed path grew),
    each judged with the scores of the trees before it."""
    y = np.asarray(y, np.float64)
    bias = init_score(y)
    score = np.full(len(y), bias)
    vscore = None
    if valid_blocks is not None:
        vy = np.asarray(valid_y, np.float64)
        vscore = np.full(len(vy), bias)
        vscore_low = round_bfloat16(vscore)
    worst: Dict[str, float] = {}
    for k, tree in enumerate(trees):
        g, h = gradients(score, y, pool)
        leaf_of_row = walk(tree, blocks, pool)
        nums = judge_tree(tree, leaf_of_row, level_cols, values, g, h, params,
                          bias if k == 0 else 0.0, control=control, detail=detail,
                          pool=pool)
        # the first tree carries the bias in its leaves; the score already has it
        score += tree.leaf_value[leaf_of_row] - (bias if k == 0 else 0.0)
        if vscore is not None:
            step = predict(tree, valid_blocks, pool) - (bias if k == 0 else 0.0)
            vscore += step
            ref = logloss(vscore, vy)
            if control is not None:
                # the control's validation score is kept in bfloat16
                vscore_low = round_bfloat16(vscore_low + step)
                nums["valid_logloss_gap"] = abs(logloss(vscore_low, vy) - ref) / ref
            elif valid_metric is not None and k < len(valid_metric):
                nums["valid_logloss_gap"] = abs(float(valid_metric[k]) - ref) / ref
        for name, v in nums.items():
            worst[name] = max(worst.get(name, 0.0), float(v))
    return worst


# ------------------------------------------- what the comparison calls


def levels_of(blocks, recipe, pool: Optional[RowPool] = None):
    """Per feature, the grid level of every row (the reference's own
    reading of the raw values; nothing of the program's binning); a block
    a thread of ``pool``."""
    g = np.float32(recipe["grid"])
    h = int(recipe["half_levels"])
    dtype = np.uint8 if 2 * h < 256 else np.int16
    f = blocks[0].shape[1]
    cols = np.empty((f, sum(b.shape[0] for b in blocks)), dtype)
    starts = np.cumsum([0] + [b.shape[0] for b in blocks[:-1]])

    def one(i):
        # a few thousand rows at a time, so that the transpose reads from cache
        b, at = blocks[i], starts[i]
        for s in range(0, b.shape[0], 4096):
            x = b[s: s + 4096]
            k = (np.rint(x * g).astype(np.int16) + h).astype(dtype)
            cols[:, at + s: at + s + x.shape[0]] = k.T

    if pool is None:
        for i in range(len(blocks)):
            one(i)
    else:
        pool.map_blocks(one, range(len(blocks)))
    values = (np.arange(-h, h + 1, dtype=np.float32) / g).astype(np.float64)
    return list(cols), values


def follow_model(tree_dumps, *, blocks, y, params, recipe, valid_blocks=None,
                 valid_y=None, valid_metric=None, control=None, detail=None):
    """The numbers ``correct`` is decided by, for the first trees of the
    model the timed path produced (``dump_model()`` tree structures)."""
    trees = [tree_from_dump(t) for t in tree_dumps]
    pool = RowPool.for_table(sum(b.shape[0] for b in blocks), blocks[0].shape[1])
    try:
        cols, values = levels_of(blocks, recipe, pool)
        return follow(trees, blocks, y, cols, values, params,
                      valid_blocks=valid_blocks, valid_y=valid_y,
                      valid_metric=valid_metric, control=control, detail=detail,
                      pool=pool)
    finally:
        if pool is not None:
            pool.close()
