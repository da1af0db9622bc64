"""The split scan as it was before the histogram became three [F, B] planes:
``best_split`` of the commit before PR 32, verbatim, on the record form
[F, B, 3] (stat axis last).  tests/test_split_planes.py holds the plane-form
scan to it bit for bit; nothing else may import it.  The helpers it calls are
the package's own (the gain arithmetic is shared, elementwise and unchanged).
"""

from typing import Optional

import jax.numpy as jnp

from lightgbm_tpu.ops.split import (
    CatParams,
    SplitCandidate,
    _EPS,
    constrained_output,
    gain_given_output,
    leaf_gain,
)


def best_split_records(
    hist: jnp.ndarray,  # [F, B, 3] (sum_grad, sum_hess, count)
    parent_g: jnp.ndarray,
    parent_h: jnp.ndarray,
    parent_cnt: jnp.ndarray,
    num_bins: jnp.ndarray,  # [F] total bins per feature (incl. NaN bin)
    nan_bins: jnp.ndarray,  # [F] NaN-bin index per feature, -1 if none
    feature_mask: jnp.ndarray,  # [F] bool — col-sampled features
    *,
    lambda_l1: float,
    lambda_l2: float,
    min_data_in_leaf: int,
    min_sum_hessian_in_leaf: float,
    min_gain_to_split: float,
    max_delta_step: float = 0.0,
    path_smooth: float = 0.0,
    monotone: Optional[jnp.ndarray] = None,  # [F] int8 in {-1, 0, +1}
    leaf_lb=None,  # scalar lower bound on child outputs (monotone)
    leaf_ub=None,
    parent_output=0.0,  # current output of the leaf (path smoothing)
    is_cat: Optional[jnp.ndarray] = None,  # [F] bool — categorical features
    cat_params: Optional[CatParams] = None,  # static; required with is_cat
    cegb_penalty: Optional[jnp.ndarray] = None,  # [F] f32 per-feature penalty
    cegb_split_penalty: float = 0.0,  # tradeoff * cegb_penalty_split
    rand_bins: Optional[jnp.ndarray] = None,  # [F] extra_trees random bin
    per_feature_gains: bool = False,  # also return max gain per feature [F]
    monotone_penalty: float = 0.0,  # depth-scaled gain penalty for monotone
    #                   features (reference monotone_constraints.hpp:357-366,
    #                   applied at serial_tree_learner.cpp:1002); needs
    #                   ``leaf_depth`` and ``monotone`` to engage
    leaf_depth=None,  # scalar i32 — depth of THIS leaf (the penalty is
    #                   evaluated at leaf_depth + 1, the children's depth)
    feature_contri: Optional[jnp.ndarray] = None,  # [F] f32 per-feature gain
    #                   multipliers (reference FeatureMetainfo::penalty,
    #                   feature_histogram.hpp:1445-1448)
    adv_bounds=None,  # advanced monotone: (lb_l, ub_l, lb_r, ub_r) [F, B]
    #                   per-THRESHOLD child bounds (reference
    #                   AdvancedLeafConstraints / CumulativeFeatureConstraint,
    #                   monotone_constraints.hpp:858/:146) — applied to the
    #                   numeric candidates instead of the scalar leaf bounds
    with_margin: bool = False,  # also return the near-tie margin: the
    #                   relative gain gap between the winning candidate and
    #                   the global runner-up, +inf when either is non-finite.
    #                   The grower's int8-default histogram path re-
    #                   accumulates in f32 when this falls below
    #                   near_tie_tol (histogram engine v2).
    bundle_end: Optional[jnp.ndarray] = None,  # [F, B] i32 — EFB planes
    #                   (bundling.py): for a bundle-plane bin inside a member
    #                   feature's sub-range, the sub-range's LAST bin; -1
    #                   elsewhere.  A candidate at bundle bin t means
    #                   "member-local bin <= t - start goes left", i.e. left
    #                   child = everything except plane bins [t, end] — the
    #                   reference's per-feature scan over a feature group's
    #                   histogram with the out-of-range mass folded into the
    #                   feature's default bin.
) -> SplitCandidate:
    """cegb_*: Cost-Effective Gradient Boosting (reference:
    cost_effective_gradient_boosting.hpp DeltaGain — gain is reduced by
    tradeoff*penalty_split*num_data plus a per-feature penalty, here the
    coupled penalty for features not yet used anywhere in the model)."""
    f, b, _ = hist.shape
    use_full_gain = monotone is not None or path_smooth > 0.0
    use_cat = is_cat is not None

    has_nan = nan_bins >= 0
    nan_idx = jnp.where(has_nan, nan_bins, 0)
    nan_stats = jnp.take_along_axis(hist, nan_idx[:, None, None], axis=1)[:, 0, :]
    nan_stats = nan_stats * has_nan[:, None]  # [F, 3]

    # zero out the NaN bin so the cumsum covers only ordered numeric bins
    bin_ids = jnp.arange(b, dtype=jnp.int32)[None, :]
    is_nan_bin = has_nan[:, None] & (bin_ids == nan_bins[:, None])
    hist_o = jnp.where(is_nan_bin[:, :, None], 0.0, hist)

    cum = jnp.cumsum(hist_o, axis=1)  # [F, B, 3] left stats (missing right)
    parent = jnp.stack(
        [parent_g.astype(jnp.float32), parent_h.astype(jnp.float32), parent_cnt.astype(jnp.float32)]
    )

    # candidate threshold at bin t is valid for t in [0, num_ordered_bins-2]
    num_ordered = num_bins - has_nan.astype(jnp.int32)
    valid_bin = bin_ids < (num_ordered[:, None] - 1)
    if bundle_end is not None:
        # EFB bundle planes: left child at bundle bin t = parent minus the
        # owning member's plane bins [t, end] (everything else — the shared
        # default bin 0 and every OTHER member's mass — is "member at its
        # default", which goes left).  left = parent - (cum[end] - cum[t-1]).
        # Non-bundle bins keep the plain cumsum; every sub-range bin is a
        # valid candidate (t = start encodes "default alone goes left").
        bundled_bin = bundle_end >= 0  # [F, B]
        plane_bundled = bundled_bin.any(axis=1)  # [F]
        cum_end = jnp.take_along_axis(
            cum, jnp.clip(bundle_end, 0, b - 1)[:, :, None], axis=1
        )  # [F, B, 3]
        cum = jnp.where(
            bundled_bin[:, :, None],
            parent[None, None, :] - cum_end + cum - hist_o,
            cum,
        )
        valid_bin = jnp.where(plane_bundled[:, None], bundled_bin, valid_bin)
    if rand_bins is not None:
        # extra_trees (extremely randomized trees): only ONE random
        # threshold per feature competes (reference USE_RAND branch of
        # FindBestThresholdSequentially, feature_histogram.hpp:870)
        valid_bin = valid_bin & (bin_ids == rand_bins[:, None])
    num_feature_mask = feature_mask & ~is_cat if use_cat else feature_mask

    def eval_gain(lg, lh, lc, l2v, ok, bnds=None):
        """Masked split gain for [F, B] left-stat candidates (reference:
        GetSplitGains, feature_histogram.hpp:759-828).  ``bnds`` overrides
        the scalar leaf bounds with per-candidate (lb_l, ub_l, lb_r, ub_r)
        arrays (advanced monotone mode, numeric candidates only)."""
        rg, rh, rc = parent[0] - lg, parent[1] - lh, parent[2] - lc
        ok = (
            ok
            & (lc >= min_data_in_leaf)
            & (rc >= min_data_in_leaf)
            & (lh >= min_sum_hessian_in_leaf)
            & (rh >= min_sum_hessian_in_leaf)
        )
        if not use_full_gain:
            gain = leaf_gain(lg, lh, lambda_l1, l2v) + leaf_gain(
                rg, rh, lambda_l1, l2v
            )
        else:
            lb_l, ub_l, lb_r, ub_r = (
                bnds if bnds is not None
                else (leaf_lb, leaf_ub, leaf_lb, leaf_ub)
            )
            # full path: constrained outputs + GetLeafGainGivenOutput
            out_l = constrained_output(
                lg, lh, lambda_l1, l2v, max_delta_step,
                path_smooth, lc, parent_output, lb_l, ub_l,
            )
            out_r = constrained_output(
                rg, rh, lambda_l1, l2v, max_delta_step,
                path_smooth, rc, parent_output, lb_r, ub_r,
            )
            gain = gain_given_output(lg, lh, lambda_l1, l2v, out_l) + \
                gain_given_output(rg, rh, lambda_l1, l2v, out_r)
            if monotone is not None:
                mc = monotone[:, None]
                violated = ((mc > 0) & (out_l > out_r)) | ((mc < 0) & (out_l < out_r))
                ok = ok & ~violated
        return jnp.where(ok, gain, -jnp.inf)

    def eval_case(left):  # left: [F, B, 3] — numeric cumsum candidates
        return eval_gain(
            left[..., 0],
            left[..., 1],
            left[..., 2],
            lambda_l2,
            valid_bin & num_feature_mask[:, None],
            bnds=adv_bounds,
        )

    gain_right = eval_case(cum)  # missing -> right (default_left = False)
    gain_left = jnp.where(
        has_nan[:, None], eval_case(cum + nan_stats[:, None, :]), -jnp.inf
    )  # missing -> left; only distinct when a NaN bin exists

    cases = [gain_right, gain_left]
    if use_cat:
        # ---- categorical splits (FindBestThresholdCategoricalInner,
        # src/treelearner/feature_histogram.cpp:147-343).  TPU formulation:
        # the per-feature sequential sorted-subset scan becomes one argsort
        # over the bin axis + prefix sums evaluated for ALL (feature, k)
        # candidates at once; the winning subset is reconstructed as a
        # bin-space bitmask from the sort ranks.
        cp = cat_params if cat_params is not None else CatParams()
        g_, h_, c_ = hist[..., 0], hist[..., 1], hist[..., 2]
        # the NaN bin never moves LEFT: prediction sends categorical NaN to
        # the right child (reference CategoricalDecision, tree.h:346), so
        # keeping its rows right during training makes train == predict
        in_range = (bin_ids < num_bins[:, None]) & ~is_nan_bin
        catf = (is_cat & feature_mask)[:, None]
        use_onehot_f = (num_bins <= cp.max_cat_to_onehot)[:, None]
        oh_ok = in_range & catf & use_onehot_f
        if rand_bins is not None:
            # extra_trees randomizes categorical candidates too (reference
            # USE_RAND in FindBestThresholdCategoricalInner): one random
            # category for one-hot ...
            oh_ok = oh_ok & (
                bin_ids == (rand_bins % jnp.maximum(num_bins, 1))[:, None]
            )
        # case 2 — one-hot: left = the single category bin (:188-241)
        gain_oh = eval_gain(g_, h_, c_, lambda_l2, oh_ok)
        # cases 3/4 — sorted subset scan, both directions (:243-342)
        l2c = lambda_l2 + cp.cat_l2
        validb = in_range & (c_ >= cp.cat_smooth)
        ctr = g_ / (h_ + cp.cat_smooth)
        key = jnp.where(validb, ctr, jnp.inf)
        order = jnp.argsort(key, axis=1, stable=True)  # [F, B] bin ids
        rank = jnp.argsort(order, axis=1)  # [F, B] sorted position per bin

        def _sorted(x):
            return jnp.take_along_axis(jnp.where(validb, x, 0.0), order, axis=1)

        pre_g = jnp.cumsum(_sorted(g_), axis=1)
        pre_h = jnp.cumsum(_sorted(h_), axis=1)
        pre_c = jnp.cumsum(_sorted(c_), axis=1)
        used = validb.sum(axis=1).astype(jnp.int32)  # [F]
        tot_g, tot_h, tot_c = pre_g[:, -1:], pre_h[:, -1:], pre_c[:, -1:]
        max_num_cat = jnp.minimum(cp.max_cat_threshold, (used + 1) // 2)
        pos_ok = bin_ids < jnp.minimum(used, max_num_cat)[:, None]
        if rand_bins is not None:
            # ... and one random subset size for the sorted scan (:271)
            rpos = rand_bins % jnp.maximum(jnp.minimum(used, max_num_cat), 1)
            pos_ok = pos_ok & (bin_ids == rpos[:, None])
        ok_sorted = catf & ~use_onehot_f & pos_ok

        bidx = used[:, None] - 2 - bin_ids  # bwd prefix end (may be < 0)
        has_pre = bidx >= 0
        bidxc = jnp.clip(bidx, 0, b - 1)

        def _bwd(pre, tot):
            return tot - jnp.where(
                has_pre, jnp.take_along_axis(pre, bidxc, axis=1), 0.0
            )

        def _group_ok(lc):
            # min_data_per_group: the reference evaluates a candidate only
            # after >= min_data_per_group rows accumulated since the last
            # evaluated candidate (:278-312). Vectorized approximation:
            # evaluate where the cumulative count crosses a multiple of
            # min_data_per_group (exact when min_data_per_group <= 1).
            if cp.min_data_per_group <= 1:
                return jnp.ones(lc.shape, bool)
            prev = jnp.concatenate(
                [jnp.zeros((f, 1), lc.dtype), lc[:, :-1]], axis=1
            )
            m = float(cp.min_data_per_group)
            return jnp.floor(lc / m) > jnp.floor(prev / m)

        mdpg_ok_fwd = parent[2] - pre_c >= cp.min_data_per_group
        gain_fwd = eval_gain(
            pre_g, pre_h, pre_c, l2c,
            ok_sorted & _group_ok(pre_c) & mdpg_ok_fwd,
        )
        bg, bh, bc = _bwd(pre_g, tot_g), _bwd(pre_h, tot_h), _bwd(pre_c, tot_c)
        gain_bwd = eval_gain(
            bg, bh, bc, l2c,
            ok_sorted & _group_ok(bc) & (parent[2] - bc >= cp.min_data_per_group),
        )
        cases += [gain_oh, gain_fwd, gain_bwd]

    gains = jnp.stack(cases)  # [C, F, B]
    if not use_full_gain:
        parent_gain = leaf_gain(parent[0], parent[1], lambda_l1, lambda_l2)
    else:
        parent_gain = gain_given_output(
            parent[0], parent[1], lambda_l1, lambda_l2,
            constrained_output(
                parent[0], parent[1], lambda_l1, lambda_l2, max_delta_step,
                0.0, None, 0.0, leaf_lb, leaf_ub,
            ),
        )
    use_penalized = feature_contri is not None or (
        monotone is not None
        and monotone_penalty > 0.0
        and leaf_depth is not None
    )
    if cegb_penalty is not None and not use_penalized:
        # per-feature penalty shifts which candidate wins (DeltaGain's
        # coupled term); applied in improvement units so the parent-gain
        # subtraction below stays correct
        gains = gains - cegb_penalty[None, :, None]
    if use_penalized:
        # the reference applies these multipliers to the IMPROVEMENT (raw
        # gain minus parent gain minus min_gain_shift) before the
        # cross-feature comparison — FindBestThreshold's
        # ``output->gain *= meta_->penalty`` (feature_histogram.hpp:1445)
        # and ComputeMonotoneSplitGainPenalty at
        # serial_tree_learner.cpp:1002 — so they can change which feature
        # wins, not just rescale the winner
        mult = jnp.ones((f,), jnp.float32)
        if (
            monotone is not None
            and monotone_penalty > 0.0
            and leaf_depth is not None
        ):
            d = (jnp.asarray(leaf_depth) + 1).astype(jnp.float32)
            if monotone_penalty <= 1.0:
                base = 1.0 - monotone_penalty / jnp.exp2(d) + _EPS
            else:
                base = 1.0 - jnp.exp2(monotone_penalty - 1.0 - d) + _EPS
            pen = jnp.where(monotone_penalty >= d + 1.0, _EPS, base)
            mult = mult * jnp.where(monotone != 0, pen, 1.0)
        if feature_contri is not None:
            mult = mult * feature_contri.astype(jnp.float32)
        imp_all = gains - parent_gain - min_gain_to_split
        scaled = jnp.where(
            jnp.isfinite(gains), imp_all * mult[None, :, None], -jnp.inf
        )
        if cegb_penalty is not None:
            # reference order: penalty multiply, THEN the CEGB delta
            scaled = scaled - cegb_penalty[None, :, None]
        sel = scaled
    else:
        sel = gains
    flat = jnp.argmax(sel)
    if with_margin:
        # relative gap to the global runner-up across EVERY candidate
        # (cases x features x bins) — a flip anywhere in this tensor is a
        # structure change, so this is the conservative near-tie signal
        sel_flat = sel.reshape(-1)
        best_v = sel_flat[flat]
        sec_v = jnp.max(
            jnp.where(
                jnp.arange(sel_flat.shape[0], dtype=jnp.int32) == flat,
                -jnp.inf,
                sel_flat,
            )
        )
        margin = jnp.where(
            jnp.isfinite(best_v) & jnp.isfinite(sec_v),
            (best_v - sec_v) / jnp.maximum(jnp.abs(best_v), _EPS),
            jnp.inf,
        ).astype(jnp.float32)
    case = (flat // (f * b)).astype(jnp.int32)
    dl = (case == 1).astype(jnp.int32)
    rem = flat % (f * b)
    feat = (rem // b).astype(jnp.int32)
    tbin = (rem % b).astype(jnp.int32)
    best_gain_raw = gains.reshape(-1)[flat]

    left = cum[feat, tbin] + jnp.where(dl == 1, nan_stats[feat], 0.0)
    if use_cat:
        left_oh = hist[feat, tbin]
        left_fwd = jnp.stack([pre_g[feat, tbin], pre_h[feat, tbin], pre_c[feat, tbin]])
        left_bwd = jnp.stack([bg[feat, tbin], bh[feat, tbin], bc[feat, tbin]])
        left = jnp.select(
            [case == 2, case == 3, case == 4],
            [left_oh, left_fwd, left_bwd],
            left,
        )
        sel_rank = rank[feat]
        sel_valid = validb[feat]
        oh_mask = jnp.arange(b, dtype=jnp.int32) == tbin
        fwd_mask = sel_valid & (sel_rank <= tbin)
        bwd_mask = sel_valid & (sel_rank >= used[feat] - 1 - tbin)
        is_cat_win = case >= 2
        cat_mask = jnp.select(
            [case == 2, case == 3, case == 4],
            [oh_mask, fwd_mask, bwd_mask],
            jnp.zeros((b,), bool),
        )
    else:
        is_cat_win = jnp.asarray(False)
        cat_mask = jnp.zeros((b if bundle_end is not None else 1,), bool)
    if bundle_end is not None:
        # a bundle-plane winner partitions by plane-bin MEMBERSHIP (left =
        # everything except the member's bins [t, end]) — expressed through
        # the existing categorical-mask machinery so every partition /
        # replay / device-predict path applies it unchanged; the host Tree
        # decode (tree.py) turns it back into a numeric threshold on the
        # original feature
        bwin_end = bundle_end[feat, tbin]
        bundled_win = bwin_end >= 0
        bids = jnp.arange(b, dtype=jnp.int32)
        bundle_mask = ~((bids >= tbin) & (bids <= bwin_end))
        is_cat_win = jnp.asarray(is_cat_win) | bundled_win
        cat_mask = jnp.where(bundled_win, bundle_mask, cat_mask)
    if use_penalized:
        improvement = scaled.reshape(-1)[flat]
    else:
        improvement = best_gain_raw - parent_gain - min_gain_to_split
    if cegb_split_penalty:
        # uniform per-split data cost: tradeoff * penalty_split * num_data
        improvement = improvement - cegb_split_penalty * parent[2]
    improvement = jnp.where(jnp.isfinite(best_gain_raw), improvement, -jnp.inf)

    cand_out = SplitCandidate(
        gain=improvement.astype(jnp.float32),
        feature=feat,
        bin=tbin,
        default_left=dl == 1,
        left_g=left[0],
        left_h=left[1],
        left_cnt=left[2],
        right_g=parent[0] - left[0],
        right_h=parent[1] - left[1],
        right_cnt=parent[2] - left[2],
        is_cat=is_cat_win,
        cat_mask=cat_mask,
    )
    if per_feature_gains:
        # best IMPROVEMENT per feature (raw gain minus the same parent/
        # min_gain offset the winning candidate uses — including the
        # constrained-parent form under use_full_gain) — the voting-parallel
        # learner's LightSplitInfo gains (voting_parallel_tree_learner.cpp:152)
        if use_penalized:
            pf = sel.max(axis=(0, 2))
        else:
            pf = gains.max(axis=(0, 2)) - parent_gain - min_gain_to_split
        return (cand_out, pf, margin) if with_margin else (cand_out, pf)
    if with_margin:
        return cand_out, margin
    return cand_out
