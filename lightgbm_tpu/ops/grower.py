"""Leaf-wise (best-first) tree grower, fully on-device under one jit.

Reference analogs: ``SerialTreeLearner::Train`` (src/treelearner/
serial_tree_learner.cpp:182 — BeforeTrain, then a loop of ConstructHistograms
-> FindBestSplitsFromHistograms -> argmax leaf -> Split) and the CUDA
single-GPU learner's per-leaf device loop (src/treelearner/cuda/
cuda_single_gpu_tree_learner.cpp:159-330).

TPU-native design decisions:
  * row->leaf membership is a dense ``leaf_id`` vector updated by a masked
    compare (the reference's DataPartition index-array shuffle and the CUDA
    prefix-sum scatter both become one vectorized ``where``);
  * the smaller child's histogram is built by a masked pass, the sibling by
    the parent-minus-smaller subtraction trick (serial_tree_learner.cpp:558);
  * per-leaf best splits are cached so each step only rescans the two leaves
    the previous split touched;
  * the whole num_leaves-1 loop is a ``lax.fori_loop`` with static shapes;
    a ``done`` flag makes trailing iterations no-ops once no leaf has a
    positive-gain split;
  * with ``axis_name`` set, histogram/root sums are ``psum``-ed across the
    data mesh axis — the data-parallel learner's ReduceScatter+Allreduce
    (src/treelearner/data_parallel_tree_learner.cpp) as XLA collectives.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..obs.collectives import timed_pmax, timed_pmin, timed_psum
from ..obs.jit import instrumented_jit
from ..obs.trace import get_tracer
from .histogram import leaf_histogram
from .score_lookup import ONEHOT_MAX_LEAVES, leaf_ids_form, tree_leaves
from .split import CatParams, SplitCandidate, best_split, leaf_gain, leaf_output


@dataclasses.dataclass(frozen=True)
class GrowerParams:
    """Static (compile-time) training parameters for one tree."""

    num_leaves: int
    max_bin: int  # B: padded bin-axis size of the histogram
    max_depth: int = -1
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_gain_to_split: float = 0.0
    max_delta_step: float = 0.0
    hist_method: str = "auto"
    axis_name: Optional[str] = None
    # voting-parallel (PV-Tree, tree_learner=voting): local top-k election,
    # psum only the elected 2k features' histogram slices; 0 = off.  Active
    # only when F > 2*top_k (see voting_active) — below that dense psum is
    # exact and cheaper, so voting aliases onto the data-parallel path.
    voting_top_k: int = 0
    # feature-parallel (tree_learner=feature with rows REPLICATED,
    # reference feature_parallel_tree_learner.cpp:37): every shard holds all
    # rows, histograms/split-finding cover only its axis_index'th feature
    # slice (F must divide the shard count), the winning candidate is
    # all-reduced, and the partition runs locally on the full columns — the
    # reference's "every machine has full data" design, so no split-result
    # broadcast is needed.  Requires hist_mode gather/full (the leaf-id
    # formulation keeps full columns addressable).  Value = number of
    # feature shards; 0 = off.
    feature_shard: int = 0
    # named-mesh second axis (parallel/mesh.py): when set, feature shards
    # elect/broadcast over THIS axis while histogram/count psums keep
    # running over axis_name — the hybrid ('data','feature') 2D layout.
    # None preserves the one-axis world: feature_shard > 1 reuses
    # axis_name for the election (rows replicated, no histogram psum).
    feature_axis_name: Optional[str] = None
    # categorical split search (sorted-subset scan, feature_histogram.cpp:147);
    # False keeps every cat-related array at width 1 (static no-op)
    use_cat: bool = False
    cat_params: Optional[CatParams] = None
    # EFB bundle planes (bundling.py): the bundle_end operand routes bundled
    # split candidates through ops/split.py and their partitions through the
    # categorical-mask machinery (masks widen to B like use_cat)
    use_bundle: bool = False
    # forced splits (forcedsplits_filename JSON BFS,
    # serial_tree_learner.cpp:627): the first n_forced loop steps apply the
    # host-precomputed (leaf, feature, bin) splits instead of the best-gain
    # argmax; a negative-gain forced split aborts the remaining forced steps
    # (reference abort_last_forced_split) and normal growth resumes
    n_forced: int = 0
    # fuse the best-split scan into one Pallas kernel on the basic numeric
    # path (ops/pallas/split_scan.py — the CUDA FindBestSplitsForLeafKernel
    # shape); targets the per-split fixed cost, default off pending on-chip
    # measurement
    fused_split_scan: bool = False
    # CEGB (cost_effective_gradient_boosting.hpp): per-split data cost is
    # static; the per-feature coupled penalty arrives as a runtime operand
    use_cegb: bool = False
    cegb_split_penalty: float = 0.0
    # "seg": keep rows PHYSICALLY in leaf-segment order (packed 256B rows);
    # each split is a stable sort of the parent's contiguous window and each
    # histogram a contiguous DMA stream — no random gathers, which serialize
    # on TPU (~35ns/element measured; see ops/segpart.py);
    # "ordered": leaf-contiguous row permutation (the reference's
    # DataPartition index array, data_partition.hpp) with per-split index
    # gathers — O(parent segment) work but gather-bound on TPU;
    # "gather": leaf-id vector + per-split jnp.nonzero compaction; "full":
    # masked pass over all rows per split.
    hist_mode: str = "ordered"
    path_smooth: float = 0.0
    use_monotone: bool = False  # monotone_constraints
    # "basic": children bounded by the split midpoint (BasicLeafConstraints,
    # monotone_constraints.hpp:465).  "intermediate": bounds propagate to
    # CONTIGUOUS leaves across the split plane and affected leaves' cached
    # candidates are refreshed (IntermediateLeafConstraints, :516) — the
    # recursive GoUp/GoDownToFindLeavesToUpdate tree walk becomes a
    # vectorized box-adjacency test over per-leaf feature-range boxes
    # [L, F, 2]: leaf b is updated from new child c iff their boxes TOUCH
    # along exactly the one monotone feature separating them and intersect
    # along every other feature (equivalent: the walk ascends to the lowest
    # common ancestor — whose split feature is the unique separating one —
    # and the descent pruning keeps exactly the box-intersecting leaves).
    monotone_method: str = "basic"
    # candidate refreshes per split for bound-tightened leaves (intermediate
    # mode); leaves beyond the K stalest keep their cached candidate until
    # their next natural refresh (outputs are still clamped to the live
    # bounds, so monotonicity never depends on this)
    monotone_recompute_k: int = 8
    use_interaction: bool = False  # interaction_constraints
    feature_fraction_bynode: float = 1.0
    extra_trees: bool = False  # one random threshold per feature (USE_RAND)
    # frontier batching: split the top-K frontier leaves per compiled loop
    # step (K partitions over disjoint windows, one batched smaller-child
    # histogram pass, 2K candidate refreshes in one scan, and ONE psum per
    # collective kind under data-parallel).  Exactness by the prefix-commit
    # rule: with batch gains g1 >= ... >= gK, commit exactly the longest
    # prefix whose gi beats the running max gain of children created by
    # earlier batch members; uncommitted members are value-preserving no-ops
    # and their leaves stay in the frontier — the committed split sequence
    # is identical to serial leaf-wise growth.  1 = the serial fori_loop,
    # byte-identical to the pre-batching grower.
    leaf_batch: int = 1
    # fused Pallas grow step (ops/pallas/grow_step.py): partition + local
    # smaller-child election + histogram for all K frontier members in ONE
    # kernel launch, collapsing the per-step dispatch/fusion-boundary share.
    # Engages only on the seg fast path with NO axis_name (the data-parallel
    # election needs a mid-step psum of per-shard counts, so that mode keeps
    # the two-launch path); the XLA composition stays the fallback and
    # correctness oracle everywhere else.  boosting/gbdt.py resolves the
    # user-facing 'auto'/'on'/'off' config into this bool.
    grow_fused: bool = False
    # depth-scaled split-gain penalty on monotone features (reference
    # ComputeMonotoneSplitGainPenalty, monotone_constraints.hpp:357)
    monotone_penalty: float = 0.0
    # per-feature gain multipliers arrive via the feature_contri operand
    use_feature_contri: bool = False
    # measured collectives (obs/collectives): swap every psum/pmax/pmin site
    # for the timed/byte-counted wrapper.  Static on purpose — toggling it
    # must retrace, never silently reuse a trace without the callbacks.
    measure_collectives: bool = False
    # histogram accumulator (histogram engine v2): "auto" engages the
    # 2-digit int8 MXU accumulation by DEFAULT on the seg TPU path (true
    # f32 grads quantized once per iteration, seg.QMAX grid) with an f32
    # re-accumulate pass for near-tie split decisions; "bf16" keeps the
    # 3-term bf16 split everywhere; "int8" is "auto" without the opt-out.
    # Does not apply to quantized-gradient training (quant_scales): values
    # already on an integer grid take the integer kernels, exactly.
    hist_acc: str = "auto"
    # relative gain gap below which the int8-default winner is considered
    # a near tie and its histogram is re-accumulated in f32 before the
    # structure decision (int8 grid step ~6e-5 relative; 1e-3 covers the
    # worst-case gain-domain amplification under gradient cancellation)
    near_tie_tol: float = 1e-3
    # double-buffered histogram collectives: under leaf_batch > 1 with a
    # histogram psum axis, split the [K, 3, F, B] frontier stack into two
    # half-window psums (sites "hist_db0"/"hist_db1") issued BETWEEN the
    # half-builds, so XLA's async all-reduce of buffer 0 overlaps the
    # histogram build of buffer 1.  Byte-identical to the single psum
    # (psum is elementwise per member; member order is preserved) and the
    # measured byte total is unchanged (obs.collectives sums every
    # psum/* site).  Structurally off at leaf_batch=1 — the serial loop
    # has nothing to overlap with.  gbdt resolves 'auto'/'on'/'off'.
    overlap_collectives: bool = False
    # vmapped model-fleet training (parallel/mesh.make_fleet_grow): name of
    # the vmap model axis.  Capacity-bucket switch indices are pmax'd over
    # this axis before the searchsorted: vmap's collective batching rule
    # reduces over the mapped dimension and returns an UNMAPPED value, so
    # the ladder switch lowers ONE shared branch for the whole fleet instead
    # of executing every branch (the select-all-branches rule for batched
    # switch indices — measured ~8x per-member at 64k rows).  Capacity only
    # pads, so the max member's bucket is value-preserving for the rest.
    fleet_axis_name: Optional[str] = None
    # row sampling on the segment path (GOSS, bagging, rf, a fixed row
    # mask): after pack_rows ONE stable partition on "mask plane > 0"
    # brings the in-bag rows to the front of the packed buffer, the root
    # window is [0, n_in_bag), and every later partition and histogram call
    # sees in-bag rows only (upstream's bag_data_indices; the windows are
    # dynamic over a static buffer, so no capacity is guessed).  Rows that
    # were never partitioned have no segment position: every row's leaf
    # comes from the tree's bin-space walk (score_lookup.tree_leaves) in
    # place of the leaf_ids sort, so the returned leaf_id is whole.  False
    # (no sampler) traces the program as it was.  Engages only where
    # bag_window_ok holds; boosting/gbdt.py resolves it.
    bag_window: bool = False


def bag_window_ok(p: "GrowerParams", cat_width: int) -> bool:
    """Whether a tree of these parameters can be grown on the in-bag window
    alone: the segment path, rows not replicated over feature shards, and a
    numeric tree small enough for the contraction walk that finds the
    out-of-bag rows' leaves (a wider ``cat_mask`` or more leaves keep the
    masked whole-table path)."""
    return (
        p.hist_mode == "seg" and p.feature_shard <= 1 and cat_width <= 1
        and p.num_leaves <= ONEHOT_MAX_LEAVES
    )


def _hist_caps(n: int, full_range: bool = False) -> list:
    """Static capacity ladder for the smaller child: N/2, N/8, N/32, ...

    The smaller child of any split holds <= floor(parent/2) <= floor(N/2)
    rows, so the top capacity always fits; smaller buckets avoid paying the
    top capacity for deep (small) leaves.  ``full_range`` extends the top to
    N: under data-parallel sharding the GLOBALLY smaller child can still hold
    up to all local rows of one shard."""
    caps = []
    top = max(n, 1) if full_range else max(n // 2, 1)
    cap = 1 << max(0, (top - 1).bit_length())
    floor_cap = min(4096, cap)
    while cap > floor_cap:
        caps.append(cap)
        cap //= 2
    caps.append(cap)
    return caps  # descending


def _part_caps(n: int) -> list:
    """Static capacity ladder for PARENT segments in ordered mode: the root
    holds all n rows, so the top is pow2ceil(n); pow-2 steps down to 8192
    bound both the wasted work (<2x the true segment size) and the number of
    compiled partition branches."""
    caps = []
    cap = 1 << max(0, (max(n, 1) - 1).bit_length())
    floor_cap = min(8192, cap)
    while cap > floor_cap:
        caps.append(cap)
        cap //= 2
    caps.append(cap)
    return sorted(caps)  # ascending


def cat_mask_width(use_cat: bool, use_bundle: bool, num_bins: int) -> int:
    """Bins a grown tree's ``cat_mask`` spans (static): 1, a no-op, for a
    numeric tree; the whole bin axis where splits can be categorical, and for
    EFB bundles too, whose splits ride the same mask machinery.  The one rule
    the grower builds its trees by and the Booster names their score update
    by (``predict.valid_walk_form``)."""
    return num_bins if (use_cat or use_bundle) else 1


class TreeArrays(NamedTuple):
    """SoA tree, mirroring the reference Tree (include/LightGBM/tree.h:497).

    Node child pointers use the reference convention: >=0 -> internal node
    index, negative -> ~leaf_index.
    Thresholds are in BIN space here; conversion to real-valued thresholds
    happens host-side at Tree materialization.
    """

    split_feature: jnp.ndarray  # [L-1] int32 (used-feature index)
    split_bin: jnp.ndarray  # [L-1] int32
    split_gain: jnp.ndarray  # [L-1] f32
    default_left: jnp.ndarray  # [L-1] bool
    left_child: jnp.ndarray  # [L-1] int32
    right_child: jnp.ndarray  # [L-1] int32
    internal_value: jnp.ndarray  # [L-1] f32 (raw output of the node)
    internal_weight: jnp.ndarray  # [L-1] f32 (sum hess)
    internal_count: jnp.ndarray  # [L-1] f32
    leaf_value: jnp.ndarray  # [L] f32 (raw, unshrunk)
    leaf_weight: jnp.ndarray  # [L] f32 (sum hess)
    leaf_count: jnp.ndarray  # [L] f32
    leaf_depth: jnp.ndarray  # [L] int32
    num_leaves: jnp.ndarray  # scalar int32
    # compiled grow-loop steps taken (serial: committed splits; batched: the
    # while_loop trip count) — the host derives the frontier-batch commit
    # rate (num_leaves-1)/(steps*K) from it to clamp leaf_batch adaptively
    grow_steps: jnp.ndarray  # scalar int32
    # committed split decisions that took the int8 near-tie f32 refine
    # (histogram engine v2); always 0 when int8 accumulation is off.  The
    # host derives hist/near_tie_refine_rate = refine_count / decisions
    # with decisions = 2*(num_leaves-1) + 1 (root + both children per split)
    refine_count: jnp.ndarray  # scalar int32
    split_is_cat: jnp.ndarray  # [L-1] bool
    cat_mask: jnp.ndarray  # [L-1, Bm] bool — bin goes left (Bm=1 if no cat)


class _State(NamedTuple):
    leaf_id: jnp.ndarray  # [N] (gather/full modes; empty in ordered mode)
    order: jnp.ndarray  # [N + maxcap] row permutation (ordered mode; else empty)
    leaf_begin: jnp.ndarray  # [L] segment start per leaf (ordered mode)
    leaf_nrows: jnp.ndarray  # [L] RAW row count per leaf (ordered mode)
    # one histogram per leaf as three [F, B] planes (g, h, count: stat axis
    # first, so a leaf's row is one contiguous slab with features on the
    # sublanes and bins on the lanes), plus a spare row L that takes the
    # children's writes of a step that does not split (_write_children)
    hist_buf: jnp.ndarray  # [L + 1, 3, F, B]
    leaf_g: jnp.ndarray
    leaf_h: jnp.ndarray
    leaf_cnt: jnp.ndarray
    leaf_depth: jnp.ndarray
    leaf_parent: jnp.ndarray
    leaf_is_right: jnp.ndarray
    leaf_lb: jnp.ndarray  # [L] monotone output lower bound
    leaf_ub: jnp.ndarray  # [L] monotone output upper bound
    leaf_box: jnp.ndarray  # [L, F, 2] bin-space feature ranges (intermediate
    #                        monotone mode; [L, 0, 2] otherwise)
    leaf_allowed: jnp.ndarray  # [L, F] interaction-constraint feature mask
    cand: SplitCandidate  # arrays of shape [L]
    split_feature: jnp.ndarray
    split_bin: jnp.ndarray
    split_gain: jnp.ndarray
    default_left: jnp.ndarray
    split_is_cat: jnp.ndarray  # [L-1]
    node_cat_mask: jnp.ndarray  # [L-1, Bm]
    left_child: jnp.ndarray
    right_child: jnp.ndarray
    internal_value: jnp.ndarray
    internal_weight: jnp.ndarray
    internal_count: jnp.ndarray
    num_leaves: jnp.ndarray
    done: jnp.ndarray
    forced_ok: jnp.ndarray  # still applying forced splits (n_forced > 0)
    cegb_used: jnp.ndarray  # [F] bool — feature bought (use_cegb)
    steps: jnp.ndarray  # scalar i32 — grow-loop steps (TreeArrays.grow_steps)
    refines: jnp.ndarray  # scalar i32 — committed near-tie f32 refines


def int8_acc_eligible(
    p: "GrowerParams", quantized: bool = False, monotone: bool = False
) -> bool:
    """Shared int8-accumulation gate (histogram engine v2).

    Every input is a static (GrowerParams fields, backend, interpret
    flag), so the SAME predicate serves both the trace-time engage
    decision inside ``grow_tree`` and the host-side ``hist/int8_engaged``
    telemetry gauge — a single source of truth instead of two copies that
    could drift.  Callers AND this with their own seg-path condition
    (``hist_mode == "seg"`` and a non-degenerate shape).
    """
    from .pallas import seg as _seg_mod

    if quantized or monotone:
        return False
    if p.hist_acc == "bf16" or p.axis_name is not None:
        return False
    if p.feature_shard > 1:
        # pure-feature mesh layout: axis_name is None but shards hold
        # feature slices, and the near-tie with_margin re-scan is not
        # plumbed through the feature-parallel election
        return False
    return _seg_mod.seg_int8_dispatch()


def live_plane_fraction(
    feature_mask, f: int, num_bins: int, n_forced: int = 0
) -> float:
    """Host-side mirror of ``grow_tree``'s ``seg_live`` plane-group mask.

    Returns the fraction of seg-histogram plane groups that stay live
    under the TREE-level feature mask (group 0 is always live; forced
    splits or a single group disable the skip -> 1.0).  Pure numpy on the
    already-host-resident mask, so the telemetry gauge
    ``hist/live_plane_skip_ratio`` = 1 - live_plane_fraction costs no
    device sync.
    """
    import numpy as np

    from .pallas.seg import hist_bpad, hist_group, hist_ngroups

    if n_forced > 0 or f <= 0:
        return 1.0
    gb = hist_group(f, hist_bpad(num_bins))
    ng = hist_ngroups(f, hist_bpad(num_bins))
    if ng <= 1:
        return 1.0
    fm = np.asarray(feature_mask).astype(bool)
    fm_pad = np.pad(fm, (0, ng * gb - f))
    live = fm_pad.reshape(ng, gb).any(axis=1)
    live[0] = True
    return float(live.sum()) / float(ng)


def voting_active(p: "GrowerParams", f: int) -> bool:
    """Voting-parallel engages only when the elected subset is actually
    smaller than F — below that, the dense psum is both exact and cheaper
    (the documented cutover: F <= 2*top_k aliases onto tree_learner=data)."""
    return (
        p.axis_name is not None and p.voting_top_k > 0 and f > 2 * p.voting_top_k
    )


def _adv_constrainers(box, boxes, mono, valid):
    """Which leaves bound a leaf with box ``box`` (advanced monotone mode).

    The reference finds constraining leaves by recursing up the tree and
    down opposite branches of monotone ancestor splits
    (AdvancedLeafConstraints::GoUpToFindConstrainingLeaves,
    monotone_constraints.hpp:1082).  The TPU formulation is a box test over
    all leaves at once: leaf b constrains this leaf iff the two boxes are
    ordered-DISJOINT along exactly ONE monotone feature and overlap along
    every other feature (points of the two leaves can then differ only in
    that monotone coordinate).

    box: [F, 2] bin-space box; boxes: [L, F, 2]; mono: [F] int8; valid: [L].
    Returns (lb_con [L], ub_con [L], ov [L, F])."""
    lo, hi = box[:, 0], box[:, 1]
    blo, bhi = boxes[:, :, 0], boxes[:, :, 1]
    ov = (blo <= hi[None, :]) & (lo[None, :] <= bhi)  # [L, F]
    nonov = ~ov
    one_nonov = nonov.sum(axis=1) == 1
    below = bhi < lo[None, :]  # leaf b strictly below this leaf along f
    above = blo > hi[None, :]
    mpos = (mono > 0)[None, :]
    mneg = (mono < 0)[None, :]
    lb_con = (
        one_nonov & valid
        & (nonov & ((below & mpos) | (above & mneg))).any(axis=1)
    )
    ub_con = (
        one_nonov & valid
        & (nonov & ((above & mpos) | (below & mneg))).any(axis=1)
    )
    return lb_con, ub_con, ov


def adv_scalar_bounds(box, boxes, outs, mono, valid):
    """Whole-box output bounds for one leaf from every constraining leaf's
    current output (the advanced analog of a recomputed BasicConstraint —
    RightToBasicConstraint/LeftToBasicConstraint after the cumulative
    update, monotone_constraints.hpp:286)."""
    lb_con, ub_con, _ = _adv_constrainers(box, boxes, mono, valid)
    lb = jnp.max(jnp.where(lb_con, outs, -jnp.inf))
    ub = jnp.min(jnp.where(ub_con, outs, jnp.inf))
    return lb, ub


def adv_planes(box, boxes, outs, mono, valid, b: int):
    """Per-THRESHOLD child bounds [F, B] for scanning one leaf (advanced
    monotone mode).

    Each constraining leaf bounds only the SLICE of the scan feature's bin
    axis where its box overlaps this leaf (reference: per-threshold
    FeatureMinOrMaxConstraints entries, monotone_constraints.hpp:99 +
    UpdateConstraints :871); when the scan feature IS the separating
    monotone feature, both children stay fully ordered against the
    constraining leaf, so the slice is the whole range.  Cumulative extrema
    over the bin axis then give, for every candidate threshold t, the bound
    on the left child (bins <= t) and right child (bins > t) — the
    reference's CumulativeFeatureConstraint (:146) as two cummax/cummin
    sweeps."""
    lb_con, ub_con, ov = _adv_constrainers(box, boxes, mono, valid)
    lo, hi = box[:, 0], box[:, 1]
    blo, bhi = boxes[:, :, 0], boxes[:, :, 1]
    s = jnp.where(ov, jnp.maximum(blo, lo[None, :]), lo[None, :])  # [L, F]
    e = jnp.where(ov, jnp.minimum(bhi, hi[None, :]), hi[None, :])
    bin_ids = jnp.arange(b, dtype=jnp.int32)[None, None, :]
    in_sl = (bin_ids >= s[:, :, None]) & (bin_ids <= e[:, :, None])  # [L,F,B]
    minp = jnp.max(
        jnp.where(in_sl & lb_con[:, None, None], outs[:, None, None], -jnp.inf),
        axis=0,
    )  # [F, B]
    maxp = jnp.min(
        jnp.where(in_sl & ub_con[:, None, None], outs[:, None, None], jnp.inf),
        axis=0,
    )
    lb_left = lax.cummax(minp, axis=1)
    ub_left = lax.cummin(maxp, axis=1)
    suf_min = lax.cummax(minp[:, ::-1], axis=1)[:, ::-1]  # extremum over [t:]
    suf_max = lax.cummin(maxp[:, ::-1], axis=1)[:, ::-1]
    ninf = jnp.full((minp.shape[0], 1), -jnp.inf)
    lb_right = jnp.concatenate([suf_min[:, 1:], ninf], axis=1)
    ub_right = jnp.concatenate([suf_max[:, 1:], -ninf], axis=1)
    return lb_left, ub_left, lb_right, ub_right


def _candidate_for_leaf(
    hist, g, h, c, num_bins, nan_bins, feature_mask, p: GrowerParams,
    monotone=None, lb=None, ub=None, parent_output=0.0, is_cat=None,
    cegb_penalty=None, rand_bins=None, adv=None, bundle_end=None,
    depth=None, feature_contri=None, with_margin=False,
):
    """Best split for one leaf.  ``hist`` ([3, F, B] planes) is the GLOBAL
    (psummed) histogram normally; under voting-parallel it is the LOCAL
    histogram and only the
    globally-elected top-2k features' slices are psummed (PV-Tree,
    reference voting_parallel_tree_learner.cpp:152 GlobalVoting + :396
    elected-feature ReduceScatter)."""
    f = hist.shape[1]
    fused_ok = (
        # grow_fused implies the Pallas scan too: the fused grow step already
        # emits the stacked hist, so the scan is the only launch left to save
        (p.fused_split_scan or p.grow_fused)
        # basic numeric path only — every feature below changes the gain
        # math or the candidate set in ways the kernel does not implement
        and monotone is None
        and not p.use_cat
        and not p.use_cegb
        and not p.extra_trees
        and p.path_smooth == 0.0
        and p.max_delta_step == 0.0
        and lb is None and ub is None and adv is None
        and bundle_end is None
        and not voting_active(p, f)
        # the kernel unrolls one [16, B] x [B, B] matmul per feature into a
        # single Mosaic program — cap the program size / VMEM footprint and
        # fall back to best_split beyond it
        and f <= 64
        and p.max_bin <= 256
    )
    if fused_ok:
        from .pallas import split_scan as _ss

        on_tpu = jax.default_backend() == "tpu"
        if on_tpu or _ss._INTERPRET:
            return _ss.fused_best_split(
                hist, g, h, c, num_bins, nan_bins, feature_mask,
                lambda_l1=p.lambda_l1,
                lambda_l2=p.lambda_l2,
                min_data_in_leaf=p.min_data_in_leaf,
                min_sum_hessian_in_leaf=p.min_sum_hessian_in_leaf,
                min_gain_to_split=p.min_gain_to_split,
                feature_contri=feature_contri,
                interpret=not on_tpu,
                with_margin=with_margin,
            )
    use_mono_pen = monotone is not None and p.monotone_penalty > 0.0
    common = dict(
        lambda_l1=p.lambda_l1,
        lambda_l2=p.lambda_l2,
        min_data_in_leaf=p.min_data_in_leaf,
        min_sum_hessian_in_leaf=p.min_sum_hessian_in_leaf,
        min_gain_to_split=p.min_gain_to_split,
        max_delta_step=p.max_delta_step,
        path_smooth=p.path_smooth,
        leaf_lb=lb,
        leaf_ub=ub,
        parent_output=parent_output,
        cat_params=p.cat_params,
        cegb_split_penalty=p.cegb_split_penalty if p.use_cegb else 0.0,
        monotone_penalty=p.monotone_penalty if use_mono_pen else 0.0,
        leaf_depth=depth if use_mono_pen else None,
    )
    if not voting_active(p, f):
        return best_split(
            hist, g, h, c, num_bins, nan_bins, feature_mask,
            monotone=monotone,
            is_cat=is_cat if p.use_cat else None,
            cegb_penalty=cegb_penalty if p.use_cegb else None,
            rand_bins=rand_bins if p.extra_trees else None,
            adv_bounds=adv,
            bundle_end=bundle_end,
            feature_contri=feature_contri,
            with_margin=with_margin,
            **common,
        )
    if with_margin:
        # int8-default never engages under axis_name (grower gate), so the
        # voting path never needs the near-tie margin
        raise ValueError("with_margin is not supported on the voting path")
    # ---- PV-Tree election.  1) local per-feature best gains from the LOCAL
    # histogram (local parent stats derive from it: feature 0's bins cover
    # every local row)
    loc = hist[:, 0].sum(axis=-1)  # [3] local (g, h, cnt)
    _, gains_f = best_split(
        hist, loc[0], loc[1], loc[2], num_bins, nan_bins, feature_mask,
        monotone=monotone,
        is_cat=is_cat if p.use_cat else None,
        cegb_penalty=cegb_penalty if p.use_cegb else None,
        rand_bins=rand_bins if p.extra_trees else None,
        adv_bounds=adv,
        feature_contri=feature_contri,
        per_feature_gains=True,
        **common,
    )
    # 2) weighted gain (GlobalVoting: gain * leaf_count / mean_num_data) on
    # the local top-k only; pmax is the allgather-of-top-k + per-feature max
    nsh = timed_psum(
        jnp.float32(1.0), p.axis_name, site="counts",
        measure=p.measure_collectives,
    )
    w = loc[2] * nsh / jnp.maximum(c, 1.0)
    # gains_f is the per-feature IMPROVEMENT (split.gain in GlobalVoting,
    # voting_parallel_tree_learner.cpp:166) — best_split subtracts its own
    # (possibly constrained) local parent gain, so no shard-local offset
    # skews the cross-shard pmax merge
    wg = jnp.where(jnp.isfinite(gains_f) & (loc[2] > 0), gains_f * w, -jnp.inf)
    kth = lax.top_k(wg, min(p.voting_top_k, f))[0][-1]
    masked = jnp.where(wg >= kth, wg, -jnp.inf)
    glob = timed_pmax(
        masked, p.axis_name, site="elect", measure=p.measure_collectives
    )
    # 3) elect top-2k features globally; every shard elects the SAME ids
    _, ids = lax.top_k(glob, min(2 * p.voting_top_k, f))
    # 4) aggregate ONLY the elected slices ([3, 2k, B] over ICI instead of
    # [3, F, B]) and scan them with GLOBAL parent stats
    sub = timed_psum(
        hist[:, ids], p.axis_name, site="hist", measure=p.measure_collectives
    )
    cand = best_split(
        sub, g, h, c, num_bins[ids], nan_bins[ids], feature_mask[ids],
        monotone=monotone[ids] if monotone is not None else None,
        is_cat=is_cat[ids] if (p.use_cat and is_cat is not None) else None,
        cegb_penalty=(
            cegb_penalty[ids] if (p.use_cegb and cegb_penalty is not None) else None
        ),
        rand_bins=(
            rand_bins[ids] if (p.extra_trees and rand_bins is not None) else None
        ),
        adv_bounds=(
            tuple(a[ids] for a in adv) if adv is not None else None
        ),
        feature_contri=(
            feature_contri[ids] if feature_contri is not None else None
        ),
        **common,
    )
    return cand._replace(feature=ids[cand.feature])


def _set_cand(
    cand: SplitCandidate, idx, new: SplitCandidate, gain_override=None, pred=None
) -> SplitCandidate:
    """Write `new` into row `idx`; with `pred` the write is value-preserving
    (old row back when pred is False) so it stays an in-place update with no
    conditional around it."""
    gain = new.gain if gain_override is None else gain_override
    vals = (gain, new.feature, new.bin, new.default_left, new.left_g, new.left_h,
            new.left_cnt, new.right_g, new.right_h, new.right_cnt,
            new.is_cat, new.cat_mask)
    if pred is None:
        return SplitCandidate(*[
            arr.at[idx].set(val) for arr, val in zip(cand, vals)
        ])
    return SplitCandidate(*[
        arr.at[idx].set(jnp.where(pred, val, arr[idx]))
        for arr, val in zip(cand, vals)
    ])


def _pack_tree_arrays_impl(ta: "TreeArrays"):
    """Pack a TreeArrays into (ints, floats) flat vectors so the host can
    fetch a whole tree in two transfers instead of ~14 (each transfer is a
    full round-trip on remote-attached TPUs)."""
    with jax.named_scope("pack_tree"):
        ints = jnp.concatenate(
            [
                ta.split_feature,
                ta.split_bin,
                ta.left_child,
                ta.right_child,
                ta.default_left.astype(jnp.int32),
                ta.leaf_depth,
                ta.num_leaves[None],
                ta.grow_steps[None],
                ta.refine_count[None],
                ta.split_is_cat.astype(jnp.int32),
                ta.cat_mask.astype(jnp.int32).reshape(-1),
            ]
        )
        floats = jnp.concatenate(
            [
                ta.split_gain,
                ta.internal_value,
                ta.internal_weight,
                ta.internal_count,
                ta.leaf_value,
                ta.leaf_weight,
                ta.leaf_count,
            ]
        )
    return ints, floats


# plain variant: the main training path still reads the TreeArrays after the
# fetch (leaf_value for the score update, split_* for the valid walk), so its
# buffers must survive the pack
pack_tree_arrays = instrumented_jit(
    _pack_tree_arrays_impl, label="pack_tree_arrays"
)
# donating variant for callers whose TreeArrays is dead after packing (the
# pipelined dispatcher hands the tree off and never touches it again): the
# ~14 per-tree buffers go back to the allocator instead of idling until GC
pack_tree_arrays_donated = instrumented_jit(
    _pack_tree_arrays_impl,
    label="pack_tree_arrays_donated",
    donate_argnums=(0,),
)


def unpack_tree_arrays(ints, floats, nn: int, L: int) -> "TreeArrays":
    """Decode host (ints, floats) from pack_tree_arrays into a TreeArrays."""
    io = [ints[i * nn : (i + 1) * nn] for i in range(4)]
    off = 4 * nn
    default_left = ints[off : off + nn].astype(bool)
    leaf_depth = ints[off + nn : off + nn + L]
    num_leaves = ints[off + nn + L]
    grow_steps = ints[off + nn + L + 1]
    refine_count = ints[off + nn + L + 2]
    off = off + nn + L + 3
    split_is_cat = ints[off : off + nn].astype(bool)
    off += nn
    bm = max(1, (len(ints) - off) // max(nn, 1))
    cat_mask = ints[off : off + nn * bm].astype(bool).reshape(nn, bm)
    fo = [floats[i * nn : (i + 1) * nn] for i in range(4)]
    off = 4 * nn
    fl = [floats[off + i * L : off + (i + 1) * L] for i in range(3)]
    return TreeArrays(
        split_feature=io[0],
        split_bin=io[1],
        split_gain=fo[0],
        default_left=default_left,
        left_child=io[2],
        right_child=io[3],
        internal_value=fo[1],
        internal_weight=fo[2],
        internal_count=fo[3],
        leaf_value=fl[0],
        leaf_weight=fl[1],
        leaf_count=fl[2],
        leaf_depth=leaf_depth,
        num_leaves=num_leaves,
        grow_steps=grow_steps,
        refine_count=refine_count,
        split_is_cat=split_is_cat,
        cat_mask=cat_mask,
    )


def fetch_tree_arrays(ta: "TreeArrays") -> "TreeArrays":
    """Pull a device TreeArrays to host as numpy with two transfers."""
    import numpy as np

    ints_d, floats_d = pack_tree_arrays(ta)
    nn = ta.split_feature.shape[0]  # L - 1
    L = ta.leaf_value.shape[0]
    with get_tracer().span("wait/fetch_tree", phase="host_materialize"):
        ints, floats = np.asarray(ints_d), np.asarray(floats_d)
    return unpack_tree_arrays(ints, floats, nn, L)


# fleet variant: one vmapped pack of the whole [M, ...] stacked TreeArrays,
# so M models cost the SAME two host transfers as one (boosting/fleet.py)
pack_fleet_tree_arrays = instrumented_jit(
    jax.vmap(_pack_tree_arrays_impl), label="fleet/pack_tree_arrays"
)


def fetch_fleet_tree_arrays(ta: "TreeArrays"):
    """Pull a fleet-stacked [M, ...] device TreeArrays to host in two
    transfers; returns a list of M per-member host TreeArrays, each
    identical to what ``fetch_tree_arrays`` would return for that member's
    slice."""
    import numpy as np

    ints_d, floats_d = pack_fleet_tree_arrays(ta)
    m = ta.split_feature.shape[0]
    nn = ta.split_feature.shape[1]  # L - 1
    L = ta.leaf_value.shape[1]
    with get_tracer().span("wait/fetch_tree", phase="host_materialize"):
        ints = np.asarray(ints_d)
        floats = np.asarray(floats_d)
    return [unpack_tree_arrays(ints[i], floats[i], nn, L) for i in range(m)]


@functools.partial(instrumented_jit, static_argnames=("params",))
def grow_tree(
    bins: jnp.ndarray,  # [N, F] int32
    grad: jnp.ndarray,  # [N] f32 (bagging/GOSS weights already applied)
    hess: jnp.ndarray,  # [N] f32
    count_mask: jnp.ndarray,  # [N] f32 — 1.0 for in-bag rows, 0.0 otherwise
    num_bins: jnp.ndarray,  # [F] int32
    nan_bins: jnp.ndarray,  # [F] int32 (-1 when the feature has no NaN bin)
    feature_mask: jnp.ndarray,  # [F] bool (feature_fraction sampling)
    params: GrowerParams,
    monotone: Optional[jnp.ndarray] = None,  # [F] int8 (use_monotone)
    interaction_sets: Optional[jnp.ndarray] = None,  # [S, F] bool
    rng: Optional[jax.Array] = None,  # for feature_fraction_bynode
    is_cat: Optional[jnp.ndarray] = None,  # [F] bool (use_cat)
    forced: Optional[Tuple] = None,  # (leaf, feat, bin, is_cat) arrays [n_forced]
    cegb_penalty: Optional[jnp.ndarray] = None,  # [F] f32 (use_cegb)
    cegb_used: Optional[jnp.ndarray] = None,  # [F] bool — already-bought features
    quant_scales=None,  # (g_scale, h_scale) of quantized-gradient training:
    #   grad/hess are integer multiples of them (ops/quantize.py)
    bundle_end: Optional[jnp.ndarray] = None,  # [F, B] i32 — EFB sub-range
    #   ends per plane bin (bundling.py / ops/split.py), -1 off-bundle
    feature_contri: Optional[jnp.ndarray] = None,  # [F] f32 gain multipliers
):
    """Grow one tree. Returns (TreeArrays, leaf_id[N])."""
    p = params
    n, f = bins.shape
    L, B = p.num_leaves, p.max_bin

    def _cap_size(x):
        # uniform capacity-bucket sizing across the fleet model axis
        # (see GrowerParams.fleet_axis_name)
        if not p.fleet_axis_name:
            return x
        return timed_pmax(
            x, p.fleet_axis_name, site="fleet_cap",
            measure=p.measure_collectives,
        )

    use_bundle = p.use_bundle and bundle_end is not None
    if not use_bundle:
        bundle_end = None
    else:
        # bundled split candidates reuse the categorical-mask partition and
        # the plain numeric gain path; modes that reinterpret the feature
        # axis or the candidate set per-feature are host-gated off
        # (boosting/gbdt.py raises first with friendlier messages)
        incompatible = [
            (p.n_forced > 0, "forced splits"),
            (p.extra_trees, "extra_trees"),
            (p.use_interaction, "interaction constraints"),
            (p.use_monotone and monotone is not None, "monotone constraints"),
            (p.use_cegb, "CEGB feature penalties"),
            (p.feature_shard > 1, "feature-parallel training"),
            (voting_active(p, bins.shape[1]), "voting-parallel training"),
            # bundle planes merge several features; a per-feature gain
            # multiplier has no well-defined plane-level analog
            (p.use_feature_contri and feature_contri is not None,
             "feature_contri"),
        ]
        for bad, what in incompatible:
            if bad:
                raise ValueError(
                    f"EFB feature bundling does not support {what}; "
                    "construct the Dataset with enable_bundle=false"
                )
    use_mono = p.use_monotone and monotone is not None
    use_inter_mono = use_mono and p.monotone_method in ("intermediate", "advanced")
    # advanced = intermediate propagation machinery + recomputed-from-boxes
    # bounds: per-threshold planes in the split scan, whole-box scalars at
    # commit (reference AdvancedLeafConstraints, monotone_constraints.hpp:858)
    use_adv_mono = use_mono and p.monotone_method == "advanced"
    mono_arr = monotone if use_mono else None

    def _leaf_outs_now(g_, h_, cnt_, parent_, ivals_, lb_, ub_):
        """Current would-be output of every leaf, matching the finalize
        sequence exactly (smoothing BEFORE the monotone clip) so advanced
        bound recomputation sees the same values the tree will emit."""
        out = leaf_output(g_, h_, p.lambda_l1, p.lambda_l2, p.max_delta_step)
        if p.path_smooth > 0.0:
            pouts = jnp.where(
                parent_ >= 0, ivals_[jnp.maximum(parent_, 0)], 0.0
            )
            ratio = cnt_ / p.path_smooth
            out = out * ratio / (ratio + 1.0) + pouts / (ratio + 1.0)
        return jnp.clip(out, lb_, ub_)
    use_cat = p.use_cat and is_cat is not None
    Bm = cat_mask_width(use_cat, use_bundle, B)
    is_cat_arr = is_cat if use_cat else None
    use_cegb = p.use_cegb and cegb_penalty is not None
    # per-feature gain multipliers (reference feature_contri /
    # feature_histogram.hpp:1445 — scales the IMPROVEMENT before the
    # cross-feature argmax)
    fc_arr = (
        feature_contri if (p.use_feature_contri and feature_contri is not None)
        else None
    )
    # monotone_penalty needs the splitting leaf's depth threaded into the scan
    use_mono_pen = (
        p.use_monotone and monotone is not None and p.monotone_penalty > 0.0
    )

    def _cegb_pen(used_mask):
        # coupled penalty only until the feature is first used in the MODEL
        # (cost_effective_gradient_boosting.hpp UpdateLeafBestSplits: buying
        # a feature unlocks it for every later candidate, same tree included)
        if not use_cegb:
            return None
        return jnp.where(used_mask, 0.0, cegb_penalty)

    def node_rand_bins(node_seed):
        """extra_trees: one uniform random candidate bin per feature for
        this node (reference rand.NextInt over the bin range)."""
        if not (p.extra_trees and rng is not None):
            return None
        key = jax.random.fold_in(jax.random.fold_in(rng, 7919), node_seed)
        num_ordered = num_bins - (nan_bins >= 0).astype(jnp.int32)
        hi = jnp.maximum(num_ordered - 1, 1)
        u = jax.random.uniform(key, (f,))
        return (u * hi).astype(jnp.int32)

    def _leaf_feature_mask(used_row):
        """Deterministic part of the per-node feature mask: bytree sampling +
        interaction constraints (allowed = union of constraint sets
        containing every feature used on the path)."""
        m = feature_mask
        if p.use_interaction and interaction_sets is not None:
            contains = (interaction_sets | ~used_row[None, :]).all(axis=1)  # [S]
            allowed = (contains[:, None] & interaction_sets).any(axis=0)  # [F]
            m = m & allowed
        return m

    def node_feature_mask(node_seed, used_row):
        """Per-node usable features: feature_fraction_bynode sampling
        (col_sampler.hpp by-node) + the deterministic mask."""
        m = _leaf_feature_mask(used_row)
        if p.feature_fraction_bynode < 1.0 and rng is not None:
            key = jax.random.fold_in(rng, node_seed)
            m = m & (jax.random.uniform(key, (f,)) < p.feature_fraction_bynode)
        return m

    use_seg = p.hist_mode == "seg" and f > 0 and n > 1
    # grown on the in-bag rows alone (GrowerParams.bag_window); n_root is
    # the root window's row count: n, or the in-bag rows once compacted
    bag_window = p.bag_window and use_seg and bag_window_ok(p, Bm)
    n_root = n
    use_ordered = p.hist_mode == "ordered" and f > 0 and n > 1
    use_gather = p.hist_mode == "gather" and f > 0 and n > 1
    # voting-parallel: histograms stay LOCAL; only elected slices are
    # psummed inside _candidate_for_leaf (scalar stats still psum globally)
    use_voting = voting_active(p, f)
    # feature-parallel: features sliced per shard over feat_axis; the only
    # feature-axis collective is the winner all-reduce (plus the
    # root-totals broadcast below).  One-axis world (feature_axis_name
    # None): feat_axis aliases axis_name, rows replicated, no histogram
    # psum.  Two-axis world (named mesh): election runs over the
    # 'feature' axis while rows stay sharded over axis_name, so histogram
    # and count psums keep running over the data axis (hybrid layout).
    feat_axis = (
        p.feature_axis_name
        if p.feature_axis_name is not None
        else (p.axis_name if p.feature_shard > 1 else None)
    )
    use_featpar = p.feature_shard > 1 and feat_axis is not None and f > 0
    # are rows partitioned across axis_name?  False when feature-parallel
    # reuses the one data axis for the election (rows replicated there)
    rows_sharded = p.axis_name is not None and (
        not use_featpar or feat_axis != p.axis_name
    )
    if use_featpar:
        if p.hist_mode not in ("gather", "full", "seg"):
            raise ValueError(
                "feature-parallel training needs hist_mode='gather', 'full' "
                "or 'seg' (ordered mode keeps no per-shard feature slices)"
            )
        if f % p.feature_shard:
            raise ValueError(
                f"feature count {f} must divide feature_shard="
                f"{p.feature_shard}"
            )
        if p.n_forced > 0:
            raise ValueError(
                "forced splits are not supported with feature-parallel "
                "training (histogram rows live on the owning shard)"
            )
        f_loc = f // p.feature_shard
        sh_lo = lax.axis_index(feat_axis) * f_loc

        def _fslice(arr, axis=0):
            return lax.dynamic_slice_in_dim(arr, sh_lo, f_loc, axis=axis)

        def _featpar_reduce(cand: SplitCandidate) -> SplitCandidate:
            """All-reduce the best candidate across feature shards
            (reference SyncUpGlobalBestSplit, feature_parallel_tree_learner
            .cpp:74 — here a pmax + owner-selected psum broadcast)."""
            gmax = timed_pmax(
                cand.gain, feat_axis, site="elect",
                measure=p.measure_collectives,
            )
            idx = lax.axis_index(feat_axis)
            owner = timed_pmin(
                jnp.where(cand.gain >= gmax, idx, p.feature_shard),
                feat_axis, site="elect", measure=p.measure_collectives,
            )
            mine = (idx == owner) & jnp.isfinite(gmax)

            def bc(x):
                xf = jnp.where(mine, x, jnp.zeros_like(x))
                return timed_psum(
                    xf, feat_axis, site="elect",
                    measure=p.measure_collectives,
                )

            return SplitCandidate(
                gain=gmax,
                feature=bc(cand.feature + sh_lo),
                bin=bc(cand.bin),
                default_left=bc(cand.default_left.astype(jnp.int32)) != 0,
                left_g=bc(cand.left_g),
                left_h=bc(cand.left_h),
                left_cnt=bc(cand.left_cnt),
                right_g=bc(cand.right_g),
                right_h=bc(cand.right_h),
                right_cnt=bc(cand.right_cnt),
                is_cat=bc(cand.is_cat.astype(jnp.int32)) != 0,
                cat_mask=bc(cand.cat_mask.astype(jnp.int32)) != 0,
            )
    else:
        f_loc = f

        def _fslice(arr, axis=0):
            return arr

    hist_axis = p.axis_name if (rows_sharded and not use_voting) else None
    # per-shard feature slice of the bin matrix (identity when not
    # feature-parallel) — used by the full-mode and root histograms
    bins_loc = _fslice(bins, axis=1) if f > 0 else bins

    # frontier batching scope: modes whose per-split bookkeeping is not
    # member-local (election/ownership state, cross-leaf bound propagation,
    # model-level CEGB purchases, path-dependent allowed-feature sets) keep
    # the serial loop.  boosting/gbdt.py clamps leaf_batch to 1 with a
    # warning before it gets here; a direct grow_tree caller gets the raise.
    leaf_k = max(1, min(p.leaf_batch, L - 1))
    if leaf_k > 1:
        unsupported = [
            (use_voting, "voting-parallel training"),
            (use_featpar, "feature-parallel training"),
            (use_cegb, "CEGB feature penalties"),
            (use_inter_mono, "intermediate/advanced monotone constraints"),
            (p.use_interaction and interaction_sets is not None,
             "interaction constraints"),
        ]
        for bad, what in unsupported:
            if bad:
                raise ValueError(
                    f"leaf_batch > 1 does not support {what}; set leaf_batch=1"
                )
    # double-buffered histogram collectives (see GrowerParams doc): only
    # meaningful when there IS a frontier stack to split and a histogram
    # psum axis to overlap against
    use_overlap = (
        p.overlap_collectives and leaf_k > 1 and hist_axis is not None
    )

    def cand_for_leaf(hist, g, h, c, fm, lb=None, ub=None, pout=0.0,
                      rand=None, cpen=None, adv=None, depth=None,
                      with_margin=False):
        with jax.named_scope("split_scan"):
            return _cand_for_leaf_impl(
                hist, g, h, c, fm, lb=lb, ub=ub, pout=pout,
                rand=rand, cpen=cpen, adv=adv, depth=depth,
                with_margin=with_margin,
            )

    def _cand_for_leaf_impl(hist, g, h, c, fm, lb=None, ub=None, pout=0.0,
                            rand=None, cpen=None, adv=None, depth=None,
                            with_margin=False):
        """Leaf candidate with the distributed-mode plumbing: per-feature
        operand slicing + winner all-reduce under feature-parallel; voting
        election happens inside _candidate_for_leaf."""
        if not use_featpar:
            return _candidate_for_leaf(
                hist, g, h, c, num_bins, nan_bins, fm, p,
                monotone=mono_arr, lb=lb, ub=ub, parent_output=pout,
                is_cat=is_cat_arr, cegb_penalty=cpen, rand_bins=rand,
                adv=adv, bundle_end=bundle_end, depth=depth,
                feature_contri=fc_arr, with_margin=with_margin,
            )
        if with_margin:
            # int8-default requires axis_name None, which excludes featpar
            raise ValueError(
                "with_margin is not supported under feature-parallel"
            )
        cand = _candidate_for_leaf(
            hist, g, h, c, _fslice(num_bins), _fslice(nan_bins),
            _fslice(fm), p,
            monotone=_fslice(mono_arr) if mono_arr is not None else None,
            lb=lb, ub=ub, parent_output=pout,
            is_cat=_fslice(is_cat_arr) if is_cat_arr is not None else None,
            cegb_penalty=_fslice(cpen) if cpen is not None else None,
            rand_bins=_fslice(rand) if rand is not None else None,
            adv=tuple(_fslice(a) for a in adv) if adv is not None else None,
            depth=depth,
            feature_contri=_fslice(fc_arr) if fc_arr is not None else None,
        )
        return _featpar_reduce(cand)

    if use_seg:
        from .pallas.seg import (
            MAX_WIDE_BIN,
            flat_planes,
            is_grouped,
            pack_rows,
            padded_rows,
            seg_hist,
            seg_hist_batch,
            stat_lanes,
        )
        from .segpart import (
            go_left_bits,
            leaf_id_from_seg,
            leaf_of_positions,
            sort_partition,
            sort_partition_batch,
        )
        from .pallas.grow_step import fused_grow_step

        # bins byte-pack two features per i16 plane up to max_bin 256; wider
        # bin spaces use one u16 plane per feature (the reference's
        # DenseBin<uint16_t> upgrade, src/io/dense_bin.hpp:18)
        seg_wide = B > 256
        if B > MAX_WIDE_BIN:
            raise ValueError(
                f"hist_mode='seg' stores bins in u16 planes: max_bin "
                f"(padded to {B}) must be <= {MAX_WIDE_BIN}"
            )
        # feature-parallel seg: each shard packs ONLY its feature slice's bin
        # planes (rows replicated, histogram work /D); the winner feature's
        # go-left bits come from the owning shard via psum at partition time
        f_seg = f_loc if use_featpar else f
        if jax.default_backend() == "tpu":
            from .pallas.seg import seg_vmem_ok

            if not seg_vmem_ok(f_seg, B, use_cat or use_bundle):
                raise ValueError(
                    f"hist_mode='seg' at {f_seg} features x max_bin {B} "
                    "exceeds the histogram kernel's VMEM scratch budget — "
                    "use hist_mode='ordered' or a smaller max_bin"
                )
        n_pad_seg = padded_rows(n)
        with jax.named_scope("pack_rows"):
            seg0 = pack_rows(
                bins_loc, grad, hess, count_mask, n_pad_seg, wide=seg_wide
            )
        # a row of more than 128 planes comes back as G plane groups that
        # share one row order (seg.pack_rows): the partition then runs once
        # a group on go-left bits computed from the split feature's own
        # plane, and the kernels whose predicate reads that plane in place
        # (the fused step, the batched partition) are not its to take
        seg_grouped = is_grouped(seg0)
        if bag_window:
            # in-bag rows to the front, in their own order; with every row
            # in the bag (GOSS's first iterations) nothing moves
            with jax.named_scope("bag_compact"):
                in_bag = jnp.pad(
                    (count_mask > 0).astype(jnp.float32), (0, n_pad_seg - n)
                )
                zero = jnp.int32(0)
                seg0, n_root, _ = sort_partition(
                    seg0, zero, jnp.int32(n), zero, zero, zero,
                    jnp.int32(-1), zero, jnp.zeros((1,), jnp.float32),
                    f=f_seg, n_pad=n_pad_seg, wide=seg_wide, gl_vec=in_bag,
                    fleet_axis_name=p.fleet_axis_name,
                    measure=p.measure_collectives, bag_compact=True,
                )
        if seg_grouped and leaf_k > 1:
            raise ValueError(
                f"leaf_batch > 1 does not support a packed row of "
                f"{seg0.shape[0]} plane groups ({f_seg} columns); set "
                "leaf_batch=1"
            )

        # quantized-gradient training: the values are integer multiples of
        # the scales, so the histogram kernels take their integer form
        # (int8 operands, exact int32 sums) whatever hist_method and
        # hist_acc say — neither applies to gradients already on the grid
        seg_qs = quant_scales
        # histogram engine v2: int8 2-digit accumulation is the DEFAULT on
        # the single-host seg TPU path — the true f32 grads are scaled onto
        # the QMAX grid once per iteration and every histogram launch runs
        # int8 x int8 -> i32 on the MXU; near-tie split decisions trigger an
        # f32 re-accumulate before the structure commit (with_margin below).
        # Excluded: explicit bf16 opt-out, quantized training (already on an
        # exact integer grid), any axis_name (distributed reduction semantics
        # and psum byte volumes stay untouched), monotone constraints (the
        # refine re-scan would need the full constraint plumbing).
        use_int8_acc = use_seg and int8_acc_eligible(
            p, quantized=seg_qs is not None, monotone=mono_arr is not None
        )
        if use_int8_acc:
            from .quantize import hist_acc_scales

            with jax.named_scope("pack_rows"):
                seg_qs = hist_acc_scales(grad, hess, count_mask)

        # live-plane skip: feature-plane groups with no usable feature under
        # the TREE-level deterministic mask (feature_fraction bytree / EFB
        # pruning) skip their one-hot build + matmul entirely.  Derived from
        # feature_mask ONLY — hist_buf rows are reused by descendants
        # (sibling subtraction, later parent reads) whose per-node bynode /
        # interaction masks differ, and those are subsets of feature_mask,
        # so masking at the tree level is the safe superset.  Group 0 stays
        # live (feature 0's plane carries the window totals); forced splits
        # may target masked-out features, so they disable the skip.
        seg_live = None
        if use_seg and not (p.n_forced > 0 and forced is not None):
            from .pallas.seg import hist_bpad, hist_group, hist_ngroups

            _gb = hist_group(f_seg, hist_bpad(B))
            _ng = hist_ngroups(f_seg, hist_bpad(B))
            if _ng > 1:
                fm_pad = jnp.pad(
                    _fslice(feature_mask).astype(bool),
                    (0, _ng * _gb - f_seg),
                )
                seg_live = (
                    fm_pad.reshape(_ng, _gb).any(axis=1)
                    .at[0].set(True).astype(jnp.int32)
                )

        def _seg_hist(seg_arr, start, cnt_rows, qs=seg_qs):
            hist = seg_hist(
                seg_arr,
                jnp.stack([start, cnt_rows]).astype(jnp.int32),
                f=f_seg,
                num_bins=B,
                n_pad=n_pad_seg,
                quant_scales=qs,
                wide=seg_wide,
                live=seg_live,
            )
            if hist_axis is not None:
                hist = timed_psum(
                    hist, hist_axis, site="hist",
                    measure=p.measure_collectives,
                )
            return hist

        # single-launch fused grow step: partition + smaller-child election +
        # histogram in one kernel.  Data-parallel (axis_name) keeps the
        # two-launch path — electing the smaller child there needs a psum of
        # per-shard partition counts BETWEEN partition and histogram, which a
        # single kernel launch cannot host.  Feature-parallel likewise: the
        # winner feature's go-left bits come from the owning shard via a
        # gl_vec psum at partition time.  A grouped row likewise: its
        # partition runs on bits, once a plane group.
        use_fused_grow = (
            p.grow_fused and p.axis_name is None and not use_featpar
            and not seg_grouped
        )
    else:
        use_fused_grow = False
        use_int8_acc = False
    if use_ordered or use_gather:
        caps = sorted(
            _hist_caps(
                n,
                full_range=rows_sharded,
            )
        )  # ascending child-histogram capacities
        caps_arr = jnp.asarray(caps, dtype=jnp.int32)
        # one zero padding row so fill indices contribute nothing
        bins_pad = jnp.concatenate([bins, jnp.zeros((1, f), bins.dtype)], axis=0)
        # feature-parallel: slice ONCE here — slicing inside the per-leaf
        # branch would gather rows at full F width first, negating the /D
        # data-volume split (gathers serialize on TPU)
        bins_pad_loc = _fslice(bins_pad, axis=1)
        grad_pad = jnp.concatenate([grad, jnp.zeros((1,), grad.dtype)])
        hess_pad = jnp.concatenate([hess, jnp.zeros((1,), hess.dtype)])
        mask_pad = jnp.concatenate([count_mask, jnp.zeros((1,), count_mask.dtype)])

    if use_gather:
        def _make_hist_branch(cap: int):
            # nonzero lives INSIDE the branch so its scatter is sized to the
            # branch capacity — deep (small) leaves compact into small buffers
            def branch(member):
                (idx,) = jnp.nonzero(member, size=cap, fill_value=n)
                return leaf_histogram(
                    bins_pad_loc[idx],
                    grad_pad[idx],
                    hess_pad[idx],
                    mask_pad[idx],
                    B,
                    method=p.hist_method,
                    axis_name=hist_axis,
                    quant_scales=quant_scales,
                    measure=p.measure_collectives,
                )

            return branch

        hist_branches = [_make_hist_branch(c) for c in caps]

        if leaf_k > 1:
            # frontier batching: each member compacts into ITS OWN capacity
            # bucket (pmax'd under data-parallel so every shard lowers the
            # same branch per member) — a shared max-over-members bucket was
            # measured 15% slower at the 1M-row bench shape because every
            # member paid the largest window's gather.  The inner histograms
            # run with axis_name=None and the [K, 3, F, B] stack psums ONCE
            # outside.
            def _make_hist_branch_loc(cap: int):
                def branch(member):  # [N] bool
                    (idx,) = jnp.nonzero(member, size=cap, fill_value=n)
                    return leaf_histogram(
                        bins_pad_loc[idx],
                        grad_pad[idx],
                        hess_pad[idx],
                        mask_pad[idx],
                        B,
                        method=p.hist_method,
                        axis_name=None,
                        quant_scales=quant_scales,
                    )

                return branch

            hist_branches_loc = [_make_hist_branch_loc(c) for c in caps]

    # transposed copy for contiguous per-feature column reads in the
    # partition step (bins is row-major; a column gather is strided)
    bins_t_cols = bins.T if f > 0 else bins.reshape(f, n)

    if use_ordered:
        # ---- ordered-partition machinery (reference DataPartition,
        # data_partition.hpp: one index array, leaves occupy contiguous
        # segments).  All per-split work is sized by a static capacity
        # bucket of the PARENT segment, never by N.
        pcaps = _part_caps(n)
        pcaps_arr = jnp.asarray(pcaps, dtype=jnp.int32)
        order_len = n + pcaps[-1]
        bins_t_pad = jnp.concatenate(
            [bins_t_cols, jnp.zeros((f, 1), bins.dtype)], axis=1
        )  # [F, n+1] — sentinel column for padded order entries

        def _make_part_branch(P: int):
            def branch(op):
                order, begin_l, cnt_l, feat, tbin, dl, cis, cmask = op
                idx = lax.dynamic_slice(order, (begin_l,), (P,))
                valid = jnp.arange(P, dtype=jnp.int32) < cnt_l
                featrow = lax.dynamic_slice_in_dim(bins_t_pad, feat, 1, axis=0)[0]
                colv = featrow[idx]
                nb = nan_bins[feat]
                gl = (colv <= tbin) | (dl & (nb >= 0) & (colv == nb))
                if use_cat or use_bundle:
                    gl = jnp.where(cis, cmask[jnp.minimum(colv, Bm - 1)], gl)
                gl = gl & valid
                gr = valid & ~gl
                nleft = jnp.sum(gl).astype(jnp.int32)
                # stable partition: left rows -> [0, nleft), right rows ->
                # [nleft, cnt_l), rows beyond the segment stay untouched
                pos_l = jnp.cumsum(gl) - 1
                pos_r = nleft + jnp.cumsum(gr) - 1
                pos = jnp.where(gl, pos_l, jnp.where(gr, pos_r, P)).astype(
                    jnp.int32
                )
                new_seg = (
                    jnp.full((P,), n, order.dtype).at[pos].set(idx, mode="drop")
                )
                new_seg = jnp.where(valid, new_seg, idx)
                order = lax.dynamic_update_slice(order, new_seg, (begin_l,))
                return order, nleft

            return branch

        part_branches = [_make_part_branch(c) for c in pcaps]

        def _make_hist_branch_ordered(C: int):
            def branch(op):
                order, start, child_cnt = op
                cidx = lax.dynamic_slice(order, (start,), (C,))
                vmask = (
                    jnp.arange(C, dtype=jnp.int32) < child_cnt
                ).astype(count_mask.dtype)
                return leaf_histogram(
                    bins_pad[cidx],
                    grad_pad[cidx],
                    hess_pad[cidx],
                    mask_pad[cidx] * vmask,
                    B,
                    method=p.hist_method,
                    axis_name=hist_axis,
                    quant_scales=quant_scales,
                    measure=p.measure_collectives,
                )

            return branch

        hist_branches_ordered = [_make_hist_branch_ordered(c) for c in caps]

        if leaf_k > 1:
            # batched analog: one (start, cnt) window at a time, each in ITS
            # OWN capacity bucket (per-member row counts are pmax'd under
            # data-parallel, so shards agree per member), inner hists local
            # (one stacked psum happens outside)
            def _make_hist_branch_ordered_loc(C: int):
                def branch(op):
                    order, start, child_cnt = op
                    cidx = lax.dynamic_slice(order, (start,), (C,))
                    vmask = (
                        jnp.arange(C, dtype=jnp.int32) < child_cnt
                    ).astype(count_mask.dtype)
                    return leaf_histogram(
                        bins_pad[cidx],
                        grad_pad[cidx],
                        hess_pad[cidx],
                        mask_pad[cidx] * vmask,
                        B,
                        method=p.hist_method,
                        axis_name=None,
                        quant_scales=quant_scales,
                    )

                return branch

            hist_branches_ordered_loc = [
                _make_hist_branch_ordered_loc(c) for c in caps
            ]

    cegb_used0 = (
        cegb_used
        if (use_cegb and cegb_used is not None)
        else jnp.zeros((max(f, 1),), bool)
    )
    with jax.named_scope("root_histogram"):  # jax.profiler trace labels
        if use_seg:
            hist0 = _seg_hist(seg0, jnp.int32(0), jnp.int32(n_root))
        else:
            hist0 = leaf_histogram(
                bins_loc, grad, hess, count_mask, B,
                method=p.hist_method,
                axis_name=hist_axis, quant_scales=quant_scales,
                measure=p.measure_collectives,
            )
    totals = hist0[:, 0].sum(axis=-1)  # every row lands in exactly one bin of feature 0
    if use_voting:
        totals = timed_psum(  # global root stats
            totals, p.axis_name, site="counts",
            measure=p.measure_collectives,
        )
    if use_featpar:
        # every shard derives totals from a DIFFERENT local feature's bins:
        # the values agree only up to summation order, and downstream gains
        # must be bit-identical across shards (out_specs declare the tree
        # replicated) — broadcast shard 0's totals
        idx0 = lax.axis_index(feat_axis) == 0
        totals = timed_psum(
            jnp.where(idx0, totals, jnp.zeros_like(totals)), feat_axis,
            site="counts", measure=p.measure_collectives,
        )
    root_used = jnp.zeros((f,), bool)
    neg_inf_s = jnp.float32(-jnp.inf)
    pos_inf_s = jnp.float32(jnp.inf)
    _root_kwargs = dict(
        lb=neg_inf_s if use_mono else None,
        ub=pos_inf_s if use_mono else None,
        pout=leaf_output(totals[0], totals[1], p.lambda_l1, p.lambda_l2, p.max_delta_step),
        cpen=_cegb_pen(cegb_used0),
        rand=node_rand_bins(0),
        depth=jnp.asarray(0, jnp.int32) if use_mono_pen else None,
    )
    cand0 = cand_for_leaf(
        hist0, totals[0], totals[1], totals[2],
        node_feature_mask(0, root_used),
        with_margin=use_int8_acc,
        **_root_kwargs,
    )
    if use_int8_acc:
        # near-tie f32 re-accumulate (histogram engine v2): when the root
        # winner's relative gain gap is inside near_tie_tol, redo the
        # window's histogram with direct f32 accumulation and re-scan
        # before the structure commit.  hist_buf keeps the INT8 histogram
        # (sibling subtraction must stay on one accumulation grid); the
        # refined copy exists only for this decision.
        cand0, margin0 = cand0
        near0 = margin0 < p.near_tie_tol
        hist0_f = _seg_hist(
            seg0, jnp.int32(0),
            jnp.where(near0, n_root, 0).astype(jnp.int32), qs=None,
        )
        cand0 = cand_for_leaf(
            jnp.where(near0, hist0_f, hist0),
            totals[0], totals[1], totals[2],
            node_feature_mask(0, root_used),
            **_root_kwargs,
        )

    with jax.named_scope("init_state"):
        neg_inf = jnp.full((L,), -jnp.inf, dtype=jnp.float32)
        cand = SplitCandidate(
            gain=neg_inf,
            feature=jnp.zeros((L,), jnp.int32),
            bin=jnp.zeros((L,), jnp.int32),
            default_left=jnp.zeros((L,), bool),
            left_g=jnp.zeros((L,), jnp.float32),
            left_h=jnp.zeros((L,), jnp.float32),
            left_cnt=jnp.zeros((L,), jnp.float32),
            right_g=jnp.zeros((L,), jnp.float32),
            right_h=jnp.zeros((L,), jnp.float32),
            right_cnt=jnp.zeros((L,), jnp.float32),
            is_cat=jnp.zeros((L,), bool),
            cat_mask=jnp.zeros((L, Bm), bool),
        )
        cand = _set_cand(cand, 0, cand0)

        if use_ordered:
            order0 = jnp.concatenate(
                [
                    jnp.arange(n, dtype=jnp.int32),
                    jnp.full((order_len - n,), n, jnp.int32),
                ]
            )
            leaf_begin0 = jnp.zeros((L,), jnp.int32)
            leaf_nrows0 = jnp.zeros((L,), jnp.int32).at[0].set(n)
            leaf_id0 = jnp.zeros((0,), jnp.int32)
        elif use_seg:
            # the order slot carries the packed segment matrix in seg mode
            order0 = seg0
            leaf_begin0 = jnp.zeros((L,), jnp.int32)
            leaf_nrows0 = jnp.zeros((L,), jnp.int32).at[0].set(n_root)
            leaf_id0 = jnp.zeros((0,), jnp.int32)
        else:
            order0 = jnp.zeros((0,), jnp.int32)
            leaf_begin0 = jnp.zeros((0,), jnp.int32)
            leaf_nrows0 = jnp.zeros((0,), jnp.int32)
            leaf_id0 = jnp.zeros((n,), jnp.int32)

        state = _State(
            leaf_id=leaf_id0,
            order=order0,
            leaf_begin=leaf_begin0,
            leaf_nrows=leaf_nrows0,
            hist_buf=jnp.zeros((L + 1, 3, f_loc, B), jnp.float32).at[0].set(hist0),
            leaf_g=jnp.zeros((L,), jnp.float32).at[0].set(totals[0]),
            leaf_h=jnp.zeros((L,), jnp.float32).at[0].set(totals[1]),
            leaf_cnt=jnp.zeros((L,), jnp.float32).at[0].set(totals[2]),
            leaf_depth=jnp.zeros((L,), jnp.int32),
            leaf_parent=jnp.full((L,), -1, jnp.int32),
            leaf_is_right=jnp.zeros((L,), bool),
            leaf_lb=jnp.full((L,), -jnp.inf, jnp.float32),
            leaf_ub=jnp.full((L,), jnp.inf, jnp.float32),
            # root box spans the whole bin space of every feature
            leaf_box=(
                jnp.zeros((L, f, 2), jnp.int32).at[:, :, 1].set(B - 1)
                if use_inter_mono
                else jnp.zeros((L, 0, 2), jnp.int32)
            ),
            leaf_allowed=jnp.zeros((L, f), bool),  # stores USED features per path
            cand=cand,
            split_feature=jnp.zeros((L - 1,), jnp.int32),
            split_bin=jnp.zeros((L - 1,), jnp.int32),
            split_gain=jnp.zeros((L - 1,), jnp.float32),
            default_left=jnp.zeros((L - 1,), bool),
            split_is_cat=jnp.zeros((L - 1,), bool),
            node_cat_mask=jnp.zeros((L - 1, Bm), bool),
            # unused nodes point at leaf 0 (~0 = -1) so walking a trivial tree
            # (no splits recorded) terminates instead of spinning on node 0
            left_child=jnp.full((L - 1,), -1, jnp.int32),
            right_child=jnp.full((L - 1,), -1, jnp.int32),
            internal_value=jnp.zeros((L - 1,), jnp.float32),
            internal_weight=jnp.zeros((L - 1,), jnp.float32),
            internal_count=jnp.zeros((L - 1,), jnp.float32),
            num_leaves=jnp.asarray(1, jnp.int32),
            done=jnp.asarray(False),
            forced_ok=jnp.asarray(p.n_forced > 0),
            cegb_used=cegb_used0,
            steps=jnp.asarray(0, jnp.int32),
            refines=(
                near0.astype(jnp.int32)
                if use_int8_acc
                else jnp.asarray(0, jnp.int32)
            ),
        )

    node_ids = jnp.arange(L - 1, dtype=jnp.int32)
    use_forced_splits = p.n_forced > 0 and forced is not None

    def _forced_stats(st: _State, f_leaf, f_feat, f_bin, f_iscat):
        """Child sums and gain of a host-forced split, read off the leaf's
        histogram row of that one feature (reference
        GatherInfoForThreshold, feature_histogram.hpp:475-595)."""
        hrow = st.hist_buf[f_leaf, :, f_feat]  # [3, B]
        if use_voting:
            # voting keeps hist_buf LOCAL; a forced split needs the
            # global row for this one feature (tiny psum)
            hrow = timed_psum(
                hrow, p.axis_name, site="hist",
                measure=p.measure_collectives,
            )
        nbv = nan_bins[f_feat]
        has_nb = nbv >= 0
        nan_s = jnp.where(has_nb, hrow[:, jnp.maximum(nbv, 0)], 0.0)
        brow_ids = jnp.arange(B, dtype=jnp.int32)
        hrow_o = jnp.where((brow_ids == nbv) & has_nb, 0.0, hrow)
        cumr = jnp.cumsum(hrow_o, axis=1)
        fpg, fph, fpc = (
            st.leaf_g[f_leaf],
            st.leaf_h[f_leaf],
            st.leaf_cnt[f_leaf],
        )
        # numeric: missing goes LEFT (GatherInfoForThresholdNumerical
        # sets default_left=true); categorical: one-hot on the bin
        f_left = jnp.where(f_iscat, hrow[:, f_bin], cumr[:, f_bin] + nan_s)
        f_lg, f_lh, f_lc = f_left[0], f_left[1], f_left[2]
        f_rg, f_rh, f_rc = fpg - f_lg, fph - f_lh, fpc - f_lc
        f_raw = leaf_gain(f_lg, f_lh, p.lambda_l1, p.lambda_l2) + leaf_gain(
            f_rg, f_rh, p.lambda_l1, p.lambda_l2
        )
        f_gain = (
            f_raw
            - leaf_gain(fpg, fph, p.lambda_l1, p.lambda_l2)
            - p.min_gain_to_split
        )
        return f_lg, f_lh, f_lc, f_rg, f_rh, f_rc, f_gain

    def _child_hists(parent_hist, sm, left_smaller):
        """(left, right) histograms of a split [.., 3, F, B]: the smaller
        child measured, its sibling by subtraction from the parent
        (serial_tree_learner.cpp:558).  Both come out whole from behind one
        barrier, so the parent's row is read off the carry once, before the
        first row of the carry is written: the compiler then has no use for
        the old buffer and updates it in place (a read of the old carry
        after its first write costs two whole-buffer copies a split)."""
        other = parent_hist - sm
        return lax.optimization_barrier((
            jnp.where(left_smaller, sm, other),
            jnp.where(left_smaller, other, sm),
        ))

    def _write_children(hist_buf, l, nl, left_hist, right_hist, ok):
        """Rows ``l`` and ``nl`` of the carry take the children's
        histograms; a step that does not split writes both to the spare row
        L instead, so no row is read back to be preserved."""
        for idx, row in ((l, left_hist), (nl, right_hist)):
            # a plain dynamic-update-slice: ``.at[].set`` is a scatter, whose
            # out-of-bounds rule reads the old row back beside the new one
            hist_buf = lax.dynamic_update_index_in_dim(
                hist_buf, row, jnp.where(ok, idx, L), axis=0
            )
        return hist_buf

    def body(t, st: _State) -> _State:
        """One split step, fully UNCONDITIONAL.

        Round-2 measurement: threading the carry through ``lax.cond``/
        ``lax.switch`` branches makes XLA materialize defensive copies of
        every large array a modifying branch touches (~0.45 ms per copy at 1M
        rows — hist_buf is 22 MB at L=255, the packed seg matrix 0.3 GB at
        1M).  So instead of an `apply` branch, every state write below is
        value-preserving under ``~can_split`` (write the old value back at
        the same index; ``hist_buf``, the one large table, reads nothing
        back and sends the write to its spare row instead), which keeps
        each update an in-place dynamic-update-slice on the loop carry with
        NO conditional in sight.
        A no-split step degenerates to zero-count partition/histogram work
        plus O(L·F·B) bookkeeping."""
        with jax.named_scope("bookkeeping"):
            norm_leaf = jnp.argmax(st.cand.gain).astype(jnp.int32)

            # ---- local candidate for this step: the per-leaf best, or — for the
            # first n_forced steps — the host-provided forced split evaluated on
            # the leaf's histogram (reference ForceSplits,
            # serial_tree_learner.cpp:627 + GatherInfoForThreshold,
            # feature_histogram.hpp:475-595)
            if use_forced_splits:
                f_leaf_a, f_feat_a, f_bin_a, f_iscat_a = forced
                tf = jnp.minimum(t, p.n_forced - 1)
                is_f_step = (t < p.n_forced) & st.forced_ok
                f_leaf = f_leaf_a[tf]
                f_feat = f_feat_a[tf]
                f_bin = f_bin_a[tf]
                f_iscat = f_iscat_a[tf]
                f_lg, f_lh, f_lc, f_rg, f_rh, f_rc, f_gain = _forced_stats(
                    st, f_leaf, f_feat, f_bin, f_iscat
                )
                use_forced = is_f_step & (f_gain > 0)
                # a failed forced split aborts the REMAINING forced steps
                # (abort_last_forced_split) and normal growth resumes
                forced_ok_next = st.forced_ok & (~is_f_step | use_forced)
                best_leaf = jnp.where(use_forced, f_leaf, norm_leaf)
            else:
                use_forced = None
                forced_ok_next = st.forced_ok
                best_leaf = norm_leaf

            l = best_leaf
            c_gain = st.cand.gain[l]
            c_feat = st.cand.feature[l]
            c_bin = st.cand.bin[l]
            c_dl = st.cand.default_left[l]
            c_cis = st.cand.is_cat[l]
            c_cmask = st.cand.cat_mask[l]
            c_lg, c_lh, c_lc = (
                st.cand.left_g[l],
                st.cand.left_h[l],
                st.cand.left_cnt[l],
            )
            c_rg, c_rh, c_rc = (
                st.cand.right_g[l],
                st.cand.right_h[l],
                st.cand.right_cnt[l],
            )
            if use_forced_splits:
                c_gain = jnp.where(use_forced, f_gain, c_gain)
                c_feat = jnp.where(use_forced, f_feat, c_feat)
                c_bin = jnp.where(use_forced, f_bin, c_bin)
                c_dl = jnp.where(use_forced, ~f_iscat, c_dl)
                c_cis = jnp.where(use_forced, f_iscat, c_cis)
                if use_cat:
                    oh = jnp.arange(Bm, dtype=jnp.int32) == f_bin
                    c_cmask = jnp.where(use_forced, oh, c_cmask)
                c_lg = jnp.where(use_forced, f_lg, c_lg)
                c_lh = jnp.where(use_forced, f_lh, c_lh)
                c_lc = jnp.where(use_forced, f_lc, c_lc)
                c_rg = jnp.where(use_forced, f_rg, c_rg)
                c_rh = jnp.where(use_forced, f_rh, c_rh)
                c_rc = jnp.where(use_forced, f_rc, c_rc)

            raw_can = c_gain > 0.0
            done = st.done | ~raw_can
            # once any step's best gain is <= 0 it stays <= 0 (cand is frozen),
            # but gate on st.done anyway so no stale candidate can ever re-split
            can_split = raw_can & ~st.done
            nl = (t + 1).astype(jnp.int32)
            feat, tbin, dl, cis, cmask = c_feat, c_bin, c_dl, c_cis, c_cmask

        # ---- partition rows of leaf l (reference DataPartition::Split) and
        # histogram the smaller child (serial_tree_learner.cpp:558-583), all
        # with a zero count when not splitting (value-level no-ops)
        if use_seg and use_fused_grow:
            # one kernel launch: partition + smaller-child election +
            # histogram (K=1 window) — dispatched as the XLA composition off
            # TPU, so structures are byte-identical to the two-launch path
            begin_l = st.leaf_begin[l]
            seg_cnt_l = jnp.where(can_split, st.leaf_nrows[l], 0)
            with jax.named_scope("fused_grow_step"):
                order, nl1, nr1, _cs1, _cc1, sm1 = fused_grow_step(
                    st.order,
                    begin_l[None],
                    seg_cnt_l[None],
                    feat[None],
                    tbin[None],
                    dl.astype(jnp.int32)[None],
                    nan_bins[feat][None],
                    cis.astype(jnp.int32)[None],
                    cmask.astype(jnp.float32)[None],
                    f=f_seg,
                    num_bins=B,
                    n_pad=n_pad_seg,
                    quant_scales=seg_qs,
                    wide=seg_wide,
                    live=seg_live,
                )
            nleft = nl1[0]
            nright = nr1[0]
            left_smaller = nleft <= nright
            sm = sm1[0]
            leaf_id = st.leaf_id
        elif use_seg:
            begin_l = st.leaf_begin[l]
            seg_cnt_l = jnp.where(can_split, st.leaf_nrows[l], 0)
            gl_vec = None
            if use_featpar:
                # only the OWNING shard holds the winner feature's bin
                # plane: it computes the go-left bits over the whole packed
                # matrix (segment order) and the psum broadcasts them —
                # every shard then applies the identical stable partition
                # (reference feature-parallel keeps partitioning local
                # because every machine holds all columns; here columns are
                # sliced, so the bits travel instead — O(N) f32 on ICI)
                owner = jnp.clip(feat // f_loc, 0, p.feature_shard - 1)
                lane = jnp.clip(feat - owner * f_loc, 0, max(f_loc - 1, 0))
                glv = go_left_bits(
                    st.order, lane, tbin, dl.astype(jnp.int32),
                    nan_bins[feat], cis.astype(jnp.int32),
                    cmask.astype(jnp.float32), wide=seg_wide,
                )
                mine = lax.axis_index(feat_axis) == owner
                gl_vec = timed_psum(
                    jnp.where(mine, glv, 0.0),
                    feat_axis, site="partition",
                    measure=p.measure_collectives,
                )
            elif seg_grouped:
                # the split feature's plane lies in ONE plane group: decide
                # go-left once, from that plane, and let the partition move
                # every group by the same bits
                with jax.named_scope("go_left"):
                    gl_vec = go_left_bits(
                        st.order, feat, tbin, dl.astype(jnp.int32),
                        nan_bins[feat], cis.astype(jnp.int32),
                        cmask.astype(jnp.float32), wide=seg_wide,
                    )
            with jax.named_scope("partition"):
                order, nleft, nright = sort_partition(
                    st.order,
                    begin_l,
                    seg_cnt_l,
                    feat,
                    tbin,
                    dl.astype(jnp.int32),
                    nan_bins[feat],
                    cis.astype(jnp.int32),
                    cmask.astype(jnp.float32),
                    f=f_seg,
                    n_pad=n_pad_seg,
                    wide=seg_wide,
                    gl_vec=gl_vec,
                    fleet_axis_name=p.fleet_axis_name,
                    measure=p.measure_collectives,
                )
            if p.axis_name is not None:
                # global smaller-child choice (see gather-mode comment)
                left_smaller = timed_psum(
                    nleft, p.axis_name, site="counts",
                    measure=p.measure_collectives,
                ) <= timed_psum(
                    nright, p.axis_name, site="counts",
                    measure=p.measure_collectives,
                )
            else:
                left_smaller = nleft <= nright
            child_start = begin_l + jnp.where(left_smaller, 0, nleft)
            child_cnt = jnp.where(left_smaller, nleft, nright)
            with jax.named_scope("histogram"):
                sm = _seg_hist(order, child_start, child_cnt)
            leaf_id = st.leaf_id
        elif use_ordered:
            # stable in-place partition of the parent's contiguous
            # segment, sized by its capacity bucket — O(parent), not O(N)
            begin_l = st.leaf_begin[l]
            cnt_l = jnp.where(can_split, st.leaf_nrows[l], 0)
            pbucket = jnp.clip(
                jnp.searchsorted(pcaps_arr, _cap_size(cnt_l), side="left"),
                0,
                len(pcaps) - 1,
            ).astype(jnp.int32)
            with jax.named_scope("partition"):
                order, nleft = lax.switch(
                    pbucket,
                    part_branches,
                    (st.order, begin_l, cnt_l, feat, tbin, dl, cis, cmask),
                )
            nright = cnt_l - nleft
            leaf_id = st.leaf_id
            if p.axis_name is not None:
                # global smaller-child choice + pmax'd capacity bucket so
                # every shard histograms the SAME child (gather-mode comment)
                nleft_g = timed_psum(
                    nleft, p.axis_name, site="counts",
                    measure=p.measure_collectives,
                )
                nright_g = timed_psum(
                    nright, p.axis_name, site="counts",
                    measure=p.measure_collectives,
                )
                left_smaller = nleft_g <= nright_g
                tc = timed_pmax(
                    jnp.where(left_smaller, nleft, nright), p.axis_name,
                    site="counts", measure=p.measure_collectives,
                )
            else:
                left_smaller = nleft <= nright
                tc = jnp.minimum(nleft, nright)
            child_start = begin_l + jnp.where(left_smaller, 0, nleft)
            child_cnt = jnp.where(left_smaller, nleft, nright)
            cbucket = jnp.clip(
                jnp.searchsorted(caps_arr, _cap_size(tc), side="left"),
                0,
                len(caps) - 1,
            ).astype(jnp.int32)
            with jax.named_scope("histogram"):
                sm = lax.switch(
                    cbucket,
                    hist_branches_ordered,
                    (order, child_start, child_cnt),
                )
        elif use_gather:
            # gather mode: the child's rows are compacted into a
            # static-capacity buffer (jnp.nonzero with static size) and the
            # histogram runs over that buffer — the TPU formulation of the
            # reference's ordered_gradients gather (rows/tree ~ N log L)
            order = st.order
            begin_l = nleft = nright = jnp.int32(0)
            col = lax.dynamic_slice_in_dim(bins_t_cols, feat, 1, axis=0)[0]
            nb = nan_bins[feat]
            go_left = (col <= tbin) | (dl & (nb >= 0) & (col == nb))
            if use_cat or use_bundle:
                go_left = jnp.where(
                    cis, cmask[jnp.minimum(col, Bm - 1)], go_left
                )
            in_leaf = (st.leaf_id == l) & can_split
            leaf_id = jnp.where(in_leaf & ~go_left, nl, st.leaf_id)
            # smaller child by RAW row count (capacity bound); masked
            # (bagging) stats still flow through lc/rc
            rows_l = jnp.sum(in_leaf & go_left).astype(jnp.int32)
            rows_in = jnp.sum(in_leaf).astype(jnp.int32)
            rows_r = rows_in - rows_l
            if rows_sharded:
                # the smaller-child choice must be GLOBAL: if shards chose
                # locally, some would histogram the left child and others
                # the right, and the psum would mix the two (the reference
                # decides smaller/larger from global counts too,
                # serial_tree_learner.cpp:343).  The capacity bucket is the
                # max over shards of the chosen child's LOCAL rows — which
                # can exceed local_n/2 on imbalanced shards, hence the
                # full_range ladder.
                rows_l_g = timed_psum(
                    rows_l, p.axis_name, site="counts",
                    measure=p.measure_collectives,
                )
                rows_r_g = timed_psum(
                    rows_r, p.axis_name, site="counts",
                    measure=p.measure_collectives,
                )
                left_smaller = rows_l_g <= rows_r_g
                target = jnp.where(left_smaller, l, nl)
                tc = timed_pmax(
                    jnp.where(left_smaller, rows_l, rows_r), p.axis_name,
                    site="counts", measure=p.measure_collectives,
                )
            else:
                left_smaller = rows_l <= rows_r
                target = jnp.where(left_smaller, l, nl)
                tc = jnp.minimum(rows_l, rows_r)
            bucket = jnp.clip(
                jnp.searchsorted(caps_arr, _cap_size(tc), side="left"),
                0,
                len(caps) - 1,
            ).astype(jnp.int32)
            with jax.named_scope("histogram"):
                sm = lax.switch(bucket, hist_branches, (leaf_id == target) & can_split)
        else:
            order = st.order
            begin_l = nleft = nright = jnp.int32(0)
            leaf_id = st.leaf_id
            col = lax.dynamic_slice_in_dim(bins_t_cols, feat, 1, axis=0)[0]
            nb = nan_bins[feat]
            go_left = (col <= tbin) | (dl & (nb >= 0) & (col == nb))
            if use_cat or use_bundle:
                go_left = jnp.where(
                    cis, cmask[jnp.minimum(col, Bm - 1)], go_left
                )
            in_leaf = (st.leaf_id == l) & can_split
            leaf_id = jnp.where(in_leaf & ~go_left, nl, st.leaf_id)
            left_smaller = c_lc <= c_rc
            target = jnp.where(left_smaller, l, nl)
            mask = count_mask * (leaf_id == target) * can_split
            with jax.named_scope("histogram"):
                sm = leaf_histogram(
                    bins_loc, grad, hess, mask, B,
                    method=p.hist_method,
                    axis_name=hist_axis, quant_scales=quant_scales,
                    measure=p.measure_collectives,
                )

        def _set1(arr, idx, val):
            """Value-preserving write: old value back when not splitting."""
            return arr.at[idx].set(jnp.where(can_split, val, arr[idx]))

        with jax.named_scope("bookkeeping"):
            # ---- record node t (reference Tree::Split, src/io/tree.cpp:65)
            pg, ph, pc = st.leaf_g[l], st.leaf_h[l], st.leaf_cnt[l]
            left_child = _set1(st.left_child, t, -(l + 1))
            right_child = _set1(st.right_child, t, -(nl + 1))
            par = st.leaf_parent[l]
            is_r = st.leaf_is_right[l]
            fix = (node_ids == par) & (par >= 0) & can_split
            left_child = jnp.where(fix & ~is_r, t, left_child)
            right_child = jnp.where(fix & is_r, t, right_child)

            split_feature = _set1(st.split_feature, t, feat)
            split_bin = _set1(st.split_bin, t, tbin)
            split_gain = _set1(st.split_gain, t, c_gain + p.min_gain_to_split)
            default_left = _set1(st.default_left, t, dl)
            split_is_cat = _set1(st.split_is_cat, t, cis)
            node_cat_mask = _set1(st.node_cat_mask, t, cmask)
            internal_value = _set1(
                st.internal_value,
                t,
                leaf_output(pg, ph, p.lambda_l1, p.lambda_l2, p.max_delta_step),
            )
            internal_weight = _set1(st.internal_weight, t, ph)
            internal_count = _set1(st.internal_count, t, pc)

            # ---- leaf bookkeeping
            lg, lh, lc = c_lg, c_lh, c_lc
            rg, rh, rc = c_rg, c_rh, c_rc
            leaf_g = _set1(_set1(st.leaf_g, l, lg), nl, rg)
            leaf_h = _set1(_set1(st.leaf_h, l, lh), nl, rh)
            leaf_cnt = _set1(_set1(st.leaf_cnt, l, lc), nl, rc)
            d_new = st.leaf_depth[l] + 1
            leaf_depth = _set1(_set1(st.leaf_depth, l, d_new), nl, d_new)
            leaf_parent = _set1(_set1(st.leaf_parent, l, t), nl, t)
            leaf_is_right = _set1(
                _set1(st.leaf_is_right, l, jnp.asarray(False)), nl, jnp.asarray(True)
            )

            # ---- histograms: smaller child measured, sibling by subtraction
            left_hist, right_hist = _child_hists(
                st.hist_buf[l], sm, left_smaller
            )
            hist_buf = _write_children(
                st.hist_buf, l, nl, left_hist, right_hist, can_split
            )

        # ---- monotone bounds for the children.
        # basic: split midpoint partitions the parent's output interval
        # (BasicLeafConstraints, monotone_constraints.hpp:465).
        # intermediate (:516): children are bounded by each other's ACTUAL
        # outputs, and the new outputs propagate to every CONTIGUOUS leaf
        # across the split plane — the reference's recursive GoUp/GoDown tree
        # walk is replaced by a vectorized box-adjacency test (see
        # GrowerParams.monotone_method); bound-tightened leaves get their
        # cached candidate refreshed below (top-K, = leaves_to_update_).
        leaf_lb, leaf_ub = st.leaf_lb, st.leaf_ub
        leaf_box = st.leaf_box
        lb_par, ub_par = st.leaf_lb[l], st.leaf_ub[l]
        inter_idxs = None
        inter_valid = None
        if use_mono:
            mc_f = mono_arr[feat]
            num_split = ~cis  # categorical splits carry no interval order
            if use_inter_mono:
                # children feature boxes (categorical: inherit unchanged)
                pbox = st.leaf_box[l]  # [F, 2]
                box_l = pbox.at[feat, 1].set(
                    jnp.where(num_split, tbin, pbox[feat, 1])
                )
                box_r = pbox.at[feat, 0].set(
                    jnp.where(num_split, tbin + 1, pbox[feat, 0])
                )
            if use_adv_mono:
                # advanced: children bounds RECOMPUTED from every existing
                # leaf's current output over the child's own box (reference
                # resets + GoUpToFindConstrainingLeaves rather than
                # inheriting the parent entry, monotone_constraints.hpp:396)
                # — the parent's old box overlaps both children everywhere,
                # so it never constrains its own children
                leaf_ids_p = jnp.arange(L, dtype=jnp.int32)
                valid_prev = (leaf_ids_p < st.num_leaves) & (leaf_ids_p != l)
                outs_prev = _leaf_outs_now(
                    st.leaf_g, st.leaf_h, st.leaf_cnt, st.leaf_parent,
                    st.internal_value, st.leaf_lb, st.leaf_ub,
                )
                lb_l0, ub_l0 = adv_scalar_bounds(
                    box_l, st.leaf_box, outs_prev, mono_arr, valid_prev
                )
                lb_r0, ub_r0 = adv_scalar_bounds(
                    box_r, st.leaf_box, outs_prev, mono_arr, valid_prev
                )
            else:
                lb_l0 = lb_r0 = lb_par
                ub_l0 = ub_r0 = ub_par
            out_l_c = jnp.clip(
                leaf_output(lg, lh, p.lambda_l1, p.lambda_l2, p.max_delta_step),
                lb_l0, ub_l0,
            )
            out_r_c = jnp.clip(
                leaf_output(rg, rh, p.lambda_l1, p.lambda_l2, p.max_delta_step),
                lb_r0, ub_r0,
            )
            if use_inter_mono:
                # sibling bounds from actual outputs
                # (UpdateConstraintsWithOutputs, :548)
                ub_l = jnp.where(
                    num_split & (mc_f > 0), jnp.minimum(ub_l0, out_r_c), ub_l0
                )
                lb_l = jnp.where(
                    num_split & (mc_f < 0), jnp.maximum(lb_l0, out_r_c), lb_l0
                )
                ub_r = jnp.where(
                    num_split & (mc_f < 0), jnp.minimum(ub_r0, out_l_c), ub_r0
                )
                lb_r = jnp.where(
                    num_split & (mc_f > 0), jnp.maximum(lb_r0, out_l_c), lb_r0
                )
                leaf_box = st.leaf_box.at[l].set(
                    jnp.where(can_split, box_l, pbox)
                )
                leaf_box = leaf_box.at[nl].set(
                    jnp.where(can_split, box_r, st.leaf_box[nl])
                )
                # propagate new outputs to contiguous leaves: b is updated
                # from child c iff their boxes TOUCH along a monotone feature
                # g and intersect along every other feature (== the leaves
                # GoDownToFindLeavesToUpdate reaches, :700)
                leaf_ids_r = jnp.arange(L, dtype=jnp.int32)
                valid_b = (
                    (leaf_ids_r <= t) & (leaf_ids_r != l)
                    & can_split & num_split
                )
                blo = leaf_box[:, :, 0]
                bhi = leaf_box[:, :, 1]
                mpos = (mono_arr > 0)[None, :]
                mneg = (mono_arr < 0)[None, :]

                def _prop(cbox, out_c, lb, ub, changed):
                    if use_adv_mono:
                        # advanced: ANY ordered-disjoint leaf across the
                        # monotone dim is constrained, not just the touching
                        # ones (the reference's recompute reaches every leaf
                        # of the opposite branches).  The set of leaves that
                        # RECEIVE a lower bound from c is, by symmetry,
                        # exactly the set that would impose an UPPER bound
                        # on c — reuse the one constrainer geometry
                        lbc, ubc = _adv_constrainers(
                            cbox, leaf_box, mono_arr, valid_b
                        )[:2]
                        need_lb, need_ub = ubc, lbc
                    else:
                        clo, chi = cbox[:, 0], cbox[:, 1]
                        ov = (blo <= chi[None, :]) & (clo[None, :] <= bhi)
                        others = (ov.sum(axis=1) == f - 1)[:, None] & ~ov
                        b_right = blo == chi[None, :] + 1  # b just right of c
                        b_left = bhi == clo[None, :] - 1
                        need_lb = (
                            others & ((b_right & mpos) | (b_left & mneg))
                        ).any(axis=1) & valid_b
                        need_ub = (
                            others & ((b_left & mpos) | (b_right & mneg))
                        ).any(axis=1) & valid_b
                    lb2 = jnp.where(need_lb, jnp.maximum(lb, out_c), lb)
                    ub2 = jnp.where(need_ub, jnp.minimum(ub, out_c), ub)
                    return lb2, ub2, changed | (lb2 > lb) | (ub2 < ub)

                ch0 = jnp.zeros((L,), bool)
                nlb, nub, ch0 = _prop(box_l, out_l_c, st.leaf_lb, st.leaf_ub, ch0)
                nlb, nub, ch0 = _prop(box_r, out_r_c, nlb, nub, ch0)
                leaf_lb = _set1(_set1(nlb, l, lb_l), nl, lb_r)
                leaf_ub = _set1(_set1(nub, l, ub_l), nl, ub_r)
                # leaves_to_update_: refresh the K highest-gain tightened
                # candidates (others keep stale-but-clamped candidates until
                # their next refresh; reference recomputes all, :717)
                inter_changed = ch0 & (st.cand.gain > 0)
                scores = jnp.where(inter_changed, st.cand.gain, -jnp.inf)
                top_vals, inter_idxs = lax.top_k(
                    scores, min(p.monotone_recompute_k, L)
                )
                inter_valid = top_vals > -jnp.inf
            else:
                mid = 0.5 * (out_l_c + out_r_c)
                lb_l = jnp.where(mc_f < 0, mid, lb_par)
                ub_l = jnp.where(mc_f > 0, mid, ub_par)
                lb_r = jnp.where(mc_f > 0, mid, lb_par)
                ub_r = jnp.where(mc_f < 0, mid, ub_par)
                leaf_lb = _set1(_set1(st.leaf_lb, l, lb_l), nl, lb_r)
                leaf_ub = _set1(_set1(st.leaf_ub, l, ub_l), nl, ub_r)
        else:
            lb_l = ub_l = lb_r = ub_r = None

        # path-used features for interaction constraints
        leaf_allowed = st.leaf_allowed
        if p.use_interaction:
            new_used = st.leaf_allowed[l] | (
                jnp.arange(f, dtype=jnp.int32) == feat
            )
            leaf_allowed = _set1(_set1(st.leaf_allowed, l, new_used), nl, new_used)
            used_l = used_r = new_used
        else:
            used_l = used_r = root_used

        cegb_used_new = (
            st.cegb_used.at[feat].set(st.cegb_used[feat] | can_split)
            if use_cegb
            else st.cegb_used
        )

        # ---- refresh split candidates for the two children in ONE vmapped
        # best_split (halves the per-split fixed scan cost vs two calls);
        # intermediate monotone mode appends the K bound-tightened leaves to
        # the same batch (the reference's leaves_to_update_ recompute)
        hist2 = jnp.stack([left_hist, right_hist])
        g2 = jnp.stack([lg, rg])
        h2 = jnp.stack([lh, rh])
        c2 = jnp.stack([lc, rc])
        fm2 = jnp.stack(
            [node_feature_mask(2 * t + 1, used_l),
             node_feature_mask(2 * t + 2, used_r)]
        )
        lb2 = ub2 = None
        if use_mono:
            lb2 = jnp.stack([lb_l, lb_r])
            ub2 = jnp.stack([ub_l, ub_r])
        seeds2 = jnp.stack([2 * t + 1, 2 * t + 2])
        if use_inter_mono:
            hist2 = jnp.concatenate([hist2, hist_buf[inter_idxs]])
            g2 = jnp.concatenate([g2, leaf_g[inter_idxs]])
            h2 = jnp.concatenate([h2, leaf_h[inter_idxs]])
            c2 = jnp.concatenate([c2, leaf_cnt[inter_idxs]])
            lb2 = jnp.concatenate([lb2, leaf_lb[inter_idxs]])
            ub2 = jnp.concatenate([ub2, leaf_ub[inter_idxs]])
            if p.use_interaction:
                # per-leaf usable features reconstructed from the path-used
                # sets; the feature_fraction_bynode random draw is NOT
                # replayed for refreshes (the original node seed is gone) —
                # refreshed candidates see the deterministic mask only
                fm_k = jax.vmap(_leaf_feature_mask)(leaf_allowed[inter_idxs])
            else:
                fm_k = jnp.broadcast_to(
                    feature_mask, (inter_idxs.shape[0], f)
                )
            fm2 = jnp.concatenate([fm2, fm_k])
            seeds2 = jnp.concatenate([seeds2, 7 * L + inter_idxs])
        po2 = leaf_output(g2, h2, p.lambda_l1, p.lambda_l2, p.max_delta_step)
        opt2 = []
        if use_mono:
            opt2 += [lb2, ub2]
        if use_adv_mono:
            # per-threshold bound planes for every leaf in the refresh batch,
            # from CURRENT leaf boxes/outputs (the advanced scan constraints)
            leaf_ids_b = jnp.arange(L, dtype=jnp.int32)
            nvalid = leaf_ids_b < (st.num_leaves + can_split.astype(jnp.int32))
            outs_new = _leaf_outs_now(
                leaf_g, leaf_h, leaf_cnt, leaf_parent,
                internal_value, leaf_lb, leaf_ub,
            )
            batch_idx = jnp.concatenate([jnp.stack([l, nl]), inter_idxs])
            adv2 = jax.vmap(
                lambda i: adv_planes(
                    leaf_box[i], leaf_box, outs_new, mono_arr,
                    nvalid & (leaf_ids_b != i), B,
                )
            )(batch_idx)
            opt2 += list(adv2)
        use_rand = p.extra_trees and rng is not None
        if use_rand:
            opt2 += [jax.vmap(node_rand_bins)(seeds2)]
        if use_mono_pen:
            depth2 = jnp.stack([d_new, d_new])
            if use_inter_mono:
                depth2 = jnp.concatenate([depth2, leaf_depth[inter_idxs]])
            opt2 += [depth2]
        cpen = _cegb_pen(cegb_used_new)

        def _child_cand(hist, g_, h_, c_, fm, po, *rest, wm=False):
            lbv = ubv = rbv = advv = dv = None
            i = 0
            if use_mono:
                lbv, ubv = rest[0], rest[1]
                i = 2
            if use_adv_mono:
                advv = tuple(rest[i:i + 4])
                i += 4
            if use_rand:
                rbv = rest[i]
                i += 1
            if use_mono_pen:
                dv = rest[i]
            return cand_for_leaf(
                hist, g_, h_, c_, fm,
                lb=lbv, ub=ubv, pout=po, cpen=cpen, rand=rbv, adv=advv,
                depth=dv, with_margin=wm,
            )

        with jax.named_scope("candidate_refresh"):
            if use_int8_acc:
                # near-tie f32 re-accumulate for the two refreshed children:
                # both child windows are re-histogrammed DIRECTLY (no
                # subtraction — the refine must not inherit the int8 grid
                # error it exists to remove), with cnt=0 for children whose
                # margin clears the tolerance (zero loop trips in-kernel)
                cand2, margins2 = jax.vmap(
                    functools.partial(_child_cand, wm=True)
                )(hist2, g2, h2, c2, fm2, po2, *opt2)
                near2 = margins2 < p.near_tie_tol  # [2]
                start2 = jnp.stack([begin_l, begin_l + nleft])
                cnt2 = jnp.where(near2, jnp.stack([nleft, nright]), 0)
                hist_rf = seg_hist_batch(
                    order,
                    jnp.stack([start2, cnt2], axis=1).astype(jnp.int32),
                    f=f_seg, num_bins=B, n_pad=n_pad_seg,
                    quant_scales=None, wide=seg_wide, live=seg_live,
                )
                hist2 = jnp.where(near2[:, None, None, None], hist_rf, hist2)
            cand2 = jax.vmap(_child_cand)(hist2, g2, h2, c2, fm2, po2, *opt2)
        with jax.named_scope("candidate_refresh"):
            cand_l = SplitCandidate(*[a[0] for a in cand2])
            cand_r = SplitCandidate(*[a[1] for a in cand2])
            depth_ok = (p.max_depth <= 0) | (d_new < p.max_depth)
            cand = _set_cand(
                st.cand, l, cand_l,
                jnp.where(depth_ok, cand_l.gain, -jnp.inf), pred=can_split,
            )
            cand = _set_cand(
                cand, nl, cand_r,
                jnp.where(depth_ok, cand_r.gain, -jnp.inf), pred=can_split,
            )
            if use_inter_mono:
                # write back the refreshed candidates of bound-tightened leaves
                for kk in range(inter_idxs.shape[0]):
                    row = SplitCandidate(*[a[2 + kk] for a in cand2])
                    cand = _set_cand(
                        cand, inter_idxs[kk], row,
                        pred=can_split & inter_valid[kk],
                    )

            if use_ordered or use_seg:
                leaf_begin = _set1(st.leaf_begin, nl, begin_l + nleft)
                leaf_nrows = _set1(_set1(st.leaf_nrows, l, nleft), nl, nright)
            else:
                leaf_begin, leaf_nrows = st.leaf_begin, st.leaf_nrows

        return _State(
            leaf_id=leaf_id,
            order=order,
            leaf_begin=leaf_begin,
            leaf_nrows=leaf_nrows,
            hist_buf=hist_buf,
            leaf_g=leaf_g,
            leaf_h=leaf_h,
            leaf_cnt=leaf_cnt,
            leaf_depth=leaf_depth,
            leaf_parent=leaf_parent,
            leaf_is_right=leaf_is_right,
            leaf_lb=leaf_lb,
            leaf_ub=leaf_ub,
            leaf_box=leaf_box,
            leaf_allowed=leaf_allowed,
            cand=cand,
            split_feature=split_feature,
            split_bin=split_bin,
            split_gain=split_gain,
            default_left=default_left,
            split_is_cat=split_is_cat,
            node_cat_mask=node_cat_mask,
            left_child=left_child,
            right_child=right_child,
            internal_value=internal_value,
            internal_weight=internal_weight,
            internal_count=internal_count,
            num_leaves=st.num_leaves + can_split.astype(jnp.int32),
            done=done,
            forced_ok=forced_ok_next,
            cegb_used=cegb_used_new,
            # serial fori_loop runs L-1 trips regardless of early done;
            # count only productive steps so commit rate reads 1.0
            steps=st.steps + can_split.astype(jnp.int32),
            refines=st.refines + (
                jnp.sum(near2.astype(jnp.int32)) * can_split.astype(jnp.int32)
                if use_int8_acc
                else 0
            ),
        )

    def body_batched(st: _State) -> _State:
        """One frontier-batched step: split up to ``leaf_k`` leaves.

        The top-K frontier leaves by cached gain are partitioned over their
        DISJOINT row windows, the K smaller children are histogrammed in one
        batched pass (one [K, 2] count psum + one [K, 3, F, B] histogram
        psum under data-parallel), and all 2K child candidates refresh in
        one vmapped scan.  Exactness by the prefix-commit rule: member i
        commits iff every earlier member committed AND its gain strictly
        exceeds the best child gain any earlier member created — exactly
        when the serial argmax would have picked leaf i next.  Uncommitted
        members only reordered rows WITHIN their leaf's window (membership
        unchanged) and are value-preserving no-ops everywhere else; their
        leaves stay in the frontier for the next step.  Member 0 is the
        plain argmax, so every step with a positive best gain commits at
        least one split and the while loop terminates.  All commit
        decisions derive from psummed quantities, so every data-parallel
        shard runs the identical trip count."""
        K = leaf_k
        iota_k = jnp.arange(K, dtype=jnp.int32)
        base = st.num_leaves - 1  # node id taken by batch member 0
        t_k = base + iota_k  # node id per member under the prefix rule
        nl_k = t_k + 1  # new leaf index per member
        gains_k, l_k = lax.top_k(st.cand.gain, K)
        l_k = l_k.astype(jnp.int32)

        # ---- forced phase: commit exactly ONE (member 0) split per step so
        # the host-precomputed forced leaf numbering stays valid; a failed
        # forced split aborts the rest (abort_last_forced_split) and the
        # whole batch resumes normal growth the same step
        if use_forced_splits:
            f_leaf_a, f_feat_a, f_bin_a, f_iscat_a = forced
            tf = jnp.clip(base, 0, p.n_forced - 1)
            is_f_step = (base < p.n_forced) & st.forced_ok
            f_leaf = f_leaf_a[tf]
            f_feat = f_feat_a[tf]
            f_bin = f_bin_a[tf]
            f_iscat = f_iscat_a[tf]
            # (voting raises at K > 1, so the row is always the global one)
            f_lg, f_lh, f_lc, f_rg, f_rh, f_rc, f_gain = _forced_stats(
                st, f_leaf, f_feat, f_bin, f_iscat
            )
            use_forced = is_f_step & (f_gain > 0)
            forced_ok_next = st.forced_ok & (~is_f_step | use_forced)
            l_k = l_k.at[0].set(jnp.where(use_forced, f_leaf, l_k[0]))
            gains_k = gains_k.at[0].set(
                jnp.where(use_forced, f_gain, gains_k[0])
            )
            forced_mask_k = jnp.where(
                use_forced, iota_k == 0, jnp.ones((K,), bool)
            )
        else:
            use_forced = None
            forced_ok_next = st.forced_ok
            forced_mask_k = jnp.ones((K,), bool)

        c_gain_k = gains_k
        c_feat_k = st.cand.feature[l_k]
        c_bin_k = st.cand.bin[l_k]
        c_dl_k = st.cand.default_left[l_k]
        c_cis_k = st.cand.is_cat[l_k]
        c_cmask_k = st.cand.cat_mask[l_k]  # [K, Bm]
        c_lg_k = st.cand.left_g[l_k]
        c_lh_k = st.cand.left_h[l_k]
        c_lc_k = st.cand.left_cnt[l_k]
        c_rg_k = st.cand.right_g[l_k]
        c_rh_k = st.cand.right_h[l_k]
        c_rc_k = st.cand.right_cnt[l_k]
        if use_forced_splits:
            def _f0(arr, val):
                return arr.at[0].set(jnp.where(use_forced, val, arr[0]))

            c_feat_k = _f0(c_feat_k, f_feat)
            c_bin_k = _f0(c_bin_k, f_bin)
            c_dl_k = _f0(c_dl_k, ~f_iscat)
            c_cis_k = _f0(c_cis_k, f_iscat)
            if use_cat:
                oh = jnp.arange(Bm, dtype=jnp.int32) == f_bin
                c_cmask_k = _f0(c_cmask_k, oh)
            c_lg_k = _f0(c_lg_k, f_lg)
            c_lh_k = _f0(c_lh_k, f_lh)
            c_lc_k = _f0(c_lc_k, f_lc)
            c_rg_k = _f0(c_rg_k, f_rg)
            c_rh_k = _f0(c_rh_k, f_rh)
            c_rc_k = _f0(c_rc_k, f_rc)

        pos_k = c_gain_k > 0.0
        # node ids are committed as a prefix, so member i's slot is statically
        # base + i; members past the node budget cannot commit
        room_k = t_k < (L - 1)
        active_k = pos_k & room_k & forced_mask_k & ~st.done
        done = st.done | ~pos_k[0]

        # ---- K partitions over disjoint windows + ONE batched smaller-child
        # histogram pass (speculative for members that end up uncommitted:
        # rows only move WITHIN their leaf's window, so nothing leaks)
        in_leaf_k = go_left_k = None
        if use_seg and use_fused_grow:
            # K partitions + K elections + K histograms in ONE kernel launch
            # (grid over members; windows are disjoint so members commute)
            begin_k = st.leaf_begin[l_k]
            cnt_k = jnp.where(active_k, st.leaf_nrows[l_k], 0)
            with jax.named_scope("fused_grow_step"):
                (
                    order,
                    nleft_k,
                    nright_k,
                    _cs_k,
                    _cc_k,
                    sm_k,
                ) = fused_grow_step(
                    st.order,
                    begin_k,
                    cnt_k,
                    c_feat_k,
                    c_bin_k,
                    c_dl_k.astype(jnp.int32),
                    nan_bins[c_feat_k],
                    c_cis_k.astype(jnp.int32),
                    c_cmask_k.astype(jnp.float32),
                    f=f_seg,
                    num_bins=B,
                    n_pad=n_pad_seg,
                    quant_scales=seg_qs,
                    wide=seg_wide,
                    live=seg_live,
                )
            left_smaller_k = nleft_k <= nright_k
        elif use_seg:
            begin_k = st.leaf_begin[l_k]
            cnt_k = jnp.where(active_k, st.leaf_nrows[l_k], 0)
            with jax.named_scope("partition"):
                order, nleft_k, nright_k = sort_partition_batch(
                    st.order,
                    begin_k,
                    cnt_k,
                    c_feat_k,
                    c_bin_k,
                    c_dl_k.astype(jnp.int32),
                    nan_bins[c_feat_k],
                    c_cis_k.astype(jnp.int32),
                    c_cmask_k.astype(jnp.float32),
                    f=f_seg,
                    n_pad=n_pad_seg,
                    wide=seg_wide,
                )
            if p.axis_name is not None:
                cnts_g = timed_psum(
                    jnp.stack([nleft_k, nright_k], axis=1), p.axis_name,
                    site="counts", measure=p.measure_collectives,
                )
                left_smaller_k = cnts_g[:, 0] <= cnts_g[:, 1]
            else:
                left_smaller_k = nleft_k <= nright_k
            child_start_k = begin_k + jnp.where(left_smaller_k, 0, nleft_k)
            child_cnt_k = jnp.where(left_smaller_k, nleft_k, nright_k)
            wins_k = jnp.stack([child_start_k, child_cnt_k], axis=1).astype(
                jnp.int32
            )

            def _seg_hist_win(w):
                return seg_hist_batch(
                    order,
                    w,
                    f=f_seg,
                    num_bins=B,
                    n_pad=n_pad_seg,
                    quant_scales=seg_qs,
                    wide=seg_wide,
                    live=seg_live,
                )

            if use_overlap:
                # double-buffered: build buffer 0, issue its psum, build
                # buffer 1 while the buffer-0 all-reduce is in flight
                kh = K // 2
                with jax.named_scope("histogram_db0"):
                    sm_a = _seg_hist_win(wins_k[:kh])
                sm_a = timed_psum(
                    sm_a, hist_axis, site="hist_db0",
                    measure=p.measure_collectives,
                )
                with jax.named_scope("histogram_db1"):
                    sm_b = _seg_hist_win(wins_k[kh:])
                sm_b = timed_psum(
                    sm_b, hist_axis, site="hist_db1",
                    measure=p.measure_collectives,
                )
                sm_k = jnp.concatenate([sm_a, sm_b], axis=0)
            else:
                with jax.named_scope("histogram"):
                    sm_k = _seg_hist_win(wins_k)
                if hist_axis is not None:
                    sm_k = timed_psum(
                        sm_k, hist_axis, site="hist",
                        measure=p.measure_collectives,
                    )
        elif use_ordered:
            begin_k = st.leaf_begin[l_k]
            cnt_k = jnp.where(active_k, st.leaf_nrows[l_k], 0)
            order = st.order
            with jax.named_scope("partition"):
                nleft_list = []
                for i in range(K):
                    pbucket_i = jnp.clip(
                        jnp.searchsorted(
                            pcaps_arr, _cap_size(cnt_k[i]), side="left"
                        ),
                        0,
                        len(pcaps) - 1,
                    ).astype(jnp.int32)
                    order, nleft_i = lax.switch(
                        pbucket_i,
                        part_branches,
                        (order, begin_k[i], cnt_k[i], c_feat_k[i], c_bin_k[i],
                         c_dl_k[i], c_cis_k[i], c_cmask_k[i]),
                    )
                    nleft_list.append(nleft_i)
                nleft_k = jnp.stack(nleft_list)
            nright_k = cnt_k - nleft_k
            if p.axis_name is not None:
                cnts_g = timed_psum(
                    jnp.stack([nleft_k, nright_k], axis=1), p.axis_name,
                    site="counts", measure=p.measure_collectives,
                )
                left_smaller_k = cnts_g[:, 0] <= cnts_g[:, 1]
                tc_k = timed_pmax(
                    jnp.where(left_smaller_k, nleft_k, nright_k), p.axis_name,
                    site="counts", measure=p.measure_collectives,
                )
            else:
                left_smaller_k = nleft_k <= nright_k
                tc_k = jnp.minimum(nleft_k, nright_k)
            child_start_k = begin_k + jnp.where(left_smaller_k, 0, nleft_k)
            child_cnt_k = jnp.where(left_smaller_k, nleft_k, nright_k)
            with jax.named_scope("histogram"):
                sm_list = []
                done_halves = []
                for i in range(K):
                    cbucket_i = jnp.clip(
                        jnp.searchsorted(
                            caps_arr, _cap_size(tc_k[i]), side="left"
                        ),
                        0,
                        len(caps) - 1,
                    ).astype(jnp.int32)
                    sm_list.append(
                        lax.switch(
                            cbucket_i,
                            hist_branches_ordered_loc,
                            (order, child_start_k[i], child_cnt_k[i]),
                        )
                    )
                    if use_overlap and i == K // 2 - 1:
                        # double-buffered: buffer 0's psum flies while the
                        # remaining members' histograms build
                        done_halves.append(timed_psum(
                            jnp.stack(sm_list), hist_axis, site="hist_db0",
                            measure=p.measure_collectives,
                        ))
                        sm_list = []
            if use_overlap:
                done_halves.append(timed_psum(
                    jnp.stack(sm_list), hist_axis, site="hist_db1",
                    measure=p.measure_collectives,
                ))
                sm_k = jnp.concatenate(done_halves, axis=0)
            else:
                sm_k = jnp.stack(sm_list)
                if hist_axis is not None:
                    sm_k = timed_psum(
                        sm_k, hist_axis, site="hist",
                        measure=p.measure_collectives,
                    )
        else:
            # gather / full: row membership per member, leaf_id writes
            # deferred to the commit decision below
            order = st.order
            begin_k = jnp.zeros((K,), jnp.int32)
            nleft_k = nright_k = jnp.zeros((K,), jnp.int32)
            gl_rows, in_rows = [], []
            for i in range(K):
                col = lax.dynamic_slice_in_dim(
                    bins_t_cols, c_feat_k[i], 1, axis=0
                )[0]
                nb = nan_bins[c_feat_k[i]]
                gli = (col <= c_bin_k[i]) | (
                    c_dl_k[i] & (nb >= 0) & (col == nb)
                )
                if use_cat or use_bundle:
                    gli = jnp.where(
                        c_cis_k[i], c_cmask_k[i][jnp.minimum(col, Bm - 1)], gli
                    )
                gl_rows.append(gli)
                in_rows.append((st.leaf_id == l_k[i]) & active_k[i])
            go_left_k = jnp.stack(gl_rows)  # [K, N]
            in_leaf_k = jnp.stack(in_rows)
            if use_gather:
                rows_l_k = jnp.sum(in_leaf_k & go_left_k, axis=1).astype(
                    jnp.int32
                )
                rows_r_k = (
                    jnp.sum(in_leaf_k, axis=1).astype(jnp.int32) - rows_l_k
                )
                if p.axis_name is not None:
                    cnts_g = timed_psum(
                        jnp.stack([rows_l_k, rows_r_k], axis=1), p.axis_name,
                        site="counts", measure=p.measure_collectives,
                    )
                    left_smaller_k = cnts_g[:, 0] <= cnts_g[:, 1]
                    tc_k = timed_pmax(
                        jnp.where(left_smaller_k, rows_l_k, rows_r_k),
                        p.axis_name, site="counts",
                        measure=p.measure_collectives,
                    )
                else:
                    left_smaller_k = rows_l_k <= rows_r_k
                    tc_k = jnp.minimum(rows_l_k, rows_r_k)
                member_k = in_leaf_k & jnp.where(
                    left_smaller_k[:, None], go_left_k, ~go_left_k
                )
                with jax.named_scope("histogram"):
                    sm_list = []
                    done_halves = []
                    for i in range(K):
                        bucket_i = jnp.clip(
                            jnp.searchsorted(
                                caps_arr, _cap_size(tc_k[i]), side="left"
                            ),
                            0,
                            len(caps) - 1,
                        ).astype(jnp.int32)
                        sm_list.append(
                            lax.switch(bucket_i, hist_branches_loc, member_k[i])
                        )
                        if use_overlap and i == K // 2 - 1:
                            done_halves.append(timed_psum(
                                jnp.stack(sm_list), hist_axis,
                                site="hist_db0",
                                measure=p.measure_collectives,
                            ))
                            sm_list = []
                    if use_overlap:
                        done_halves.append(timed_psum(
                            jnp.stack(sm_list), hist_axis, site="hist_db1",
                            measure=p.measure_collectives,
                        ))
                        sm_k = jnp.concatenate(done_halves, axis=0)
                    else:
                        sm_k = jnp.stack(sm_list)
            else:
                left_smaller_k = c_lc_k <= c_rc_k
                member_k = in_leaf_k & jnp.where(
                    left_smaller_k[:, None], go_left_k, ~go_left_k
                )

                def _full_hist(mask_win):
                    return jax.vmap(
                        lambda m: leaf_histogram(
                            bins_loc, grad, hess, m, B,
                            method=p.hist_method,
                            axis_name=None,
                            quant_scales=quant_scales,
                        )
                    )(mask_win)

                mask_k = count_mask[None, :] * member_k
                if use_overlap:
                    kh = K // 2
                    with jax.named_scope("histogram_db0"):
                        sm_a = _full_hist(mask_k[:kh])
                    sm_a = timed_psum(
                        sm_a, hist_axis, site="hist_db0",
                        measure=p.measure_collectives,
                    )
                    with jax.named_scope("histogram_db1"):
                        sm_b = _full_hist(mask_k[kh:])
                    sm_b = timed_psum(
                        sm_b, hist_axis, site="hist_db1",
                        measure=p.measure_collectives,
                    )
                    sm_k = jnp.concatenate([sm_a, sm_b], axis=0)
                else:
                    with jax.named_scope("histogram"):
                        sm_k = _full_hist(mask_k)
            if hist_axis is not None and not use_overlap:
                sm_k = timed_psum(
                    sm_k, hist_axis, site="hist",
                    measure=p.measure_collectives,
                )

        with jax.named_scope("bookkeeping"):
            # ---- sibling histograms by subtraction, per pair
            left_hist_k, right_hist_k = _child_hists(
                st.hist_buf[l_k],  # [K, 3, f_loc, B]
                sm_k, left_smaller_k[:, None, None, None],
            )

        lg_k, lh_k, lc_k = c_lg_k, c_lh_k, c_lc_k
        rg_k, rh_k, rc_k = c_rg_k, c_rh_k, c_rc_k

        # basic monotone bounds are member-local: each member reads only its
        # OWN parent's interval, which no other batch member writes
        if use_mono:
            mc_f_k = mono_arr[c_feat_k]
            lb_par_k = st.leaf_lb[l_k]
            ub_par_k = st.leaf_ub[l_k]
            out_l_c = jnp.clip(
                leaf_output(
                    lg_k, lh_k, p.lambda_l1, p.lambda_l2, p.max_delta_step
                ),
                lb_par_k, ub_par_k,
            )
            out_r_c = jnp.clip(
                leaf_output(
                    rg_k, rh_k, p.lambda_l1, p.lambda_l2, p.max_delta_step
                ),
                lb_par_k, ub_par_k,
            )
            mid_k = 0.5 * (out_l_c + out_r_c)
            lb_l_k = jnp.where(mc_f_k < 0, mid_k, lb_par_k)
            ub_l_k = jnp.where(mc_f_k > 0, mid_k, ub_par_k)
            lb_r_k = jnp.where(mc_f_k > 0, mid_k, lb_par_k)
            ub_r_k = jnp.where(mc_f_k < 0, mid_k, ub_par_k)

        d_new_k = st.leaf_depth[l_k] + 1

        # ---- refresh all 2K child candidates in ONE vmapped scan
        hist2 = jnp.concatenate([left_hist_k, right_hist_k])
        g2 = jnp.concatenate([lg_k, rg_k])
        h2 = jnp.concatenate([lh_k, rh_k])
        c2 = jnp.concatenate([lc_k, rc_k])
        seeds2 = jnp.concatenate([2 * t_k + 1, 2 * t_k + 2])
        fm2 = jax.vmap(lambda s: node_feature_mask(s, root_used))(seeds2)
        po2 = leaf_output(g2, h2, p.lambda_l1, p.lambda_l2, p.max_delta_step)
        opt2 = []
        if use_mono:
            opt2 += [
                jnp.concatenate([lb_l_k, lb_r_k]),
                jnp.concatenate([ub_l_k, ub_r_k]),
            ]
        use_rand = p.extra_trees and rng is not None
        if use_rand:
            opt2 += [jax.vmap(node_rand_bins)(seeds2)]
        if use_mono_pen:
            opt2 += [jnp.concatenate([d_new_k, d_new_k])]

        def _child_cand_b(hist, g_, h_, c_, fm, po, *rest, wm=False):
            lbv = ubv = rbv = dv = None
            i = 0
            if use_mono:
                lbv, ubv = rest[0], rest[1]
                i = 2
            if use_rand:
                rbv = rest[i]
                i += 1
            if use_mono_pen:
                dv = rest[i]
            return cand_for_leaf(
                hist, g_, h_, c_, fm,
                lb=lbv, ub=ubv, pout=po, rand=rbv, depth=dv, with_margin=wm,
            )

        with jax.named_scope("candidate_refresh"):
            if use_int8_acc:
                # near-tie f32 re-accumulate over the 2K refreshed children
                # (one extra plane-tiled launch; cnt=0 rows cost nothing)
                cand2, margins2 = jax.vmap(
                    functools.partial(_child_cand_b, wm=True)
                )(hist2, g2, h2, c2, fm2, po2, *opt2)
                near2 = margins2 < p.near_tie_tol  # [2K]
                start2 = jnp.concatenate([begin_k, begin_k + nleft_k])
                cnt2 = jnp.where(
                    near2, jnp.concatenate([nleft_k, nright_k]), 0
                )
                hist_rf = seg_hist_batch(
                    order,
                    jnp.stack([start2, cnt2], axis=1).astype(jnp.int32),
                    f=f_seg, num_bins=B, n_pad=n_pad_seg,
                    quant_scales=None, wide=seg_wide, live=seg_live,
                )
                hist2 = jnp.where(near2[:, None, None, None], hist_rf, hist2)
            cand2 = jax.vmap(_child_cand_b)(hist2, g2, h2, c2, fm2, po2, *opt2)
        depth_ok_k = (p.max_depth <= 0) | (d_new_k < p.max_depth)
        gain_l_k = jnp.where(depth_ok_k, cand2.gain[:K], -jnp.inf)
        gain_r_k = jnp.where(depth_ok_k, cand2.gain[K:], -jnp.inf)
        child_best_k = jnp.maximum(gain_l_k, gain_r_k)

        # ---- prefix-commit: member i's gain must STRICTLY beat the best
        # child gain created by earlier members (a tie defers to the next
        # step, where the serial argmax tie-break applies natively), and all
        # earlier members must themselves have committed
        prev_max = lax.cummax(
            jnp.concatenate(
                [jnp.full((1,), -jnp.inf, jnp.float32), child_best_k[:-1]]
            )
        )
        ok_k = pos_k & room_k & forced_mask_k & (c_gain_k > prev_max)
        commit_k = lax.associative_scan(jnp.logical_and, ok_k) & ~st.done

        # ---- commit the prefix: value-preserving writes per member (node
        # ids t_i = base + i are disjoint, as are the members' leaf rows)
        def _setb(arr, idx, val, ok):
            return arr.at[idx].set(jnp.where(ok, val, arr[idx]))

        left_child = st.left_child
        right_child = st.right_child
        split_feature = st.split_feature
        split_bin = st.split_bin
        split_gain = st.split_gain
        default_left = st.default_left
        split_is_cat = st.split_is_cat
        node_cat_mask = st.node_cat_mask
        internal_value = st.internal_value
        internal_weight = st.internal_weight
        internal_count = st.internal_count
        leaf_g = st.leaf_g
        leaf_h = st.leaf_h
        leaf_cnt = st.leaf_cnt
        leaf_depth = st.leaf_depth
        leaf_parent = st.leaf_parent
        leaf_is_right = st.leaf_is_right
        leaf_lb, leaf_ub = st.leaf_lb, st.leaf_ub
        hist_buf = st.hist_buf
        cand = st.cand
        leaf_begin, leaf_nrows = st.leaf_begin, st.leaf_nrows
        leaf_id = st.leaf_id
        for i in range(K):
            ok = commit_k[i]
            t_i, l_i, nl_i = t_k[i], l_k[i], nl_k[i]
            left_child = _setb(left_child, t_i, -(l_i + 1), ok)
            right_child = _setb(right_child, t_i, -(nl_i + 1), ok)
            par = st.leaf_parent[l_i]  # no member writes another's leaf row
            is_r = st.leaf_is_right[l_i]
            fix = (node_ids == par) & (par >= 0) & ok
            left_child = jnp.where(fix & ~is_r, t_i, left_child)
            right_child = jnp.where(fix & is_r, t_i, right_child)
            split_feature = _setb(split_feature, t_i, c_feat_k[i], ok)
            split_bin = _setb(split_bin, t_i, c_bin_k[i], ok)
            split_gain = _setb(
                split_gain, t_i, c_gain_k[i] + p.min_gain_to_split, ok
            )
            default_left = _setb(default_left, t_i, c_dl_k[i], ok)
            split_is_cat = _setb(split_is_cat, t_i, c_cis_k[i], ok)
            node_cat_mask = _setb(node_cat_mask, t_i, c_cmask_k[i], ok)
            pg, ph, pc = st.leaf_g[l_i], st.leaf_h[l_i], st.leaf_cnt[l_i]
            internal_value = _setb(
                internal_value,
                t_i,
                leaf_output(pg, ph, p.lambda_l1, p.lambda_l2, p.max_delta_step),
                ok,
            )
            internal_weight = _setb(internal_weight, t_i, ph, ok)
            internal_count = _setb(internal_count, t_i, pc, ok)
            leaf_g = _setb(_setb(leaf_g, l_i, lg_k[i], ok), nl_i, rg_k[i], ok)
            leaf_h = _setb(_setb(leaf_h, l_i, lh_k[i], ok), nl_i, rh_k[i], ok)
            leaf_cnt = _setb(
                _setb(leaf_cnt, l_i, lc_k[i], ok), nl_i, rc_k[i], ok
            )
            leaf_depth = _setb(
                _setb(leaf_depth, l_i, d_new_k[i], ok), nl_i, d_new_k[i], ok
            )
            leaf_parent = _setb(
                _setb(leaf_parent, l_i, t_i, ok), nl_i, t_i, ok
            )
            leaf_is_right = _setb(
                _setb(leaf_is_right, l_i, jnp.asarray(False), ok),
                nl_i, jnp.asarray(True), ok,
            )
            hist_buf = _write_children(
                hist_buf, l_i, nl_i, left_hist_k[i], right_hist_k[i], ok
            )
            if use_mono:
                leaf_lb = _setb(
                    _setb(leaf_lb, l_i, lb_l_k[i], ok), nl_i, lb_r_k[i], ok
                )
                leaf_ub = _setb(
                    _setb(leaf_ub, l_i, ub_l_k[i], ok), nl_i, ub_r_k[i], ok
                )
            cand_l_i = SplitCandidate(*[a[i] for a in cand2])
            cand_r_i = SplitCandidate(*[a[K + i] for a in cand2])
            cand = _set_cand(cand, l_i, cand_l_i, gain_l_k[i], pred=ok)
            cand = _set_cand(cand, nl_i, cand_r_i, gain_r_k[i], pred=ok)
            if use_ordered or use_seg:
                leaf_begin = _setb(
                    leaf_begin, nl_i, begin_k[i] + nleft_k[i], ok
                )
                leaf_nrows = _setb(
                    _setb(leaf_nrows, l_i, nleft_k[i], ok),
                    nl_i, nright_k[i], ok,
                )
        if in_leaf_k is not None:
            for i in range(K):
                leaf_id = jnp.where(
                    in_leaf_k[i] & ~go_left_k[i] & commit_k[i],
                    nl_k[i], leaf_id,
                )

        return _State(
            leaf_id=leaf_id,
            order=order,
            leaf_begin=leaf_begin,
            leaf_nrows=leaf_nrows,
            hist_buf=hist_buf,
            leaf_g=leaf_g,
            leaf_h=leaf_h,
            leaf_cnt=leaf_cnt,
            leaf_depth=leaf_depth,
            leaf_parent=leaf_parent,
            leaf_is_right=leaf_is_right,
            leaf_lb=leaf_lb,
            leaf_ub=leaf_ub,
            leaf_box=st.leaf_box,
            leaf_allowed=st.leaf_allowed,
            cand=cand,
            split_feature=split_feature,
            split_bin=split_bin,
            split_gain=split_gain,
            default_left=default_left,
            split_is_cat=split_is_cat,
            node_cat_mask=node_cat_mask,
            left_child=left_child,
            right_child=right_child,
            internal_value=internal_value,
            internal_weight=internal_weight,
            internal_count=internal_count,
            num_leaves=st.num_leaves + jnp.sum(commit_k.astype(jnp.int32)),
            done=done,
            forced_ok=forced_ok_next,
            cegb_used=st.cegb_used,
            steps=st.steps + 1,
            # near2 is [2K] ordered [K left, K right]; a refine counts only
            # when its member committed (speculative members re-run anyway)
            refines=st.refines + (
                jnp.sum(
                    jnp.where(
                        jnp.concatenate([commit_k, commit_k]),
                        near2.astype(jnp.int32),
                        0,
                    )
                )
                if use_int8_acc
                else 0
            ),
        )

    with jax.named_scope("leaf_loop"):
        if leaf_k > 1:
            # dynamic trip count: every step commits >= 1 split while any
            # leaf remains splittable, so this takes ceil((num_splits)/avg
            # batch) steps instead of a fixed L - 1
            state = lax.while_loop(
                lambda st: ~st.done & (st.num_leaves < L),
                body_batched,
                state,
            )
        else:
            state = lax.fori_loop(0, L - 1, body, state)

    with jax.named_scope("leaf_values"):
        leaf_idx = jnp.arange(L, dtype=jnp.int32)
        active = leaf_idx < state.num_leaves
        out = leaf_output(
            state.leaf_g, state.leaf_h, p.lambda_l1, p.lambda_l2, p.max_delta_step
        )
        if p.path_smooth > 0.0:
            parent_out = jnp.where(
                state.leaf_parent >= 0,
                state.internal_value[jnp.maximum(state.leaf_parent, 0)],
                0.0,
            )
            ratio = state.leaf_cnt / p.path_smooth
            out = out * ratio / (ratio + 1.0) + parent_out / (ratio + 1.0)
        if use_mono:
            out = jnp.clip(out, state.leaf_lb, state.leaf_ub)
        # a tree with no splits contributes NOTHING (reference outputs a const-0
        # tree and stops, gbdt.cpp:428) — zeroing here lets the booster dispatch
        # the score update before knowing num_leaves on host (async pipeline)
        leaf_value = jnp.where(active & (state.num_leaves > 1), out, 0.0)

    tree = TreeArrays(
        split_feature=state.split_feature,
        split_bin=state.split_bin,
        split_gain=state.split_gain,
        default_left=state.default_left,
        left_child=state.left_child,
        right_child=state.right_child,
        internal_value=state.internal_value,
        internal_weight=state.internal_weight,
        internal_count=state.internal_count,
        leaf_value=leaf_value.astype(jnp.float32),
        leaf_weight=state.leaf_h,
        leaf_count=state.leaf_cnt,
        leaf_depth=state.leaf_depth,
        num_leaves=state.num_leaves,
        grow_steps=state.steps,
        refine_count=state.refines,
        split_is_cat=state.split_is_cat,
        cat_mask=state.node_cat_mask,
    )

    if bag_window or (
        use_seg
        and leaf_ids_form(p.num_leaves, f, Bm, p.feature_shard) == "walk"
    ):
        # every row's leaf from the tree's own walk: one predicate over one
        # matrix, so a row gets the leaf the partition put it in, and at
        # 8M x 67 it reads 11 ms where leaf_ids' marker cumsum, gather and
        # sort read 93 (PERF.md section 6, PR 35).  Under bag_window it is
        # the only form (the rows past the root window were never
        # partitioned); elsewhere score_lookup.leaf_ids_form says where it
        # is the cheaper one
        members = lax.axis_size(p.fleet_axis_name) if p.fleet_axis_name else 1
        with jax.named_scope("oob_score" if bag_window else "leaf_ids"):
            return tree, tree_leaves(
                bins, nan_bins, tree.split_feature, tree.split_bin,
                tree.default_left, tree.left_child, tree.right_child,
                members=members,
            )
    if use_seg:
        # leaf per segment position (marker-cumsum) -> row order via ONE sort
        # (the scatter alternative serializes on TPU): categorical and
        # bundled trees, feature shards, trees past the walk's size
        with jax.named_scope("leaf_ids"):
            lp = leaf_of_positions(
                state.leaf_begin, state.leaf_nrows, state.num_leaves, n
            )
            GLO = stat_lanes(f_seg, seg_wide, seg_grouped)[0]
            planes = flat_planes(state.order)
            ridx = (planes[GLO + 5, :n].astype(jnp.int32) & 0xFFFF) | (
                (planes[GLO + 6, :n].astype(jnp.int32) & 0xFFFF) << 16
            )
            return tree, leaf_id_from_seg(ridx, lp)
    if use_ordered:
        # reconstruct the per-row leaf-id vector from the segment layout in
        # ONE O(N) pass: mark each active leaf's segment start, turn starts
        # into segment ordinals via cumsum, map ordinals to leaf indices via
        # a begin-sorted permutation, scatter through the row permutation.
        # Zero-row leaves sort BEFORE the non-empty leaf sharing their begin
        # (key = 2*begin + (nrows>0)) so the cumsum lands on the real owner.
        begin_marks = jnp.where(active, state.leaf_begin, n)
        marker = (
            jnp.zeros((n,), jnp.int32).at[begin_marks].add(1, mode="drop")
        )
        sort_key = jnp.where(
            active,
            2 * state.leaf_begin + (state.leaf_nrows > 0).astype(jnp.int32),
            2 * n + 2,
        )
        sorted_leaf = jnp.argsort(sort_key, stable=True).astype(jnp.int32)
        seg_ord = jnp.clip(jnp.cumsum(marker) - 1, 0, L - 1)
        leaf_of_pos = sorted_leaf[seg_ord]
        leaf_id = (
            jnp.zeros((n,), jnp.int32)
            .at[state.order[:n]]
            .set(leaf_of_pos, mode="drop")
        )
        return tree, leaf_id
    return tree, state.leaf_id
