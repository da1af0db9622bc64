"""Per-leaf best-split scan as one Pallas kernel.

Reference analog: the CUDA per-(leaf, feature) scan kernel
``FindBestSplitsForLeafKernel`` (src/treelearner/cuda/
cuda_best_split_finder.cu:776): take a leaf's histogram, produce each
feature's best (gain, threshold, missing-direction, left stats) in one
launch.  The XLA formulation (ops/split.py best_split) builds [C, F, B]
gain tensors through several fused-but-separate HBM-bound ops.  This
kernel does the whole scan in VMEM:
cumulative sums by triangular matmul (exact for counts, ~2^-26 relative
for g/h via the three-digit bf16 split), gain evaluation, and per-feature
argmax, emitting an [F, 8] result row per feature.

Covers the BASIC numeric path (the hot one): no categorical, monotone,
path smoothing, CEGB, or extra-trees randomization — ``fused_eligible``
in ops/grower.py gates dispatch; everything else stays on best_split.
Missing-value direction handling (NaN bin counted left vs right) IS
covered, matching FindBestThresholdSequentially's two-direction scan
(src/treelearner/feature_histogram.hpp:832).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...obs.jit import instrumented_jit
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_EPS = 1e-15
_NEG = float("-inf")  # plain float: a jnp scalar would be captured as a
#                       pallas closure constant, which is rejected

# tests flip this to route the grower's fused path through interpret mode
# off-TPU (production dispatch requires a real TPU backend)
_INTERPRET = False


def _digits3(x):
    """Split f32 [1, B] into three bf16 digit rows, exact to ~26 bits
    (integers < 2^24 split exactly — counts ride this for exact cumsums)."""
    d0 = x.astype(jnp.bfloat16)
    r1 = x - d0.astype(jnp.float32)
    d1 = r1.astype(jnp.bfloat16)
    d2 = (r1 - d1.astype(jnp.float32)).astype(jnp.bfloat16)
    return d0, d1, d2


def _split_scan_kernel(
    par_ref,  # VMEM [8, bpad] f32: rows 0-2 = parent g, h, cnt on every lane
    num_ref,  # VMEM [fpad, bpad] i32: row fj = feature fj's total bins
    #           (incl. NaN bin) on every lane
    nanb_ref,  # VMEM [fpad, bpad] i32: NaN-bin index, -1 if none
    mask_ref,  # VMEM [fpad, bpad] f32: feature mask (col sampling /
    #            interaction)
    hist_ref,  # VMEM [3, F * bpad] f32 (g, h, count — plane-major)
    tri_ref,  # VMEM [bpad, bpad] bf16: tri[j, i] = (j <= i)
    out_ref,  # VMEM [fpad, 128] f32: per-feature
    #          (gain, bin, dl, lg, lh, lc, 0...) rows
    *,
    f: int,
    bpad: int,
    l1: float,
    l2: float,
    min_data: int,
    min_hess: float,
):
    # The per-leaf / per-feature scalars ride as lane-broadcast VMEM rows,
    # not SMEM: the grower calls this kernel under jax.vmap (both children
    # of a split, all 2K children of a batched step), and the Pallas
    # batching rule turns a whole-array SMEM operand into a (squeezed, n)
    # block that the Mosaic lowering rejects.  Every use below is an
    # elementwise op against a [1, bpad] row, so a pre-broadcast row gives
    # bit-identical results to the scalar it replaces.
    pg = par_ref[0:1, :]
    ph = par_ref[1:2, :]
    pc = par_ref[2:3, :]
    iota_l = lax.broadcasted_iota(jnp.int32, (1, bpad), 1)
    iota_f32 = iota_l.astype(jnp.float32)
    iota_o = lax.broadcasted_iota(jnp.int32, (1, 128), 1)
    out_ref[...] = jnp.zeros_like(out_ref)

    def leaf_gain(g, h):
        if l1 > 0.0:
            t = jnp.where(g > l1, g - l1, jnp.where(g < -l1, g + l1, 0.0))
        else:
            t = g
        return (t * t) / (h + l2 + _EPS)

    for fj in range(f):
        sl = slice(fj * bpad, (fj + 1) * bpad)
        gb = hist_ref[0:1, sl]  # [1, bpad] f32
        hb = hist_ref[1:2, sl]
        cb = hist_ref[2:3, sl]
        nb = nanb_ref[fj : fj + 1, :]  # [1, bpad], same value per lane
        nbins = num_ref[fj : fj + 1, :]
        fm = mask_ref[fj : fj + 1, :]

        # NaN-bin stats out, ordered cumsum over the rest (split.py:148-158)
        is_nan = (iota_l == nb).astype(jnp.float32)  # nb = -1 matches nothing
        nan_g = jnp.sum(gb * is_nan)
        nan_h = jnp.sum(hb * is_nan)
        nan_c = jnp.sum(cb * is_nan)
        keep = 1.0 - is_nan
        rows = []
        for x in (gb * keep, hb * keep, cb * keep):
            rows.extend(_digits3(x))
        digits = jnp.concatenate(
            rows + [jnp.zeros((7, bpad), jnp.bfloat16)], axis=0
        )  # [16, bpad]
        cum = lax.dot_general(
            digits, tri_ref[...],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [16, bpad] inclusive cumsums of the digit rows
        cg = cum[0:1] + cum[1:2] + cum[2:3]
        ch = cum[3:4] + cum[4:5] + cum[5:6]
        cc = cum[6:7] + cum[7:8] + cum[8:9]

        # candidate validity: threshold t in [0, num_ordered - 2]
        has_nan = 1 - ((nb >> 31) & 1)  # i32 0/1 row
        num_ordered = nbins - has_nan
        base_ok = ((iota_l < num_ordered - 1).astype(jnp.float32)) * fm

        def dir_gain(lg_v, lh_v, lc_v, extra_ok):
            rg, rh, rc = pg - lg_v, ph - lh_v, pc - lc_v
            ok = (
                base_ok * extra_ok
                * (lc_v >= min_data).astype(jnp.float32)
                * (rc >= min_data).astype(jnp.float32)
                * (lh_v >= min_hess).astype(jnp.float32)
                * (rh >= min_hess).astype(jnp.float32)
            )
            gain = leaf_gain(lg_v, lh_v) + leaf_gain(rg, rh)
            return jnp.where(ok > 0.5, gain, _NEG)

        gain_r = dir_gain(cg, ch, cc, 1.0)  # missing -> right
        gain_l = dir_gain(
            cg + nan_g, ch + nan_h, cc + nan_c,
            has_nan.astype(jnp.float32),  # only distinct with a NaN bin
        )

        m_r = jnp.max(gain_r)
        m_l = jnp.max(gain_l)
        # strictly-greater: ties keep missing->right, matching best_split's
        # case-major argmax order (case 0 = right first)
        go_left = m_l > m_r
        best_gain = jnp.maximum(m_r, m_l)
        cb_vec = jnp.broadcast_to(go_left, gain_r.shape)
        gwin = jnp.where(cb_vec, gain_l, gain_r)
        # first bin achieving the max (ties -> lowest bin, as in argmax)
        bin_f = jnp.min(jnp.where(gwin == best_gain, iota_f32, float(bpad)))
        onehot = (iota_f32 == bin_f).astype(jnp.float32)
        lg_vec = jnp.where(cb_vec, cg + nan_g, cg)
        lh_vec = jnp.where(cb_vec, ch + nan_h, ch)
        lc_vec = jnp.where(cb_vec, cc + nan_c, cc)
        lg_w = jnp.sum(lg_vec * onehot)
        lh_w = jnp.sum(lh_vec * onehot)
        lc_w = jnp.sum(lc_vec * onehot)

        # within-feature runner-up over BOTH directions (winner's (dir, bin)
        # excluded) — the grower's near-tie margin combines this with the
        # other features' best rows (fused_best_split)
        glose = jnp.where(cb_vec, gain_r, gain_l)
        sec = jnp.maximum(
            jnp.max(jnp.where(onehot > 0.0, _NEG, gwin)), jnp.max(glose)
        )

        row = jnp.where(iota_o == 0, best_gain, 0.0)
        row = jnp.where(iota_o == 1, bin_f, row)
        row = jnp.where(iota_o == 2, go_left.astype(jnp.float32), row)
        row = jnp.where(iota_o == 3, lg_w, row)
        row = jnp.where(iota_o == 4, lh_w, row)
        row = jnp.where(iota_o == 5, lc_w, row)
        row = jnp.where(iota_o == 6, sec, row)
        out_ref[fj, :] = row[0, :]


@functools.partial(
    instrumented_jit,
    static_argnames=(
        "f", "num_bins_pad", "l1", "l2", "min_data", "min_hess", "interpret"
    ),
)
def split_scan_pallas(
    hist: jnp.ndarray,  # [3, F, B] f32 leaf histogram (g, h, count planes)
    parent: jnp.ndarray,  # [3] f32 (g, h, cnt)
    num_bins: jnp.ndarray,  # [F] i32
    nan_bins: jnp.ndarray,  # [F] i32
    feature_mask: jnp.ndarray,  # [F] bool/f32
    *,
    f: int,
    num_bins_pad: int,
    l1: float,
    l2: float,
    min_data: int,
    min_hess: float,
    interpret: bool = False,
) -> jnp.ndarray:
    """Per-feature best numeric split rows [F, 8]:
    (gain, bin, default_left, left_g, left_h, left_cnt, second_gain, 0)."""
    bpad = (max(num_bins_pad, 1) + 127) // 128 * 128
    b = hist.shape[2]
    if b < bpad:
        hist = jnp.pad(hist, ((0, 0), (0, 0), (0, bpad - b)))
    h3 = hist.reshape(3, f * bpad).astype(jnp.float32)
    fpad = max(8, -(-f // 8) * 8)
    tri = jnp.tril(jnp.ones((bpad, bpad), jnp.bfloat16)).T  # tri[j,i] = j<=i

    def rows(x, n_rows, dtype):
        """[n] per-row values -> lane-broadcast [n_rows, bpad] VMEM table."""
        x = x.astype(dtype)
        x = jnp.pad(x, (0, n_rows - x.shape[0]))
        return jnp.broadcast_to(x[:, None], (n_rows, bpad))

    kernel = functools.partial(
        _split_scan_kernel, f=f, bpad=bpad, l1=float(l1), l2=float(l2),
        min_data=int(min_data), min_hess=float(min_hess),
    )
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        kernel,
        grid=(1,),
        in_specs=[vmem] * 6,
        out_specs=vmem,
        out_shape=jax.ShapeDtypeStruct((fpad, 128), jnp.float32),
        interpret=interpret,
    )(
        rows(parent, 8, jnp.float32),
        rows(num_bins, fpad, jnp.int32),
        rows(nan_bins, fpad, jnp.int32),
        rows(feature_mask, fpad, jnp.float32),
        h3,
        tri,
    )
    return out[:f, :8]


def fused_best_split(
    hist, parent_g, parent_h, parent_cnt, num_bins, nan_bins, feature_mask,
    *,
    lambda_l1: float,
    lambda_l2: float,
    min_data_in_leaf: int,
    min_sum_hessian_in_leaf: float,
    min_gain_to_split: float,
    feature_contri=None,
    interpret: bool = False,
    with_margin: bool = False,
):
    """best_split (basic numeric path) backed by the Pallas scan kernel;
    ``hist`` is best_split's [3, F, B] planes and feeds the kernel as is.

    Returns the same SplitCandidate best_split would for configurations
    fused_eligible() admits (tie order differs only on exact cross-feature
    float-gain ties).

    ``feature_contri`` ([F] f32): per-feature gain multipliers (reference
    FeatureMetainfo::penalty) — applied OUTSIDE the kernel to the
    per-feature improvement rows before the cross-feature argmax, mirroring
    best_split's penalized path.

    ``with_margin``: also return the relative gain gap between the winner
    and the global runner-up (other features' best rows + the winning
    feature's in-kernel second-best, row col 6) — the int8-default
    histogram path's near-tie trigger (non-finite gains -> +inf margin,
    i.e. nothing to refine)."""
    from ..split import SplitCandidate, leaf_gain

    _, f, b = hist.shape
    rows = split_scan_pallas(
        hist,
        jnp.stack([
            jnp.asarray(parent_g, jnp.float32),
            jnp.asarray(parent_h, jnp.float32),
            jnp.asarray(parent_cnt, jnp.float32),
        ]),
        num_bins, nan_bins, feature_mask,
        f=f, num_bins_pad=b, l1=lambda_l1, l2=lambda_l2,
        min_data=min_data_in_leaf, min_hess=min_sum_hessian_in_leaf,
        interpret=interpret,
    )
    gains = rows[:, 0]
    parent_gain = leaf_gain(
        jnp.asarray(parent_g, jnp.float32), jnp.asarray(parent_h, jnp.float32),
        lambda_l1, lambda_l2,
    )
    if feature_contri is not None:
        imp_f = gains - parent_gain - min_gain_to_split
        scaled = jnp.where(
            jnp.isfinite(gains),
            imp_f * feature_contri.astype(jnp.float32),
            -jnp.inf,
        )
        feat = jnp.argmax(scaled).astype(jnp.int32)
        r = rows[feat]
        improvement = scaled[feat]
    else:
        feat = jnp.argmax(gains).astype(jnp.int32)
        r = rows[feat]
        improvement = r[0] - parent_gain - min_gain_to_split
    improvement = jnp.where(jnp.isfinite(r[0]), improvement, -jnp.inf)
    if with_margin:
        # global runner-up gain: best of the OTHER features vs the winning
        # feature's own second-best (kernel row col 6); the parent/min_gain
        # offset cancels in (best - second) so raw gains suffice
        other = jnp.max(
            jnp.where(
                jnp.arange(f, dtype=jnp.int32) == feat, -jnp.inf, gains
            )
        ) if f > 1 else jnp.float32(-jnp.inf)
        sec = jnp.maximum(other, r[6])
        margin = jnp.where(
            jnp.isfinite(r[0]) & jnp.isfinite(sec),
            (r[0] - sec) / jnp.maximum(jnp.abs(r[0]), _EPS),
            jnp.inf,
        ).astype(jnp.float32)
    cand = SplitCandidate(
        gain=improvement.astype(jnp.float32),
        feature=feat,
        bin=r[1].astype(jnp.int32),
        default_left=r[2] > 0.5,
        left_g=r[3],
        left_h=r[4],
        left_cnt=r[5],
        right_g=parent_g - r[3],
        right_h=parent_h - r[4],
        right_cnt=parent_cnt - r[5],
        is_cat=jnp.asarray(False),
        cat_mask=jnp.zeros((1,), bool),
    )
    return (cand, margin) if with_margin else cand
