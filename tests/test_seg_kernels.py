"""Oracle tests for the segment-resident layout (ops/pallas/seg.py) and the
sort-based partition (ops/segpart.py).

Reference semantics under test: DataPartition::Split (stable partition,
src/treelearner/data_partition.hpp:101) and DenseBin::ConstructHistogram
(src/io/dense_bin.hpp:99), via a NumPy oracle.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from lightgbm_tpu.ops.histogram import leaf_histogram_segment
from lightgbm_tpu.ops.pallas.seg import (
    pack_rows,
    padded_rows,
    seg_hist,
    unpack_stats,
)
from lightgbm_tpu.ops.segpart import (
    leaf_id_from_seg,
    leaf_of_positions,
    sort_partition,
)


@pytest.fixture(scope="module")
def packed():
    rng = np.random.default_rng(7)
    f, n = 11, 5000
    n_pad = padded_rows(n)
    bins = rng.integers(0, 256, size=(n, f)).astype(np.int32)
    g = rng.normal(size=n).astype(np.float32)
    h = rng.random(n).astype(np.float32) + 0.5
    m = (rng.random(n) < 0.8).astype(np.float32)
    seg = pack_rows(
        jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h), jnp.asarray(m), n_pad
    )  # PLANE-MAJOR [LANES, n_pad]
    catmask = (rng.random(256) < 0.5).astype(np.float32)
    return dict(
        f=f, n=n, n_pad=n_pad, bins=bins, g=g, h=h, m=m,
        seg=seg, segnp=np.asarray(seg), catmask=catmask,
    )


def test_pack_unpack_roundtrip(packed):
    p = packed
    b2, g2, h2, m2, r2 = unpack_stats(p["seg"], p["f"], n=p["n"])
    assert np.array_equal(np.asarray(b2), p["bins"])
    assert np.array_equal(np.asarray(g2), p["g"])  # exact f32 bit transport
    assert np.array_equal(np.asarray(h2), p["h"])
    assert np.array_equal(np.asarray(m2), p["m"])
    assert np.array_equal(np.asarray(r2), np.arange(p["n"]))


def _np_partition(segnp, f, sb, cnt, feat, tbin, dl, nanb, iscat, catmask):
    rows = segnp[:, sb : sb + cnt].T  # [cnt, LANES]
    packedcol = rows[:, feat // 2].view(np.uint16).astype(np.int64)
    colv = (packedcol >> (8 * (feat % 2))) & 0xFF
    if iscat:
        gl = (catmask[np.clip(colv, 0, len(catmask) - 1)] > 0.5) & (
            colv < len(catmask)
        )
    else:
        gl = (colv <= tbin) | ((dl != 0) & (nanb >= 0) & (colv == nanb))
    return rows[gl], rows[~gl]


@pytest.mark.parametrize(
    "sb,cnt,feat,tbin,dl,nanb,iscat",
    [
        (0, 5000, 3, 120, 0, -1, 0),  # root
        (17, 3000, 5, 80, 1, 200, 0),  # unaligned begin, NaN default-left
        (1000, 37, 2, 128, 0, -1, 0),  # tiny segment
        (513, 1029, 7, 30, 0, -1, 1),  # categorical
        (5, 600, 1, 255, 0, -1, 0),  # all-left
        (9, 600, 1, -1, 0, -1, 0),  # all-right
        (4000, 1000, 10, 100, 0, -1, 0),  # tail of the array
    ],
)
def test_sort_partition_vs_oracle(packed, sb, cnt, feat, tbin, dl, nanb, iscat):
    p = packed
    seg1, nl, nr = sort_partition(
        p["seg"], jnp.int32(sb), jnp.int32(cnt), jnp.int32(feat),
        jnp.int32(tbin), jnp.int32(dl), jnp.int32(nanb), jnp.int32(iscat),
        jnp.asarray(p["catmask"]), f=p["f"], n_pad=p["n_pad"],
    )
    nl, nr = int(nl), int(nr)
    expL, expR = _np_partition(
        p["segnp"], p["f"], sb, cnt, feat, tbin, dl, nanb, iscat, p["catmask"]
    )
    assert (nl, nr) == (len(expL), len(expR))
    got = np.asarray(seg1)
    assert np.array_equal(got[:, sb : sb + nl].T, expL)  # stable left
    assert np.array_equal(got[:, sb + nl : sb + cnt].T, expR)  # stable right
    assert np.array_equal(got[:, :sb], p["segnp"][:, :sb])  # neighbors
    assert np.array_equal(got[:, sb + cnt :], p["segnp"][:, sb + cnt :])


@pytest.mark.parametrize("st,cnt", [(0, 5000), (17, 3000), (513, 1029), (1000, 37)])
def test_seg_hist_vs_oracle(packed, st, cnt):
    p = packed
    hs = seg_hist(
        p["seg"], jnp.asarray([st, cnt], jnp.int32),
        f=p["f"], num_bins=256, n_pad=p["n_pad"],
    )
    bo, go, ho, mo, _ = unpack_stats(p["seg"][:, st : st + cnt], p["f"])
    ref = leaf_histogram_segment(bo, go, ho, mo, 256)
    d = np.abs(np.asarray(hs) - np.asarray(ref)).max()
    rel = d / max(1e-9, np.abs(np.asarray(ref)).max())
    assert rel < 5e-6  # three-term bf16 split: ~26-bit addends (r3)


@pytest.mark.parametrize("st,cnt", [(0, 5000), (17, 3000), (1000, 37)])
def test_seg_hist_pallas_kernel_interpret(packed, st, cnt):
    """Exercise the actual Pallas kernel body (DMA tiling, in-VMEM transpose,
    bf16 hi/lo split) in interpret mode — off-TPU the `seg_hist` dispatcher
    would otherwise route to the same reference impl the oracle uses."""
    from lightgbm_tpu.ops.pallas.seg import seg_hist_pallas

    p = packed
    hs = seg_hist_pallas(
        p["seg"], jnp.asarray([st, cnt], jnp.int32),
        f=p["f"], num_bins=256, n_pad=p["n_pad"], interpret=True,
    )
    bo, go, ho, mo, _ = unpack_stats(p["seg"][:, st : st + cnt], p["f"])
    ref = leaf_histogram_segment(bo, go, ho, mo, 256)
    d = np.abs(np.asarray(hs) - np.asarray(ref)).max()
    rel = d / max(1e-9, np.abs(np.asarray(ref)).max())
    assert rel < 5e-6  # three-term bf16 split: ~26-bit addends (r3)


def test_leaf_mapping_roundtrip(packed):
    n = packed["n"]
    rng = np.random.default_rng(3)
    Lb = jnp.asarray([0, 1200, 700, 0], jnp.int32)
    Lr = jnp.asarray([700, n - 1200, 500, 0], jnp.int32)
    lp = np.asarray(leaf_of_positions(Lb, Lr, jnp.int32(3), n))
    assert (lp[:700] == 0).all()
    assert (lp[700:1200] == 2).all()
    assert (lp[1200:] == 1).all()
    perm = rng.permutation(n).astype(np.int32)
    lid = np.asarray(leaf_id_from_seg(jnp.asarray(perm), jnp.asarray(lp)))
    exp = np.empty(n, np.int32)
    exp[perm] = lp
    assert np.array_equal(lid, exp)


def test_seg_hist_int8_quantized_exact(packed):
    """Quantized-gradient int8 variant: grid multiples accumulate EXACTLY
    in i32 (gradient_discretizer.cpp grid), so the kernel must match the
    f32 oracle bit-for-bit at these magnitudes."""
    from lightgbm_tpu.ops.pallas.seg import seg_hist_pallas

    p = packed
    rng = np.random.default_rng(13)
    gs, hs = np.float32(0.037), np.float32(0.0021)
    kq = rng.integers(-63, 64, size=p["n"]).astype(np.float32)
    hq = rng.integers(0, 64, size=p["n"]).astype(np.float32)
    seg = pack_rows(
        jnp.asarray(p["bins"]),
        jnp.asarray(kq * gs),
        jnp.asarray(hq * hs),
        jnp.asarray(p["m"]),
        p["n_pad"],
    )
    hs_out = seg_hist_pallas(
        seg, jnp.asarray([17, 3000], jnp.int32),
        jnp.asarray([gs, hs], jnp.float32),
        f=p["f"], num_bins=256, n_pad=p["n_pad"],
        quantized=True, interpret=True,
    )
    bo, go, ho, mo, _ = unpack_stats(seg[:, 17 : 17 + 3000], p["f"])
    ref = leaf_histogram_segment(bo, go, ho, mo, 256)
    got = np.asarray(hs_out)
    # counts exact; g/h equal to the integer sums times the scales
    assert np.array_equal(got[2], np.asarray(ref)[2])
    assert np.allclose(got, np.asarray(ref), rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# wide (u16) bin planes — max_bin > 256 (reference DenseBin<uint16_t>,
# src/io/dense_bin.hpp:18)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def packed_wide():
    rng = np.random.default_rng(17)
    f, n, b = 5, 3000, 1000
    n_pad = padded_rows(n)
    bins = rng.integers(0, b, size=(n, f)).astype(np.int32)
    g = rng.normal(size=n).astype(np.float32)
    h = rng.random(n).astype(np.float32) + 0.5
    m = (rng.random(n) < 0.8).astype(np.float32)
    seg = pack_rows(
        jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h), jnp.asarray(m),
        n_pad, wide=True,
    )
    catmask = (rng.random(b) < 0.5).astype(np.float32)
    return dict(
        f=f, n=n, b=b, n_pad=n_pad, bins=bins, g=g, h=h, m=m,
        seg=seg, segnp=np.asarray(seg), catmask=catmask,
    )


def test_wide_pack_unpack_roundtrip(packed_wide):
    p = packed_wide
    b2, g2, h2, m2, r2 = unpack_stats(p["seg"], p["f"], n=p["n"], wide=True)
    assert np.array_equal(np.asarray(b2), p["bins"])
    assert np.array_equal(np.asarray(g2), p["g"])
    assert np.array_equal(np.asarray(h2), p["h"])
    assert np.array_equal(np.asarray(m2), p["m"])
    assert np.array_equal(np.asarray(r2), np.arange(p["n"]))


def _np_partition_wide(segnp, sb, cnt, feat, tbin, dl, nanb, iscat, catmask):
    rows = segnp[:, sb : sb + cnt].T  # [cnt, LANES]
    colv = rows[:, feat].view(np.uint16).astype(np.int64)
    if iscat:
        gl = (catmask[np.clip(colv, 0, len(catmask) - 1)] > 0.5) & (
            colv < len(catmask)
        )
    else:
        gl = (colv <= tbin) | ((dl != 0) & (nanb >= 0) & (colv == nanb))
    return rows[gl], rows[~gl]


@pytest.mark.parametrize(
    "sb,cnt,feat,tbin,dl,nanb,iscat",
    [
        (0, 3000, 3, 500, 0, -1, 0),  # root, threshold past 256
        (17, 2000, 1, 700, 1, 900, 0),  # unaligned, NaN bin > 256
        (513, 777, 2, 300, 0, -1, 1),  # categorical, wide mask
        (100, 500, 0, 90, 0, -1, 0),  # low threshold
    ],
)
def test_wide_sort_partition_vs_oracle(
    packed_wide, sb, cnt, feat, tbin, dl, nanb, iscat
):
    p = packed_wide
    seg1, nl, nr = sort_partition(
        p["seg"], jnp.int32(sb), jnp.int32(cnt), jnp.int32(feat),
        jnp.int32(tbin), jnp.int32(dl), jnp.int32(nanb), jnp.int32(iscat),
        jnp.asarray(p["catmask"]), f=p["f"], n_pad=p["n_pad"], wide=True,
    )
    nl, nr = int(nl), int(nr)
    expL, expR = _np_partition_wide(
        p["segnp"], sb, cnt, feat, tbin, dl, nanb, iscat, p["catmask"]
    )
    assert (nl, nr) == (len(expL), len(expR))
    got = np.asarray(seg1)
    assert np.array_equal(got[:, sb : sb + nl].T, expL)
    assert np.array_equal(got[:, sb + nl : sb + cnt].T, expR)
    assert np.array_equal(got[:, :sb], p["segnp"][:, :sb])
    assert np.array_equal(got[:, sb + cnt :], p["segnp"][:, sb + cnt :])


@pytest.mark.parametrize("st,cnt", [(0, 3000), (17, 2000)])
def test_wide_seg_hist_vs_oracle(packed_wide, st, cnt):
    p = packed_wide
    hs = seg_hist(
        p["seg"], jnp.asarray([st, cnt], jnp.int32),
        f=p["f"], num_bins=p["b"], n_pad=p["n_pad"], wide=True,
    )
    bo, go, ho, mo, _ = unpack_stats(
        p["seg"][:, st : st + cnt], p["f"], wide=True
    )
    ref = leaf_histogram_segment(bo, go, ho, mo, p["b"])
    d = np.abs(np.asarray(hs) - np.asarray(ref)).max()
    rel = d / max(1e-9, np.abs(np.asarray(ref)).max())
    assert rel < 5e-6


def test_wide_seg_hist_pallas_kernel_interpret(packed_wide):
    from lightgbm_tpu.ops.pallas.seg import seg_hist_pallas

    p = packed_wide
    st, cnt = 17, 1500
    hs = seg_hist_pallas(
        p["seg"], jnp.asarray([st, cnt], jnp.int32),
        f=p["f"], num_bins=p["b"], n_pad=p["n_pad"], wide=True,
        interpret=True,
    )
    bo, go, ho, mo, _ = unpack_stats(
        p["seg"][:, st : st + cnt], p["f"], wide=True
    )
    ref = leaf_histogram_segment(bo, go, ho, mo, p["b"])
    d = np.abs(np.asarray(hs) - np.asarray(ref)).max()
    rel = d / max(1e-9, np.abs(np.asarray(ref)).max())
    assert rel < 5e-6


def test_wide_partition_kernel_interpret(packed_wide):
    """The Pallas streaming partition on wide planes must match the XLA
    sort path bit-for-bit (the byte-split one-hot compaction is content
    agnostic; only the key extraction reads u16)."""
    from lightgbm_tpu.ops.pallas.partition import seg_partition_pallas
    from lightgbm_tpu.ops.segpart import sort_partition_xla

    p = packed_wide
    sb, cnt, feat, tbin = 17, 2000, 1, 700
    bm = len(p["catmask"])
    bmt = max(256, -(-bm // 128) * 128)
    catm = jnp.zeros((1, bmt), jnp.float32).at[0, :bm].set(
        jnp.asarray(p["catmask"])
    )
    scal = jnp.asarray([sb, cnt, feat, tbin, 1, 900, 0, 0], jnp.int32)
    got, nl_k = seg_partition_pallas(
        p["seg"], scal, catm, f=p["f"], n_pad=p["n_pad"], use_cat=True,
        wide=True, interpret=True,
    )
    want, nl_s, _ = sort_partition_xla(
        p["seg"], jnp.int32(sb), jnp.int32(cnt), jnp.int32(feat),
        jnp.int32(tbin), jnp.int32(1), jnp.int32(900), jnp.int32(0),
        jnp.asarray(p["catmask"]), f=p["f"], n_pad=p["n_pad"], wide=True,
    )
    assert int(nl_k) == int(nl_s)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_wide_grow_tree_matches_ordered():
    """End-to-end: a seg-mode tree at max_bin=1024 equals the ordered-mode
    tree (same splits, same leaf values)."""
    from lightgbm_tpu.ops.grower import GrowerParams, grow_tree

    rng = np.random.default_rng(23)
    n, f, b = 4000, 4, 1024
    bins = rng.integers(0, b, size=(n, f)).astype(np.int32)
    grad = rng.normal(size=n).astype(np.float32)
    hess = (rng.random(n).astype(np.float32) + 0.5)
    num_bins = jnp.full((f,), b, jnp.int32)
    nan_bins = jnp.full((f,), -1, jnp.int32)
    trees = {}
    for mode in ("seg", "ordered"):
        params = GrowerParams(
            num_leaves=15, max_bin=b, min_data_in_leaf=5,
            min_sum_hessian_in_leaf=0.0, lambda_l2=0.1, hist_mode=mode,
        )
        tree, leaf_id = grow_tree(
            jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
            jnp.ones(n, jnp.float32), num_bins, nan_bins,
            jnp.ones(f, bool), params,
        )
        trees[mode] = (tree, np.asarray(leaf_id))
    ts, tord = trees["seg"][0], trees["ordered"][0]
    assert int(ts.num_leaves) == int(tord.num_leaves)
    np.testing.assert_array_equal(
        np.asarray(ts.split_feature), np.asarray(tord.split_feature)
    )
    np.testing.assert_array_equal(
        np.asarray(ts.split_bin), np.asarray(tord.split_bin)
    )
    np.testing.assert_allclose(
        np.asarray(ts.leaf_value), np.asarray(tord.leaf_value), rtol=1e-5,
        atol=1e-7,
    )
    np.testing.assert_array_equal(trees["seg"][1], trees["ordered"][1])


def test_seg_vmem_gate(monkeypatch):
    from lightgbm_tpu.ops.pallas.seg import seg_vmem_ok

    assert seg_vmem_ok(28, 256)  # the bench config always fits
    assert seg_vmem_ok(121, 1024)  # wide, moderate
    # plane-tiled grid (histogram engine v2): the accumulator/one-hot
    # scratch is sized per feature-GROUP, not per full feature set, so the
    # old 18 MB full-F accumulator shape now fits comfortably
    assert seg_vmem_ok(100, 4096)
    assert not seg_vmem_ok(121, 65536)
    assert not seg_vmem_ok(4, 65536, has_cat=True)  # cat one-hot blows up
    # the partition's block-prefetch scratch is counted beside the
    # histogram's (both live in the fused grow step): the largest admitted
    # shape — every one of the 128 planes, the widest histogram group that
    # fits — still passes with it, and a budget one byte under their sum
    # refuses it
    from lightgbm_tpu.ops.pallas.partition import (
        T, block_tiles, partition_scratch_bytes,
    )
    from lightgbm_tpu.ops.pallas import seg

    assert seg_vmem_ok(121, 8192, has_cat=True)
    assert seg_vmem_ok(242, 256, has_cat=True)
    part = partition_scratch_bytes(128)
    assert 2 * 128 * block_tiles(128) * T * 2 < part < seg.SEG_VMEM_BUDGET // 4
    # two staging slots, the H = 1 operands ([32 | 8192, TILE]), the
    # accumulators, the raw output block and the loop's temporaries
    hist_alone = seg.hist_scratch_bytes(121, 8192, seg.hist_sub(121, True))
    assert hist_alone > 2 * 128 * seg.TILE * 2 + 8192 * seg.TILE * 2
    assert hist_alone + part <= seg.SEG_VMEM_BUDGET
    monkeypatch.setattr(seg, "SEG_VMEM_BUDGET", hist_alone + part)
    assert seg_vmem_ok(121, 8192)
    monkeypatch.setattr(seg, "SEG_VMEM_BUDGET", hist_alone + part - 1)
    assert not seg_vmem_ok(121, 8192)


def test_wide_seg_hist_int8_quantized(packed_wide):
    """wide (u16) planes + int8 grid accumulation together: counts exact,
    g/h equal to integer sums times the grid scales."""
    from lightgbm_tpu.ops.pallas.seg import seg_hist_pallas

    p = packed_wide
    rng = np.random.default_rng(29)
    gs, hs = np.float32(0.041), np.float32(0.003)
    kq = rng.integers(-63, 64, size=p["n"]).astype(np.float32)
    hq = rng.integers(0, 64, size=p["n"]).astype(np.float32)
    seg = pack_rows(
        jnp.asarray(p["bins"]), jnp.asarray(kq * gs), jnp.asarray(hq * hs),
        jnp.asarray(p["m"]), p["n_pad"], wide=True,
    )
    out = seg_hist_pallas(
        seg, jnp.asarray([17, 1500], jnp.int32),
        jnp.asarray([gs, hs], jnp.float32),
        f=p["f"], num_bins=p["b"], n_pad=p["n_pad"],
        quantized=True, wide=True, interpret=True,
    )
    bo, go, ho, mo, _ = unpack_stats(seg[:, 17:17 + 1500], p["f"], wide=True)
    ref = leaf_histogram_segment(bo, go, ho, mo, p["b"])
    got = np.asarray(out)
    assert np.array_equal(got[2], np.asarray(ref)[2])
    assert np.allclose(got, np.asarray(ref), rtol=1e-6, atol=1e-6)


def test_seg_hist_pallas_batch_interpret(packed):
    """K-program batched histogram launch == K serial kernel results,
    including a zero-cnt member (all-zero histogram)."""
    from lightgbm_tpu.ops.pallas.seg import seg_hist_pallas, seg_hist_pallas_batch

    p = packed
    windows = [(0, 1500), (1500, 1000), (2500, 0), (2600, 2400)]
    scal_k = jnp.asarray(windows, jnp.int32)
    got = seg_hist_pallas_batch(
        p["seg"], scal_k, f=p["f"], num_bins=256, n_pad=p["n_pad"],
        interpret=True,
    )
    assert got.shape[0] == len(windows)
    for i, (st, cnt) in enumerate(windows):
        want = seg_hist_pallas(
            p["seg"], jnp.asarray([st, cnt], jnp.int32),
            f=p["f"], num_bins=256, n_pad=p["n_pad"], interpret=True,
        )
        np.testing.assert_array_equal(np.asarray(got[i]), np.asarray(want))


def test_seg_hist_batch_dispatch_cpu(packed):
    """Off-TPU dispatch: seg_hist_batch == vmapped serial seg_hist."""
    from lightgbm_tpu.ops.pallas.seg import seg_hist_batch

    p = packed
    windows = [(0, 2000), (2000, 3000)]
    scal_k = jnp.asarray(windows, jnp.int32)
    got = seg_hist_batch(
        p["seg"], scal_k, f=p["f"], num_bins=256, n_pad=p["n_pad"]
    )
    for i, (st, cnt) in enumerate(windows):
        want = seg_hist(
            p["seg"], jnp.asarray([st, cnt], jnp.int32),
            f=p["f"], num_bins=256, n_pad=p["n_pad"],
        )
        np.testing.assert_array_equal(np.asarray(got[i]), np.asarray(want))


def test_seg_hist_int8_default_error_bound(packed):
    """int8-by-default accumulation on TRUE f32 gradients: per-bin error is
    bounded by the grid's rounding budget (cnt * scale / 2 per stat — each
    row contributes at most half a quantization step; the i32 digit sums
    themselves are exact)."""
    from lightgbm_tpu.ops.pallas.seg import seg_hist_pallas
    from lightgbm_tpu.ops.quantize import hist_acc_scales

    p = packed
    gs, hs = hist_acc_scales(
        jnp.asarray(p["g"]), jnp.asarray(p["h"]), jnp.asarray(p["m"])
    )
    got = np.asarray(seg_hist_pallas(
        p["seg"], jnp.asarray([17, 3000], jnp.int32),
        jnp.stack([gs, hs]),
        f=p["f"], num_bins=256, n_pad=p["n_pad"],
        quantized=True, interpret=True,
    ))
    bo, go, ho, mo, _ = unpack_stats(p["seg"][:, 17:17 + 3000], p["f"])
    ref = np.asarray(leaf_histogram_segment(bo, go, ho, mo, 256))
    cnt = ref[2]
    assert np.array_equal(got[2], cnt)  # counts are exact
    assert (np.abs(got[0] - ref[0]) <= 0.5 * float(gs) * cnt + 1e-6).all()
    assert (np.abs(got[1] - ref[1]) <= 0.5 * float(hs) * cnt + 1e-6).all()


def test_seg_hist_live_plane_skip_interpret(packed):
    """Dead plane groups under ``live`` come back all-zero while live
    groups are untouched; group 0 carries the totals so the grower always
    forces it live."""
    from lightgbm_tpu.ops.pallas.seg import (
        hist_bpad, hist_group, hist_ngroups, seg_hist_pallas,
    )

    p = packed
    bpad = hist_bpad(256)
    gb = hist_group(p["f"], bpad)
    ng = hist_ngroups(p["f"], bpad)
    assert ng > 1  # 11 features at bpad 256 -> 2 groups of 8
    full = np.asarray(seg_hist_pallas(
        p["seg"], jnp.asarray([17, 3000], jnp.int32),
        f=p["f"], num_bins=256, n_pad=p["n_pad"], interpret=True,
    ))
    live = jnp.zeros((ng,), jnp.int32).at[0].set(1)
    got = np.asarray(seg_hist_pallas(
        p["seg"], jnp.asarray([17, 3000], jnp.int32), live=live,
        f=p["f"], num_bins=256, n_pad=p["n_pad"], interpret=True,
    ))
    np.testing.assert_array_equal(got[:, :gb], full[:, :gb])  # live group intact
    assert (got[:, gb:] == 0.0).all()  # dead group fully skipped
    all_live = np.asarray(seg_hist_pallas(
        p["seg"], jnp.asarray([17, 3000], jnp.int32),
        live=jnp.ones((ng,), jnp.int32),
        f=p["f"], num_bins=256, n_pad=p["n_pad"], interpret=True,
    ))
    np.testing.assert_array_equal(all_live, full)


@pytest.fixture(scope="module")
def packed_big():
    """Above the CPU windowing threshold (32*TILE rows)."""
    rng = np.random.default_rng(41)
    f, n = 3, 40000
    n_pad = padded_rows(n)
    bins = rng.integers(0, 256, size=(n, f)).astype(np.int32)
    g = rng.normal(size=n).astype(np.float32)
    h = rng.random(n).astype(np.float32) + 0.5
    m = (rng.random(n) < 0.8).astype(np.float32)
    seg = pack_rows(
        jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h), jnp.asarray(m),
        n_pad,
    )
    return dict(f=f, n=n, n_pad=n_pad, seg=seg)


@pytest.mark.parametrize("st,cnt", [(0, 40000), (7000, 300), (33000, 6500)])
def test_seg_hist_cpu_windowed_parity(packed_big, st, cnt):
    """The capacity-bucketed windowed CPU pass == the full masked pass for
    aligned and unaligned windows across capacity rungs."""
    from lightgbm_tpu.ops.pallas.seg import (
        _CPU_WINDOW_ROWS, seg_hist_ref,
    )

    p = packed_big
    assert p["n_pad"] > _CPU_WINDOW_ROWS
    scal = jnp.asarray([st, cnt], jnp.int32)
    got = seg_hist(
        p["seg"], scal, f=p["f"], num_bins=256, n_pad=p["n_pad"]
    )
    want = seg_hist_ref(
        p["seg"], scal, f=p["f"], num_bins=256, n_pad=p["n_pad"]
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-4
    )
    # counts must be exact (integral sums of the same values)
    np.testing.assert_array_equal(
        np.asarray(got)[2], np.asarray(want)[2]
    )


def test_seg_hist_batch_cpu_windowed(packed_big):
    """Batched off-TPU dispatch above the windowing threshold: per-member
    capacity buckets (python loop) == serial windowed calls."""
    from lightgbm_tpu.ops.pallas.seg import seg_hist_batch

    p = packed_big
    windows = [(0, 30000), (30000, 0), (31000, 5000)]
    scal_k = jnp.asarray(windows, jnp.int32)
    got = seg_hist_batch(
        p["seg"], scal_k, f=p["f"], num_bins=256, n_pad=p["n_pad"]
    )
    for i, (st, cnt) in enumerate(windows):
        want = seg_hist(
            p["seg"], jnp.asarray([st, cnt], jnp.int32),
            f=p["f"], num_bins=256, n_pad=p["n_pad"],
        )
        np.testing.assert_array_equal(np.asarray(got[i]), np.asarray(want))


# ---------------------------------------------------------------------------
# the two-digit one-hot (bin = hi * L + lo) against the full one-hot (H = 1)
# and the masked reference
# ---------------------------------------------------------------------------

# whole table, off a 128-column boundary, empty, short
_WINDOWS = [(0, 1500), (133, 513), (700, 0), (1000, 37)]


def _long_windows(step):
    """Windows that cross from the kernel's long steps into its TILE-row tail
    and sit on its edges (``step`` = ``seg.hist_step``): one row short of a
    long step (the tail loop alone), a long step and 5 rows, a start off a
    128-column boundary, three long steps and a ragged tail, a start off a
    boundary with ``off + cnt`` an exact multiple of the step (no tail, so no
    read past the last long step), empty, short, exactly one aligned step."""
    return [(0, step - 1), (5, step), (130, step + 1), (77, 3 * step + 37),
            (393, 2 * step - 9), (700, 0), (3000, 37), (128, step)]


def _case_windows(rows, f, num_bins, wide=False):
    """(windows, table rows, index of the window the K = 1 grid takes):
    ``rows`` "short" is the 1,500-row table every window of which runs the
    TILE-row loop alone; "long" a table of three long steps and more."""
    from lightgbm_tpu.ops.pallas import seg

    if rows == "short":
        return _WINDOWS, 1500, 1
    step = seg.hist_step(f, seg.hist_bpad(num_bins), seg.hist_sub(f, wide))
    assert step > seg.TILE  # else the case would not reach the long loop
    return _long_windows(step), 3 * step + 600, 3


def _hist_case(f, num_bins, wide=False, n=1500, seed=3):
    rng = np.random.default_rng(seed + f)
    n_pad = padded_rows(n)
    bins = rng.integers(0, num_bins, size=(n, f)).astype(np.int32)
    g = rng.normal(size=n).astype(np.float32)
    h = rng.random(n).astype(np.float32) * 0.24 + 0.01
    m = (rng.random(n) < 0.8).astype(np.float32)
    return pack_rows(
        jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h), jnp.asarray(m),
        n_pad, wide=wide,
    ), n_pad


def _both_forms(full_onehot, seg, n_pad, f, num_bins, quantized, wide=False,
                windows=_WINDOWS, alone=1):
    """(two-digit, H = 1) results of a grid over ``windows``, and of a K=1
    grid over window ``alone`` with the last plane group dead.  The H = 1
    form runs TILE-row steps alone: the kernel as it was before the long
    step."""
    from lightgbm_tpu.ops.pallas.seg import (
        QMAX, hist_bpad, hist_ngroups, seg_hist_pallas_batch,
    )

    scal = jnp.asarray(windows, jnp.int32)
    scales = jnp.asarray([5.0 / QMAX, 0.25 / QMAX], jnp.float32)
    ng = hist_ngroups(f, hist_bpad(num_bins))
    live = jnp.ones((ng,), jnp.int32).at[ng - 1].set(int(ng == 1))
    kw = dict(f=f, num_bins=num_bins, n_pad=n_pad, quantized=quantized,
              wide=wide, interpret=True)

    def run():
        return [np.asarray(a) for a in (
            seg_hist_pallas_batch(seg, scal, scales, **kw),
            seg_hist_pallas_batch(seg, scal[alone:alone + 1], scales, live,
                                  **kw),
        )]

    got = run()
    with full_onehot():
        want = run()
    return got, want


def _assert_forms_agree(got, want, quantized):
    for a, b in zip(got, want):
        if quantized:
            np.testing.assert_array_equal(a, b)  # integer sums
        else:
            np.testing.assert_allclose(a, b, rtol=0, atol=1e-6 * np.abs(b).max())


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("f,num_bins,rows", [
    (1, 256, "short"), (7, 256, "short"), (8, 256, "short"),
    (28, 256, "short"), (67, 256, "short"),
    (1, 128, "short"), (7, 100, "short"), (8, 128, "short"),
    (28, 128, "short"), (67, 127, "short"),
    (7, 256, "long"), (28, 256, "long"), (11, 128, "long"),
])
def test_two_digit_onehot_equals_full_onehot_and_reference(
        f, num_bins, rows, quantized, full_onehot):
    """bpad 256 -> (8, 32) and bpad 128 -> (4, 32): F = 1, 7 (a feature block
    that is not full), 8, 28 (a last program of 4 features), 67 (of 3);
    K = 4 and K = 1; a window off a 128-column boundary, one of cnt = 0; a
    dead plane group.  "long": windows on every edge of a long step
    (``_long_windows``), K = 8, against the H = 1 form, which keeps the
    TILE-row loop alone.  int8 sums are integers and equal exactly."""
    from lightgbm_tpu.ops.pallas.seg import (
        hist_bpad, hist_digits, hist_group, hist_ngroups, seg_hist_ref,
    )

    bpad = hist_bpad(num_bins)
    assert hist_digits(bpad) == {256: (8, 32), 128: (4, 32)}[bpad]
    windows, n, alone = _case_windows(rows, f, num_bins)
    seg, n_pad = _hist_case(f, num_bins, n=n)
    got, want = _both_forms(full_onehot, seg, n_pad, f, num_bins, quantized,
                            windows=windows, alone=alone)
    _assert_forms_agree(got, want, quantized)
    for i, (st, cnt) in enumerate(windows):
        ref = np.asarray(seg_hist_ref(
            seg, jnp.asarray([st, cnt], jnp.int32), f=f, num_bins=num_bins,
            n_pad=n_pad))
        np.testing.assert_array_equal(got[0][i][2], ref[2])
        if not quantized:  # three-term bf16 split: ~26-bit addends
            assert np.abs(got[0][i] - ref).max() <= 5e-6 * max(
                1e-9, np.abs(ref).max())
    # K = 1 is the grid's member: the dead group's features zero, the live whole
    ng, gb = hist_ngroups(f, bpad), hist_group(f, bpad)
    live_f = gb * (ng - 1) if ng > 1 else f
    np.testing.assert_array_equal(
        got[1][0][:, :live_f], got[0][alone][:, :live_f])
    assert (got[1][0][:, live_f:] == 0).all()


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("f,num_bins,digits,rows", [
    (9, 500, (8, 64), "short"), (4, 700, (12, 64), "short"),
    (5, 1000, (16, 64), "short"), (3, 1100, (9, 128), "short"),
    (3, 2000, (16, 128), "short"), (3, 4000, (1, 4096), "short"),
    (5, 1000, (16, 64), "long"), (3, 2000, (16, 128), "long"),
])
def test_wide_two_digit_onehot_equals_full_onehot(f, num_bins, digits, rows,
                                                   quantized, full_onehot):
    """u16 bin planes: the widths the factoring takes (a high digit of up
    to 16 values) and the one past them, which IS the full one-hot; "long":
    the long step's edges at bpad 1024 and 2048."""
    from lightgbm_tpu.ops.pallas.seg import hist_bpad, hist_digits, seg_hist_ref

    assert hist_digits(hist_bpad(num_bins)) == digits
    windows, n, alone = _case_windows(rows, f, num_bins, wide=True)
    seg, n_pad = _hist_case(f, num_bins, wide=True, n=n)
    got, want = _both_forms(full_onehot, seg, n_pad, f, num_bins, quantized,
                            wide=True, windows=windows, alone=alone)
    _assert_forms_agree(got, want, quantized)
    for i in (1, 3):
        ref = np.asarray(seg_hist_ref(
            seg, jnp.asarray(windows[i], jnp.int32), f=f, num_bins=num_bins,
            n_pad=n_pad, wide=True))
        np.testing.assert_array_equal(got[0][i][2], ref[2])
        if not quantized:
            assert np.abs(got[0][i] - ref).max() <= 5e-6 * np.abs(ref).max()


@pytest.mark.parametrize("bpad,digits,block", [
    (128, (4, 32), 4), (256, (8, 32), 2), (384, (6, 64), 2), (512, (8, 64), 2),
    (640, (10, 64), 1), (1024, (16, 64), 1), (2048, (16, 128), 1),
    (2176, (1, 2176), 1), (8192, (1, 8192), 1),
])
def test_hist_digits_come_from_bpad_alone(bpad, digits, block):
    """(8, 32), the swept winner, at the cells' bpad 256; H = 1 (the full one-hot) where the
    high digit's 8 * H rows would pass the MXU's 128; every width factors
    exactly and a feature's rows never straddle an int8 tile."""
    from lightgbm_tpu.ops.pallas import seg

    assert seg.hist_digits(bpad) == digits
    assert seg.hist_feature_block(100, bpad) == block
    high, low = digits
    assert high * low == bpad and 8 * high <= seg.MXU_ROWS
    nblk, arows, brows = seg.hist_operands(100, bpad)
    assert nblk * block >= seg.hist_group(100, bpad) > (nblk - 1) * block
    assert brows == block * low
    assert arows == (32 if high == 1 else seg.MXU_ROWS)
    assert high == 1 or block * seg._digit_rows(bpad) <= seg.MXU_ROWS


# every shape tools/aot_check.py compiles a histogram caller at: the six
# cells' (28 and 67 one-group, 2,000 grouped), bpad 128, u16 widths, grouped
# int8 and u16, the widest one-group row, and two H = 1 widths
@pytest.mark.parametrize("f,num_bins,wide,step", [
    (28, 256, False, 4096), (67, 256, False, 4096), (2000, 256, False, 4096),
    (28, 127, False, 2048), (242, 256, False, 2048), (500, 256, False, 4096),
    (4, 1024, True, 4096), (9, 1000, True, 4096), (200, 512, True, 4096),
    (3, 2000, True, 4096), (83, 256, False, 2048), (3, 4000, True, 512),
    (121, 8192, True, 512),
])
def test_hist_step_comes_from_static_shapes(f, num_bins, wide, step):
    """STEP is the largest of 8, 4, 2 and 1 TILE whose scratch fits the
    budget beside the partition's: 4096 at the cells' shapes, 2048 at bpad
    128 and past 48 planes a tile, TILE (the kernel as it was) where the
    form is the full one-hot; the scratch the callers allocate is that
    wide, and ``seg_vmem_ok`` reckons from the same function."""
    from lightgbm_tpu.ops.pallas import seg
    from lightgbm_tpu.ops.pallas.partition import partition_scratch_bytes

    bpad = seg.hist_bpad(num_bins)
    grouped = seg.plane_groups(f, wide) > 1
    sub = seg.hist_sub(f, wide, grouped)
    assert seg.hist_step(f, bpad, sub) == step
    # a DMA is a static slice of the packed matrix: no step is longer than it
    for n in (300, 1500, 3000, 5000):
        n_pad = seg.padded_rows(n)
        bounded = seg.hist_step(f, bpad, sub, n_pad)
        assert bounded <= max(seg.TILE, min(step, n_pad))
        assert bounded == step or 2 * bounded > n_pad or bounded == seg.TILE
    assert (step == seg.TILE) == (seg.hist_digits(bpad)[0] == 1)
    refs = seg.hist_scratch(f, bpad, sub, quantized=True, grouped=grouped,
                            step=step)
    assert [r.shape[-1] for r in refs[:4]] == [step] * 4
    assert seg.hist_scratch_bytes(f, bpad, sub) == seg.hist_scratch_bytes(
        f, bpad, sub, step)
    assert (seg.hist_scratch_bytes(f, bpad, sub)
            + partition_scratch_bytes(seg.LANES) <= seg.SEG_VMEM_BUDGET)
    assert seg.seg_vmem_ok(f, num_bins)
    if seg.TILE < step < 8 * seg.TILE:  # the next candidate does not fit
        assert (seg.hist_scratch_bytes(f, bpad, sub, 2 * step)
                + partition_scratch_bytes(seg.LANES) > seg.SEG_VMEM_BUDGET)
