"""Oracle tests for the Pallas streaming partition kernel
(ops/pallas/partition.py) against the stable-sort partition it replaces.

The kernel must be BIT-IDENTICAL to ops/segpart.sort_partition (both are
stable partitions of the same window), including untouched neighbors.
Reference semantics: DataPartition::Split (src/treelearner/data_partition.hpp:101).
"""

import numpy as np
import pytest
import jax.numpy as jnp

from lightgbm_tpu.ops.pallas.partition import (
    T,
    partition_sub,
    seg_partition_pallas,
    window_block_tiles,
)
from lightgbm_tpu.ops.pallas.seg import pack_rows, padded_rows
from lightgbm_tpu.ops.segpart import sort_partition_xla


# (features, rows, wide): sub = 16 and 24 at the original 5000 rows, then the
# widths that set the DMA block (partition.block_tiles): sub = 8, 48, 128
# with at least two blocks of rows, and u16 (wide) planes
@pytest.fixture(
    scope="module",
    params=[(11, 5000, False), (28, 5000, False), (2, 9500, False),
            (67, 5500, False), (242, 3500, False), (5, 5000, True)],
    ids=lambda p: f"f{p[0]}{'w' if p[2] else ''}",
)
def packed(request):
    rng = np.random.default_rng(7)
    f, n, wide = request.param
    nb = 1024 if wide else 256
    n_pad = padded_rows(n)
    bins = rng.integers(0, nb, size=(n, f)).astype(np.int32)
    g = rng.normal(size=n).astype(np.float32)
    h = rng.random(n).astype(np.float32) + 0.5
    m = (rng.random(n) < 0.8).astype(np.float32)
    seg = pack_rows(
        jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h), jnp.asarray(m),
        n_pad, wide=wide,
    )
    catmask = (rng.random(nb) < 0.5).astype(np.float32)
    return dict(f=f, n=n, n_pad=n_pad, seg=seg, catmask=catmask, wide=wide,
                nb=nb, B=window_block_tiles(partition_sub(f, wide), n_pad) * T)


def _cols(p, expr):
    """A window coordinate: an int, or an expression in the fixture's DMA
    block columns B, the tile T and the rows N (clamped by the caller)."""
    if isinstance(expr, str):
        return int(eval(expr, {"B": p["B"], "T": T, "N": p["n"]}))
    return expr


@pytest.mark.parametrize(
    "sb,cnt,feat,tbin,dl,nanb,iscat",
    [
        (0, 5000, 3, 120, 0, -1, 0),  # root, multi-tile
        (17, 3000, 5, 80, 1, 200, 0),  # unaligned begin, NaN default-left
        (1000, 37, 2, 128, 0, -1, 0),  # tiny segment within one tile
        (513, 1029, 7, 30, 0, -1, 1),  # categorical
        (5, 600, 1, 255, 0, -1, 0),  # all-left
        (9, 600, 1, -1, 0, -1, 0),  # all-right
        (4000, 1000, 10, 100, 0, -1, 0),  # tail of the array
        (130, 255, 4, 100, 0, -1, 0),  # offset > 128 alignment fold
        (333, 0, 0, 10, 0, -1, 0),  # empty window (done step)
        (256, 512, 6, 100, 0, -1, 0),  # exactly tile-aligned window
        # --- the block-prefetching tile loop (PR 28) ---
        (0, "N", 1, 120, 0, -1, 0),  # every block of the array
        (77, 1, 0, 100, 0, -1, 0),  # one row
        (77, "T - 1", 0, 100, 0, -1, 0),
        (77, "T", 0, 100, 0, -1, 0),  # T rows over two tiles
        (128, "T", 1, 100, 0, -1, 0),  # window base on a COL_ALIGN, off a T
        (0, "B - 1", 1, 90, 0, -1, 0),  # one block less a row
        (0, "B", 1, 90, 0, -1, 0),  # exactly one block
        (0, "B + 1", 1, 90, 0, -1, 0),  # one row into the second block
        (130, "B - 1", 0, 140, 0, -1, 0),  # the same, begin inside a tile
        (130, "B", 0, 140, 0, -1, 0),
        (130, "B + 1", 0, 140, 0, -1, 0),
        ("B - 3", "B + 7", 1, 128, 0, -1, 0),  # begins at a block's end
        ("B + 128", "B", 0, 60, 0, -1, 1),  # blocks off the array's T grid
        (9, "2 * B", 1, 100000, 0, -1, 0),  # all-left: no right stream
        (9, "2 * B", 1, -1, 0, -1, 0),  # all-right: no left stream
        (1, "2 * B + T", 0, 128, 1, 7, 0),  # ends inside a third block
    ],
)
def test_partition_kernel_matches_sort(packed, sb, cnt, feat, tbin, dl, nanb, iscat):
    p = packed
    sb = min(_cols(p, sb), p["n"])
    cnt = min(_cols(p, cnt), p["n"] - sb)
    feat = feat % p["f"]
    catm = jnp.asarray(p["catmask"]).reshape(1, p["nb"])
    scal = jnp.asarray([sb, cnt, feat, tbin, dl, nanb, iscat, 0], jnp.int32)
    got, nl_k = seg_partition_pallas(
        p["seg"], scal, catm, f=p["f"], n_pad=p["n_pad"],
        use_cat=True, wide=p["wide"], interpret=True,
    )
    want, nl_s, _ = sort_partition_xla(
        p["seg"], jnp.int32(sb), jnp.int32(cnt), jnp.int32(feat),
        jnp.int32(tbin), jnp.int32(dl), jnp.int32(nanb), jnp.int32(iscat),
        jnp.asarray(p["catmask"]), f=p["f"], n_pad=p["n_pad"], wide=p["wide"],
    )
    assert int(nl_k) == int(nl_s)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_partition_kernel_sequential_tree_stress():
    """Drive the kernel through a leaf-wise tree's partition SEQUENCE
    (windows shrink and nest, state carries forward) and require bit-equal
    state vs the sort path after every step — errors would compound."""
    rng = np.random.default_rng(42)
    f, n = 14, 20000
    n_pad = padded_rows(n)
    bins = rng.integers(0, 256, size=(n, f)).astype(np.int32)
    g = rng.normal(size=n).astype(np.float32)
    h = np.ones(n, np.float32)
    m = np.ones(n, np.float32)
    seg_k = pack_rows(
        jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h), jnp.asarray(m), n_pad
    )
    seg_s = seg_k
    catm = jnp.asarray(np.zeros(256, np.float32)).reshape(1, 256)
    # maintain (begin, cnt) segments like the grower does
    segments = [(0, n)]
    for step in range(12):
        # split the largest segment on a pseudo-random feature/threshold
        segments.sort(key=lambda t: -t[1])
        sb, cnt = segments.pop(0)
        if cnt < 2:
            break
        feat = int(rng.integers(0, f))
        tbin = int(rng.integers(20, 236))
        scal = jnp.asarray([sb, cnt, feat, tbin, 0, -1, 0, 0], jnp.int32)
        seg_k, nl_k = seg_partition_pallas(
            seg_k, scal, catm, f=f, n_pad=n_pad, use_cat=False, interpret=True
        )
        seg_s, nl_s, _ = sort_partition_xla(
            seg_s, jnp.int32(sb), jnp.int32(cnt), jnp.int32(feat),
            jnp.int32(tbin), jnp.int32(0), jnp.int32(-1), jnp.int32(0),
            jnp.zeros((1,), jnp.float32), f=f, n_pad=n_pad,
        )
        assert int(nl_k) == int(nl_s), f"step {step}: nl {nl_k} != {nl_s}"
        assert np.array_equal(np.asarray(seg_k), np.asarray(seg_s)), (
            f"state diverged at step {step}"
        )
        nl = int(nl_k)
        segments += [(sb, nl), (sb + nl, cnt - nl)]


def test_partition_kernel_gl_vec_matches_sort():
    """Bits-fed kernel variant (feature-parallel seg): partitioning by a
    precomputed go-left vector must be bit-identical to the column-reading
    sort path given the same bits."""
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(11)
    f, n = 9, 40_000
    n_pad = padded_rows(n)
    bins = rng.integers(0, 256, size=(n, f)).astype(np.int32)
    g = rng.normal(size=n).astype(np.float32)
    h = np.ones(n, np.float32)
    m = np.ones(n, np.float32)
    seg = pack_rows(
        jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h), jnp.asarray(m),
        n_pad,
    )
    # sub = 16 here: a DMA block is 4096 columns, and the bits ride in
    # blocks of their own beside the rows
    for sb, cnt, feat, tbin in (
        (0, n, 3, 120), (137, 7000, 5, 40), (4095, 4098, 1, 99),
        (130, 4096, 2, 255), (77, 1, 0, 10),
    ):
        colv = np.zeros(n_pad, np.int64)
        colv[:n] = bins[:, feat]
        glv = jnp.asarray((colv <= tbin).astype(np.float32))
        catm = jnp.zeros((1, 256), jnp.float32)
        scal = jnp.asarray([sb, cnt, feat, tbin, 0, -1, 0, 0], jnp.int32)
        got, nl_k = seg_partition_pallas(
            seg, scal, catm, glv, f=f, n_pad=n_pad, use_cat=False,
            interpret=True,
        )
        want, nl_s, _ = sort_partition_xla(
            seg, jnp.int32(sb), jnp.int32(cnt), jnp.int32(feat),
            jnp.int32(tbin), jnp.int32(0), jnp.int32(-1), jnp.int32(0),
            jnp.zeros((1,), jnp.float32), f=f, n_pad=n_pad,
        )
        assert int(nl_k) == int(nl_s)
        assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize(
    "rows",
    [
        # disjoint windows incl. a zero-cnt member and a categorical member
        [
            (0, 1200, 3, 120, 0, -1, 0, 0),
            (1200, 800, 5, 80, 1, 200, 0, 0),
            (2000, 0, 0, 10, 0, -1, 0, 0),  # no-op
            (2500, 1500, 7, 30, 0, -1, 1, 0),  # categorical
        ],
        # adjacent windows that share COL_ALIGN blocks, the first longer
        # than a DMA block (4096 columns at this width): every program
        # re-reads a boundary block the one before it rewrote, and the
        # second's first block starts inside the first's last tile
        [
            (0, 4100, 3, 120, 0, -1, 0, 0),
            (4100, 1, 5, 80, 0, -1, 0, 0),
            (4101, 255, 2, 128, 0, -1, 1, 0),
            (4356, 644, 7, 30, 0, -1, 0, 0),
        ],
    ],
    ids=["disjoint", "adjacent-over-a-block"],
)
def test_partition_kernel_batch_matches_serial_loop(rows):
    """K-program batched launch over DISJOINT windows == K serial kernel
    calls (bit-equal state), including zero-cnt no-op members."""
    from lightgbm_tpu.ops.pallas.partition import seg_partition_pallas_batch

    rng = np.random.default_rng(9)
    f, n = 11, 5000
    n_pad = padded_rows(n)
    bins = rng.integers(0, 256, size=(n, f)).astype(np.int32)
    g = rng.normal(size=n).astype(np.float32)
    h = np.ones(n, np.float32)
    m = np.ones(n, np.float32)
    seg = pack_rows(
        jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h), jnp.asarray(m), n_pad
    )
    catmask = (rng.random(256) < 0.5).astype(np.float32)
    scal = jnp.asarray(rows, jnp.int32)
    catm = jnp.broadcast_to(jnp.asarray(catmask), (4, 256))
    got, nl_b = seg_partition_pallas_batch(
        seg, scal, catm, f=f, n_pad=n_pad, use_cat=True, interpret=True,
    )
    want = seg
    nls = []
    for r in rows:
        want, nl, _ = sort_partition_xla(
            want, *(jnp.int32(v) for v in r[:7]),
            jnp.asarray(catmask), f=f, n_pad=n_pad,
        )
        nls.append(int(nl))
    assert [int(v) for v in nl_b] == nls
    assert np.array_equal(np.asarray(got), np.asarray(want))


def _aliasing_case():
    """Batched K=2 case where the windows are adjacent and the second
    window's aligned DMA base falls INSIDE the first window: program 1
    re-reads the shared COL_ALIGN boundary block that program 0's
    partition already rewrote."""
    from lightgbm_tpu.ops.pallas.seg import COL_ALIGN

    rng = np.random.default_rng(21)
    f, n = 9, 2000
    n_pad = padded_rows(n)
    bins = rng.integers(0, 256, size=(n, f)).astype(np.int32)
    g = rng.normal(size=n).astype(np.float32)
    seg = pack_rows(
        jnp.asarray(bins), jnp.asarray(g), jnp.ones((n,), jnp.float32),
        jnp.ones((n,), jnp.float32), n_pad,
    )
    # window 0 ends mid-block at 900 (900 % 128 != 0), window 1 begins
    # there: its aligned DMA base (896) re-reads the tail block window 0
    # rewrote
    assert 900 % COL_ALIGN != 0
    rows = [
        (0, 900, 3, 120, 0, -1, 0, 0),
        (900, 1100, 5, 80, 0, -1, 0, 0),
    ]
    return seg, rows, f, n_pad


def test_batch_aliased_boundary_reads_are_correct():
    """Adjacent windows sharing a COL_ALIGN block: the batched kernel must
    equal the sequential sort oracle (program 1 sees program 0's writes)."""
    seg, rows, f, n_pad = _aliasing_case()
    from lightgbm_tpu.ops.pallas.partition import seg_partition_pallas_batch

    scal = jnp.asarray(rows, jnp.int32)
    catm = jnp.zeros((2, 256), jnp.float32)
    got, nl_b = seg_partition_pallas_batch(
        seg, scal, catm, f=f, n_pad=n_pad, use_cat=False, interpret=True,
    )
    want = seg
    nls = []
    for r in rows:
        want, nl, _ = sort_partition_xla(
            want, *(jnp.int32(v) for v in r[:7]),
            jnp.zeros((1,), jnp.float32), f=f, n_pad=n_pad,
        )
        nls.append(int(nl))
    assert [int(v) for v in nl_b] == nls
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_read_via_input_recreates_aliasing_bug():
    """Regression guard for aliased_tile_dma: reading boundary tiles
    through the INPUT ref of the input/output-aliased seg matrix (the
    PR-3 bug) makes interpret mode serve stale pre-partition data to the
    second program — this test FAILS (i.e. the outputs differ) if someone
    reverts the helper to input-ref reads.  If this test ever starts
    asserting equality, the read_via_input knob has stopped modelling the
    bug and both it and this test should be removed together."""
    seg, rows, f, n_pad = _aliasing_case()
    from lightgbm_tpu.ops.pallas.partition import seg_partition_pallas_batch

    scal = jnp.asarray(rows, jnp.int32)
    catm = jnp.zeros((2, 256), jnp.float32)
    good, _ = seg_partition_pallas_batch(
        seg, scal, catm, f=f, n_pad=n_pad, use_cat=False, interpret=True,
    )
    bad, _ = seg_partition_pallas_batch(
        seg, scal, catm, f=f, n_pad=n_pad, use_cat=False, interpret=True,
        read_via_input=True,
    )
    assert not np.array_equal(np.asarray(bad), np.asarray(good)), (
        "read_via_input=True no longer corrupts the shared boundary block; "
        "the aliasing regression knob is not exercising the bug path"
    )


def test_fused_step_aliased_boundary_reads_are_correct():
    """Same aliasing hazard through the FUSED grow-step kernel, which
    re-reads partitioned tiles in its own histogram phase on top of the
    program-to-program boundary: adjacent windows must still match the
    oracle partition state and split decisions bit-for-bit (histogram is
    bf16-vs-f32, compared at kernel tolerance)."""
    from lightgbm_tpu.ops.pallas.grow_step import fused_grow_step_pallas
    from lightgbm_tpu.ops.pallas.grow_step import fused_grow_step
    from lightgbm_tpu.ops.pallas.seg import hist_bpad, hist_ngroups

    seg, rows, f, n_pad = _aliasing_case()
    scal = jnp.asarray(rows, jnp.int32)
    catm = jnp.zeros((2, 256), jnp.float32)
    ones = jnp.ones((2,), jnp.float32)
    live = jnp.ones((hist_ngroups(f, hist_bpad(256)),), jnp.int32)
    seg_k, dec, hist = fused_grow_step_pallas(
        seg, scal, catm, ones, live, f=f, num_bins=256, n_pad=n_pad,
        use_cat=False, interpret=True,
    )
    args = tuple(
        jnp.asarray([rows[0][j], rows[1][j]], jnp.int32) for j in range(7)
    )
    want = fused_grow_step(
        seg, *args, jnp.zeros((2, 1), jnp.float32),
        f=f, num_bins=256, n_pad=n_pad,
    )
    assert np.array_equal(np.asarray(seg_k), np.asarray(want[0]))
    assert np.array_equal(np.asarray(dec[:, 0]), np.asarray(want[1]))  # nl
    np.testing.assert_allclose(
        np.asarray(hist), np.asarray(want[5]), rtol=1e-3, atol=1e-3
    )
    # the input-ref read corrupts this kernel the same way
    seg_bad, _, _ = fused_grow_step_pallas(
        seg, scal, catm, ones, live, f=f, num_bins=256, n_pad=n_pad,
        use_cat=False, interpret=True, read_via_input=True,
    )
    assert not np.array_equal(np.asarray(seg_bad), np.asarray(seg_k))
