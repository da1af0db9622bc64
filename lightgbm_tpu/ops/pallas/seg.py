"""Segment-resident training layout + Pallas histogram over packed rows.

Reference analogs: ``DataPartition`` (src/treelearner/data_partition.hpp — an
index-array indirection over row-major bins) and
``DenseBin::ConstructHistogramInner`` (src/io/dense_bin.hpp:99).

Why this exists: XLA's random gather/scatter on TPU lowers to a serialized
per-element loop (~30-55 ns/element measured on v5e — 0.1-2 GB/s effective),
so the reference's "index array + gather ordered_gradients" formulation is
2-3 orders of magnitude off HBM roofline on TPU.  The TPU-native answer is to
keep the training rows PHYSICALLY in leaf-segment order, so that:

  * the per-split partition is a stable sort of the parent's contiguous
    window by the 2-bit go-left key (XLA's TPU sort moves ~170 MB/ms — the
    full 11-payload row sorts at ~6 ns/row, measured), implemented in
    ops/segpart.py as pure XLA;
  * the histogram of any leaf is one contiguous DMA stream over the packed
    rows — the kernel below — with zero gathers.

Storage layout: one PLANE-MAJOR i16 matrix ``[storage_lanes(F), n_pad]``
(used planes rounded to a 32-sublane tile) — plane p, data-row r.  Planes
[0, ceil(F/2)) hold bins byte-packed two features per plane (feature j
lives in byte j&1 of plane j>>1); then 7 stat planes: g_lo16, g_hi16,
h_lo16, h_hi16 (the EXACT f32 bit patterns of grad/hess split into 16-bit
halves — no precision loss), mask (0/1), ridx_lo, ridx_hi (original row
index, for the final segment-order -> row-order inverse permutation).

Rows wider than ``LANES`` planes (more than 242 byte-binned or 121 u16
features) are stored as G PLANE GROUPS, ``[G, sub, n_pad]`` with ``sub`` <=
128 a multiple of 16 (``group_shape``): plane p of the row is
``[p // sub, p % sub]``, the groups share one row order, and the array's
rank is how every consumer tells the two forms apart — G comes from the
column count and the bin width alone.  A group is what ONE pass of the
partition kernel moves (its VMEM blocks hold at most 128 planes); the
kernel runs once a group, every group permuted by the same go-left bits
(``segpart.go_left_bits``).  The 7 stat planes live ONCE, in a 16-plane
block that starts at the first multiple of 16 past the bin planes
(``stat_lanes(..., grouped=True)``): the partition moves every plane alike,
so it needs them nowhere in particular; the histogram reads them beside
each feature group's bin planes as one aligned 16-plane DMA, whichever
group that block falls in; a copy a group would cost 7 planes x G of HBM
and of every partition pass and buy nothing.

Plane-major is the layout XLA itself assigns this loop-carried matrix (the
sort-partition reads whole planes); storing it that way keeps every consumer
layout-native — the row-major alternative made XLA insert TWO full-array
relayout copies per split (~0.3 ms each at 1M rows, measured).  The
histogram kernel DMAs [sub, T] column tiles covering only the used planes
(minor-dim starts 128-aligned, misalignment folded into the validity mask)
and works on them as they arrive, rows on the lanes: nothing is transposed.

Histogram engine v2 (this file's kernel contract):

  * The kernel grid is PLANE-TILED: ``(K, G)`` where K is the frontier
    batch and G = ceil(F / group) is the number of feature-plane groups.
    Each program accumulates ONE group's [8, group*bpad] block, so the
    per-program VMEM scratch is O(group*bpad) instead of O(F*bpad) — wide
    (F, max_bin) shapes that previously failed ``seg_vmem_ok`` now fit.
    The trade: every program re-streams the window's stat planes (G-fold
    redundant DMA traffic); the read of tile t+1 runs under the compute of
    tile t (two staging slots), so it costs nothing while a tile's compute
    outlasts its DMA (0.22 us against 0.61 on the v5e, PERF.md section 5).
  * The one-hot is TWO-DIGIT.  With (H, L) = ``hist_digits(bpad)`` — from
    ``bpad`` alone, (8, 32) at the usual bpad 256 — a bin is
    ``b = hi * L + lo`` and

        hist[s, j, b] = sum_r stats[s, r] * [bin_j(r) = b]
                      = sum_r (stats[s, r] * [hi_j(r) = hi]) * [lo_j(r) = lo]

    so a tile builds, for a block of nf = ``hist_feature_block`` features,
    ``A[(j, hi, s), r]`` (the 8 stat rows masked by each high digit: 128
    rows) and ``B[(j', lo), r]`` (the low digits' one-hots: nf * L rows) and
    issues ONE ``A . B^T`` over the tile's rows.  The MXU sees 128
    accumulator rows instead of 8 and the VPU builds ``L + 8 H`` elements a
    feature and row instead of ``bpad``.  The result's diagonal blocks
    j = j' are the nf histograms, laid out [(hi, s), lo]; the off-diagonal
    blocks are joint counts of two DIFFERENT features, accumulated with the
    rest and never read.  The whole result accumulates in VMEM across the
    window's tiles; the diagonal blocks are copied into the raw output
    block once a program.  The sums are the same addends as a full
    one-hot's — the same bf16 | int8 terms times exact 0/1 masks, added in
    f32 | i32 over the same rows in the same order, zeros elsewhere — and
    the raw planes came out bit-identical to the full one-hot kernel's on
    the chip, both dtypes (PERF.md section 6, PR 30).  ``H = 1`` (bpad past
    2048, where 8 H would pass the MXU's 128 rows) is the full one-hot
    itself through the same code: ``A`` is the 8 stat rows, every result
    block diagonal.
  * The window is walked in TWO LOOPS over one body (PR 38).  A 512-row
    tile-program costs about 0.62 us on the v5e, of which 0.17 is paid a
    step whatever its width: the DMA's descriptor and wait (a DMA of any
    width takes 0.23 us alone), the plane dispatch, the MXU's fill and
    drain, the pop and the add into the accumulators.  So where the
    window's aligned span allows, a step takes ``STEP`` rows
    (``hist_step``: the largest of 8, 4, 2 and 1 ``TILE`` that the packed
    matrix is long enough for and whose scratch fits ``SEG_VMEM_BUDGET``
    beside the partition's, from static shapes alone — 4096 at the usual
    bpad 256 — and ``TILE`` itself, i.e. no long loop, where the form is
    the full one-hot): ONE DMA, one dispatch, one matmul a feature
    block contracting ``STEP`` rows, one add into ``acc``.  The rest of
    the span, under ``STEP`` rows, runs ``TILE``-row steps in the first
    ``TILE`` columns of the same scratch.  The tail exists because
    ``padded_rows``, the CPU path's window ladder and every window shorter
    than ``STEP`` (most windows of a wide, short table) rest on the 512-row
    tile: no step reads a column the ``TILE``-row loop alone would not.
    The read-ahead carries across the loops.  The f32 partial sums are
    cut every ``STEP`` rows in the long loop where they were cut every
    512; integer sums do not depend on the cut (the raw planes are the
    parent kernel's bit for bit in int8, and within 1e-6 of an
    accumulator's sum of absolute addends in bf16: PERF.md section 6).
  * Kernels emit RAW 8-sublane accumulator planes, ``[K, G, 8, group *
    bpad]`` (f32 for the bf16 path, i32 for the int8 paths; column
    ``j * bpad + b`` of feature j of the group); the digit-recombine/
    dequantize runs OUTSIDE the kernel in plain XLA.  8 is exactly the
    f32/i32 VMEM tile height, which retires the three GL005 sublane-3
    layouts the previous ``[3, F*bpad]`` outputs needed baselined.
  * int8 accumulation is 2-DIGIT: q = round(stat/scale) clipped to
    ±QMAX (127*128), split as q = hi*128 + lo with |hi| <= 127 and
    |lo| <= 64 — both int8-safe — accumulated as int8 x int8 -> i32 on the
    MXU and recombined outside as (S_hi*128 + S_lo)*scale.  For
    quantized-gradient training (|q| <= 127 so hi in {-1,0,1}) this is
    EXACT like the old 1-digit path; as the grower's default histogram
    accumulator ("hist_acc") it carries ~14 bits per addend (relative
    quantization step 1/16256 ~= 6e-5), and near-tie split candidates are
    re-accumulated in the bf16/f32 path before any structure decision
    (ops/grower.py near_tie_tol).
  * Dead plane groups are SKIPPED: a [G] live mask (SMEM) zeroes a
    program's tile loop, so feature_fraction / EFB-bundled workloads pay
    only for live bundles.  Group 0 is always live (the grower reads
    feature 0's row as the totals row).

Precision contract (ADVICE r2, tightened r3): the bf16 path accumulates
grad/hess as a THREE-TERM bf16 split (~26 mantissa bits per addend — i.e.
f32-accurate for all practical gradients, the extra rows ride the stat
rows' 6->8 sublane padding for free) with f32 accumulators, vs double
histograms in the reference.  Near-tie split decisions can still flip vs the f64
reference within f32 epsilon, which golden-model parity tests tolerate.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ...obs.jit import instrumented_jit
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128  # planes one partition pass moves: a plane group's cap
STAT_BLOCK = 16  # grouped rows: the stat planes' aligned block (an i16 tile)
TILE = 512  # rows per DMA tile in seg_hist
N_STAT_LANES = 7
MAX_SEG_BIN = 256  # byte-packed bins: values must fit u8 (narrow layout)
MAX_WIDE_BIN = 65536  # u16 planes (wide layout, max_bin > 256)

# 2-digit int8 quantization ceiling: q in [-QMAX, QMAX] splits as
# q = hi*128 + lo with |hi| <= 127, |lo| <= 64 — both int8-safe.
QMAX = 127 * 128

# Test hook: route the seg histogram through the Pallas interpret-mode
# kernels even off-TPU (tools/run_tests.sh int8 smoke).  Read at TRACE time,
# like grow_step._INTERPRET.  This is also the grower's signal that the
# int8-default histogram accumulator may engage off-TPU (the CPU fallback
# ignores hist_acc — its masked/windowed reference path is the byte-level
# oracle and stays f32).
_INTERPRET = False


def seg_int8_dispatch() -> bool:
    """Whether ``seg_hist`` given scales runs the integer kernels in this
    process (a TPU, or the interpret hook): elsewhere the reference path
    sums the same values in f32."""
    return jax.default_backend() == "tpu" or _INTERPRET


def bin_lanes(f: int, wide: bool = False) -> int:
    """i16 lanes holding bins: byte-packed two per plane normally, one u16
    plane per feature when max_bin > 256 (``wide`` — the reference's
    DenseBin<uint16_t> analog, src/io/dense_bin.hpp:18)."""
    return f if wide else (f + 1) // 2


def plane_groups(f: int, wide: bool = False) -> int:
    """G, the plane groups of a packed row — from the column count and the
    bin width alone.  1 while bins and stats fit ``LANES`` planes."""
    if bin_lanes(f, wide) + N_STAT_LANES <= LANES:
        return 1
    return -(-used_lanes(f, wide, grouped=True) // LANES)


def is_grouped(seg) -> bool:
    """Whether a packed matrix is in the grouped ``[G, sub, n_pad]`` form."""
    return seg.ndim == 3


def flat_planes(seg: jnp.ndarray) -> jnp.ndarray:
    """The ``[planes, n_pad]`` view of either form (a bitcast: ``sub`` is a
    multiple of the i16 sublane tile)."""
    return seg.reshape(-1, seg.shape[-1]) if is_grouped(seg) else seg


def stat_lanes(f: int, wide: bool = False, grouped: bool = False
               ) -> Tuple[int, int, int, int, int, int, int]:
    """Plane indices of (g_lo, g_hi, h_lo, h_hi, mask, ridx_lo, ridx_hi) —
    right after the bin planes; in a grouped row at the next multiple of
    ``STAT_BLOCK`` (indices into ``flat_planes``)."""
    s = bin_lanes(f, wide)
    if grouped:
        s = -(-s // STAT_BLOCK) * STAT_BLOCK
    return s, s + 1, s + 2, s + 3, s + 4, s + 5, s + 6


def used_lanes(f: int, wide: bool = False, grouped: bool = False) -> int:
    if grouped:
        return stat_lanes(f, wide, True)[0] + STAT_BLOCK
    return bin_lanes(f, wide) + N_STAT_LANES


def storage_lanes(f: int, wide: bool = False) -> int:
    """Allocated planes of a one-group row: used planes rounded to an i16
    sublane-tile multiple (32).  Storing only these — not the full 128 cap
    — cuts the segment matrix HBM footprint 4x at F=28 (2.7 GB -> 0.7 GB at
    10.5M rows)."""
    return min(LANES, -(-used_lanes(f, wide) // 32) * 32)


def group_shape(f: int, wide: bool = False, groups: int = None
                ) -> Tuple[int, int]:
    """(G, sub) of a packed row.  One group: (1, ``storage_lanes``).  More:
    the used planes dealt evenly over G groups of ``sub`` planes, ``sub`` a
    multiple of 16 (243 features: 2 x 80, 2,000: 8 x 128).  ``groups``
    overrides G (tests: two groups at a width that needs one)."""
    g = plane_groups(f, wide) if groups is None else groups
    if g == 1:
        return 1, storage_lanes(f, wide)
    used = used_lanes(f, wide, grouped=True)
    sub = -(-(-(-used // g)) // STAT_BLOCK) * STAT_BLOCK
    if sub > LANES:
        raise ValueError(f"{g} plane groups cannot hold {used} planes")
    return g, sub


COL_ALIGN = 128  # minor-dim DMA starts must be 128-lane aligned
SEG_VMEM_BUDGET = 12 * 1024 * 1024  # scratch ceiling for the seg kernels


def seg_vmem_ok(f: int, num_bins: int, has_cat: bool = False) -> bool:
    """Whether the seg kernels' VMEM scratch fits at this (F, max_bin).

    The plane-tiled grid makes the histogram footprint per program
    (``hist_scratch_bytes``: two staging slots, the two-digit one-hot's
    operands and accumulators of one feature group, the raw output block)
    independent of F.  The partition's read blocks, flush buffers and
    stagings (partition.partition_scratch_bytes, set by the planes one pass
    moves: the used planes, or one plane group's) sit beside it in the fused
    grow step.  The categorical partition additionally builds a [bmt, 256]
    one-hot (bf16), unchanged by the plane tiling, so it still binds
    wide-bin categorical configs."""
    from .partition import partition_scratch_bytes, partition_sub

    bpad = hist_bpad(num_bins)
    wide = num_bins > MAX_SEG_BIN
    grouped = plane_groups(f, wide) > 1
    hist = hist_scratch_bytes(f, bpad, hist_sub(f, wide, grouped))
    # a grouped row's partition pass moves one group, and so counts one
    sub = (
        group_shape(f, wide)[1] if grouped
        else partition_sub(f, wide)  # the used planes, to 8 not to 32
    )
    part = partition_scratch_bytes(sub)
    cat = (max(256, bpad) * 256 * 2) if has_cat else 0
    return max(hist + part, part + cat) <= SEG_VMEM_BUDGET


def padded_rows(n: int) -> int:
    """Storage rows: slack so the largest sort-partition window and the final
    column-aligned seg_hist tile stay in bounds."""
    return ((n + 2 * TILE + COL_ALIGN) + TILE - 1) // TILE * TILE


# ---------------------------------------------------------------------------
# host/XLA-side pack & unpack
# ---------------------------------------------------------------------------


def _u16(x: jnp.ndarray) -> jnp.ndarray:
    """Low 16 bits of an i32/u32 array as i16 (bit pattern preserved)."""
    return lax.bitcast_convert_type((x & 0xFFFF).astype(jnp.uint16), jnp.int16)


def pack_rows(
    bins: jnp.ndarray,  # [N, F] integer bins (values < 256, or < 65536 wide)
    grad: jnp.ndarray,  # [N] f32
    hess: jnp.ndarray,  # [N] f32
    mask: jnp.ndarray,  # [N] f32 in {0, 1}
    n_pad: int,
    wide: bool = False,
    groups: Optional[int] = None,
) -> jnp.ndarray:
    """Pack rows into the PLANE-MAJOR i16 layout (ridx = iota):
    ``[storage_lanes, n_pad]``, or ``[G, sub, n_pad]`` when the row needs
    G > 1 plane groups.  ``groups`` is for tests alone: it deals a row that
    needs one group over several, to tie the grouped form to the plain one."""
    n, f = bins.shape
    g = plane_groups(f, wide) if groups is None else groups
    grouped = g > 1
    bt = bins.T.astype(jnp.int32)  # [F, N]
    if wide:
        # one u16 plane per feature (DenseBin<uint16_t>, dense_bin.hpp:18)
        bin16 = _u16(jnp.clip(bt, 0, MAX_WIDE_BIN - 1))  # [F, N]
    else:
        # byte-packed bins: values >= 256 would bleed into the paired feature
        bt = jnp.clip(bt, 0, MAX_SEG_BIN - 1)
        if f % 2:
            bt = jnp.concatenate([bt, jnp.zeros((1, n), jnp.int32)], axis=0)
        bin16 = _u16(bt[0::2] | (bt[1::2] << 8))  # [ceil(F/2), N]
    gbits = lax.bitcast_convert_type(grad.astype(jnp.float32), jnp.uint32).astype(jnp.int32)
    hbits = lax.bitcast_convert_type(hess.astype(jnp.float32), jnp.uint32).astype(jnp.int32)
    ridx = jnp.arange(n, dtype=jnp.int32)
    planes = [
        bin16,
        # a grouped row's stat planes start their own aligned block
        jnp.zeros(
            (stat_lanes(f, wide, grouped)[0] - bin16.shape[0], n), jnp.int16
        ),
        _u16(gbits)[None, :],
        _u16(gbits >> 16)[None, :],
        _u16(hbits)[None, :],
        _u16(hbits >> 16)[None, :],
        (mask > 0).astype(jnp.int16)[None, :],
        _u16(ridx)[None, :],
        _u16(ridx >> 16)[None, :],
    ]
    packed = jnp.concatenate(planes, axis=0)
    g, sub = group_shape(f, wide, g)
    packed = jnp.pad(packed, ((0, g * sub - packed.shape[0]), (0, n_pad - n)))
    return packed.reshape(g, sub, n_pad) if grouped else packed


def _plane_u16(seg: jnp.ndarray, plane) -> jnp.ndarray:
    return seg[plane].astype(jnp.int32) & 0xFFFF


def unpack_stats(seg: jnp.ndarray, f: int, n: Optional[int] = None,
                 wide: bool = False):
    """Recover (bins[N,F] i32, g f32, h f32, mask f32, ridx i32) from the
    plane-major matrix of either form (optionally only the first n data
    rows)."""
    GLO, GHI, HLO, HHI, M, RLO, RHI = stat_lanes(f, wide, is_grouped(seg))
    seg = flat_planes(seg)
    if n is None:
        n = seg.shape[1]
    seg = seg[:, :n]
    packed = seg[: bin_lanes(f, wide)].astype(jnp.int32) & 0xFFFF  # [bl, N]
    if wide:
        bins = packed.T  # [N, F] — one u16 plane per feature
    else:
        lo = packed & 0xFF
        hi = (packed >> 8) & 0xFF
        bins = jnp.stack([lo, hi], axis=1).reshape(-1, n)[:f].T  # [N, F]
    g = lax.bitcast_convert_type(
        (_plane_u16(seg, GLO) | (_plane_u16(seg, GHI) << 16)).astype(jnp.uint32),
        jnp.float32,
    )
    h = lax.bitcast_convert_type(
        (_plane_u16(seg, HLO) | (_plane_u16(seg, HHI) << 16)).astype(jnp.uint32),
        jnp.float32,
    )
    m = seg[M].astype(jnp.float32)
    ridx = _plane_u16(seg, RLO) | (_plane_u16(seg, RHI) << 16)
    return bins, g, h, m, ridx


# ---------------------------------------------------------------------------
# seg_hist kernel — histogram of a contiguous packed-row range
# ---------------------------------------------------------------------------

_TARGET_LANES = 2048


def hist_bpad(num_bins: int) -> int:
    """Bin-axis padding (128-lane multiple) used by the hist kernels."""
    return (max(num_bins, 1) + 127) // 128 * 128


def hist_group(f: int, bpad: int) -> int:
    """Features per one-hot matmul group (bounded by the MXU lane target)."""
    return min(max(1, _TARGET_LANES // bpad), f)


def hist_ngroups(f: int, bpad: int) -> int:
    """Feature-plane groups — the second grid dimension of the plane-tiled
    hist kernels (each program accumulates exactly one group's block)."""
    return -(-f // hist_group(f, bpad))


MXU_ROWS = 128  # accumulator rows one matmul can give the MXU


def hist_digits(bpad: int) -> Tuple[int, int]:
    """(H, L) of the two-digit one-hot, from ``bpad`` alone: a bin is
    ``b = hi * L + lo`` with ``lo`` in [0, L) and ``hi`` in [0, H).  L is the
    smallest power of two (32..128) with ``L * L >= 4 * bpad``, i.e. a block
    of ``16 / H`` features gives the MXU all its 128 rows and at least 64
    result columns: (4, 32) at bpad 128, (8, 32) at 256, (8, 64) at 512,
    (16, 64) at 1024, (16, 128) at 2048.  Swept on the v5e at bpad 256
    (PERF.md section 6, PR 30): (8, 32) 6 % ahead of (4, 64), (2, 128) far
    behind.  Past 2048 ``8 * H`` would pass the MXU's 128 rows and the form
    resolves to (1, bpad): the stats against the whole one-hot."""
    low = 32
    while low < 128 and low * low < 4 * bpad:
        low *= 2
    high = bpad // low  # exact: bpad is a multiple of 128
    if 8 * high > MXU_ROWS:
        return 1, bpad
    return high, low


def _digit_rows(bpad: int) -> int:
    """Rows of ``A`` a feature owns: its H masked copies of the 8 stat rows,
    padded to the int8 sublane tile (32) so no block straddles a tile."""
    return -(-8 * hist_digits(bpad)[0] // 32) * 32


def hist_feature_block(f: int, bpad: int) -> int:
    """nf, the features one matmul of the seg histogram takes: as many as
    fit the MXU's 128 accumulator rows at ``_digit_rows`` each (4 at bpad
    128, 2 at 256); all of the program's where H = 1 (they share the stat
    rows)."""
    group = hist_group(f, bpad)
    if hist_digits(bpad)[0] == 1:
        return group
    return min(group, MXU_ROWS // _digit_rows(bpad))


def hist_operands(f: int, bpad: int) -> Tuple[int, int, int]:
    """(blocks, A rows, B rows) of a program: its features in blocks of
    ``hist_feature_block``, each one matmul ``A[rows, TILE] x B[rows, TILE]``
    contracting the tile's rows."""
    high, low = hist_digits(bpad)
    nf = hist_feature_block(f, bpad)
    nblk = -(-hist_group(f, bpad) // nf)
    return nblk, (32 if high == 1 else MXU_ROWS), nf * low


def hist_sub(f: int, wide: bool, grouped: bool = False) -> int:
    """DMA sublanes: only the used planes (bins + stats), padded to an i16
    sublane multiple — 32 planes at F=28, 4x less tile traffic than the
    128-plane cap.  A grouped row: the program's own 16-plane bin block and
    the stat block."""
    if grouped:
        return 2 * STAT_BLOCK
    return min(storage_lanes(f, wide), (used_lanes(f, wide) + 15) // 16 * 16)


def hist_variants(group: int, wide: bool) -> Tuple[int, int]:
    """Grouped rows: (bin planes a histogram program owns, programs that
    share one aligned 16-plane block).  A program's one-hot build selects
    its planes from the block at one of these few static offsets, so the
    kernel's code does not grow with the number of feature groups."""
    ppp = group if wide else group // 2
    if ppp < 1 or STAT_BLOCK % ppp or (not wide and group % 2):
        raise ValueError(
            f"grouped seg histogram: {group} features a program do not tile "
            f"a {STAT_BLOCK}-plane block"
        )
    return ppp, STAT_BLOCK // ppp


def hist_step(f: int, bpad: int, sub: int, n_pad: Optional[int] = None) -> int:
    """STEP, the rows one long step of ``_hist_window`` contracts, from
    static shapes alone: the largest of 8, 4, 2 and 1 ``TILE`` (4096 ..
    512) that is no longer than the packed matrix (``n_pad`` columns: a DMA
    is a static slice of it; None = any length) and whose
    ``hist_scratch_bytes`` fits ``SEG_VMEM_BUDGET`` beside the
    partition's scratch at its widest (the fused grow step holds both; 128
    planes, so the rule needs neither the bin width nor the row's form):
    4096 at bpad 256 while a tile is 48 planes or fewer (82 byte-binned
    features a one-group row; any grouped row), 2048 on wider one-group
    rows and at bpad 128.  ``TILE`` (no long step: the kernel as it was)
    where the form is the full one-hot, H = 1: ``B`` is then ``bpad`` rows a feature, 8 MB at 512 columns and
    bpad 8192, and the matmul has 8 live rows whatever it contracts.  On
    the v5e 0.17 of a 512-row tile-program's 0.62 us is paid a step, not a
    row; kernel alone 4096 reads 23 % under 512, 2048 20 %, 1024 12 %
    (PERF.md section 5, PR 38)."""
    if hist_digits(bpad)[0] == 1:
        return TILE
    from .partition import partition_scratch_bytes

    room = SEG_VMEM_BUDGET - partition_scratch_bytes(LANES)
    for step in (8 * TILE, 4 * TILE, 2 * TILE):
        if (n_pad is None or step <= n_pad) and (
                hist_scratch_bytes(f, bpad, sub, step) <= room):
            return step
    return TILE


def hist_scratch(f: int, bpad: int, sub: int, quantized: bool,
                 grouped: bool = False, *, step: int):
    """Scratch of ``_hist_window`` in its argument order, then the DMA
    semaphores of the caller's ``tile_dmas`` (two slots x the DMAs a step
    takes) — the one list ``seg_hist_pallas_batch`` and the fused grow step
    allocate.  Staging slots and operands are ``step`` columns wide
    (``hist_step`` of the shapes and the matrix's length); a ``TILE``-row
    step uses their first ``TILE`` columns."""
    nblk, arows, brows = hist_operands(f, bpad)
    high = hist_digits(bpad)[0]
    op = jnp.int8 if quantized else jnp.bfloat16
    return [
        pltpu.VMEM((2, sub, step), jnp.int16),  # stage: two staging slots
        pltpu.VMEM((_bin_rows(hist_group(f, bpad)), step), jnp.int32),  # bins
        pltpu.VMEM((nblk, arows, step), op),  # a_op: masked stats, per block
        pltpu.VMEM((nblk, brows, step), op),  # b_op: low-digit one-hots
        pltpu.VMEM(  # acc: every block's whole matmul result
            (nblk, 8 if high == 1 else arows, brows),
            jnp.int32 if quantized else jnp.float32,
        ),
        pltpu.SemaphoreType.DMA((2 * (2 if grouped else 1),)),
    ]


def hist_scratch_bytes(f: int, bpad: int, sub: int,
                       step: Optional[int] = None) -> int:
    """VMEM bytes of ``hist_scratch`` at the wider operand type (a last
    dimension under 128 still takes whole lane tiles), the pipelined raw
    output block and the step's own temporaries (the unpacked tile, one
    low-digit compare and one 32-row block of ``A``); at ``hist_step`` of
    a matrix of any length unless ``step`` says otherwise."""
    if step is None:
        step = hist_step(f, bpad, sub)
    scratch = sum(
        math.prod(ref.shape[:-1]) * max(ref.shape[-1], 128)
        * jnp.dtype(ref.dtype).itemsize
        for ref in hist_scratch(f, bpad, sub, quantized=False, step=step)[:-1]
    )
    out = 2 * 8 * hist_group(f, bpad) * bpad * 4
    low = min(hist_digits(bpad)[1], 128)
    temps = sub * step * 4 + 2 * low * step * 4 + 2 * 32 * step * 4
    return scratch + out + temps


def _bin_rows(group: int) -> int:
    """Rows of the bins scratch: a program's features, even ones in the
    first half and odd ones in the second (byte-packed planes unpack to two
    aligned row blocks), each half a multiple of the i32 sublane tile."""
    return 2 * (-(-group // 16) * 8)


def _hist_window(
    start,  # scalar i32 — window begin (data-row index)
    cnt,  # scalar i32 — window rows (0 = all-zero histogram)
    pt,  # scalar i32 — this program's feature-plane group (grid dim 1)
    live,  # scalar i32 — 0 skips the step loops entirely (dead plane group)
    tile_dmas,  # (slot, base_col, width) -> the DMAs staging [SUB, width]
    scales_ref,  # SMEM [2] f32: g_scale, h_scale (quantized mode; else 1s)
    out,  # VMEM [8, group * bpad] f32 | i32 — the program's RAW output block
    stage,  # VMEM [2, SUB, STEP] i16 — step s in slot s % 2
    bins,  # VMEM [_bin_rows(group), STEP] i32 — the program's features' bins
    a_op,  # VMEM [nblk, A rows, STEP] bf16 | i8
    b_op,  # VMEM [nblk, B rows, STEP] bf16 | i8
    acc,  # VMEM [nblk, A rows | 8, B rows] f32 | i32
    *,
    f: int,
    bpad: int,
    group: int,
    quantized: bool,
    wide: bool,
    grouped: bool = False,
):
    """Histogram accumulation over ONE packed-row window (the per-program
    body of the seg hist kernel, factored out so the fused grow-step kernel
    can run it over just-partitioned data — its ``tile_dmas`` read through
    the output alias; see partition.aliased_tile_dma).

    Fills ``out`` with the program's RAW [8, group*bpad] block for plane
    group ``pt``; the digit recombine runs outside the kernel
    (``combine_hist_raw``).  Row convention (both dtypes): 0 g_hi, 1 h_hi,
    2 count, 3 g_lo, 4 h_lo, 5 zero, 6 g_lo2, 7 h_lo2 (int8 leaves 5-7 zero).

    The two-digit one-hot.  With (H, L) = ``hist_digits(bpad)`` a bin is
    ``b = hi * L + lo`` and

        hist[s, j, b] = sum_r (stats[s, r] * [hi_j(r) = hi]) * [lo_j(r) = lo]

    so for a block of nf = ``hist_feature_block`` features a step builds,
    its rows on the lanes as they arrive (no transpose),
    ``A[(j, hi, s), r]`` = the stat row s where feature j's high digit is
    hi, else 0, and ``B[(j', lo), r]`` = [feature j' has low digit lo], and
    issues ONE ``A . B^T`` contracting the rows.  The [A rows, nf * L] result's
    diagonal blocks j = j' are the nf histograms as [(hi, s), lo]; the
    off-diagonal blocks are joint counts of two different features and are
    never read.  Every block's whole result accumulates in ``acc`` across
    steps and the diagonal blocks are copied to ``out`` once, after the
    loops.  The sums are the same addends as a full one-hot's: the same
    bf16 | int8 terms times exact 0/1, added in f32 | i32 over the same rows
    in the same order, zeros elsewhere.  H = 1 is the full one-hot itself:
    ``A`` is the 8 stat rows, shared by all the program's features, ``B``
    their whole one-hots, every result block "diagonal".

    Two loops over one body.  With STEP the width of the staging slots
    (``hist_step``), the window's aligned span ``off + cnt`` is walked in
    ``span // STEP`` long steps — each one DMA of STEP columns, one plane
    dispatch, one matmul a feature block contracting STEP rows, one add
    into ``acc`` — and the rest in ``TILE``-row steps in the first ``TILE``
    columns of the same scratch, so no step reads a column the ``TILE``-row
    loop alone would not (``padded_rows`` holds) and a window under STEP
    rows runs the short loop and nothing else.  What a step costs whatever
    its width (the DMA's fixed part, the dispatch, the MXU's fill, drain and
    pops) is paid once for STEP rows in the long loop, not once for 512.
    The f32 partial sums are cut every STEP rows there, every ``TILE`` in
    the tail; integer sums are the same whatever the cut.

    The read of step s+1 is started before step s is waited for, into the
    other of the two staging slots, across both loops: the last long step
    starts the first short one's.

    ``grouped``: a tile is the program's own aligned 16-plane bin block
    over the stat block (``hist_sub``); the program's planes sit in the
    block at offset (pt mod nvar) * ppp (``hist_variants``)."""
    high, low = hist_digits(bpad)
    nf = hist_feature_block(f, bpad)
    nblk, arows, brows = hist_operands(f, bpad)
    drows = 0 if high == 1 else _digit_rows(bpad)  # H = 1: shared stat rows
    step = stage.shape[-1]
    abegin = (start // COL_ALIGN) * COL_ALIGN
    off = start - abegin
    # dead plane group (feature_fraction / EFB bundling): zero trips — the
    # output block stays zero and the grower never reads those rows
    span = jnp.where(live != 0, off + cnt, 0)
    n_long = span // step if step > TILE else 0
    tail0 = n_long * step  # where the TILE-row steps begin, from abegin
    n_short = (span - tail0 + TILE - 1) // TILE
    acc[...] = jnp.zeros_like(acc)
    # hoisted out of the step loops: reciprocal-multiply instead of two
    # full-width divides per step (quotients round to integers, so the
    # rounding difference cannot change the result)
    inv_g = 1.0 / scales_ref[0]
    inv_h = 1.0 / scales_ref[1]
    if grouped:
        GLO, GHI, HLO, HHI, M = range(STAT_BLOCK, STAT_BLOCK + 5)
        ppp, nvar = hist_variants(group, wide)
    else:
        GLO, GHI, HLO, HHI, M, _, _ = stat_lanes(f, wide)
    ngroups = hist_ngroups(f, bpad)
    half = bins.shape[0] // 2
    op_dtype = jnp.int8 if quantized else jnp.bfloat16
    pref = jnp.int32 if quantized else jnp.float32

    def bin_row(j):
        """Row of ``bins`` that holds feature j of the program."""
        return j if wide else (j >> 1) + (j & 1) * half

    def start_read(s, pos0, width):
        """Start step s's read: ``width`` columns from ``abegin + pos0``."""
        for dma in tile_dmas(s % 2, abegin + pos0, width):
            dma.start()

    def step_of(width):
        """The work of one step of ``width`` rows in the first ``width``
        columns of the scratch, as ``run(s, pos0)``."""
        cols = slice(0, width)
        iota_pos = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
        row8 = jax.lax.broadcasted_iota(jnp.int32, (8, width), 0)
        is_g = (row8 == 0) | (row8 == 3) | (row8 == 6)
        hi32 = jax.lax.broadcasted_iota(jnp.int32, (32, width), 0) >> 3
        iota_lo = jax.lax.broadcasted_iota(
            jnp.int32, (min(low, 128), width), 0)

        def run(s, pos0):
            for dma in tile_dmas(s % 2, abegin + pos0, width):
                dma.wait()
            xu = stage[s % 2, :, cols].astype(jnp.int32) & 0xFFFF  # [SUB, w]
            pos = iota_pos + pos0
            valid = ((pos >= off) & (pos < off + cnt)).astype(jnp.float32)
            m = xu[M:M + 1].astype(jnp.float32) * valid  # [1, width]
            # the 8 stat rows at once, g's on rows 0/3/6 and h's on 1/4/7
            # (one row costs a vreg a 128 lanes like eight do)
            lo16 = jnp.where(is_g, xu[GLO:GLO + 1], xu[HLO:HLO + 1])
            hi16 = jnp.where(is_g, xu[GHI:GHI + 1], xu[HHI:HHI + 1])
            vm = lax.bitcast_convert_type(
                (lo16 | (hi16 << 16)).astype(jnp.uint32), jnp.float32
            ) * m  # [8, width]: g * m | h * m
            if quantized:
                # int8 MXU path (2x bf16 throughput), 2-DIGIT: q is clipped
                # to +-QMAX and split q = hi*128 + lo (|hi| <= 127,
                # |lo| <= 64 — the +64 bias makes the shift round-to-nearest
                # so the low digit stays in int8 range).  Quantized-gradient
                # training (gradient_discretizer.cpp:70 grid, |q| <= 127 so
                # hi is just the sign spill) stays EXACT like the old
                # 1-digit path: per-bin integer sums are exact to 2^31/192
                # rows (~11M at the |q|=127 extreme) in i32 and the f32
                # recombine is exact below 2^24.  As the default hist
                # accumulator the grid carries ~14 bits per addend — near
                # ties are re-accumulated in bf16/f32 by the grower before
                # any structure decision.
                q = jnp.clip(
                    jnp.round(vm * jnp.where(is_g, inv_g, inv_h)),
                    -QMAX, QMAX,
                ).astype(jnp.int32)
                q_hi = (q + 64) >> 7
                q_lo = q - (q_hi << 7)
                # 5 live rows pad to the i32 tile's 8 sublanes anyway
                stats = jnp.where(
                    row8 < 2, q_hi,
                    jnp.where(row8 == 2, m.astype(jnp.int32),
                              jnp.where(row8 < 5, q_lo, 0)),
                )
            else:
                # THREE-term bf16 split of each f32 addend (~26 mantissa
                # bits) — the stat rows pad 6 -> 8 sublanes anyway, so the
                # two extra residual rows are free (ADVICE r2: tighter
                # precision contract at zero cost).  Kept in f32 registers:
                # every value is a bf16.
                v_hi = vm.astype(jnp.bfloat16).astype(jnp.float32)
                r1 = vm - v_hi
                v_lo = r1.astype(jnp.bfloat16).astype(jnp.float32)
                v_lo2 = (r1 - v_lo).astype(jnp.bfloat16).astype(jnp.float32)
                stats = jnp.where(
                    row8 < 2, v_hi,
                    jnp.where(row8 == 2, m,
                              jnp.where(row8 < 5, v_lo,
                                        jnp.where(row8 == 5, 0.0, v_lo2))),
                )
            stats32 = jnp.concatenate([stats] * 4, axis=0)  # [32, width]

            def take_planes(p0, nfl):
                """The program's bins from planes [p0, ...) of the tile, at
                STATIC offsets (hence the unrolled dispatch on the dynamic
                program id below); -1, which matches no digit, for the
                features a last group lacks."""
                if nfl < group:
                    bins[:, cols] = jnp.full(
                        (bins.shape[0], width), -1, jnp.int32)
                if wide:
                    bins[0:nfl, cols] = xu[p0:p0 + nfl]  # a u16 plane each
                    return
                planes = xu[p0:p0 + (nfl + 1) // 2]
                bins[0:(nfl + 1) // 2, cols] = planes & 0xFF
                if nfl > 1:
                    bins[half:half + nfl // 2, cols] = (
                        planes[:nfl // 2] >> 8) & 0xFF

            if grouped:
                # the last program's features past F read planes of the
                # padding; ``combine_hist_raw`` drops their columns
                for v in range(nvar):
                    pl.when(pt % nvar == v)(
                        functools.partial(take_planes, v * ppp, group))
            elif ngroups == 1:
                take_planes(0, f)
            else:
                for gi in range(ngroups):
                    basef = gi * group
                    pl.when(pt == gi)(functools.partial(
                        take_planes, basef if wide else basef >> 1,
                        min(group, f - basef)))
            b = bins[:, cols]
            lo_dig = b if high == 1 else b & (low - 1)
            hi_dig = (
                jnp.zeros_like(b) if high == 1
                else b >> (low.bit_length() - 1)
            )
            for fb in range(nblk):
                for c in range(arows // 32):
                    # 32 rows of A: feature j's stat rows under four high
                    # digits
                    j, h0 = (0, 0) if high == 1 else (
                        (32 * c) // drows, (32 * c) % drows // 8)
                    jg = fb * nf + j
                    if j >= nf or jg >= group:
                        blk = jnp.zeros((32, width), op_dtype)
                    else:
                        r = bin_row(jg)
                        blk = jnp.where(
                            hi_dig[r:r + 1] == hi32 + h0, stats32, 0
                        ).astype(op_dtype)
                    a_op[fb, 32 * c:32 * (c + 1), cols] = blk
                for j in range(nf):
                    jg = fb * nf + j
                    # H = 1: by 128s
                    for l0 in range(0, low, iota_lo.shape[0]):
                        rows = pl.ds(j * low + l0, iota_lo.shape[0])
                        if jg >= group:
                            b_op[fb, rows, cols] = jnp.zeros(
                                iota_lo.shape, op_dtype)
                        else:
                            r = bin_row(jg)
                            b_op[fb, rows, cols] = (
                                lo_dig[r:r + 1] == iota_lo + l0
                            ).astype(op_dtype)
                # ONE matmul a feature block a step
                part = jax.lax.dot_general(
                    a_op[fb, :, cols], b_op[fb, :, cols],
                    dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=pref,
                )
                acc[fb] += part[:acc.shape[1]]

        return run

    if step > TILE:
        pl.when(n_long > 0)(lambda: start_read(0, 0, step))
        pl.when((n_long == 0) & (n_short > 0))(
            lambda: start_read(0, 0, TILE))
        run_long = step_of(step)

        def long_body(t, _):
            @pl.when(t + 1 < n_long)
            def _read_ahead():
                start_read(t + 1, (t + 1) * step, step)

            @pl.when((t + 1 == n_long) & (n_short > 0))
            def _read_tail_ahead():
                start_read(t + 1, tail0, TILE)

            run_long(t, t * step)
            return 0

        lax.fori_loop(0, n_long, long_body, 0)
    else:
        pl.when(n_short > 0)(lambda: start_read(0, 0, TILE))
    run_short = step_of(TILE)

    def short_body(u, _):
        @pl.when(u + 1 < n_short)
        def _read_ahead():
            start_read(n_long + u + 1, tail0 + (u + 1) * TILE, TILE)

        run_short(n_long + u, tail0 + u * TILE)
        return 0

    lax.fori_loop(0, n_short, short_body, 0)
    # the diagonal blocks, once a program: out[s, (jg, hi, lo)]
    for jg in range(group):
        fb, j = divmod(jg, nf)
        for hi in range(high):
            out[:, jg * bpad + hi * low:jg * bpad + (hi + 1) * low] = acc[
                fb, j * drows + 8 * hi:j * drows + 8 * hi + 8,
                j * low:(j + 1) * low,
            ]


def combine_hist_raw(
    raw: jnp.ndarray,  # [K, G, 8, group * bpad] i32 | f32 raw planes
    scales: jnp.ndarray,  # [2] f32 (quantized; ignored otherwise)
    *,
    f: int,
    bpad: int,
    group: int,
    num_bins: int,
    quantized: bool,
) -> jnp.ndarray:
    """Recombine the kernels' raw 8-sublane accumulator planes into the
    [K, 3, F, B] histogram — the three (g, h, count) planes, stat axis
    first — in plain XLA, outside the kernel.

    int8: g = (S_hi*128 + S_lo)*g_scale (the *128 is a f32 exponent bump,
    exact; the digit sum is exact below 2^24 — same bound as the old
    in-kernel dequantize).  bf16: the same 3-term sums the kernel used to
    do in its epilogue."""
    k, ngroups = raw.shape[0], raw.shape[1]
    a = raw.reshape(k, ngroups, 8, group, bpad)
    a = a.transpose(0, 2, 1, 3, 4).reshape(k, 8, ngroups * group, bpad)
    a = a[:, :, :f, :]
    if quantized:
        af = a.astype(jnp.float32)
        g = (af[:, 0] * 128.0 + af[:, 3]) * scales[0]
        h = (af[:, 1] * 128.0 + af[:, 4]) * scales[1]
        c = af[:, 2]
    else:
        g = a[:, 0] + a[:, 3] + a[:, 6]
        h = a[:, 1] + a[:, 4] + a[:, 7]
        c = a[:, 2] + a[:, 5]
    return jnp.stack([g, h, c], axis=1)[..., :num_bins]


def _seg_hist_kernel(
    scal_ref,  # SMEM [K, 2] i32: (start, cnt) per batch member
    scales_ref,  # SMEM [2] f32: g_scale, h_scale (quantized mode; else 1s)
    live_ref,  # SMEM [G] i32: per-plane-group live mask
    seg_any,  # ANY [LANES, n_pad] | [G, sub, n_pad] i16 (plane-major)
    out_ref,  # VMEM [1, 1, 8, group * bpad] f32 | i32 block (raw planes)
    *scratch,  # hist_scratch(...): only the used planes are DMA'd
    f: int,
    bpad: int,
    group: int,
    sub: int,
    quantized: bool,
    wide: bool,
    grouped: bool = False,
):
    stage, sem_in = scratch[0], scratch[-1]
    i = pl.program_id(0)
    pt = pl.program_id(1)

    def tile_dmas(slot, base_col, width):
        cols = pl.ds(pl.multiple_of(base_col, COL_ALIGN), width)
        into = pl.ds(0, width)  # a TILE-row step: the slot's first columns
        if not grouped:
            return [pltpu.make_async_copy(
                seg_any.at[pl.ds(0, sub), cols], stage.at[slot, :, into],
                sem_in.at[slot],
            )]
        # the program's aligned bin block, then the stat block: flat
        # plane p of the row lives at [p // gsub, p % gsub]
        gsub = seg_any.shape[1]
        row0 = (pt // hist_variants(group, wide)[1]) * STAT_BLOCK
        stat0 = stat_lanes(f, wide, True)[0]
        return [
            pltpu.make_async_copy(
                seg_any.at[
                    r // gsub,
                    pl.ds(pl.multiple_of(r % gsub, STAT_BLOCK), STAT_BLOCK),
                    cols,
                ],
                stage.at[slot, pl.ds(k * STAT_BLOCK, STAT_BLOCK), into],
                sem_in.at[2 * slot + k],
            )
            for k, r in enumerate((row0, stat0))
        ]

    _hist_window(
        scal_ref[i, 0],
        scal_ref[i, 1],
        pt,
        live_ref[pt],
        tile_dmas,
        scales_ref,
        out_ref.at[0, 0],
        *scratch[:-1],
        f=f,
        bpad=bpad,
        group=group,
        quantized=quantized,
        wide=wide,
        grouped=grouped,
    )


def seg_hist_pallas(
    seg: jnp.ndarray,
    scal: jnp.ndarray,  # [2] i32: start, cnt
    scales: Optional[jnp.ndarray] = None,  # [2] f32 grid scales (quantized)
    live: Optional[jnp.ndarray] = None,  # [G] i32 plane-group live mask
    *,
    f: int,
    num_bins: int,
    n_pad: int,
    quantized: bool = False,
    wide: bool = False,
    interpret: bool = False,
) -> jnp.ndarray:
    """Histogram [3, F, B] (g, h, count planes) of packed rows
    [start, start+cnt).

    A thin K=1 wrapper over the batched plane-tiled kernel (one launch, G
    grid programs).  ``quantized=True`` (requires ``scales``): 2-digit
    integer accumulation on the int8 MXU path — exact on the quantized-
    training grid and ~2x the bf16 throughput."""
    out = seg_hist_pallas_batch(
        seg, scal.reshape(1, 2), scales, live,
        f=f, num_bins=num_bins, n_pad=n_pad, quantized=quantized, wide=wide,
        interpret=interpret,
    )
    return out[0]


@functools.partial(
    instrumented_jit,
    static_argnames=("f", "num_bins", "n_pad", "quantized", "wide", "interpret"),
)
def seg_hist_pallas_batch(
    seg: jnp.ndarray,
    scal: jnp.ndarray,  # [K, 2] i32: (start, cnt) per batch member
    scales: Optional[jnp.ndarray] = None,
    live: Optional[jnp.ndarray] = None,  # [G] i32 plane-group live mask
    *,
    f: int,
    num_bins: int,
    n_pad: int,
    quantized: bool = False,
    wide: bool = False,
    interpret: bool = False,
) -> jnp.ndarray:
    """K histograms [K, 3, F, B] of K disjoint packed-row windows in ONE
    plane-tiled launch: a (K, G) grid — batch member x feature-plane group
    — over the shared kernel (TPU grid programs run sequentially on the
    core, so the shared staging/accumulator scratch is reused safely
    program-to-program).  Frontier-batched growth (ops/grower.py
    leaf_batch) uses this to build all K smaller-child histograms per step
    with one launch's fixed cost; ``live`` (default all-ones) skips dead
    plane groups under feature_fraction / EFB bundling.  A grouped row
    (``[G, sub, n_pad]``) runs the same grid: a program DMAs its own aligned
    bin block and the stat block instead of every used plane."""
    k = scal.shape[0]
    bpad = hist_bpad(num_bins)
    group = hist_group(f, bpad)
    ngroups = hist_ngroups(f, bpad)
    grouped = is_grouped(seg)
    sub = hist_sub(f, wide, grouped)
    acc_dtype = jnp.int32 if quantized else jnp.float32
    kernel = functools.partial(
        _seg_hist_kernel, f=f, bpad=bpad, group=group, sub=sub,
        quantized=quantized, wide=wide, grouped=grouped,
    )
    if scales is None:
        scales = jnp.ones((2,), jnp.float32)
    if live is None:
        live = jnp.ones((ngroups,), jnp.int32)
    raw = pl.pallas_call(
        kernel,
        grid=(k, ngroups),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, 8, group * bpad), lambda i, pt: (i, pt, 0, 0),
            memory_space=pltpu.VMEM,
        ),
        out_shape=jax.ShapeDtypeStruct((k, ngroups, 8, group * bpad), acc_dtype),
        scratch_shapes=hist_scratch(
            f, bpad, sub, quantized, grouped,
            step=hist_step(f, bpad, sub, seg.shape[-1])),
        interpret=interpret,
    )(
        scal.astype(jnp.int32), scales.astype(jnp.float32),
        live.astype(jnp.int32), seg,
    )
    return combine_hist_raw(
        raw, scales.astype(jnp.float32), f=f, bpad=bpad, group=group,
        num_bins=num_bins, quantized=quantized,
    )


def seg_hist_ref(seg: jnp.ndarray, scal: jnp.ndarray, *, f: int, num_bins: int,
                 n_pad: int, wide: bool = False):
    """Pure-JAX reference/CPU path: masked histogram over the whole array
    (static shapes; rows outside [start, start+cnt) masked out)."""
    from ..histogram import leaf_histogram_segment

    start, cnt = scal[0], scal[1]
    bins, g, h, m, _ = unpack_stats(seg, f, wide=wide)
    idx = jnp.arange(seg.shape[-1], dtype=jnp.int32)
    window = (idx >= start) & (idx < start + cnt)
    return leaf_histogram_segment(bins, g, h, m * window.astype(jnp.float32), num_bins)


# CPU windowing engages only above this row count: below it the plain
# masked full pass is cheap, and keeping small shapes on the original path
# keeps every existing golden dump byte-stable (a windowed sum can differ
# from the full-pass sum in -0.0/+0.0 only, but why risk even that).
_CPU_WINDOW_ROWS = 32 * TILE


def _window_caps(n_pad: int):
    """Capacity ladder for the windowed CPU pass: 16*TILE, x4 per rung,
    closed by the full array (mirrors the ordered path's _hist_caps)."""
    caps, c = [], 16 * TILE
    while c < n_pad:
        caps.append(c)
        c *= 4
    caps.append(n_pad)
    return caps


def _seg_hist_windowed(seg, scal, *, f: int, num_bins: int, n_pad: int,
                       wide: bool = False):
    """Windowed CPU seg histogram: slice the smallest TILE-aligned capacity
    bucket covering [start, start+cnt) and run the masked reference over
    just that window, so CPU histogram work is proportional to the leaf
    size instead of the full padded array (the dominant cost of the old
    full-pass fallback at 1M+ rows).  lax.switch keeps the trace static
    per capacity rung."""
    caps = _window_caps(n_pad)
    start = scal[0].astype(jnp.int32)
    cnt = scal[1].astype(jnp.int32)
    # TILE-aligning the window start costs < TILE rows of slack
    need = cnt + TILE

    def _branch(cap):
        def _b(seg, start, cnt):
            s0 = jnp.clip((start // TILE) * TILE, 0, n_pad - cap)
            win = lax.dynamic_slice_in_dim(seg, s0, cap, axis=seg.ndim - 1)
            return seg_hist_ref(
                win, jnp.stack([start - s0, cnt]), f=f, num_bins=num_bins,
                n_pad=cap, wide=wide,
            )
        return _b

    idx = jnp.int32(0)
    for c in caps[:-1]:
        idx = idx + (need > c).astype(jnp.int32)
    return lax.switch(idx, [_branch(c) for c in caps], seg, start, cnt)


def seg_hist_cpu(seg, scal, *, f: int, num_bins: int, n_pad: int,
                 wide: bool = False):
    """Off-TPU seg histogram: capacity-bucketed windowed pass at scale,
    plain masked full pass below the threshold (byte-identical to the
    original fallback, keeping small goldens bit-stable).  Shared by the
    two-launch dispatchers below AND the fused grow step's XLA oracle, so
    fused-vs-two-launch stays byte-identical by construction."""
    if n_pad > _CPU_WINDOW_ROWS:
        return _seg_hist_windowed(
            seg, scal, f=f, num_bins=num_bins, n_pad=n_pad, wide=wide
        )
    return seg_hist_ref(seg, scal, f=f, num_bins=num_bins, n_pad=n_pad,
                        wide=wide)


def seg_hist_batch_cpu(seg, scal_k, *, f: int, num_bins: int, n_pad: int,
                       wide: bool = False):
    """Off-TPU K-window histogram.  Above the windowing threshold each
    member picks its own capacity bucket via a sequential Python loop (K is
    small and static; vmapping lax.switch would execute every rung),
    below it the vmapped full pass matches the historical path exactly."""
    if n_pad > _CPU_WINDOW_ROWS:
        return jnp.stack([
            _seg_hist_windowed(
                seg, scal_k[i], f=f, num_bins=num_bins, n_pad=n_pad, wide=wide
            )
            for i in range(scal_k.shape[0])
        ])
    return jax.vmap(
        lambda s: seg_hist_ref(
            seg, s, f=f, num_bins=num_bins, n_pad=n_pad, wide=wide
        )
    )(scal_k)


def seg_hist(seg, scal, *, f: int, num_bins: int, n_pad: int,
             quant_scales=None, wide: bool = False, live=None):
    """Platform dispatch: Pallas on TPU (2-digit int8 grid accumulation
    when ``quant_scales`` is given — quantized training or the grower's
    int8-default hist accumulator), windowed/masked reference elsewhere."""
    quantized = quant_scales is not None
    scales = (
        jnp.stack([quant_scales[0], quant_scales[1]]).astype(jnp.float32)
        if quantized
        else jnp.ones((2,), jnp.float32)
    )
    if jax.default_backend() != "tpu":
        # no TPU in this process: take the reference path without tracing
        # the Pallas branch, or the interpret-mode kernel under the
        # _INTERPRET test hook
        if _INTERPRET:
            return seg_hist_pallas(
                seg, scal, scales, live, f=f, num_bins=num_bins, n_pad=n_pad,
                quantized=quantized, wide=wide, interpret=True,
            )
        return seg_hist_cpu(seg, scal, f=f, num_bins=num_bins, n_pad=n_pad,
                            wide=wide)
    if live is None:
        live = jnp.ones((hist_ngroups(f, hist_bpad(num_bins)),), jnp.int32)
    return jax.lax.platform_dependent(
        seg,
        scal,
        scales,
        live,
        tpu=functools.partial(
            seg_hist_pallas, f=f, num_bins=num_bins, n_pad=n_pad,
            quantized=quantized, wide=wide,
        ),
        default=lambda seg, scal, _s, _l: seg_hist_cpu(
            seg, scal, f=f, num_bins=num_bins, n_pad=n_pad, wide=wide
        ),
    )


def seg_hist_batch(seg, scal_k, *, f: int, num_bins: int, n_pad: int,
                   quant_scales=None, wide: bool = False, live=None):
    """K-window histogram dispatch ([K, 2] (start, cnt) -> [K, 3, F, B]):
    one plane-tiled Pallas launch on TPU, the windowed/masked reference
    elsewhere."""
    quantized = quant_scales is not None
    scales = (
        jnp.stack([quant_scales[0], quant_scales[1]]).astype(jnp.float32)
        if quantized
        else jnp.ones((2,), jnp.float32)
    )

    if jax.default_backend() != "tpu":
        if _INTERPRET:
            return seg_hist_pallas_batch(
                seg, scal_k, scales, live, f=f, num_bins=num_bins,
                n_pad=n_pad, quantized=quantized, wide=wide, interpret=True,
            )
        return seg_hist_batch_cpu(seg, scal_k, f=f, num_bins=num_bins,
                                  n_pad=n_pad, wide=wide)
    if live is None:
        live = jnp.ones((hist_ngroups(f, hist_bpad(num_bins)),), jnp.int32)
    return jax.lax.platform_dependent(
        seg,
        scal_k,
        scales,
        live,
        tpu=functools.partial(
            seg_hist_pallas_batch, f=f, num_bins=num_bins, n_pad=n_pad,
            quantized=quantized, wide=wide,
        ),
        default=lambda seg, scal_k, _s, _l: seg_hist_batch_cpu(
            seg, scal_k, f=f, num_bins=num_bins, n_pad=n_pad, wide=wide
        ),
    )
