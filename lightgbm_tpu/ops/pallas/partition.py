"""Streaming in-place stable partition of packed rows — Pallas TPU kernel.

Reference analogs: ``DataPartition::Split`` (src/treelearner/data_partition.hpp:101)
and the CUDA partition pipeline (``GenDataToLeftBitVectorKernel`` -> prefix
sums -> ``SplitInnerKernel``, src/treelearner/cuda/cuda_data_partition.cu).

Why this kernel exists: the round-2 design partitioned a leaf's contiguous
window with ``lax.sort`` over pow-2 capacity buckets (ops/segpart.py).  That
was already the fastest pure-XLA formulation, but it pays (a) a multi-pass
comparison sort for what is a 1-bit-key partition, (b) up to 2x window
overshoot from the pow-2 ladder, and (c) a defensive full-array copy per
``lax.switch`` branch.  This kernel streams the EXACT window once, tile by
tile, and compacts rows with a ONE-HOT MATMUL — the MXU as a crossbar.  TPUs
have no vector scatter/compaction primitive; a permutation applied as a
``[T, T]`` 0/1 matrix multiply is exact (i16 planes split into two 0..255
byte planes, each exact in bf16).

Measured on a v5e (PR 28, one 10.5M-row window of the 28-feature layout,
``sub`` = 24 planes; 8M rows at 67 features, ``sub`` = 48, in brackets):
the tile loop of PRs 3-27 took 8.48 [9.25] ns a row — four ``[sub, T] x
[2T, T]`` compactions, three cumulative sums and three blocking DMAs a tile,
DMA and compute strictly in series (40 + 48 of 89 ms); this one takes 1.57
[1.72] ns a row (16.5 [13.8] ms a window), of which the DMAs alone are
0.54.  The memory bound — every plane read once and written once, the
right rows once more each way — is 0.18 ns a row at 819 GB/s; what is left
is the per-tile latency of three dependent matmuls and a vector-to-scalar
count, not bytes.  The ``lax.sort`` formulation was quoted at ~6 ns a row
when it was replaced and was not measured again.

Algorithm (stable, in place; the window is read once and written once, the
right stream spilled and read back once more):
  pass 1: stream aligned ``[SUB, T]`` tiles of the window left to right, G
    at a loop iteration.  Per tile: evaluate the split predicate on the
    packed bin byte, one cumulative count of the LEFT columns (left rows
    plus the sub-tile alignment prefix), and ONE permutation matmul over the
    lo and hi byte planes stacked on the M axis: lefts in order, then
    rights (right rows plus the alignment suffix) in order, rotated so the
    lefts land on the left staging's free lanes.  A lane rotation
    (``pltpu.roll``, dynamic shift) and a lane mask put the rights on the
    right staging's.  Stagings are one block of integers each; a block that
    fills is flushed by an aligned DMA that is left in flight: the left
    stream writes IN PLACE, the right stream to an HBM scratch buffer.
  pass 2: the spilled right stream is a copy shifted by the left stream's
    residual fill — rotate, merge under a mask, flush; no count, no
    permutation.  Its last partial block never leaves VMEM.  Every block
    write is 128-aligned, and the two passes together rewrite exactly the
    tiles pass 1 read.

Reads come in blocks of ``block_tiles(sub)`` tiles through two buffers:
block b + 1 is started before block b is computed.  Why that is safe in
place: flush k of the left stream needs k + 1 blocks' worth of left columns
consumed, so it writes tile k only while tile t >= k is being computed — the
write position trails the read cursor — and a block read ahead holds only
tiles beyond t, columns no flush has reached.  The read-ahead never crosses
the window's last tile (the last, partial block goes tile by tile), and
every flush is waited for before ``_partition_window`` returns, so a later
grid program — or the fused kernel's histogram phase — that re-reads a
shared boundary block sees this window's writes.  The spill is waited for
before pass 2 reads it.

Stability: both children preserve original row order (streams keep tile
order and the in-tile permutation keeps column order within each side), so
results are bit-identical to the stable-sort path this replaces.

The per-window body is factored into ``_partition_window`` so the fused
grow-step kernel (ops/pallas/grow_step.py) can run partition + smaller-child
histogram in ONE launch; ``aliased_tile_dma`` is the
read-through-the-output-alias helper of that kernel's histogram phase (see
its docstring for the interpret-mode aliasing pitfall it guards against),
and the partition's block reads take the same source (``_aliased_cols``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...obs.jit import instrumented_jit
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .seg import COL_ALIGN, is_grouped, used_lanes

# Test hook: route segpart.sort_partition through this kernel in interpret
# mode off-TPU (read at TRACE time, like seg._INTERPRET).
_INTERPRET = False

T = 256  # streaming tile columns (rows of training data)
G = 4  # tiles a loop iteration takes together (block_tiles is a multiple)


def partition_sub(f: int, wide: bool = False) -> int:
    """Planes the partition moves: the used ones, rounded up because Mosaic
    wants second-minor DMA slice shapes in 8-sublane multiples."""
    return -(-used_lanes(f, wide) // 8) * 8


def block_tiles(sub: int) -> int:
    """Tiles per read block, from the one shape the kernel can see: a block
    of ``sub`` planes is 128-256 KB (64 KB at sub = 8, where 16 tiles cap
    it), so two of them stay a small part of SEG_VMEM_BUDGET at sub = 128."""
    return max(G, min(16, 1 << ((128 * 1024) // (sub * T)).bit_length() - 1))


def window_block_tiles(sub: int, n_pad: int) -> int:
    """``block_tiles`` clipped to the matrix: a block DMA's static extent
    must fit its ``n_pad`` columns (toy sizes only)."""
    return min(block_tiles(sub), n_pad // T) // G * G


def partition_scratch(sub: int, use_gl: bool):
    """Scratch of ``_partition_window`` in its argument order — the one list
    the three ``pallas_call`` wrappers (and the fused grow step) allocate."""
    bc = block_tiles(sub) * T
    return [
        pltpu.VMEM((2, sub, bc), jnp.int16),  # in_blk: two read blocks
        pltpu.VMEM((2, 1, bc if use_gl else COL_ALIGN), jnp.float32),  # gl_blk
        pltpu.VMEM((2, sub, T), jnp.int16),  # out_l: left-stream flushes
        pltpu.VMEM((2, sub, T), jnp.int16),  # out_r: right-stream spills
        pltpu.VMEM((sub, T), jnp.int32),  # stage_l: < T pending columns
        pltpu.VMEM((sub, T), jnp.int32),  # stage_r
        pltpu.SemaphoreType.DMA((2,)),  # sem_in, one per read block
        pltpu.SemaphoreType.DMA((2,)),  # sem_gl
        pltpu.SemaphoreType.DMA((2,)),  # sem_l, one per flush buffer
        pltpu.SemaphoreType.DMA((2,)),  # sem_r
    ]


def partition_scratch_bytes(sub: int) -> int:
    """VMEM bytes of ``partition_scratch`` (with the go-left-bits blocks,
    each row of which pads to 8 sublanes) plus the tile loop's own
    temporaries (tri, Q, the stacked byte planes and their product)."""
    bc = block_tiles(sub) * T
    blocks = 2 * sub * bc * 2 + 2 * 8 * bc * 4
    stages = 2 * 2 * sub * T * 2 + 2 * sub * T * 4
    temps = 2 * T * T * 2 + 2 * sub * T * (2 + 4)
    return blocks + stages + temps


def _plane_index(grp, sub, base_col, cols):
    """Index of a ``[sub, cols]`` window of a packed matrix at a 128-aligned
    column: of plane group ``grp`` of a grouped ``[G, sub, n_pad]`` matrix,
    or (``grp`` None) of the first ``sub`` planes of a ``[LANES, n_pad]``
    one."""
    at = (pl.ds(0, sub), pl.ds(pl.multiple_of(base_col, COL_ALIGN), cols))
    return at if grp is None else (grp,) + at


def _aliased_cols(seg_in, seg_out, sub, base_col, cols, read_via_input,
                  grp=None):
    """``[sub, cols]`` window of an IN-PLACE (input/output-aliased) packed
    segment matrix, as a DMA source, through the OUTPUT alias (see
    ``aliased_tile_dma``)."""
    src = seg_in if read_via_input else seg_out
    # the input-ref read below is unreachable in production: it only
    # engages under the test-only read_via_input knob of aliased_tile_dma
    return src.at[_plane_index(grp, sub, base_col, cols)]  # graftlint: disable=GL002


def aliased_tile_dma(seg_in, seg_out, stage, sem, base_col, *,
                     read_via_input: bool = False):
    """The DMA of one aligned ``[sub, cols]`` tile of an IN-PLACE (input/
    output-aliased) packed segment matrix into VMEM ``stage``, for the
    caller to start and wait for.

    Reads go through the OUTPUT alias, not the input ref: on TPU they are
    the same HBM buffer, but batched grids re-read boundary tiles an
    earlier program (or an earlier phase of the SAME program, in the fused
    grow-step kernel) already rewrote — adjacent leaf windows share
    COL_ALIGN blocks — and Pallas interpret mode only makes those writes
    visible on the output ref.  Used by the fused grow-step kernel's
    histogram phase (ops/pallas/grow_step.py), which starts a tile's read
    while it works on the one before: phase 1 of the member has finished by
    then, so reading ahead is as safe as reading.  The partition's block
    reads take the same source (``_aliased_cols``).

    ``read_via_input=True`` recreates the PR-3 aliasing bug by reading the
    input ref instead — a TEST-ONLY knob for the regression test in
    tests/test_partition_kernel.py; never set it from production code.
    """
    sub, cols = stage.shape
    return pltpu.make_async_copy(
        _aliased_cols(seg_in, seg_out, sub, base_col, cols, read_via_input),
        stage,
        sem,
    )


def _partition_window(
    sbegin,  # scalar i32 — segment begin
    cnt,  # scalar i32 — segment rows (0 = no-op)
    feat,  # scalar i32 — split feature (used-feature index)
    tbin,  # scalar i32
    dl,  # scalar i32 (default-left)
    nanb,  # scalar i32 (NaN bin or -1)
    iscat,  # scalar i32
    seg_any,  # ANY [LANES, n_pad] i16 (aliased to seg_out)
    seg_out,  # ANY [LANES, n_pad] i16 (aliased with seg_any)
    scratch_out,  # ANY [SUB, n_pad] i16 — right-stream spill
    cat_ref,  # VMEM [1, bmt] f32 — bin -> goes-left (categorical)
    tri_ref,  # VMEM [T, T] bf16 — tri[i, j] = (i <= j), cumsum-by-matmul
    gl_any,  # ANY [1, n_pad] f32 go-left bits (a dummy when not use_gl)
    scratch,  # the refs of partition_scratch(sub, use_gl), in its order
    *,
    use_cat: bool,
    sub: int,
    wide: bool,
    bmt: int,
    use_gl: bool,
    read_via_input: bool = False,
    grp=None,
):
    """Stable in-place partition of ONE leaf window (the per-program body of
    the seg partition kernel, factored out so the fused grow-step kernel can
    run it before its histogram phase).  Returns nl — rows going left.

    ``grp`` (scalar i32): the window is plane group ``grp`` of a grouped
    ``[G, sub, n_pad]`` matrix.  The predicate then has to come as bits
    (``use_gl``): the split feature's plane lies in one group only, and
    every group is permuted by the same bits."""
    (in_blk, gl_blk, out_l, out_r, stage_l, stage_r,
     sem_in, sem_gl, sem_l, sem_r) = scratch
    nbt = window_block_tiles(sub, seg_out.shape[-1])
    bc = nbt * T
    abegin = (sbegin // COL_ALIGN) * COL_ALIGN
    off = sbegin - abegin
    nt = (off + cnt + T - 1) // T

    iota_j = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
    iota_jf = iota_j.astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (sub, T), 1)
    # tpu.iota only produces integers; cast for the f32 dest compare.
    # [T(dest), T(src)] orientation: dest stays a [1, T] row (Mosaic cannot
    # legalize the [1, T] -> [T, 1] transpose) and the compact matmul
    # contracts the shared source dim of the planes and Q ("NT" form).
    iota_q = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0).astype(jnp.float32)

    # Mosaic has no value-level dynamic_slice: the split feature's byte row
    # comes out of the stacked [lo; hi] byte planes by a one-hot row matmul
    # (the MXU as a dynamic row gather).  Row 0 picks the byte compared
    # (narrow: the feature's half of its plane; wide: the low byte), row 1
    # the high byte of a wide (u16) plane.
    plane = feat if wide else feat >> 1
    sel_r = jax.lax.broadcasted_iota(jnp.int32, (8, 2 * sub), 0)
    sel_c = jax.lax.broadcasted_iota(jnp.int32, (8, 2 * sub), 1)
    if wide:
        sel = ((sel_r == 0) & (sel_c == plane)) | (
            (sel_r == 1) & (sel_c == plane + sub)
        )
    else:
        sel = (sel_r == 0) & (sel_c == plane + (feat & 1) * sub)
    sel = sel.astype(jnp.bfloat16)

    def _start(d):
        d.start()

    def _wait(d):
        d.wait()

    def _read_block(op, src_cols, with_gl, b, ntiles, slot):
        """``op`` (start | wait) the DMAs of read block ``b`` — tiles
        [b*nbt, (b+1)*nbt) of a stream of ``ntiles`` — into buffer ``slot``.
        A block wholly inside the stream is one DMA; the stream's last,
        partial block goes tile by tile, so no read crosses the last tile
        (nothing past it is this window's to touch, and n_pad ends soon
        after the last window)."""

        def copies(c0, cols):
            out = [pltpu.make_async_copy(
                src_cols(b * bc + c0, cols),
                in_blk.at[slot, pl.ds(0, sub), pl.ds(c0, cols)],
                sem_in.at[slot],
            )]
            if with_gl:
                # precomputed go-left bits (feature-parallel seg: the
                # winner's plane lives on the owning shard; the bits
                # arrived by psum) ride beside their rows
                out.append(pltpu.make_async_copy(
                    gl_any.at[
                        pl.ds(0, 1),
                        pl.ds(pl.multiple_of(abegin + b * bc + c0, COL_ALIGN), cols),
                    ],
                    gl_blk.at[slot, pl.ds(0, 1), pl.ds(c0, cols)],
                    sem_gl.at[slot],
                ))
            return out

        whole = (b + 1) * nbt <= ntiles

        @pl.when(whole)
        def _():
            for d in copies(0, bc):
                op(d)

        @pl.when(jnp.logical_not(whole))
        def _():
            def one(k, c):
                for d in copies(pl.multiple_of(k * T, T), T):
                    op(d)
                return c

            lax.fori_loop(0, ntiles - b * nbt, one, 0)

    def _tile_group(i, src_cols, with_gl, ntiles):
        """Tiles G*i .. G*i + G - 1 of the stream as u16-in-i32 (and their
        go-left bits); G divides nbt, so a group never straddles a block.
        At a block's first group, block b + 1 is started BEFORE block b is
        waited for, so its DMA overlaps the compute of block b.  Beyond the
        stream's last tile a group holds stale buffer content, which its
        caller appends none of."""
        t = G * i
        b = t // nbt
        k = t - b * nbt
        slot = b % 2

        @pl.when(k == 0)
        def _():
            _read_block(_start, src_cols, with_gl, b + 1, ntiles, 1 - slot)
            _read_block(_wait, src_cols, with_gl, b, ntiles, slot)

        def tile(k):
            c0 = pl.multiple_of(k * T, T)
            xu = in_blk[slot, :, pl.ds(c0, T)].astype(jnp.int32) & 0xFFFF
            return xu, gl_blk[slot, :, pl.ds(c0, T)] if with_gl else None

        return [tile(k + j) for j in range(G)]

    def _flush_dma(outb, sem, dst, dst_base, blk, slot):
        return pltpu.make_async_copy(
            outb.at[slot],
            # the spill is one group's worth whatever the matrix's form
            dst.at[_plane_index(grp if dst is seg_out else None, sub,
                                dst_base + blk * T, T)],
            sem.at[slot],
        )

    def _append(vals, shift, fill, n, nblk, stage, outb, sem, dst, dst_base):
        """Append ``n`` columns of ``vals`` — those a lane rotation by
        ``shift`` (None: none needed) brings to lanes [fill, fill + n) mod T
        — to a stream whose staging holds ``fill`` (< T) pending columns.  A
        block that fills is flushed: flush ``nblk`` goes through buffer
        nblk % 2, which is waited for only now, before it is written again;
        the DMA is left in flight.  Columns rotated past lane T open the
        next block."""
        rl = vals if shift is None else pltpu.roll(vals, shift, 1)
        nf = fill + n
        cur = jnp.where((lane >= fill) & (lane < nf), rl, stage[...])
        full = nf >= T

        @pl.when(full)
        def _():
            slot = nblk % 2

            @pl.when(nblk >= 2)
            def _():
                _flush_dma(outb, sem, dst, dst_base, 0, slot).wait()

            outb[slot] = jax.lax.bitcast_convert_type(
                cur.astype(jnp.uint16), jnp.int16
            )
            _flush_dma(outb, sem, dst, dst_base, nblk, slot).start()

        stage[...] = jnp.where(lane < nf - T, rl, cur)
        fulli = full.astype(jnp.int32)
        return nf - fulli * T, nblk + fulli

    def _drain(nblk, outb, sem, dst):
        """Wait for the (at most two) flushes of a stream still in flight."""
        for back in (1, 2):
            @pl.when(nblk >= back)
            def _():
                _flush_dma(outb, sem, dst, 0, 0, (nblk - back) % 2).wait()

    def seg_cols(c0, cols):
        # boundary tiles must come through the OUTPUT alias — see
        # aliased_tile_dma for the interpret-mode pitfall this guards
        return _aliased_cols(
            seg_any, seg_out, sub, abegin + c0, cols, read_via_input, grp
        )

    def spill_cols(c0, cols):
        return scratch_out.at[
            pl.ds(0, sub), pl.ds(pl.multiple_of(c0, COL_ALIGN), cols)
        ]

    # ---- pass 1: split the window's tiles into the two streams
    def _count(t, xu, gl):
        """Which columns of tile ``t`` the left stream takes (its left rows
        and the alignment prefix; the right stream takes the rest), their
        inclusive running count and total nl_t — nothing here waits for the
        tile before it."""
        rpos = iota_j + t * T
        in_seg = (rpos >= off) & (rpos < off + cnt)
        # byte planes, lo over hi on the M axis: each exact in bf16
        x2 = jnp.concatenate([xu & 0xFF, xu >> 8], axis=0).astype(jnp.bfloat16)
        if use_gl:
            go = gl > 0.5  # [1, T]
        else:
            rows = jax.lax.dot_general(
                sel, x2, dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ).astype(jnp.int32)  # [8, T]
            colv = rows[0:1]
            if wide:
                # one u16 plane per feature (max_bin > 256)
                colv = colv | (rows[1:2] << 8)
            go = (colv <= tbin) | ((dl != 0) & (nanb >= 0) & (colv == nanb))
            if use_cat:
                oh = (
                    colv == jax.lax.broadcasted_iota(jnp.int32, (bmt, T), 0)
                ).astype(jnp.bfloat16)  # [bmt, T]
                catv = jax.lax.dot_general(
                    cat_ref[...].astype(jnp.bfloat16), oh,
                    dimension_numbers=(((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # [1, T]
                # select over f32 operands: an i1-operand select needs an
                # i1 truncation Mosaic does not implement
                gof = jnp.where(
                    jnp.broadcast_to(iscat != 0, go.shape),
                    catv, go.astype(jnp.float32),
                )
                go = gof > 0.5
        keep_l = (rpos < off) | (in_seg & go)
        keepf = keep_l.astype(jnp.float32)
        csum = jax.lax.dot_general(
            keepf.astype(jnp.bfloat16), tri_ref[...],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [1, T] inclusive count of left columns
        return x2, keepf, csum, csum[0, T - 1].astype(jnp.int32)

    def _compact(x2, keepf, csum, nl_t, fill_l):
        """ONE permutation a tile: a left column goes to its rank among the
        lefts, a right column to nl_t + its rank among the rights (its
        position less the lefts before it), all rotated by fill_l so that
        the lefts sit where the left staging wants them — no scatter
        anywhere."""
        dest = keepf * (csum - 1.0) + (1.0 - keepf) * (
            nl_t.astype(jnp.float32) + iota_jf - csum
        ) + fill_l.astype(jnp.float32)  # [1, T]
        dest = jnp.where(dest >= T, dest - T, dest)
        q = (iota_q == dest).astype(jnp.bfloat16)  # [T, T] one-hot rows
        comp = jax.lax.dot_general(
            x2, q, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(jnp.int32)  # [2 * sub, T]
        return comp[:sub] | (comp[sub:] << 8)

    def _split(cu, nl_t, nr_t, carry):
        fill_l, bl, fill_r, br = carry
        # the right columns begin at lane fill_l + nl_t of cu
        shift_r = (fill_r - fill_l - nl_t) & (T - 1)
        fill_l, bl = _append(
            cu, None, fill_l, nl_t, bl, stage_l, out_l, sem_l, seg_out, abegin
        )
        fill_r, br = _append(
            cu, shift_r, fill_r, nr_t, br, stage_r, out_r, sem_r,
            scratch_out, 0,
        )
        return fill_l, bl, fill_r, br

    def body1(i, carry):
        # G tiles an iteration, all counted and compacted before any is
        # moved: the tiles' matmul chains are independent but for the scalar
        # fill_l each takes from the counts before it, and they sit ahead of
        # the flushes' branches, where the scheduler can overlap them
        tiles = _tile_group(i, seg_cols, use_gl, nt)
        counts = [_count(G * i + j, *tiles[j]) for j in range(G)]
        fill_l = carry[0]
        cus = []
        for c in counts:
            cus.append(_compact(*c, fill_l))
            fill_l = (fill_l + c[3]) & (T - 1)
        for j, (c, cu) in enumerate(zip(counts, cus)):
            # past the last tile every column is beyond the window: nl_t is
            # 0 there and the right stream must take none either
            nr_t = jnp.where(G * i + j < nt, T - c[3], 0)
            carry = _split(cu, c[3], nr_t, carry)
        return carry

    _read_block(_start, seg_cols, use_gl, 0, nt, 0)
    zero = jnp.int32(0)
    fill_l, bl, fill_r, br = lax.fori_loop(
        0, (nt + G - 1) // G, body1, (zero, zero, zero, zero)
    )
    nl = bl * T + fill_l - off  # the left stream less its alignment prefix

    # ---- pass 2: the spilled right stream is a copy shifted by fill_l
    # lanes; its last, partial block never left the staging
    _drain(br, out_r, sem_r, scratch_out)  # the reads below need the spills

    def body2(i, bl):
        tiles = _tile_group(i, spill_cols, False, br)
        for j, (xu, _) in enumerate(tiles):
            _, bl = _append(
                xu, fill_l, fill_l, jnp.where(G * i + j < br, T, 0), bl,
                stage_l, out_l, sem_l, seg_out, abegin,
            )
        return bl

    _read_block(_start, spill_cols, False, 0, br, 0)
    bl = lax.fori_loop(0, (br + G - 1) // G, body2, bl)
    _, bl = _append(
        stage_r[...], fill_l, fill_l, fill_r, bl, stage_l, out_l, sem_l,
        seg_out, abegin,
    )
    _drain(bl, out_l, sem_l, seg_out)
    return nl


def _seg_partition_kernel(
    scal_ref,  # SMEM [K, 8] i32: sbegin, cnt, feat, tbin, dl, nanb, iscat,
    #          pad — one row per grid program (K=1 for the serial call)
    seg_any,  # ANY [LANES, n_pad] i16 (aliased to seg_out)
    cat_ref,  # VMEM [1, 256] f32 — bin -> goes-left (categorical); batched
    #          calls block a [K, bmt] table to one row per program
    tri_ref,  # VMEM [T, T] bf16 — tri[i, j] = (i <= j), cumsum-by-matmul
    gl_any,  # ANY [1, n_pad] f32 — precomputed go-left bits (use_gl; else
    #          a [1, COL_ALIGN] dummy)
    seg_out,  # ANY [LANES, n_pad] i16 (aliased with seg_any)
    scratch_out,  # ANY [SUB, n_pad] i16 — right-stream spill
    nl_ref,  # SMEM [K, 1] i32 — rows of the segment going left, per program
    *scratch,  # partition_scratch(sub, use_gl)
    f: int,
    n_pad: int,
    use_cat: bool,
    sub: int,
    wide: bool,
    bmt: int,
    use_gl: bool,
    read_via_input: bool = False,
    grouped: bool = False,
):
    # a grouped matrix: one window, program g permutes plane group g by the
    # same bits (every program finds, and writes, the same nl)
    pid = 0 if grouped else pl.program_id(0)
    nl_ref[pid, 0] = _partition_window(
        scal_ref[pid, 0],
        scal_ref[pid, 1],
        scal_ref[pid, 2],
        scal_ref[pid, 3],
        scal_ref[pid, 4],
        scal_ref[pid, 5],
        scal_ref[pid, 6],
        seg_any,
        seg_out,
        scratch_out,
        cat_ref,
        tri_ref,
        gl_any,
        scratch,
        use_cat=use_cat,
        sub=sub,
        wide=wide,
        bmt=bmt,
        use_gl=use_gl,
        read_via_input=read_via_input,
        grp=pl.program_id(0) if grouped else None,
    )


def _seg_partition(
    seg: jnp.ndarray,  # [LANES, n_pad] i16 plane-major packed rows
    scal: jnp.ndarray,  # [8] i32: sbegin, cnt, feat, tbin, dl, nanb, iscat, 0
    catmask: jnp.ndarray,  # [1, bmt] f32 (bmt >= 256, 128-multiple)
    gl_vec: jnp.ndarray = None,  # [n_pad] f32 go-left bits (featpar seg)
    *,
    f: int,
    n_pad: int,
    use_cat: bool,
    wide: bool = False,
    interpret: bool = False,
    read_via_input: bool = False,
):
    """Partition seg[sbegin : sbegin+cnt) by the split rule, in place.

    ``gl_vec``: the go-left decision comes from precomputed bits instead of
    the feature column (feature-parallel seg — only the owning shard holds
    the winner's bin plane).

    ``read_via_input``: test-only knob (see aliased_tile_dma).

    A grouped matrix (``[G, sub, n_pad]``, seg.pack_rows) needs ``gl_vec``
    and takes a G-program grid: one launch, the loop's code once, program g
    moving group g through the same VMEM scratch and HBM spill.

    Returns (seg', nl).  Left child lands at [sbegin, sbegin+nl), right at
    [sbegin+nl, sbegin+cnt), both in stable (original) order; every column
    outside the window keeps its value.
    """
    use_gl = gl_vec is not None
    grouped = is_grouped(seg)
    if grouped and not use_gl:
        raise ValueError(
            "a grouped segment matrix is partitioned by precomputed go-left "
            "bits (segpart.go_left_bits): pass gl_vec"
        )
    sub = seg.shape[1] if grouped else partition_sub(f, wide)
    tri = jnp.tril(jnp.ones((T, T), jnp.bfloat16)).T  # tri[i, j] = i <= j
    gl_arr = (
        gl_vec.reshape(1, n_pad).astype(jnp.float32)
        if use_gl
        else jnp.zeros((1, COL_ALIGN), jnp.float32)
    )
    kernel = functools.partial(
        _seg_partition_kernel, f=f, n_pad=n_pad, use_cat=use_cat, sub=sub,
        wide=wide, bmt=catmask.shape[1], use_gl=use_gl,
        read_via_input=read_via_input, grouped=grouped,
    )
    seg_new, _, nl = pl.pallas_call(
        kernel,
        grid=(seg.shape[0] if grouped else 1,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(seg.shape, jnp.int16),
            jax.ShapeDtypeStruct((sub, n_pad), jnp.int16),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        scratch_shapes=partition_scratch(sub, use_gl),
        input_output_aliases={1: 0},
        interpret=interpret,
    )(scal.reshape(1, 8), seg, catmask, tri, gl_arr)
    return seg_new, nl[0, 0]


_STATIC = ("f", "n_pad", "use_cat", "wide", "interpret", "read_via_input")


@functools.partial(instrumented_jit, static_argnames=_STATIC)
def seg_partition_pallas(*args, **kwargs):
    return _seg_partition(*args, **kwargs)


seg_partition_pallas.__doc__ = _seg_partition.__doc__


@functools.partial(instrumented_jit, static_argnames=_STATIC)
def bag_compact_pallas(*args, **kwargs):
    """``seg_partition_pallas`` under a name of its own: the once-a-tree
    stable partition on the bag's bits (``gl_vec`` = in-bag) that brings the
    in-bag rows to the front of the packed buffer (ops/grower.py,
    ``bag_window``).  A device trace names a kernel by its entry, and the
    splits' partitions are read against the splits' work."""
    return _seg_partition(*args, **kwargs)


@functools.partial(
    instrumented_jit,
    static_argnames=("f", "n_pad", "use_cat", "wide", "interpret",
                     "read_via_input"),
)
def seg_partition_pallas_batch(
    seg: jnp.ndarray,  # [LANES, n_pad] i16 plane-major packed rows
    scal: jnp.ndarray,  # [K, 8] i32 rows: sbegin, cnt, feat, tbin, dl,
    #                     nanb, iscat, 0 — one DISJOINT window per row
    catmask: jnp.ndarray,  # [K, bmt] f32 (bmt >= 256, 128-multiple)
    *,
    f: int,
    n_pad: int,
    use_cat: bool,
    wide: bool = False,
    interpret: bool = False,
    read_via_input: bool = False,
):
    """K in-place stable partitions over K disjoint windows in ONE launch.

    A K-program grid over the serial streaming kernel: TPU grid programs
    execute sequentially on the core, so the in-place aliasing and shared
    staging scratch stay safe — each program completes its read-rewrite of
    its (over-covered, boundary-preserving) window before the next starts.
    A zero-cnt row is a no-op (its window rewrite preserves every value).
    Frontier-batched growth (ops/grower.py leaf_batch) pays ONE program's
    fixed cost for K splits.

    ``read_via_input``: test-only knob (see aliased_tile_dma).

    Returns (seg', nl[K])."""
    if is_grouped(seg):
        raise ValueError(
            "the batched partition takes a one-group segment matrix; a "
            "grouped one is partitioned window by window (leaf_batch=1)"
        )
    k = scal.shape[0]
    sub = partition_sub(f, wide)
    lanes = seg.shape[0]
    bmt = catmask.shape[1]
    tri = jnp.tril(jnp.ones((T, T), jnp.bfloat16)).T  # tri[i, j] = i <= j
    gl_arr = jnp.zeros((1, COL_ALIGN), jnp.float32)
    kernel = functools.partial(
        _seg_partition_kernel, f=f, n_pad=n_pad, use_cat=use_cat, sub=sub,
        wide=wide, bmt=bmt, use_gl=False, read_via_input=read_via_input,
    )
    seg_new, _, nl = pl.pallas_call(
        kernel,
        grid=(k,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            # one catmask row per program, so the kernel body sees the same
            # [1, bmt] block the serial call passes.  The table rides as
            # [K, 1, bmt] with the member axis squeezed: Mosaic rejects a
            # (1, bmt) block of a (K, bmt) array (second-minor block dim
            # must be a multiple of 8 or the full dim)
            pl.BlockSpec(
                (None, 1, bmt), lambda i: (i, 0, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((lanes, n_pad), jnp.int16),
            jax.ShapeDtypeStruct((sub, n_pad), jnp.int16),
            jax.ShapeDtypeStruct((k, 1), jnp.int32),
        ],
        scratch_shapes=partition_scratch(sub, False),
        input_output_aliases={1: 0},
        interpret=interpret,
    )(scal.astype(jnp.int32), seg, catmask.reshape(k, 1, bmt), tri, gl_arr)
    return seg_new, nl[:, 0]
