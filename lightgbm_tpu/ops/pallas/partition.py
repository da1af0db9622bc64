"""Streaming in-place stable partition of packed rows — Pallas TPU kernel.

Reference analogs: ``DataPartition::Split`` (src/treelearner/data_partition.hpp:101)
and the CUDA partition pipeline (``GenDataToLeftBitVectorKernel`` -> prefix
sums -> ``SplitInnerKernel``, src/treelearner/cuda/cuda_data_partition.cu).

Why this kernel exists: the round-2 design partitioned a leaf's contiguous
window with ``lax.sort`` over pow-2 capacity buckets (ops/segpart.py).  That
was already the fastest pure-XLA formulation (~6 ns/row for the 44-byte
packed row), but it pays (a) a multi-pass comparison sort for what is a
1-bit-key partition, (b) up to 2x window overshoot from the pow-2 ladder,
and (c) a defensive full-array copy per ``lax.switch`` branch (~0.45 ms per
1M rows, measured).  This kernel streams the EXACT window once, tile by
tile, and compacts rows with ONE-HOT MATMULS — the MXU as a crossbar.  TPUs
have no vector scatter/compaction primitive; a permutation applied as a
``[T, W]`` 0/1 matrix multiply is exact (i16 planes split into two 0..255
byte planes, each exact in bf16) and runs at MXU rate, far above the
serialized per-element path XLA lowers gathers/scatters to.

Algorithm (stable, in place, ~2.5 HBM passes over the window):
  pass 1: stream aligned ``[SUB, T]`` tiles of the window left to right.
    Per tile: evaluate the split predicate on the packed bin byte, then
    matmul-compact the tile's LEFT rows (plus the sub-tile alignment
    prefix) into a VMEM staging buffer and its RIGHT rows (plus the
    alignment suffix) into a second staging buffer.  Full staged blocks
    flush with aligned DMA writes: the left stream writes IN PLACE (flush
    position provably trails the read cursor), the right stream writes to
    an HBM scratch buffer.
  pass 2: stream the right scratch back through the same staging machinery,
    appending after the left stream — every block write is 128-aligned, and
    the two passes together rewrite exactly the tiles pass 1 read.

Stability: both children preserve original row order (streams keep tile
order and the in-tile compaction keeps column order), so results are
bit-identical to the stable-sort path this replaces.

The per-window body is factored into ``_partition_window`` so the fused
grow-step kernel (ops/pallas/grow_step.py) can run partition + smaller-child
histogram in ONE launch; ``read_aliased_tile`` is the shared
read-through-the-output-alias helper both kernels use (see its docstring
for the interpret-mode aliasing pitfall it guards against).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...obs.jit import instrumented_jit
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .seg import COL_ALIGN, used_lanes

T = 256  # streaming tile columns (rows of training data)
W = 2 * T  # staging width: residual (< T) + one tile's append (<= T)


def _bytes_bf16(xu):
    """Split u16 values [SUB, T] into two exact-in-bf16 byte planes."""
    lo = (xu & 0xFF).astype(jnp.bfloat16)
    hi = ((xu >> 8) & 0xFF).astype(jnp.bfloat16)
    return lo, hi


def read_aliased_tile(seg_in, seg_out, stage, sem, base_col, *,
                      read_via_input: bool = False):
    """DMA one aligned ``[sub, cols]`` tile of an IN-PLACE (input/output-
    aliased) packed segment matrix into VMEM ``stage``; return u16-in-i32.

    Reads go through the OUTPUT alias, not the input ref: on TPU they are
    the same HBM buffer, but batched grids re-read boundary tiles an
    earlier program (or an earlier phase of the SAME program, in the fused
    grow-step kernel) already rewrote — adjacent leaf windows share
    COL_ALIGN blocks — and Pallas interpret mode only makes those writes
    visible on the output ref.  Shared by the seg partition kernel and the
    fused grow-step kernel (ops/pallas/grow_step.py).

    ``read_via_input=True`` recreates the PR-3 aliasing bug by reading the
    input ref instead — a TEST-ONLY knob for the regression test in
    tests/test_partition_kernel.py; never set it from production code.
    """
    sub, cols = stage.shape
    src = seg_in if read_via_input else seg_out
    dma = pltpu.make_async_copy(
        # the input-ref read below is unreachable in production: it only
        # engages under the test-only read_via_input knob documented above
        src.at[pl.ds(0, sub), pl.ds(pl.multiple_of(base_col, COL_ALIGN), cols)],  # graftlint: disable=GL002
        stage,
        sem,
    )
    dma.start()
    dma.wait()
    return stage[...].astype(jnp.int32) & 0xFFFF


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
    gl_any,  # ANY [1, n_pad] f32 go-left bits, or None when not use_gl
    in_stage,  # VMEM [SUB, T] i16
    out_stage,  # VMEM [SUB, T] i16
    stage_lo,  # VMEM [SUB, W] f32 — left/main stream staging (lo bytes)
    stage_hi,  # VMEM [SUB, W] f32
    rstage_lo,  # VMEM [SUB, W] f32 — right stream staging
    rstage_hi,  # VMEM [SUB, W] f32
    gl_stage,  # VMEM [1, T] f32 go-left tile, or None when not use_gl
    sem_in,
    sem_out,
    sem_gl,
    *,
    use_cat: bool,
    sub: int,
    wide: bool,
    bmt: int,
    use_gl: bool,
    read_via_input: bool = False,
):
    """Stable in-place partition of ONE leaf window (the per-program body of
    the seg partition kernel, factored out so the fused grow-step kernel can
    run it before its histogram phase).  Returns nl — rows going left."""
    abegin = (sbegin // COL_ALIGN) * COL_ALIGN
    off = sbegin - abegin
    nt = (off + cnt + T - 1) // T

    iota_j = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
    # tpu.iota only produces integers; cast for the f32 dest compare.
    # [W, T] orientation: dest stays a [1, T] row (Mosaic cannot legalize
    # the [1, T] -> [T, 1] transpose) and the compact matmul contracts the
    # shared T dim of lo/hi and Q ("NT" form).
    iota_q = jax.lax.broadcasted_iota(jnp.int32, (W, T), 0).astype(jnp.float32)

    stage_lo[...] = jnp.zeros_like(stage_lo)
    stage_hi[...] = jnp.zeros_like(stage_hi)
    rstage_lo[...] = jnp.zeros_like(rstage_lo)
    rstage_hi[...] = jnp.zeros_like(rstage_hi)

    def _append(lo, hi, keep, fill, slo, shi):
        """Matmul-compact `keep` columns of the tile into staging at `fill`.

        P[j, w] = keep[j] & (dest[j] == w) with dest[j] = fill - 1 +
        (#kept among cols <= j); built from iota compares plus one
        cumsum-by-triangular-matmul — no scatter anywhere."""
        keepf = keep.astype(jnp.bfloat16)  # [1, T]
        csum = jax.lax.dot_general(
            keepf, tri_ref[...],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [1, T] inclusive cumsum
        nkeep = csum[0, T - 1].astype(jnp.int32)
        # fold `keep` into dest arithmetically (dropped rows -> -1, matching
        # no staging lane): kept rows have csum >= 1 so dest >= fill >= 0
        keep32 = keep.astype(jnp.float32)
        dest = (csum + (fill - 1).astype(jnp.float32)) * keep32 - (
            1.0 - keep32
        )  # [1, T]
        Q = (iota_q == dest).astype(jnp.bfloat16)  # [W, T] one-hot rows
        slo[...] += jax.lax.dot_general(
            lo, Q, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        shi[...] += jax.lax.dot_general(
            hi, Q, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return fill + nkeep

    def _combine_block(slo, shi):
        lo32 = slo[:, :T].astype(jnp.int32)
        hi32 = shi[:, :T].astype(jnp.int32)
        u16 = (lo32 | (hi32 << 8)).astype(jnp.uint16)
        out_stage[...] = jax.lax.bitcast_convert_type(u16, jnp.int16)

    def _flush(fill, nblk, slo, shi, dst, dst_base):
        """If a full block is staged, DMA it out and shift staging left."""
        do = fill >= T

        @pl.when(do)
        def _():
            _combine_block(slo, shi)
            dma = pltpu.make_async_copy(
                out_stage,
                dst.at[
                    pl.ds(0, sub),
                    pl.ds(pl.multiple_of(dst_base + nblk * T, COL_ALIGN), T),
                ],
                sem_out,
            )
            dma.start()
            dma.wait()
            slo[:, :T] = slo[:, T:]
            slo[:, T:] = jnp.zeros((sub, T), jnp.float32)
            shi[:, :T] = shi[:, T:]
            shi[:, T:] = jnp.zeros((sub, T), jnp.float32)

        doi = do.astype(jnp.int32)
        return fill - doi * T, nblk + doi

    def body1(t, carry):
        fill_l, bl, fill_r, br, nl = carry
        # boundary tiles must come through the OUTPUT alias — see
        # read_aliased_tile for the interpret-mode pitfall this guards
        xu = read_aliased_tile(
            seg_any, seg_out, in_stage, sem_in, abegin + t * T,
            read_via_input=read_via_input,
        )
        rpos = iota_j + t * T
        in_seg = (rpos >= off) & (rpos < off + cnt)
        if use_gl:
            # precomputed go-left bits (feature-parallel seg: the winner's
            # plane lives on the owning shard; the bits arrived by psum)
            dma = pltpu.make_async_copy(
                gl_any.at[
                    pl.ds(0, 1),
                    pl.ds(pl.multiple_of(abegin + t * T, COL_ALIGN), T),
                ],
                gl_stage,
                sem_gl,
            )
            dma.start()
            dma.wait()
            go = gl_stage[...] > 0.5  # [1, T]
        else:
            # Mosaic has no value-level dynamic_slice: extract the feature's
            # lane with a one-hot row matmul over the exact bf16 byte planes
            # (0..255 each — the MXU as a dynamic row gather)
            lane = feat if wide else feat >> 1
            lane_oh = (
                jax.lax.broadcasted_iota(jnp.int32, (1, sub), 1) == lane
            ).astype(jnp.bfloat16)
            xlo, xhi = _bytes_bf16(xu)
            row_lo = jax.lax.dot_general(
                lane_oh, xlo, dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ).astype(jnp.int32)  # [1, T]
            row_hi = jax.lax.dot_general(
                lane_oh, xhi, dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ).astype(jnp.int32)
            if wide:
                # one u16 plane per feature (max_bin > 256)
                colv = row_lo | (row_hi << 8)  # [1, T]
            else:
                # scalar-cond select over a vector fails Mosaic
                # legalization; broadcast the condition first
                odd = jnp.broadcast_to((feat & 1) != 0, row_lo.shape)
                colv = jnp.where(odd, row_hi, row_lo)
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
        keep_r = jnp.logical_not(keep_l)
        nl = nl + jnp.sum((in_seg & go).astype(jnp.int32))
        lo, hi = _bytes_bf16(xu)
        fill_l = _append(lo, hi, keep_l, fill_l, stage_lo, stage_hi)
        fill_l, bl = _flush(fill_l, bl, stage_lo, stage_hi, seg_out, abegin)
        fill_r = _append(lo, hi, keep_r, fill_r, rstage_lo, rstage_hi)
        fill_r, br = _flush(fill_r, br, rstage_lo, rstage_hi, scratch_out, 0)
        return fill_l, bl, fill_r, br, nl

    fill_l, bl, fill_r, br, nl = lax.fori_loop(
        0,
        nt,
        body1,
        (jnp.int32(0), jnp.int32(0), jnp.int32(0), jnp.int32(0), jnp.int32(0)),
    )

    # spill the partial right-stream block (cols beyond fill_r are garbage;
    # pass 2 masks them out via the stream length)
    @pl.when(fill_r > 0)
    def _():
        _combine_block(rstage_lo, rstage_hi)
        dma = pltpu.make_async_copy(
            out_stage,
            scratch_out.at[
                pl.ds(0, sub), pl.ds(pl.multiple_of(br * T, COL_ALIGN), T)
            ],
            sem_out,
        )
        dma.start()
        dma.wait()

    # ---- pass 2: append the right stream after the left stream
    sr = nt * T - off - nl  # right-stream length (rights + alignment suffix)
    nt2 = (sr + T - 1) // T

    def body2(t2, carry):
        fill_l, bl = carry
        xu = read_aliased_tile(
            scratch_out, scratch_out, in_stage, sem_in, t2 * T,
        )
        spos = iota_j + t2 * T
        keep = spos < sr
        lo, hi = _bytes_bf16(xu)
        fill_l = _append(lo, hi, keep, fill_l, stage_lo, stage_hi)
        fill_l, bl = _flush(fill_l, bl, stage_lo, stage_hi, seg_out, abegin)
        return fill_l, bl

    lax.fori_loop(0, nt2, body2, (fill_l, bl))
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
    in_stage,  # VMEM [SUB, T] i16
    out_stage,  # VMEM [SUB, T] i16
    stage_lo,  # VMEM [SUB, W] f32 — left/main stream staging (lo bytes)
    stage_hi,  # VMEM [SUB, W] f32
    rstage_lo,  # VMEM [SUB, W] f32 — right stream staging
    rstage_hi,  # VMEM [SUB, W] f32
    gl_stage,  # VMEM [1, T] f32 — go-left tile (use_gl)
    sem_in,
    sem_out,
    sem_gl,
    *,
    f: int,
    n_pad: int,
    use_cat: bool,
    sub: int,
    wide: bool,
    bmt: int,
    use_gl: bool,
    read_via_input: bool = False,
):
    pid = pl.program_id(0)
    nl = _partition_window(
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
        in_stage,
        out_stage,
        stage_lo,
        stage_hi,
        rstage_lo,
        rstage_hi,
        gl_stage,
        sem_in,
        sem_out,
        sem_gl,
        use_cat=use_cat,
        sub=sub,
        wide=wide,
        bmt=bmt,
        use_gl=use_gl,
        read_via_input=read_via_input,
    )
    nl_ref[pid, 0] = nl


@functools.partial(
    instrumented_jit,
    static_argnames=("f", "n_pad", "use_cat", "wide", "interpret",
                     "read_via_input"),
)
def seg_partition_pallas(
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

    ``read_via_input``: test-only knob (see read_aliased_tile).

    Returns (seg', nl).  Left child lands at [sbegin, sbegin+nl), right at
    [sbegin+nl, sbegin+cnt), both in stable (original) order; every column
    outside the window keeps its value.
    """
    use_gl = gl_vec is not None
    # Mosaic requires second-minor DMA slice shapes in 8-sublane multiples
    sub = -(-used_lanes(f, wide) // 8) * 8
    lanes = seg.shape[0]
    tri = jnp.tril(jnp.ones((T, T), jnp.bfloat16)).T  # tri[i, j] = i <= j
    gl_arr = (
        gl_vec.reshape(1, n_pad).astype(jnp.float32)
        if use_gl
        else jnp.zeros((1, COL_ALIGN), jnp.float32)
    )
    kernel = functools.partial(
        _seg_partition_kernel, f=f, n_pad=n_pad, use_cat=use_cat, sub=sub,
        wide=wide, bmt=catmask.shape[1], use_gl=use_gl,
        read_via_input=read_via_input,
    )
    seg_new, _, nl = pl.pallas_call(
        kernel,
        grid=(1,),
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
            jax.ShapeDtypeStruct((lanes, n_pad), jnp.int16),
            jax.ShapeDtypeStruct((sub, n_pad), jnp.int16),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((sub, T), jnp.int16),
            pltpu.VMEM((sub, T), jnp.int16),
            pltpu.VMEM((sub, W), jnp.float32),
            pltpu.VMEM((sub, W), jnp.float32),
            pltpu.VMEM((sub, W), jnp.float32),
            pltpu.VMEM((sub, W), jnp.float32),
            pltpu.VMEM((1, T), jnp.float32),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
        input_output_aliases={1: 0},
        interpret=interpret,
    )(scal.reshape(1, 8), seg, catmask, tri, gl_arr)
    return seg_new, nl[0, 0]


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

    ``read_via_input``: test-only knob (see read_aliased_tile).

    Returns (seg', nl[K])."""
    k = scal.shape[0]
    sub = -(-used_lanes(f, wide) // 8) * 8
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
        scratch_shapes=[
            pltpu.VMEM((sub, T), jnp.int16),
            pltpu.VMEM((sub, T), jnp.int16),
            pltpu.VMEM((sub, W), jnp.float32),
            pltpu.VMEM((sub, W), jnp.float32),
            pltpu.VMEM((sub, W), jnp.float32),
            pltpu.VMEM((sub, W), jnp.float32),
            pltpu.VMEM((1, T), jnp.float32),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
        input_output_aliases={1: 0},
        interpret=interpret,
    )(scal.astype(jnp.int32), seg, catmask.reshape(k, 1, bmt), tri, gl_arr)
    return seg_new, nl[:, 0]
