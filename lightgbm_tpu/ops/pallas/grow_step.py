"""Fused grow step — partition + smaller-child histogram in ONE Pallas launch.

The frontier-batched grower (ops/grower.py, leaf_batch=K) already amortizes
per-split fixed cost, but each compiled step still runs partition ->
election -> histogram as separately-launched regions with full HBM
round-trips and dispatch gaps between them.  This kernel fuses the
per-member pipeline over a
PLANE-TILED ``(K, G)`` grid (batch member x feature-plane group — the
histogram-engine-v2 layout shared with seg.py): for each of the K disjoint
frontier windows, the member's FIRST plane program

  1. streams the window once and stably partitions it in place
     (partition._partition_window — the exact machinery of the standalone
     seg partition kernel);
  2. elects the smaller child locally and parks the decision in the
     persistent SMEM ``dec`` output (nl <= cnt - nl — the grower's
     single-host election; under tree_learner=data the election needs a
     psum of per-shard counts MID-STEP, which is why the fused path only
     engages when no axis_name is set and the two-launch path remains the
     data-parallel fallback);

and then EVERY plane program (i, pt) — grid programs run sequentially, so
(i, 0)'s writes are visible — reads the decision back and histograms its
plane group over the freshly-partitioned rows (seg._hist_window), reading
tiles through the OUTPUT alias so the histogram observes the partition's
writes (partition.aliased_tile_dma — the same idiom that fixes
cross-program boundary reads, and the reason the fused kernel works at
all: the partition happened in an EARLIER program of the same sequential
grid).  Dead plane groups (feature_fraction / EFB) skip their tile loop
via the ``live`` mask.  Each program emits one RAW [8, group*bpad]
accumulator block (i32 on the int8 path, f32 on bf16); the digit
recombine runs outside the kernel (seg.combine_hist_raw).  The best-split
scan stays a separate launch: it needs the psummed histogram under
tree_learner=data and the parent-minus-child sibling subtraction, neither
of which is per-member-local.  On the basic numeric path it runs as the
existing fused Pallas scan (ops/pallas/split_scan.py), so the whole grow
step is two kernel launches instead of three compiled regions plus their
dispatch boundaries.

Plane-tiling trade (same as seg.py): per-program VMEM scratch shrinks to
O(group*bpad) — independent of F — at the cost of each plane program
re-streaming the window's stat planes (G-fold redundant DMA, read one tile
ahead of the two-digit one-hot's matmuls and hidden under them).

The XLA composition (`sort_partition_xla` chain + local election + masked
reference histogram) is the always-available fallback AND the correctness
oracle — it is definitionally the same computation the two-launch grower
path performs (including the windowed CPU histogram, seg.seg_hist_cpu), so
CPU results are byte-identical by construction and tests/test_fused_step.py
asserts the Pallas kernel (interpret mode off-TPU) matches it bit-for-bit.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ...obs.jit import instrumented_jit
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .partition import (
    T,
    _partition_window,
    aliased_tile_dma,
    partition_scratch,
    partition_sub,
)
from .seg import (
    COL_ALIGN,
    _hist_window,
    combine_hist_raw,
    hist_bpad,
    hist_group,
    hist_ngroups,
    hist_scratch,
    hist_step,
    hist_sub,
)

# Test hook: route the fused step through the Pallas interpret-mode kernel
# even off-TPU (tools/run_tests.sh smoke + tests/test_fused_step.py).  Read
# at TRACE time — flip it before the first train in a fresh process, or use
# params that force a fresh trace; a cached trace keeps the path it was
# traced with (the XLA oracle, which is parity-identical).
_INTERPRET = False


def _fused_grow_kernel(
    scal_ref,  # SMEM [K, 8] i32: sbegin, cnt, feat, tbin, dl, nanb, iscat, 0
    scales_ref,  # SMEM [2] f32: g_scale, h_scale (int8 mode; else 1s)
    live_ref,  # SMEM [G] i32: per-plane-group live mask
    seg_any,  # ANY [LANES, n_pad] i16 (aliased to seg_out)
    cat_ref,  # VMEM [1, bmt] f32 block — bin -> goes-left, one row/member
    tri_ref,  # VMEM [T, T] bf16 — tri[i, j] = (i <= j), cumsum-by-matmul
    gl_any,  # ANY [1, COL_ALIGN] f32 dummy (featpar never takes this path)
    seg_out,  # ANY [LANES, n_pad] i16 (aliased with seg_any)
    scratch_out,  # ANY [SUB_P, n_pad] i16 — partition right-stream spill
    dec_ref,  # SMEM [K, 4] i32: nl, nr, child_start, child_cnt per member
    hist_ref,  # VMEM [1, 1, 8, group * bpad] f32 | i32 block (raw planes)
    *scratch,  # partition_scratch(sub_p, False), then the histogram's
    #            (seg.hist_scratch: two staging slots, the two-digit one-hot's
    #            operands and accumulators, the slots' semaphores)
    f: int,
    n_pad: int,
    use_cat: bool,
    sub_p: int,
    sub_h: int,
    wide: bool,
    bmt: int,
    bpad: int,
    group: int,
    quantized: bool,
    n_hist: int,  # refs of seg.hist_scratch at the end of ``scratch``
    read_via_input: bool = False,
):
    part_scratch, hist_scratch_refs = scratch[:-n_hist], scratch[-n_hist:]
    hist_stage, sem_hist = hist_scratch_refs[0], hist_scratch_refs[-1]
    i = pl.program_id(0)
    pt = pl.program_id(1)
    sbegin = scal_ref[i, 0]
    cnt = scal_ref[i, 1]

    # ---- phases 1+2 run ONCE per member, on its first plane program
    @pl.when(pt == 0)
    def _partition_and_elect():
        # phase 1: in-place stable partition of this member's window
        nl = _partition_window(
            sbegin,
            cnt,
            scal_ref[i, 2],
            scal_ref[i, 3],
            scal_ref[i, 4],
            scal_ref[i, 5],
            scal_ref[i, 6],
            seg_any,
            seg_out,
            scratch_out,
            cat_ref,
            tri_ref,
            gl_any,
            part_scratch,
            use_cat=use_cat,
            sub=sub_p,
            wide=wide,
            bmt=bmt,
            use_gl=False,
            read_via_input=read_via_input,
        )
        # phase 2: local smaller-child election (single-host rule; the
        # data-parallel psummed election cannot live mid-kernel, so that
        # mode keeps the two-launch path — see module docstring).  The
        # decision lands in the persistent SMEM output so this member's
        # later plane programs can read it back.
        nr = cnt - nl
        left_smaller = nl <= nr
        dec_ref[i, 0] = nl
        dec_ref[i, 1] = nr
        dec_ref[i, 2] = sbegin + jnp.where(left_smaller, 0, nl)
        dec_ref[i, 3] = jnp.where(left_smaller, nl, nr)

    # ---- phase 3: this plane group's histogram over the JUST-partitioned
    # rows; tiles come through the output alias so phase 1's writes (from
    # this member's pt==0 program) are visible
    child_start = dec_ref[i, 2]
    child_cnt = dec_ref[i, 3]

    def tile_dmas(slot, base_col, width):
        return [aliased_tile_dma(
            seg_any, seg_out, hist_stage.at[slot, :, pl.ds(0, width)],
            sem_hist.at[slot], base_col, read_via_input=read_via_input,
        )]

    _hist_window(
        child_start,
        child_cnt,
        pt,
        live_ref[pt],
        tile_dmas,
        scales_ref,
        hist_ref.at[0, 0],
        *hist_scratch_refs[:-1],
        f=f,
        bpad=bpad,
        group=group,
        quantized=quantized,
        wide=wide,
    )


@functools.partial(
    instrumented_jit,
    static_argnames=(
        "f", "num_bins", "n_pad", "use_cat", "quantized", "wide",
        "interpret", "read_via_input",
    ),
)
def fused_grow_step_pallas(
    seg: jnp.ndarray,  # [LANES, n_pad] i16 plane-major packed rows
    scal: jnp.ndarray,  # [K, 8] i32 rows: sbegin, cnt, feat, tbin, dl,
    #                     nanb, iscat, 0 — one DISJOINT window per member
    catmask: jnp.ndarray,  # [K, bmt] f32 (bmt >= 256, 128-multiple)
    scales: jnp.ndarray,  # [2] f32 grid scales (int8 mode; else 1s)
    live: jnp.ndarray,  # [G] i32 plane-group live mask
    *,
    f: int,
    num_bins: int,
    n_pad: int,
    use_cat: bool,
    quantized: bool = False,
    wide: bool = False,
    interpret: bool = False,
    read_via_input: bool = False,
):
    """K fused partition+election+histogram steps in ONE kernel launch.

    Returns (seg', dec[K, 4], hist[K, 3, F, B]) with dec rows
    (nl, nr, child_start, child_cnt).  Grid programs run sequentially on
    the core, so the in-place aliasing, the shared scratch, and the
    dec-written-at-pt==0 handoff stay safe program-to-program (same
    argument as the batched partition kernel)."""
    k = scal.shape[0]
    lanes = seg.shape[0]
    bmt = catmask.shape[1]
    # hist tiles DMA only the used planes padded to an i16 sublane multiple
    sub_p = partition_sub(f, wide)
    sub_h = hist_sub(f, wide)
    bpad = hist_bpad(num_bins)
    group = hist_group(f, bpad)
    ngroups = hist_ngroups(f, bpad)
    acc_dtype = jnp.int32 if quantized else jnp.float32
    tri = jnp.tril(jnp.ones((T, T), jnp.bfloat16)).T  # tri[i, j] = i <= j
    gl_arr = jnp.zeros((1, COL_ALIGN), jnp.float32)
    hist_refs = hist_scratch(
        f, bpad, sub_h, quantized,
        step=hist_step(f, bpad, sub_h, seg.shape[-1]))
    kernel = functools.partial(
        _fused_grow_kernel, f=f, n_pad=n_pad, use_cat=use_cat, sub_p=sub_p,
        sub_h=sub_h, wide=wide, bmt=bmt, bpad=bpad, group=group,
        quantized=quantized, n_hist=len(hist_refs),
        read_via_input=read_via_input,
    )
    seg_new, _, dec, raw = pl.pallas_call(
        kernel,
        grid=(k, ngroups),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            # member axis squeezed out of a [K, 1, bmt] table (see
            # seg_partition_pallas_batch: a (1, bmt) block of (K, bmt) is
            # not a legal Mosaic block)
            pl.BlockSpec(
                (None, 1, bmt), lambda i, pt: (i, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(
                (1, 1, 8, group * bpad), lambda i, pt: (i, pt, 0, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((lanes, n_pad), jnp.int16),
            jax.ShapeDtypeStruct((sub_p, n_pad), jnp.int16),
            jax.ShapeDtypeStruct((k, 4), jnp.int32),
            jax.ShapeDtypeStruct((k, ngroups, 8, group * bpad), acc_dtype),
        ],
        scratch_shapes=partition_scratch(sub_p, False) + hist_refs,
        input_output_aliases={3: 0},
        interpret=interpret,
    )(scal.astype(jnp.int32), scales.astype(jnp.float32),
      live.astype(jnp.int32), seg, catmask.reshape(k, 1, bmt), tri, gl_arr)
    hist = combine_hist_raw(
        raw, scales.astype(jnp.float32), f=f, bpad=bpad, group=group,
        num_bins=num_bins, quantized=quantized,
    )
    return seg_new, dec, hist


def fused_grow_step(
    seg,
    sbegins,  # [K] i32 — segment begins (disjoint windows; K=1 for serial)
    cnts,  # [K] i32 — segment rows (0 = no-op member)
    feats,  # [K] i32
    tbins,  # [K] i32
    dls,  # [K] i32
    nanbs,  # [K] i32
    iscats,  # [K] i32
    catmasks,  # [K, Bm] f32
    *,
    f: int,
    num_bins: int,
    n_pad: int,
    quant_scales=None,
    wide: bool = False,
    live=None,  # [G] i32 plane-group live mask (None = all live)
):
    """Platform dispatch for the fused grow step.

    TPU: one (K, G)-program Pallas launch (2-digit int8 accumulation when
    ``quant_scales`` is given — quantized training or the grower's default
    hist accumulator, like seg_hist).  Elsewhere: the XLA oracle
    composition — sequential stable-sort partitions (disjoint windows make
    the chain order-independent), the same local election, and the
    windowed/masked reference histogram (seg.seg_hist_batch_cpu, the exact
    computation the two-launch grower path performs), so CPU training is
    byte-identical by construction.  The ``_INTERPRET`` hook routes off-TPU
    calls through the interpret-mode kernel instead, which is how tier-1
    exercises the kernel without a TPU.

    Returns (seg', nl[K], nr[K], child_start[K], child_cnt[K],
    hist[K, 3, F, B])."""
    # fault-injection consult (trace time — the moment a Mosaic compile
    # failure would surface); disarmed it costs one dict truthiness check
    from ...resilience import chaos

    chaos.maybe_raise_pallas("fused_grow_step")

    from ..segpart import sort_partition_xla
    from .seg import seg_hist_batch_cpu

    k = sbegins.shape[0]
    quantized = quant_scales is not None
    scales = (
        jnp.stack([quant_scales[0], quant_scales[1]]).astype(jnp.float32)
        if quantized
        else jnp.ones((2,), jnp.float32)
    )
    if live is None:
        live = jnp.ones((hist_ngroups(f, hist_bpad(num_bins)),), jnp.int32)

    def _pallas(seg, sbegins, cnts, feats, tbins, dls, nanbs, iscats,
                catmasks, scales, live, interpret=False):
        bm = catmasks.shape[1]
        bmt = max(256, -(-bm // 128) * 128)  # cat-table width (wide bins)
        catm = jnp.zeros((k, bmt), jnp.float32)
        catm = catm.at[:, :bm].set(catmasks.astype(jnp.float32))
        scal = jnp.stack(
            [sbegins, cnts, feats, tbins, dls, nanbs, iscats,
             jnp.zeros_like(sbegins)],
            axis=1,
        ).astype(jnp.int32)
        seg_new, dec, hist = fused_grow_step_pallas(
            seg, scal, catm, scales, live, f=f, num_bins=num_bins,
            n_pad=n_pad, use_cat=bm > 1, quantized=quantized, wide=wide,
            interpret=interpret,
        )
        return seg_new, dec[:, 0], dec[:, 1], dec[:, 2], dec[:, 3], hist

    def _xla(seg, sbegins, cnts, feats, tbins, dls, nanbs, iscats,
             catmasks, _scales, _live):
        # the oracle ignores quant_scales/live, matching seg_hist's CPU
        # behavior (f32 histograms of every plane — the byte-level
        # reference the int8/plane-skip fast path is validated against)
        nls = []
        for i in range(k):
            seg, nl_i, _ = sort_partition_xla(
                seg, sbegins[i], cnts[i], feats[i], tbins[i], dls[i],
                nanbs[i], iscats[i], catmasks[i],
                f=f, n_pad=n_pad, wide=wide, use_gl_vec=False,
            )
            nls.append(nl_i)
        nl = jnp.stack(nls)
        nr = cnts - nl
        left_smaller = nl <= nr
        child_start = sbegins + jnp.where(left_smaller, 0, nl)
        child_cnt = jnp.where(left_smaller, nl, nr)
        hist = seg_hist_batch_cpu(
            seg,
            jnp.stack([child_start, child_cnt], axis=1).astype(jnp.int32),
            f=f, num_bins=num_bins, n_pad=n_pad, wide=wide,
        )
        return seg, nl, nr, child_start, child_cnt, hist

    args = (seg, sbegins, cnts, feats, tbins, dls, nanbs, iscats, catmasks,
            scales, live)
    if jax.default_backend() != "tpu":
        # no TPU in this process: the XLA oracle without tracing the Pallas
        # branch, or the interpret-mode kernel under the _INTERPRET test hook
        if _INTERPRET:
            return _pallas(*args, interpret=True)
        return _xla(*args)
    return jax.lax.platform_dependent(*args, tpu=_pallas, default=_xla)
