"""Sort-based segment partition — the TPU-native DataPartition::Split.

Reference analog: ``DataPartition::Split`` (src/treelearner/data_partition.hpp:101)
and the CUDA partition pipeline (``GenDataToLeftBitVectorKernel`` -> prefix
sums -> ``SplitInnerKernel``, src/treelearner/cuda/cuda_data_partition.cu).

The reference keeps an index indirection and gathers `ordered_gradients`;
on TPU random gathers serialize (~35 ns/element), so instead the rows live
physically in leaf-segment order (see ops/pallas/seg.py for the row layout)
and each split STABLY SORTS the parent's contiguous window by a small key:

  key 0: rows before the segment (window over-covers for static shapes)
  key 1: rows of the segment going left
  key 2: rows of the segment going right
  key 3: rows after the segment

A stable sort leaves groups 0 and 3 exactly where they were (so the
over-covered window writes back without corrupting neighbors) and compacts
the left/right children into contiguous runs — XLA's TPU sort moves the
full 256-byte packed row (viewed as 11 i32 lanes for F<=28) at ~6 ns/row,
within ~2x of a pure streaming copy and with zero custom-kernel risk.

Static shapes: window capacities come from a pow-2 ladder (`lax.switch`),
like the reference's histogram-pool size classes.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..obs.jit import instrumented_jit
from jax import lax

from .pallas.seg import _u16, flat_planes, is_grouped, used_lanes


def window_caps(n_pad: int, floor: int = 8192) -> list:
    """Ascending pow-2 window capacities, topped by the whole array."""
    caps = []
    cap = min(floor, n_pad)
    while cap < n_pad:
        caps.append(cap)
        cap *= 2
    caps.append(n_pad)
    return caps


def _go_left(colv, tbin, dl, nanb, iscat, catmask):
    """Split predicate in bin space — must match ops/grower.py partition:
    numeric v <= t with NaN-bin default-left; categorical membership mask."""
    num = (colv <= tbin) | ((dl != 0) & (nanb >= 0) & (colv == nanb))
    bm = catmask.shape[0]
    cat = (catmask[jnp.clip(colv, 0, bm - 1)] > 0.5) & (colv < bm)
    return jnp.where(iscat != 0, cat, num)


def go_left_bits(seg, feat, tbin, dl, nanb, iscat, catmask, *, wide=False):
    """[n_pad] f32 go-left bits of every packed row, in segment order, from
    the split feature's own plane: feature ``feat`` of the packed columns
    lives in plane feat >> 1 (byte feat & 1), or plane feat of a ``wide``
    row.  One pass over ONE plane of the matrix, whatever its form.

    Who needs the predicate as bits: a grouped matrix (the plane lies in one
    plane group; every group is permuted by the same bits) and
    feature-parallel shards (the plane lies on one shard)."""
    planes = flat_planes(seg)
    if wide:
        p16 = lax.dynamic_slice_in_dim(planes, feat, 1, axis=0)[0]
        colv = p16.astype(jnp.int32) & 0xFFFF
    else:
        p16 = lax.dynamic_slice_in_dim(planes, feat >> 1, 1, axis=0)[0]
        colv = ((p16.astype(jnp.int32) & 0xFFFF) >> ((feat & 1) * 8)) & 0xFF
    if catmask.shape[0] <= 1:
        # no categorical column in the table (the grower's Bm = 1): skip the
        # membership lookup, an O(rows) gather that serializes on a TPU
        go = (colv <= tbin) | ((dl != 0) & (nanb >= 0) & (colv == nanb))
    else:
        go = _go_left(colv, tbin, dl, nanb, iscat, catmask)
    return go.astype(jnp.float32)


def _sort_partition_grouped_xla(seg, sbegin, cnt, gl_vec, cnt_cap, *, n_pad):
    """The stable-sort partition of a grouped matrix: the key and the
    position are sorted, every plane of every group is gathered through the
    one permutation (a 1,000-plane row as sort payloads would be 500
    operands)."""
    shape = seg.shape
    flat = flat_planes(seg)
    caps = window_caps(n_pad)

    def make_branch(P: int):
        def branch(op):
            flat, sbegin, cnt, glv = op
            start = jnp.minimum(sbegin, n_pad - P)
            off = sbegin - start
            win = lax.dynamic_slice_in_dim(flat, start, P, axis=1)
            pos = jnp.arange(P, dtype=jnp.int32)
            in_seg = (pos >= off) & (pos < off + cnt)
            gl = (lax.dynamic_slice(glv, (start,), (P,)) > 0.5) & in_seg
            key = jnp.where(
                pos < off, 0, jnp.where(gl, 1, jnp.where(in_seg, 2, 3))
            ).astype(jnp.int32)
            _, perm = lax.sort((key, pos), num_keys=1, is_stable=True)
            flat = lax.dynamic_update_slice(
                flat, jnp.take(win, perm, axis=1), (0, start)
            )
            return flat, jnp.sum(gl).astype(jnp.int32)

        return branch

    bucket = jnp.clip(
        jnp.searchsorted(jnp.asarray(caps, jnp.int32), cnt_cap, side="left"),
        0, len(caps) - 1,
    ).astype(jnp.int32)
    flat, nl = lax.switch(
        bucket, [make_branch(P) for P in caps], (flat, sbegin, cnt, gl_vec)
    )
    return flat.reshape(shape), nl, cnt - nl


@functools.partial(
    instrumented_jit,
    static_argnames=("f", "n_pad", "wide", "use_gl_vec"),
)
def sort_partition_xla(
    seg: jnp.ndarray,  # [LANES, n_pad] i16 packed rows, PLANE-MAJOR — the
    #                    layout XLA assigns this loop carry anyway; storing it
    #                    that way avoids full-array relayout copies per split
    sbegin: jnp.ndarray,  # scalar i32 — segment begin
    cnt: jnp.ndarray,  # scalar i32 — segment rows
    feat: jnp.ndarray,  # scalar i32 — split feature (used-feature index)
    tbin: jnp.ndarray,  # scalar i32
    dl: jnp.ndarray,  # scalar i32 (default-left)
    nanb: jnp.ndarray,  # scalar i32 (NaN bin or -1)
    iscat: jnp.ndarray,  # scalar i32
    catmask: jnp.ndarray,  # [Bm] f32 — bin -> goes left (categorical)
    gl_vec: Optional[jnp.ndarray] = None,  # [n_pad] f32 go-left bits
    *,
    f: int,
    n_pad: int,
    wide: bool = False,
    use_gl_vec: bool = False,
    cnt_cap: Optional[jnp.ndarray] = None,  # fleet-wide max cnt (bucket
    #   sizing only; defaults to cnt — see sort_partition)
):
    """Partition seg[sbegin : sbegin+cnt) by the split rule.

    ``use_gl_vec``: the go-left decision comes from a precomputed [n_pad]
    bit vector instead of the feature column (feature-parallel seg mode —
    only the owning shard holds the winner's bin plane; the bits arrive by
    psum and every shard applies the identical stable partition).

    Returns (seg', nl, nr): left child at [sbegin, sbegin+nl), right child at
    [sbegin+nl, sbegin+cnt), both in stable order; rows outside untouched.
    """
    if is_grouped(seg):
        return _sort_partition_grouped_xla(
            seg, sbegin, cnt, gl_vec, cnt if cnt_cap is None else cnt_cap,
            n_pad=n_pad,
        )
    n_ops = (used_lanes(f, wide) + 1) // 2  # i32 lanes that carry real data
    caps = window_caps(n_pad)
    if gl_vec is None:
        gl_vec = jnp.zeros((n_pad,), jnp.float32)

    def make_branch(P: int):
        def branch(op):
            seg, sbegin, cnt, feat, tbin, dl, nanb, iscat, glv = op
            start = jnp.minimum(sbegin, n_pad - P)
            off = sbegin - start
            # window-first: only O(P) data is ever materialized — a
            # full-array bitcast/reassemble here would copy the whole
            # 256B-per-row matrix on every split
            # only the used planes are sliced/rewritten (the rest are zero)
            win16 = lax.dynamic_slice(seg, (0, start), (2 * n_ops, P))
            uT = win16.astype(jnp.int32) & 0xFFFF  # [2*n_ops, P]
            pos = jnp.arange(P, dtype=jnp.int32)
            in_seg = (pos >= off) & (pos < off + cnt)
            if use_gl_vec:
                gl = (lax.dynamic_slice(glv, (start,), (P,)) > 0.5) & in_seg
            else:
                if wide:
                    # one u16 plane per feature (max_bin > 256)
                    colv = lax.dynamic_slice(uT, (feat, 0), (1, P))[0]
                else:
                    # feature column: byte j&1 of i16 lane j>>1
                    lane = feat >> 1
                    shift = (feat & 1) * 8
                    col16 = lax.dynamic_slice(uT, (lane, 0), (1, P))[0]
                    colv = (col16 >> shift) & 0xFF
                gl = _go_left(colv, tbin, dl, nanb, iscat, catmask) & in_seg
            key = jnp.where(
                pos < off,
                0,
                jnp.where(gl, 1, jnp.where(in_seg, 2, 3)),
            ).astype(jnp.int32)
            # combine i16 lane pairs into i32 payloads with strided slices
            # (a widening bitcast would materialize a [P, 64, 2] tensor whose
            # 2-wide minor dim tile-pads 64x)
            win32T = uT[0::2] | (uT[1::2] << 16)  # [n_ops, P]
            ops_in = (key,) + tuple(win32T[i] for i in range(n_ops))
            sorted_ops = lax.sort(ops_in, num_keys=1, is_stable=True)
            wsT = jnp.stack(sorted_ops[1:], axis=0)  # [n_ops, P] i32
            outT = jnp.zeros((2 * n_ops, P), jnp.int32)
            outT = outT.at[0::2].set(wsT & 0xFFFF)
            outT = outT.at[1::2].set((wsT >> 16) & 0xFFFF)
            win16_new = _u16(outT)  # [2*n_ops, P]
            seg = lax.dynamic_update_slice(seg, win16_new, (0, start))
            nl = jnp.sum(gl).astype(jnp.int32)
            return seg, nl

        return branch

    caps_arr = jnp.asarray(caps, dtype=jnp.int32)
    # fleet-vmapped growth: the caller pre-reduces cnt over the model axis
    # (cnt_cap) so ONE window branch lowers for the whole fleet — the
    # collective stays OUTSIDE the platform branches (sort_partition)
    if cnt_cap is None:
        cnt_cap = cnt
    bucket = jnp.clip(
        jnp.searchsorted(caps_arr, cnt_cap, side="left"), 0, len(caps) - 1
    ).astype(jnp.int32)
    branches = [make_branch(P) for P in caps]
    seg_new, nl = lax.switch(
        bucket, branches,
        (seg, sbegin, cnt, feat, tbin, dl, nanb, iscat, gl_vec),
    )
    nr = cnt - nl
    return seg_new, nl, nr


def sort_partition(
    seg, sbegin, cnt, feat, tbin, dl, nanb, iscat, catmask, *, f: int,
    n_pad: int, wide: bool = False, gl_vec=None, fleet_axis_name=None,
    measure: bool = False, bag_compact: bool = False,
):
    """Platform dispatch for the segment partition: the Pallas streaming
    kernel on TPU (ops/pallas/partition.py — exact window, in place, no
    defensive copies), the stable-sort formulation elsewhere.  Both are
    stable partitions with bit-identical results.

    ``gl_vec`` (feature-parallel seg, grouped matrices): the go-left
    decision comes from a precomputed [n_pad] bit vector (``go_left_bits``);
    the Pallas kernel DMAs a bits tile per row tile instead of reading the
    feature column.  A grouped matrix cannot do without it.

    ``bag_compact``: the once-a-tree pass that brings the in-bag rows to the
    front (ops/grower.py, ``bag_window``).  The same kernel under a name of
    its own (``bag_compact_pallas``), so a device trace tells it from the
    splits' partitions."""
    from .pallas import partition as _part
    from .pallas.partition import bag_compact_pallas, seg_partition_pallas

    kernel = bag_compact_pallas if bag_compact else seg_partition_pallas
    from ..obs.collectives import timed_pmax

    use_gl = gl_vec is not None
    if is_grouped(seg) and not use_gl:
        raise ValueError(
            "a grouped segment matrix is partitioned by precomputed go-left "
            "bits (go_left_bits): pass gl_vec"
        )
    # fleet-vmapped growth: reduce cnt over the model axis HERE, outside
    # the platform branches, so both lower the same collective sequence
    # (none) and the XLA window ladder sizes one shared branch
    if fleet_axis_name is not None:
        cnt_cap = timed_pmax(
            cnt, fleet_axis_name, site="fleet_cap", measure=measure
        )
    else:
        cnt_cap = cnt

    def _pallas(seg, sbegin, cnt, cnt_cap, feat, tbin, dl, nanb, iscat,
                catmask, *maybe_gl, interpret=False):
        bm = catmask.shape[0]
        bmt = max(256, -(-bm // 128) * 128)  # cat-table width (wide bins)
        catm = jnp.zeros((1, bmt), jnp.float32)
        catm = catm.at[0, :bm].set(catmask.astype(jnp.float32))
        scal = jnp.stack(
            [sbegin, cnt, feat, tbin, dl, nanb, iscat, jnp.int32(0)]
        ).astype(jnp.int32)
        seg_new, nl = kernel(
            seg, scal, catm, maybe_gl[0] if maybe_gl else None,
            f=f, n_pad=n_pad, use_cat=bm > 1, wide=wide,
            interpret=interpret,
        )
        return seg_new, nl, cnt - nl

    def _xla(seg, sbegin, cnt, cnt_cap, feat, tbin, dl, nanb, iscat,
             catmask, *maybe_gl):
        return sort_partition_xla(
            seg, sbegin, cnt, feat, tbin, dl, nanb, iscat, catmask,
            maybe_gl[0] if maybe_gl else None,
            f=f, n_pad=n_pad, wide=wide, use_gl_vec=use_gl,
            cnt_cap=cnt_cap,
        )

    args = (seg, sbegin, cnt, cnt_cap, feat, tbin, dl, nanb, iscat, catmask)
    if use_gl:
        args = args + (gl_vec,)
    if jax.default_backend() != "tpu":
        # no TPU in this process: don't trace the Pallas branch (but for
        # the interpret-mode kernel under the test hook)
        if _part._INTERPRET:
            return _pallas(*args, interpret=True)
        return _xla(*args)
    return jax.lax.platform_dependent(*args, tpu=_pallas, default=_xla)


def sort_partition_batch(
    seg,
    sbegins,  # [K] i32 — segment begins (disjoint windows)
    cnts,  # [K] i32 — segment rows (0 = no-op member)
    feats,  # [K] i32
    tbins,  # [K] i32
    dls,  # [K] i32
    nanbs,  # [K] i32
    iscats,  # [K] i32
    catmasks,  # [K, Bm] f32
    *,
    f: int,
    n_pad: int,
    wide: bool = False,
):
    """K stable partitions over K DISJOINT leaf windows (frontier-batched
    growth, ops/grower.py leaf_batch).  One K-program Pallas launch on TPU;
    elsewhere a sequential chain of the stable-sort partitions (disjoint
    windows make the chain order-independent and bit-identical to K serial
    calls).  Returns (seg', nl[K], nr[K])."""
    from .pallas.partition import seg_partition_pallas_batch

    if is_grouped(seg):
        raise ValueError(
            "the batched partition takes a one-group segment matrix; a "
            "grouped one is partitioned window by window (leaf_batch=1)"
        )
    k = sbegins.shape[0]

    def _pallas(seg, sbegins, cnts, feats, tbins, dls, nanbs, iscats,
                catmasks):
        bm = catmasks.shape[1]
        bmt = max(256, -(-bm // 128) * 128)
        catm = jnp.zeros((k, bmt), jnp.float32)
        catm = catm.at[:, :bm].set(catmasks.astype(jnp.float32))
        scal = jnp.stack(
            [sbegins, cnts, feats, tbins, dls, nanbs, iscats,
             jnp.zeros_like(sbegins)],
            axis=1,
        ).astype(jnp.int32)
        seg_new, nl = seg_partition_pallas_batch(
            seg, scal, catm, f=f, n_pad=n_pad, use_cat=bm > 1, wide=wide,
        )
        return seg_new, nl, cnts - nl

    def _xla(seg, sbegins, cnts, feats, tbins, dls, nanbs, iscats, catmasks):
        nls = []
        for i in range(k):
            seg, nl_i, _ = sort_partition_xla(
                seg, sbegins[i], cnts[i], feats[i], tbins[i], dls[i],
                nanbs[i], iscats[i], catmasks[i],
                f=f, n_pad=n_pad, wide=wide, use_gl_vec=False,
            )
            nls.append(nl_i)
        nl = jnp.stack(nls)
        return seg, nl, cnts - nl

    args = (seg, sbegins, cnts, feats, tbins, dls, nanbs, iscats, catmasks)
    if jax.default_backend() != "tpu":
        return _xla(*args)
    return jax.lax.platform_dependent(*args, tpu=_pallas, default=_xla)


def leaf_of_positions(
    leaf_sbegin: jnp.ndarray,  # [L] i32 (active leaves' segment begins)
    leaf_rows: jnp.ndarray,  # [L] i32
    num_leaves: jnp.ndarray,  # scalar i32
    n: int,
) -> jnp.ndarray:
    """leaf index per segment POSITION via the marker-cumsum trick (no
    scatter of rows): mark each active leaf's begin, cumsum to segment
    ordinals, map ordinals through a begin-sorted leaf permutation."""
    L = leaf_sbegin.shape[0]
    active = jnp.arange(L, dtype=jnp.int32) < num_leaves
    begin_marks = jnp.where(active & (leaf_rows > 0), leaf_sbegin, n)
    marker = jnp.zeros((n,), jnp.int32).at[begin_marks].add(1, mode="drop")
    sort_key = jnp.where(active & (leaf_rows > 0), leaf_sbegin, 2 * n + 2)
    sorted_leaf = jnp.argsort(sort_key).astype(jnp.int32)
    seg_ord = jnp.clip(jnp.cumsum(marker) - 1, 0, L - 1)
    return sorted_leaf[seg_ord]


def leaf_id_from_seg(
    ridx: jnp.ndarray,  # [n] i32 — original row index per segment position
    leaf_pos: jnp.ndarray,  # [n] i32 — leaf per segment position
) -> jnp.ndarray:
    """Invert the segment permutation with one sort (XLA TPU sort is fast;
    a scatter here would serialize)."""
    _, leaf_id = lax.sort((ridx, leaf_pos), num_keys=1)
    return leaf_id
