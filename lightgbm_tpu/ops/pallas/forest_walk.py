"""Pallas forest-walk kernel — batched level-synchronous tree inference.

Reference analog: the fork's cache-blocked batch predictor
``PredictTreeBatchAVX512`` (include/LightGBM/tree_avx512.hpp:41): 8-row
level-synchronous walks with the tree resident in cache; categorical and
missing handling inline (:112-168).  The TPU-native formulation walks a
1024-row tile through EVERY tree with all trees' node tables resident in
VMEM.

Layout decisions:
  * the walk state (current node per row) lives as ONE [8, 128] vreg per
    1024-row tile; node-table lookups are in-VMEM lane-gathers
    (``tpu.dynamic_gather`` spans one 128-lane vreg, so an H*128-node table
    is H [8,128] gathers + a select tree — a handful of vector ops instead
    of the 16-vreg broadcasts a row-major formulation pays);
  * all per-node scalars (threshold, feature, default-left, NaN bin,
    is-categorical) are bit-packed into ONE i32 table, so a level costs two
    table lookups plus one bin fetch;
  * categorical splits read one word of the node's 256-bit category bitset:
    eight word-tables indexed like the node tables, selected by fval>>5
    (the reference's ``FindInBitset``, tree.h:346, as vector ops).

Supported: numeric + categorical splits in BIN space, bin values < 256
(byte-packed), trees up to 512 nodes / 512 leaves, F <= 512 features (4 per
i32 lane across ceil(F/128) plane groups; the plane-select tree deepens
with F), any class count (output padded to a multiple of 8).  Wider-bin
models fall back to the XLA walker.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from ...obs.jit import instrumented_jit
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
ROW_TILE = 1024
MAX_NODES = 512  # hard cap (4 lane-gather halves); per-model H is smaller
MAX_THR = 256  # bin values are byte-packed: thresholds/NaN bins must fit u8
MAX_F = 512  # feature cap: 9-bit feature field, 128 packed i32 planes
KPAD = 8  # minimum output class columns (padded to a multiple of 8)
CAT_WORDS = 8  # 256-bit category bitset = 8 i32 words per node
VMEM_TABLE_BUDGET = 12 * 1024 * 1024  # fall back when tables outgrow VMEM


def n_planes(num_features: int) -> int:
    """Packed i32 bin planes for F features: pow2(ceil(F/4)), min 32."""
    p = 32
    while p * 4 < num_features:
        p *= 2
    return p


def tile_bucket(n_rows: int) -> int:
    """Bucketed tile count for an n_rows walk: the power-of-two ceiling of
    ceil(n_rows / ROW_TILE).  The pallas grid is sized by tile count, so
    without bucketing every distinct row count compiles a fresh executable;
    with it a stream of arbitrary batch sizes reuses a small ladder of
    cached programs (the streaming engine's bucket contract)."""
    tiles = max(1, -(-n_rows // ROW_TILE))
    b = 1
    while b < tiles:
        b <<= 1
    return b


def bucket_pad_rows(n_rows: int) -> int:
    """Row count padded to the tile-bucket boundary (bucket-shape entry:
    feed `pad_bins_for_walk`/`_pack_bins_device` this many rows)."""
    return tile_bucket(n_rows) * ROW_TILE


class ForestTables(NamedTuple):
    """Per-tree node tables, shaped [T, H, 128] (H lane-gather halves — the
    leading dim carries the tree index so per-tree slicing never hits the
    tiled-dim alignment rules)."""

    pk1: jnp.ndarray  # i32: thr | feat<<9 | dl<<18 | (nanb+1)<<19 | cat<<28
    pk2: jnp.ndarray  # i32: (left+m_nodes) | (right+m_nodes)<<16 (neg = ~leaf)
    leaf: jnp.ndarray  # f32 [T, H, 128]: leaf value by LEAF index
    catw: jnp.ndarray  # i32 [T, CAT_WORDS, H, 128] category bitset words
    #                    ([1, 1, 1, 128] dummy when the model has no cat)
    n_trees: int
    max_depth: int
    m_nodes: int  # 128 * H
    has_cat: bool


def walk_reject_reason(
    records, nan_bins: np.ndarray, num_features: int, max_bin: int
):
    """None when the kernel can run this model, else a human-readable reason
    (<=511 splits/tree, bin space fits a byte, F <= 512; categorical OK)."""
    if num_features > MAX_F:
        return f"{num_features} features > {MAX_F}"
    if max_bin > MAX_THR:
        # input bins would clip at 255 and could misroute at high thresholds
        return f"max_bin {max_bin} > {MAX_THR} (bins must fit a byte)"
    if len(nan_bins) and int(np.max(nan_bins)) >= MAX_THR:
        # NaN bin must fit the 8-bit fval (nanb+1 has 9 bits)
        return f"NaN bin {int(np.max(nan_bins))} >= {MAX_THR}"
    n_nodes_max = 1
    has_cat = False
    for r in records:
        sf = r.get("split_feature")
        if sf is None or len(sf) >= MAX_NODES:
            return (
                "a tree has no bin-space record"
                if sf is None
                else f"a tree has {len(sf)} splits >= {MAX_NODES}"
            )
        n_nodes_max = max(n_nodes_max, len(sf) + 1)
        sic = r.get("split_is_cat")
        if sic is not None and np.any(np.asarray(sic)):
            has_cat = True
            cm = r.get("cat_mask")
            if cm is None or (np.size(cm) and np.asarray(cm).shape[-1] > 256):
                return "a categorical mask is wider than 256 bins"
            cma = np.asarray(cm)
            if np.size(cma) and cma.shape[-1] == 256 and np.any(cma[..., 255]):
                # pad_bins_for_walk clips the unseen-category sentinel to
                # 255: if a real mask claims bin 255 goes left, the clipped
                # sentinel would misroute left (the walker/reference sends
                # unseen categories right) — fall back
                return "a categorical mask claims bin 255 (sentinel clash)"
        if len(sf) and int(np.max(np.asarray(r["split_bin"]))) >= MAX_THR:
            return f"a split threshold bin >= {MAX_THR}"
    h = max(1, -(-n_nodes_max // LANES))
    if h == 3:
        h = 4  # build_tables pads to a power-of-two of halves
    table_bytes = len(records) * h * LANES * 4 * (3 + (CAT_WORDS if has_cat else 0))
    if table_bytes > VMEM_TABLE_BUDGET:
        return (
            f"node tables ({table_bytes >> 20} MiB for {len(records)} trees) "
            "exceed the VMEM budget"
        )
    return None


def walk_eligible(
    records, nan_bins: np.ndarray, num_features: int, max_bin: int
) -> bool:
    return walk_reject_reason(records, nan_bins, num_features, max_bin) is None


def build_tables(records, nan_bins: np.ndarray) -> ForestTables:
    """Stack bin-space tree records (host dicts, see gbdt._bin_records) into
    kernel tables.  Caller must have checked `walk_eligible`."""
    t = len(records)
    n_nodes_max = 1
    has_cat = False
    for r in records:
        n_nodes_max = max(n_nodes_max, len(r["split_feature"]) + 1)
        sic = r.get("split_is_cat")
        if sic is not None and np.any(np.asarray(sic)):
            has_cat = True
    h = max(1, -(-n_nodes_max // LANES))
    if h == 3:
        h = 4  # select tree wants a power of two of halves
    m_nodes = h * LANES
    pk1 = np.zeros((t, m_nodes), np.int32)
    pk2 = np.zeros((t, m_nodes), np.int32)
    leaf = np.zeros((t, m_nodes), np.float32)
    catw = (
        np.zeros((t, CAT_WORDS, m_nodes), np.int32)
        if has_cat
        else np.zeros((1, 1, 1, LANES), np.int32)
    )
    nan_bins = np.asarray(nan_bins, np.int64)
    max_depth = 1
    for i, r in enumerate(records):
        sf = np.asarray(r["split_feature"], np.int64)
        nn = len(sf)
        lv = np.asarray(r["leaf_value"], np.float32)
        leaf[i, : len(lv)] = lv
        if nn == 0:
            # single-leaf tree: node 0 routes every row to leaf 0
            pk2[i, 0] = (~0 + m_nodes) | ((~0 + m_nodes) << 16)
            continue
        thr = np.asarray(r["split_bin"], np.int64)
        dl = np.asarray(r["default_left"], np.int64)
        lc = np.asarray(r["left_child"], np.int64)
        rc = np.asarray(r["right_child"], np.int64)
        nb = nan_bins[sf] + 1  # 0 = no NaN bin
        sic = r.get("split_is_cat")
        cat = (
            np.asarray(sic, np.int64)
            if sic is not None and np.size(sic)
            else np.zeros(nn, np.int64)
        )
        pk1[i, :nn] = (
            thr | (sf << 9) | (dl << 18) | (nb << 19) | (cat << 28)
        ).astype(np.int32)
        pk2[i, :nn] = ((lc + m_nodes) | ((rc + m_nodes) << 16)).astype(np.int32)
        if has_cat and cat.any():
            cm = np.asarray(r["cat_mask"], bool)  # [nn, Bm]
            bm = cm.shape[-1]
            for mi in range(nn):
                if not cat[mi]:
                    continue
                bits = np.zeros(256, np.int64)
                bits[:bm] = cm[mi]
                # word w bit b (LSB-first) = "bin 32w+b goes left"
                vals = (bits.reshape(8, 32) << np.arange(32)[None, :]).sum(axis=1)
                catw[i, :, mi] = vals.astype(np.uint32).view(np.int32)
        depth = np.ones(nn, np.int32)
        for m in range(nn):
            for c in (lc[m], rc[m]):
                if c >= 0:
                    depth[c] = depth[m] + 1
        max_depth = max(max_depth, int(depth.max()) + 1)
    shape = (t, h, LANES)
    return ForestTables(
        pk1=jnp.asarray(pk1.reshape(shape)),
        pk2=jnp.asarray(pk2.reshape(shape)),
        leaf=jnp.asarray(leaf.reshape(shape)),
        catw=jnp.asarray(
            catw.reshape(t, CAT_WORDS, h, LANES) if has_cat else catw
        ),
        n_trees=t,
        max_depth=max_depth,
        m_nodes=m_nodes,
        has_cat=has_cat,
    )


def _lookup(table_hx128, cur, h: int):
    """table [H, 128] gathered by cur [8, 128] in [0, H*128) -> [8, 128].
    H broadcasts + H single-vreg lane-gathers + a select tree."""
    idx = cur & 127
    halves = [
        jnp.take_along_axis(
            jnp.broadcast_to(table_hx128[i : i + 1, :], (8, LANES)), idx, axis=1
        )
        for i in range(h)
    ]
    hsel = cur >> 7
    bit = 0
    while len(halves) > 1:
        b = (hsel >> bit) & 1
        halves = [
            jnp.where(b != 0, halves[2 * i + 1], halves[2 * i])
            for i in range(len(halves) // 2)
        ]
        bit += 1
    return halves[0]


def _walk_kernel(
    bins_ref,  # VMEM [1, P, 8, 128] i32 — 4 bins per i32, tile rows laid
    #           out as (sublane, lane); everything in the walk is a
    #           vreg-shaped [8, 128] op — no reshapes, no row-major crossings
    pk1_ref,  # VMEM [T, H, 128] i32
    pk2_ref,
    leaf_ref,  # VMEM [T, H, 128] f32
    catw_ref,  # VMEM [T, CAT_WORDS, H, 128] i32 (dummy when not has_cat)
    out_ref,  # VMEM [1, kpad, 8, 128] f32
    *,
    n_trees: int,
    max_depth: int,
    k: int,
    kpad: int,
    h: int,
    m_nodes: int,
    has_cat: bool,
    planes_n: int,
):
    planes = [bins_ref[0, p] for p in range(planes_n)]  # P x [8, 128]
    out_ref[...] = jnp.zeros_like(out_ref)
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (kpad, 8, LANES), 0)
    sel_bits = planes_n.bit_length() - 1  # planes_n is a power of two

    def select_plane(lane_idx):
        """(P-1)-select binary tree: out[s,l] = planes[lane_idx[s,l]][s,l]."""
        level_vals = planes
        for bit in range(sel_bits):
            b = (lane_idx >> bit) & 1
            level_vals = [
                jnp.where(b != 0, level_vals[2 * i + 1], level_vals[2 * i])
                for i in range(len(level_vals) // 2)
            ]
        return level_vals[0]

    def tree_body(t, _):
        pk1 = pk1_ref[t]  # [H, 128]
        pk2 = pk2_ref[t]
        lv = leaf_ref[t]
        if has_cat:
            # mostly-numeric models: only trees that actually contain a
            # categorical node pay the 8-word bitset lookup per level (one
            # vector reduce per tree buys a lax.cond skip of ~8H gathers +
            # selects per level for the all-numeric trees)
            tree_cat = jnp.any(((pk1 >> 28) & 1) != 0)

        def level(_, cur):
            curc = jnp.maximum(cur, 0)  # [8, 128]
            p1 = _lookup(pk1, curc, h)
            thr = p1 & 0x1FF
            feat = (p1 >> 9) & 0x1FF
            dl = (p1 >> 18) & 1
            nb = ((p1 >> 19) & 0x1FF) - 1
            packed = select_plane(feat >> 2)
            fval = (packed >> ((feat & 3) * 8)) & 0xFF
            gl = (fval <= thr) | ((dl != 0) & (nb >= 0) & (fval == nb))
            if has_cat:
                def cat_gl(g32):
                    # one bitset word per row: 8 word-tables gathered by
                    # node, selected by fval>>5, tested at bit fval&31 (the
                    # vectorized CategoricalDecision, tree.h:346; bins >= the
                    # mask width have zero bits and route right like unseen
                    # categories)
                    words = [
                        _lookup(catw_ref[t, w], curc, h)
                        for w in range(CAT_WORDS)
                    ]
                    wi = fval >> 5
                    bit = 0
                    while len(words) > 1:
                        b = (wi >> bit) & 1
                        words = [
                            jnp.where(b != 0, words[2 * i + 1], words[2 * i])
                            for i in range(len(words) // 2)
                        ]
                        bit += 1
                    catgo = (words[0] >> (fval & 31)) & 1
                    isc = (p1 >> 28) & 1
                    # i32-operand select: Mosaic cannot truncate to the i1
                    # operands the direct boolean select would need
                    return jnp.where(isc != 0, catgo, g32)

                # the cond carries i32, not i1: Mosaic cannot legalize an
                # scf.if whose result is an i1 vector
                gl = lax.cond(
                    tree_cat, cat_gl, lambda g: g, gl.astype(jnp.int32)
                ) != 0
            p2 = _lookup(pk2, curc, h)
            child = jnp.where(gl, p2 & 0xFFFF, (p2 >> 16) & 0xFFFF) - m_nodes
            return jnp.where(cur >= 0, child, cur)

        nodes = lax.fori_loop(
            0, max_depth, level, jnp.zeros((8, LANES), jnp.int32)
        )
        val = jnp.where(
            nodes < 0,
            _lookup(lv, ~jnp.minimum(nodes, -1), h),
            0.0,
        )
        col = t % k  # class of tree t (trees interleave classes)
        out_ref[0] += jnp.where(iota_k == col, val[None, :, :], 0.0)
        return 0

    lax.fori_loop(0, n_trees, tree_body, 0)


def forest_walk(
    bins: jnp.ndarray,  # [n_tiles, P, 8, 128] i32 (P = n_planes(F))
    tables: ForestTables,
    *,
    n_trees: int,
    max_depth: int,
    k: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Raw scores [n_tiles, kpad, 8, 128] (sum of leaf outputs per class;
    row n of tile i lives at [i, :, n // 128, n % 128])."""
    return _forest_walk_jit(
        bins,
        tables.pk1,
        tables.pk2,
        tables.leaf,
        tables.catw,
        n_trees=n_trees,
        max_depth=max_depth,
        k=k,
        m_nodes=tables.m_nodes,
        has_cat=tables.has_cat,
        interpret=interpret,
    )


@functools.partial(
    instrumented_jit,
    static_argnames=(
        "n_trees", "max_depth", "k", "m_nodes", "has_cat", "interpret"
    ),
)
def _forest_walk_jit(
    bins, pk1, pk2, leaf, cw, *, n_trees, max_depth, k, m_nodes, has_cat,
    interpret,
):
    n_tiles = bins.shape[0]
    planes_n = bins.shape[1]
    h = pk1.shape[1]
    kpad = max(KPAD, -(-k // 8) * 8)
    kernel = functools.partial(
        _walk_kernel,
        n_trees=n_trees,
        max_depth=max_depth,
        k=k,
        kpad=kpad,
        h=h,
        m_nodes=m_nodes,
        has_cat=has_cat,
        planes_n=planes_n,
    )
    return pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((1, planes_n, 8, LANES), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((n_trees, h, LANES), lambda i: (0, 0, 0)),
            pl.BlockSpec((n_trees, h, LANES), lambda i: (0, 0, 0)),
            pl.BlockSpec((n_trees, h, LANES), lambda i: (0, 0, 0)),
            pl.BlockSpec(cw.shape, lambda i: (0, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, kpad, 8, LANES), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_tiles, kpad, 8, LANES), jnp.float32),
        interpret=interpret,
    )(bins, pk1, pk2, leaf, cw)


@functools.partial(instrumented_jit, static_argnames=("n_pad",))
def _pack_bins_device(mat_u8: jnp.ndarray, n_pad: int) -> jnp.ndarray:
    """Device-side bin packing: [N, F] u8 -> [n_tiles, P, 8, 128] i32."""
    n, f = mat_u8.shape
    p = n_planes(f)
    b = jnp.zeros((n_pad, 4 * p), jnp.int32)
    b = b.at[:n, :f].set(mat_u8.astype(jnp.int32))
    packed = (
        b[:, 0::4] | (b[:, 1::4] << 8) | (b[:, 2::4] << 16) | (b[:, 3::4] << 24)
    )  # [n_pad, P]
    return packed.reshape(n_pad // ROW_TILE, 8, LANES, p).transpose(
        0, 3, 1, 2
    )


def pad_bins_for_walk(bins: np.ndarray, n_pad: int = 0) -> jnp.ndarray:
    """[N, F] int bins -> [n_tiles, P, 8, 128] i32, 4 bins
    byte-packed per i32 (feature j in byte j&3 of pack j>>2); row n sits at
    [n // 1024, :, (n % 1024) // 128, n % 128].  Only the compact u8 matrix
    crosses host->device (the padded i32 form is 9x bigger — built on
    device).  ``n_pad`` overrides the padded row count (pass
    ``bucket_pad_rows(n)`` to land on the bucket ladder); 0 keeps the
    minimal ROW_TILE ceiling."""
    n, f = bins.shape
    if n_pad <= 0:
        n_pad = (n + ROW_TILE - 1) // ROW_TILE * ROW_TILE
    # clip: categorical columns may carry an out-of-range unseen-category
    # sentinel — clipping to 255 keeps byte packing intact, and bin 255 is
    # outside every cat mask (<= 256 wide only when max_bin == 256... the
    # mask bit there is 0 unless bin 255 is a real seen category, in which
    # case the sentinel equals it; walk_eligible enforces max_bin <= 256)
    mat_u8 = np.clip(bins, 0, 255).astype(np.uint8)
    return _pack_bins_device(jnp.asarray(mat_u8), n_pad)


def unpack_walk_scores(out: np.ndarray, n: int, k: int) -> np.ndarray:
    """[n_tiles, kpad, 8, 128] -> [n, k] row-major scores."""
    t, kpad = out.shape[0], out.shape[1]
    flat = out.transpose(0, 2, 3, 1).reshape(t * ROW_TILE, kpad)
    return flat[:n, :k]


# ---------------------------------------------------------------------------
# device-side prediction binning (reference BinMapper::ValueToBin, bin.h:173)
# ---------------------------------------------------------------------------
#
# Host binning (searchsorted per feature) costs ~1.4s per 500k x 28 rows and
# dominated predict latency. On device, value->bin is a fused compare-reduce
# (bin = sum_b [ub_b < v], no gathers): ~ms at the same scale. Comparisons
# run in f32 (TPUs have no f64), so values within f32 epsilon of a bin
# boundary may bin differently from the f64 host path — the XLA-walker
# fallback keeps exact host binning.

def build_devbin_tables(mappers, used_features):
    """Pack numeric mappers into device arrays; None if any used feature is
    categorical (those need dict lookups — host binning handles them)."""
    ubs = []
    nanb = []
    mtype = []
    for j in used_features:
        m = mappers[j]
        if m.is_categorical:
            return None
        ubs.append(np.asarray(m.bin_upper_bound, np.float64))
        nanb.append(m.nan_bin)
        mtype.append(m.missing_type)
    bmax = max((len(u) for u in ubs), default=1)
    ub = np.full((len(ubs), bmax), np.inf, np.float64)
    for i, u in enumerate(ubs):
        ub[i, : len(u)] = u
    return (
        jnp.asarray(ub.astype(np.float32)),
        jnp.asarray(np.asarray(nanb, np.int32)),
        jnp.asarray(np.asarray(mtype, np.int32)),
    )


@instrumented_jit
def bin_numeric_device(
    X: jnp.ndarray,  # [N, F] f32 — used-feature columns
    ub: jnp.ndarray,  # [F, Bmax] f32, +inf padded
    nanb: jnp.ndarray,  # [F] i32
    mtype: jnp.ndarray,  # [F] i32
):
    """Vectorized ValueToBin: searchsorted(ub, v, 'left') == sum(ub < v),
    with the NaN/zero missing rules of the host path.

    Returns (bins [N, F] i32, suspect [N] bool): a row is suspect when any
    value sits within a few f32 ulps of a bin boundary — there the f32
    compare may disagree with the f64 host rule, so the caller re-bins
    those rows on host (prediction stays bit-identical to the host path)."""
    from ...binning import K_ZERO_THRESHOLD, MissingType

    isnan = jnp.isnan(X)
    safe = jnp.where(isnan, 0.0, X)
    # fused compare+reduce per feature: no [N, F, Bmax] materialization
    cmp = ub[None, :, :] < safe[:, :, None]
    bins = jnp.sum(cmp, axis=2, dtype=jnp.int32)
    tol = 8.0 * jnp.finfo(jnp.float32).eps * jnp.maximum(
        jnp.abs(safe)[:, :, None], jnp.abs(ub)[None, :, :]
    )
    near = jnp.abs(safe[:, :, None] - ub[None, :, :]) <= tol
    suspect = jnp.any(near & jnp.isfinite(ub)[None, :, :], axis=(1, 2))
    miss_zero = (mtype[None, :] == MissingType.ZERO) & (
        isnan | (jnp.abs(safe) <= K_ZERO_THRESHOLD)
    )
    miss_nan = (mtype[None, :] == MissingType.NAN) & isnan & (nanb[None, :] >= 0)
    return jnp.where(miss_zero | miss_nan, nanb[None, :], bins), suspect
