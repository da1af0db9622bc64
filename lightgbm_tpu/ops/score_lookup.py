"""A tree's output for a row without per-row gathers.

On the TPU an XLA gather costs about 8 ns an element whatever the table's
size (PERF.md section 6, PR 34: ``leaf_value[leaf_id]`` over 8M rows read
68.7 ms, 590 times what its bytes need).  Comparisons against an iota and
contractions against one-hots run at memory speed on the VPU and the MXU, so
both lookups of the score update are written that way here:

* ``leaf_lookup``: ``leaf_value[leaf_id]`` as a one-hot of ``leaf_id``
  contracted with the leaf values;
* ``tree_values``: the output of ONE bin-space numeric tree for every row of
  a binned matrix (the validation walk) as Hummingbird's GEMM strategy
  (Nakandala et al., OSDI 2020) for a tree of any shape: the split features'
  columns picked by a one-hot matmul, every node decided at once with the
  walker's predicate, and a row's leaf found where its signed decisions
  agree with every ancestor of the leaf.

Both return the bits the gathers return: every sum has one non-zero addend,
every matmul operand is a small integer that bfloat16 holds exactly, and the
leaf values travel as the bytes of their bit patterns, so -0.0, denormals,
NaN and inf reach their own rows and no others.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

# above this many leaves both lookups keep the gathers they replace: the
# contractions cost N * L (the path match N * L * L, and its [L, L] tables
# L * L bytes), the gathers N.  The largest size read on the chip (PERF.md
# section 6, PR 34): at L = 4,095 the lookup of 8M rows takes 25 ms against
# the gather's 54 and the walk of 400,000 rows 90 ms against the walker's
# 534.  Nothing above it was read, so the rule stops where the readings do;
# no cell of the benchmark has more than 255 leaves
ONEHOT_MAX_LEAVES = 4096

# a booster that HAS ``leaf_ids`` to fall back on (the segment path: every
# row sits at a segment position of its leaf) takes the walk only while it is
# the cheaper form.  The walk is about 2 * N * Jp * (F + Lp) bf16
# multiply-adds (column pick [Jp, F] x [F, N], path match [Jp, Lp] x [Jp, N];
# Jp, Lp the node and leaf counts padded to 128); ``leaf_ids`` (a scatter of L
# marks, a cumsum, a gather of one leaf a position, a sort of N pairs) costs
# the rows alone.  Both forms alone on a v5e (PERF.md section 6, PR 36), walk
# against segment in ms: 8M x 67 rows at 255 / 511 / 767 / 1,023 leaves 11.4
# / 38.4 / 69.6 / 121.2 against 85.7 / 85.6 / 62.8 / 62.8; 10.5M x 28 14.2 /
# 48.4 / 90.6 / 152.0 against 124.1 / 124.1 / 93.8 / 93.9; 400,000 x 2,000 at
# 255 / 511 / 1,023 leaves 3.12 / 6.28 / 14.35 against 3.88 / 3.87 / 2.72.
# The walk reads 1.2e-5 to 1.7e-5 ns a row a unit of Jp * (F + Lp), the
# segment form 6.8 to 11.8 ns a row; they cross at 5.7e5 / 6.4e5 / 7.5e5 on
# the three tables, so the bound on Jp * (F + Lp) is
LEAF_WALK_MAX_WORK = 600_000

# elements of one [leaves, rows] temporary of ``tree_values`` (32 MB in f32):
# the rows of a block are this over the padded leaf count
_BLOCK_ELEMS = 1 << 23


def _pad128(n: int) -> int:
    return max(128, -(-n // 128) * 128)


def lookup_form(num_leaves: int) -> str:
    """``"onehot"`` or ``"gather"`` for a leaf table of this (static) size."""
    return "onehot" if num_leaves <= ONEHOT_MAX_LEAVES else "gather"


def leaf_ids_form(
    num_leaves: int, num_features: int, cat_width: int, feature_shard: int
) -> str:
    """``"walk"`` or ``"segment"``: how a tree grown on the segment path
    gives every row its leaf, from static shapes alone.  ``"walk"``
    (``tree_leaves`` over the binned matrix) for a numeric tree
    (``cat_width`` <= 1: no categorical column, no EFB bundle) whose rows
    are not replicated over feature shards and whose contractions stay under
    ``LEAF_WALK_MAX_WORK``; ``"segment"`` (``segpart.leaf_of_positions`` and
    ``leaf_id_from_seg``) otherwise."""
    jp, lp = _pad128(num_leaves - 1), _pad128(num_leaves)
    numeric_local = cat_width <= 1 and feature_shard <= 1
    small = jp * (num_features + lp) <= LEAF_WALK_MAX_WORK
    return "walk" if numeric_local and small else "segment"


def _pick(match: jnp.ndarray, leaf_value: jnp.ndarray) -> jnp.ndarray:
    """[N] f32 from ``match`` [Lp, N] bool, at most one True a column: the
    value of the matched leaf, +0.0 where none matched.  One MXU contraction
    of the one-hot against the values' BIT PATTERNS, a byte a term (0..255 is
    exact in bfloat16, and a sum with one non-zero addend is exact in any
    order), joined again by shifts: a NaN, an inf or a -0.0 is four bytes like
    any other value, so it reaches its own rows and no others."""
    lp = match.shape[0]
    bits = lax.bitcast_convert_type(leaf_value.astype(jnp.float32), jnp.int32)
    bits = jnp.pad(bits, (0, lp - bits.shape[0]))
    table = jnp.stack([(bits >> (8 * d)) & 255 for d in range(4)])  # [4, lp]
    got = jnp.dot(
        table.astype(jnp.bfloat16), match.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    ).astype(jnp.int32)  # [4, N]
    word = got[0] | (got[1] << 8) | (got[2] << 16) | (got[3] << 24)
    return lax.bitcast_convert_type(word, jnp.float32)


def leaf_lookup(leaf_value: jnp.ndarray, leaf_id: jnp.ndarray) -> jnp.ndarray:
    """``leaf_value[leaf_id]`` ([L] f32, [N] i32 -> [N] f32), bit for bit,
    for ``leaf_id`` in [0, L).  The rows stay on the lanes ([Lp, N]) and,
    INSIDE A PROGRAM, the compiler builds the one-hot in the matmul's fusion
    (16 bytes a row of temporaries).  Dispatched op by op the one-hot is an
    array of 3 * Lp bytes a row, so every call site sits under a jit
    (tests/test_score_lookup.py holds the boosters to it)."""
    num_leaves = leaf_value.shape[0]
    with jax.named_scope("score_update"):
        if lookup_form(num_leaves) == "gather":
            return leaf_value[leaf_id]
        lp = _pad128(num_leaves)
        match = leaf_id[None, :] == jnp.arange(lp, dtype=jnp.int32)[:, None]
        return _pick(match, leaf_value)


def tree_paths(
    left_child: jnp.ndarray,  # [J] i32 (neg = ~leaf)
    right_child: jnp.ndarray,  # [J] i32
    jp: int,
    lp: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(A [jp, lp] bf16 in {-1, 0, +1}, depth [lp] f32)``: ``A[j, l]`` is
    +1 where leaf ``l`` lies under node ``j``'s left child and -1 under its
    right, ``depth[l]`` the number of ancestors of a leaf the root reaches,
    -1 for every other.  Built on the device (the pipelined update holds no
    host copy of the tree): the reflexive-transitive closure of the child
    relation by repeated squaring, so a 254-deep chain costs the same eight
    [jp, jp] matmuls as a balanced tree.  Nodes the root does not reach (the
    padding beyond ``num_leaves - 1``) contribute nothing.  A tree that never
    split sends every row to leaf 0, as the walker does."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    j = left_child.shape[0]
    lc = jnp.pad(left_child.astype(jnp.int32), (0, jp - j), constant_values=-1)
    rc = jnp.pad(right_child.astype(jnp.int32), (0, jp - j), constant_values=-1)
    nodes = jnp.arange(jp, dtype=jnp.int32)
    to_left = lc[:, None] == nodes[None, :]  # [jp, jp] node -> internal child
    to_right = rc[:, None] == nodes[None, :]
    reach = ((to_left | to_right) | (nodes[:, None] == nodes[None, :])).astype(bf16)
    for _ in range(max(1, (jp - 1).bit_length())):
        reach = jnp.minimum(
            jnp.dot(reach, reach, preferred_element_type=f32), f32(1)
        ).astype(bf16)
    live = reach[0][:, None]  # [jp, 1]: the root's descendants and itself
    sign = (to_left.astype(bf16) - to_right.astype(bf16)) * live
    # the branch taken at a strict ancestor a on the way to node c
    branch = jnp.dot(sign, reach, preferred_element_type=f32).astype(bf16)
    leaves = ~jnp.arange(lp, dtype=jnp.int32)
    leaf_sign = (
        (lc[:, None] == leaves[None, :]).astype(bf16)
        - (rc[:, None] == leaves[None, :]).astype(bf16)
    ) * live  # [jp, lp]: the branch at a leaf's parent
    a = leaf_sign.astype(f32) + jnp.dot(
        branch, jnp.abs(leaf_sign), preferred_element_type=f32
    )
    depth = jnp.sum(jnp.abs(a), axis=0)
    first = jnp.arange(lp, dtype=jnp.int32) == 0
    has_leaf = (depth > 0) | (first & ~jnp.any(depth > 0))
    return a.astype(bf16), jnp.where(has_leaf, depth, f32(-1))


def _feature_columns(bins: jnp.ndarray, select: jnp.ndarray) -> jnp.ndarray:
    """``bins[:, split_feature]`` as [Jp, Nb] int32 by matmuls against the
    one-hot ``select`` [Jp, F]: a bin goes in as digits of 8 bits, which
    bfloat16 holds exactly (one digit for the uint8 matrix a ``Dataset`` makes
    up to 256 bins, two for its uint16 one)."""
    digits = jnp.dtype(bins.dtype).itemsize
    if digits == 1:
        parts = [bins]
    else:
        wide = bins.astype(jnp.int32)
        parts = [(wide >> (8 * d)) & 255 for d in range(digits)]
    out = None
    for d, part in enumerate(parts):
        col = lax.dot_general(
            select, part.astype(jnp.bfloat16),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        ).astype(jnp.int32)
        out = col if out is None else out | (col << (8 * d))
    return out


def tree_values(
    bins: jnp.ndarray,  # [N, F] non-negative integer bins
    nan_bins: jnp.ndarray,  # [F] i32
    split_feature: jnp.ndarray,  # [J] i32
    split_bin: jnp.ndarray,  # [J] i32
    default_left: jnp.ndarray,  # [J] bool
    left_child: jnp.ndarray,  # [J] i32
    right_child: jnp.ndarray,  # [J] i32
    leaf_value: jnp.ndarray,  # [L] f32
    block_rows: Optional[int] = None,
    members: int = 1,
) -> jnp.ndarray:
    """[N] f32: the value of the leaf the bin-space walk of one NUMERIC tree
    reaches for every row — the walker's leaf, the walker's bits.  Rows go in
    blocks, so no temporary grows with N.  ``members``: how many trees a
    ``vmap`` walks at once over the same rows (a fleet): a block's
    temporaries are held once a member (34 MB each at 255 leaves by a
    deviceless v5e compile), so the block shrinks by that count."""
    n, f = bins.shape
    j = split_feature.shape[0]
    jp, lp = _pad128(j), _pad128(leaf_value.shape[0])
    i32 = jnp.int32

    def col(v, fill):  # per-node vector -> [jp, 1]
        return jnp.pad(v, (0, jp - j), constant_values=fill)[:, None]

    feat = split_feature.astype(i32)
    select = (
        col(feat, -1) == jnp.arange(f, dtype=i32)[None, :]
    ).astype(jnp.bfloat16)  # [jp, f]
    thr = col(split_bin.astype(i32), 0)
    nan_bin = nan_bins.astype(i32)[feat]  # a gather of J elements, once a tree
    dl_nan = col(jnp.where(default_left & (nan_bin >= 0), nan_bin, -1), -1)
    a, depth = tree_paths(left_child, right_child, jp, lp)

    def block(rows):  # [nb, f] -> [nb] f32
        x = _feature_columns(rows, select)  # [jp, nb]
        # the walker's predicate, every node at once; bins are >= 0, so a
        # node without a default-left NaN bin compares against -1
        go_left = (x <= thr) | (x == dl_nan)
        signed = jnp.where(go_left, jnp.bfloat16(1), jnp.bfloat16(-1))
        s = lax.dot_general(
            a, signed, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [lp, nb]
        return _pick(s == depth[:, None], leaf_value)

    nb = block_rows or max(1024, _BLOCK_ELEMS // (members * max(jp, lp)))
    if n <= nb:
        return block(bins)

    def body(i, out):
        # the last block starts early and recomputes rows of the one before
        start = jnp.minimum(i * nb, n - nb)
        vals = block(lax.dynamic_slice_in_dim(bins, start, nb, 0))
        return lax.dynamic_update_slice_in_dim(out, vals, start, 0)

    return lax.fori_loop(0, -(-n // nb), body, jnp.zeros((n,), jnp.float32))


def tree_leaves(
    bins: jnp.ndarray,  # [N, F] non-negative integer bins
    nan_bins: jnp.ndarray,  # [F] i32
    split_feature: jnp.ndarray,  # [J] i32
    split_bin: jnp.ndarray,  # [J] i32
    default_left: jnp.ndarray,  # [J] bool
    left_child: jnp.ndarray,  # [J] i32
    right_child: jnp.ndarray,  # [J] i32
    members: int = 1,
) -> jnp.ndarray:
    """[N] i32: the LEAF the bin-space walk of one numeric tree reaches for
    every row (``tree_values`` over the leaves' own indices, which travel as
    bytes like any value).  A tree grown on the in-bag rows alone
    (``GrowerParams.bag_window``) never partitioned the others, as upstream's
    ``ScoreUpdater::AddScore`` walks the out-of-bag indices; here the walk is
    cheap enough (11 ms for 8M x 67 rows on a v5e) to give every row its
    leaf, the in-bag ones too.  ``members``: ``tree_values``'."""
    leaves = jnp.arange(split_feature.shape[0] + 1, dtype=jnp.float32)
    return tree_values(
        bins, nan_bins, split_feature, split_bin, default_left, left_child,
        right_child, leaves, members=members,
    ).astype(jnp.int32)
