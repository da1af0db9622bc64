"""The two forms of "a leaf id per row" alone on the chip: the readings
``score_lookup.LEAF_WALK_MAX_WORK`` rests on (PERF.md section 6, PR 36).

``score_lookup.tree_leaves`` (the tree's contraction walk over the binned
matrix) against ``segpart.leaf_of_positions`` + ``leaf_id_from_seg`` (the
segment form of the scope ``leaf_ids``), each jitted by itself on a random
tree, at the cells' table shapes and 255 to 1,023 leaves.  A builder's tool:
nothing imports it, and its times mean something on a TPU only.

    chiprun -- python3 tools/leaf_ids_forms.py [--out chiprun_out/leaf_ids_forms.json]
"""

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lightgbm_tpu.ops.score_lookup import _pad128, leaf_ids_form, tree_leaves  # noqa: E402
from lightgbm_tpu.ops.segpart import leaf_id_from_seg, leaf_of_positions  # noqa: E402

# (rows, columns, dtype of the bins, leaf counts): criteo67, the same with
# two-digit bins, higgs, epsilon
SHAPES = [
    (8_000_000, 67, jnp.uint8, [255, 511, 767, 1023]),
    (8_000_000, 67, jnp.uint16, [255]),
    (10_500_000, 28, jnp.uint8, [255, 511, 767, 1023]),
    (400_000, 2000, jnp.uint8, [255, 511, 1023]),
]


def timed_ms(fn, *args, reps=8):
    jax.block_until_ready(fn(*args))  # compiles
    start = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / reps * 1e3


def random_tree(num_leaves, num_features, rng):
    """(split_feature, split_bin, left_child, right_child) of a tree grown by
    splitting a random leaf ``num_leaves - 1`` times (the grower's numbering:
    the left child keeps the leaf's index, the right takes the next)."""
    left = np.full(num_leaves - 1, -1, np.int32)
    right = np.full(num_leaves - 1, -1, np.int32)
    parent_of = {0: None}
    for node in range(num_leaves - 1):
        leaf = int(rng.integers(0, node + 1)) if node else 0
        if parent_of[leaf] is not None:
            side, at = parent_of[leaf]
            side[at] = node
        left[node], right[node] = ~leaf, ~(node + 1)
        parent_of[leaf], parent_of[node + 1] = (left, node), (right, node)
    return (rng.integers(0, num_features, num_leaves - 1).astype(np.int32),
            rng.integers(20, 220, num_leaves - 1).astype(np.int32), left, right)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default="chiprun_out/leaf_ids_forms.json")
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"not a TPU ({device.platform}): these times would say nothing", file=sys.stderr)
        return 2
    walk = jax.jit(tree_leaves)
    readings = []
    for rows, features, dtype, leaf_counts in SHAPES:
        bins = jax.random.randint(jax.random.PRNGKey(2), (rows, features), 0, 241).astype(dtype)
        row_of = jax.random.permutation(jax.random.PRNGKey(3), rows).astype(jnp.int32)
        nan_bins = jnp.full((features,), -1, jnp.int32)
        for leaves in leaf_counts:
            sf, sb, lc, rc = map(jnp.asarray, random_tree(leaves, features, np.random.default_rng(leaves)))
            walk_ms = timed_ms(walk, bins, nan_bins, sf, sb, jnp.zeros((leaves - 1,), bool), lc, rc)
            begin = jnp.sort(jax.random.randint(jax.random.PRNGKey(4), (leaves,), 0, rows)).at[0].set(0)
            counts = jnp.diff(jnp.concatenate([begin, jnp.asarray([rows])]))
            segment = jax.jit(lambda b, c, r, leaves=leaves, rows=rows: leaf_id_from_seg(
                r, leaf_of_positions(b, c, jnp.int32(leaves), rows)))
            readings.append({
                "rows": rows, "features": features, "dtype": jnp.dtype(dtype).name, "leaves": leaves,
                "work": _pad128(leaves - 1) * (features + _pad128(leaves)),
                "walk_ms": walk_ms, "segment_ms": timed_ms(segment, begin, counts, row_of),
                "rule": leaf_ids_form(leaves, features, 1, 0),
            })
            print(json.dumps(readings[-1]), flush=True)
        del bins, row_of
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w", encoding="utf-8") as fh:
        json.dump({"device": device.device_kind, "readings": readings}, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
