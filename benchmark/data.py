"""Inputs from ``--seed``: one general generator, parameters from data files.

The recipe is the benchmark's own copy of ``chip_smoke.make_data``'s (a noisy
linear logit over standard-normal features: learnable, not separable) with
one change, stated in PERF.md: every feature lies on a fixed grid of
``levels`` values (``clip(rint(z * grid), -half, half) / grid``).  A feature
with at most ``max_bin`` distinct values gets one bin per value, so the
candidate thresholds of the exact greedy algorithm and of the histogram
algorithm are the same set and the plain reference needs nothing of the
program's (no bin boundaries) to judge a split.  The binned matrix on the
device has the same shape and type as for continuous features.

Rows are made in blocks, each from its own ``SeedSequence`` child, so the
same seed gives the same rows however many threads make them, and 32M x 67
never exists as float64.

What ``--seed`` changes: the table's VALUES come from the recipe's
``base_seed`` and the run's seed draws the ORDER: a permutation of the
feature columns and a mirror (sign flip) of each.  Every seed then gives a
different matrix whose trees are the same up to renaming, so every seed
gives the device the same amount of work — the contract's "the same set of
sizes, in another order" — and run-to-run spread is the machine's, not the
data's.  The rows keep their order, so the labels are the same for every
seed: the program's launch scan bakes the labels into its executable
(PERF.md, Findings), and labels that moved with the seed would compile
anew, a minute, in every run.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Tuple

import numpy as np

BLOCK_ROWS = 500_000
# stream ids under one seed
_TRAIN, _VALID, _WEIGHTS, _ORDER = 0, 1, 2, 3

def _weights(seed: int, n_features: int, recipe: Dict[str, Any]) -> np.ndarray:
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), _WEIGHTS]))
    scale = recipe["logit_scale_at_28"] * np.sqrt(28.0 / n_features)
    return rng.normal(size=n_features) * scale


def _block(seed: int, stream: int, index: int, rows: int, n_features: int,
           w: np.ndarray, recipe: Dict[str, Any]) -> Tuple[np.ndarray, np.ndarray]:
    rng = np.random.default_rng(
        np.random.SeedSequence([int(seed), int(stream), int(index)])
    )
    z = rng.standard_normal((rows, n_features), dtype=np.float32)
    g = np.float32(recipe["grid"])
    h = np.float32(recipe["half_levels"])
    np.multiply(z, g, out=z)
    np.rint(z, out=z)
    np.clip(z, -h, h, out=z)
    np.divide(z, g, out=z)
    noise = rng.standard_normal(rows, dtype=np.float32) * np.float32(
        recipe["noise_scale"]
    )
    logit = z @ w.astype(np.float32) + noise
    return z, (logit > 0).astype(np.float32)


def make_blocks(seed: int, rows: int, n_features: int, *, recipe: Dict[str, Any],
                valid: bool = False, threads: int = 8) -> Tuple[List[np.ndarray], np.ndarray]:
    """``rows`` x ``n_features`` float32 in blocks of ``BLOCK_ROWS`` and the
    labels.  ``recipe`` is the configuration file's ``data`` (grid points per
    unit, clip, logit and noise scales, ``base_seed``); ``valid`` draws from a
    second stream."""
    if recipe["kind"] != "grid_normal_linear_logit":
        raise ValueError(f"unknown data recipe {recipe['kind']!r}")
    value_seed = int(recipe["base_seed"])
    w = _weights(value_seed, n_features, recipe)
    stream = _VALID if valid else _TRAIN
    sizes = [min(BLOCK_ROWS, rows - s) for s in range(0, rows, BLOCK_ROWS)]
    cols = np.random.default_rng(np.random.SeedSequence([int(seed), _ORDER]))
    perm = cols.permutation(n_features)  # the same columns for train and valid
    sign = cols.choice(np.array([-1.0, 1.0], np.float32), size=n_features)

    def one(a):
        x, y = _block(value_seed, stream, a[0], a[1], n_features, w, recipe)
        return x[:, perm] * sign, y  # the grid is symmetric: a mirror stays on it

    with ThreadPoolExecutor(max_workers=max(1, threads)) as ex:
        parts = list(ex.map(one, enumerate(sizes)))
    return [p[0] for p in parts], np.concatenate([p[1] for p in parts])
