"""Row-sampling strategies: bagging and GOSS.

Reference analogs: ``SampleStrategy`` (include/LightGBM/sample_strategy.h),
``BaggingSampleStrategy`` (src/boosting/bagging.hpp — per-row Bernoulli
``NextFloat() < bagging_fraction`` :239, balanced pos/neg variant :248) and
``GOSSStrategy`` (src/boosting/goss.hpp:30 — keep top ``top_rate`` rows by
sum_k |g_k*h_k|, sample ``other_rate`` of the rest, reweight by
(cnt-top_k)/other_k; no sampling for the first 1/learning_rate iterations).

TPU-native formulation: the reference's bag_data_indices index arrays become a
dense ``[N]`` f32 mask (1 = in bag); shapes stay static.  On the segment path
the grower brings the in-bag rows to the front of the packed buffer once a
tree and grows on that window alone (``GrowerParams.bag_window``,
ops/grower.py), so histogram and partition visit in-bag rows only; every other
histogram producer takes the mask as it is.  GOSS's ArgMaxAtK partial sort is
an exact selection of the ``top_k``-th largest value on the floats' bit
patterns (:func:`kth_largest`: comparisons and sums, no sort, no scatter), and
its "rest" draws are a hash of (``bagging_seed``, iteration, global row), the
same inside the launch scan and out of it, on one device and on any mesh.
"""

from __future__ import annotations

import functools
from typing import Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ..config import Config
from ..obs.jit import instrumented_jit
from ..ops.quantize import hashed_uniforms

# GOSS's rest draws take the stream words with the top bit set
# (ops/quantize.hashed_uniforms): word = GOSS_STREAM | iteration
GOSS_STREAM = 0x80000000


def kth_largest(values: jnp.ndarray, k) -> jnp.ndarray:
    """The ``k``-th largest of ``values`` ([N] f32, none negative), exactly:
    the bit pattern of a non-negative float orders as the float does, so the
    answer is the largest ``t`` with ``count(bits >= t) >= k``, found four
    bits a pass (fifteen candidates counted in one read of the array, eight
    passes).  Comparisons and sums alone: on a TPU a sort of 8M floats is
    tens of passes over them and a histogram by scatter serializes."""
    u32 = jnp.uint32
    bits = jax.lax.bitcast_convert_type(values.astype(jnp.float32), u32)
    digits = jnp.arange(1, 16, dtype=u32)
    t = u32(0)
    for shift in range(28, -1, -4):
        cands = t | (digits << u32(shift))  # ascending
        counts = jnp.sum(
            bits[None, :] >= cands[:, None], axis=1, dtype=jnp.int32
        )
        # the counts fall as the candidates rise: how many still hold k rows
        t = t | (jnp.sum(counts >= k).astype(u32) << u32(shift))
    return jax.lax.bitcast_convert_type(t, jnp.float32)


@functools.partial(instrumented_jit, static_argnames=("n", "top_k", "other_k"))
def goss_sample(grad, hess, iteration, seed, *, n: int, top_k: int, other_k: int):
    """One GOSS draw over [K, N] gradients (goss.hpp:30): (mask [N] f32,
    grad', hess', top_rows, threshold_ties).  Every row whose sum over the
    classes of |g h| is at or above the ``top_k``-th largest is in the top
    set (upstream's ArgMaxAtK and ``>=``); a row of the rest is kept iff its
    hash draw of (``seed``, ``iteration``, global row) lies under ``other_k /
    (n - top_k)`` (upstream selects sequentially per thread block, which no
    other layout reproduces) and is amplified by ``(n - top_k) / other_k``.
    One jitted entry: dispatched op by op the selection's [15, N] compare
    would be an array."""
    with jax.named_scope("sample"):
        metric = jnp.abs(grad * hess).sum(axis=0)  # sum over classes [N]
        threshold = kth_largest(metric, top_k)
        is_top = metric >= threshold
        rest_prob = jnp.float32(other_k / max(1, n - top_k))
        draws = hashed_uniforms(
            seed, iteration | jnp.uint32(GOSS_STREAM), metric.shape[0]
        )
        mask = (is_top | (draws < rest_prob)).astype(jnp.float32)
        factor = jnp.where(is_top, 1.0, (n - top_k) / other_k)[None, :]
        return (
            mask, grad * factor * mask[None, :], hess * factor * mask[None, :],
            jnp.sum(is_top, dtype=jnp.int32),
            jnp.sum(metric == threshold, dtype=jnp.int32),
        )


class SampleStrategy:
    """Base: no sampling."""

    is_hessian_change = False

    def __init__(self, config: Config, num_data: int):
        self.config = config
        self.num_data = num_data
        self._ones = jnp.ones((num_data,), jnp.float32)
        self._live_count: int | None = None

    def set_live_count(self, n: int | None) -> None:
        """Row count the strategy should size itself against when a fixed
        row mask (Booster.set_row_mask — CV folds, holdouts) restricts
        training to a subset: GOSS derives top_k/other_k and its
        reweighting factor from the LIVE rows, not the full matrix.  None
        restores full-data sizing; bagging is per-row Bernoulli and needs
        no adjustment (the fixed mask intersects it downstream)."""
        self._live_count = int(n) if n is not None else None

    def sample(
        self, iteration: int, grad: jnp.ndarray, hess: jnp.ndarray, rng: jax.Array
    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        return self._ones, grad, hess

    # -- device-resident boosting (boosting/launch.py): a trace-safe step
    # form of sample().  ``iteration`` is a traced i32 scalar inside the
    # lax.scan body, so the host-side refresh/warmup branches become
    # whole-array jnp.where selects (byte-equivalent: the rng key is drawn
    # every iteration in the serial loop too, and a select of untouched
    # inputs preserves their exact bit patterns — including -0.0).
    # ``carried_mask`` threads the bagging mask through the scan carry;
    # strategies without persistent state pass it through unchanged.

    def scan_sample(self, iteration, grad, hess, rng, carried_mask):
        ones = jnp.ones((self.num_data,), jnp.float32)
        return ones, grad, hess, carried_mask

    def scan_counters(self):
        """``(top_rows, threshold_ties)`` i32 scalars of the last
        ``scan_sample`` of the trace under way, for the launch's flight
        record (GOSS: the rows at or above the threshold, and those equal to
        it; zeros for every other strategy and for an unsampled iteration)."""
        return jnp.int32(0), jnp.int32(0)


class BaggingStrategy(SampleStrategy):
    """Per-row Bernoulli bagging, refreshed every ``bagging_freq`` iterations.

    ``query_sizes`` switches to per-QUERY bagging (reference
    ``bagging_by_query``, src/boosting/bagging.hpp:52): whole queries are
    kept or dropped as units so lambdarank's within-query pairs never see a
    partially-sampled query.  The reference rebuilds ``bag_data_indices``
    query by query; the TPU formulation draws one Bernoulli per query and
    expands it to rows with a static-shape ``jnp.repeat`` (query sizes are
    host constants — no gather)."""

    def __init__(self, config: Config, num_data: int, is_pos=None,
                 query_sizes=None, pad_query_mask=None):
        super().__init__(config, num_data)
        self._mask = self._ones
        self._last_refresh = -1
        self._is_pos = is_pos  # device bool [N] for balanced bagging, or None
        self._qsizes = None
        if query_sizes is not None:
            qs = np.asarray(query_sizes, np.int64)
            padq = (
                np.zeros(len(qs), bool)
                if pad_query_mask is None
                else np.asarray(pad_query_mask, bool)
            )
            pad = num_data - int(qs.sum())
            if pad < 0:
                raise ValueError(
                    f"query sizes sum {qs.sum()} > num_data {num_data}"
                )
            if pad:
                # trailing padding rows form a pseudo-query, never in bag
                # (multi-process feeding interleaves per-block pad entries
                # via pad_query_mask instead)
                qs = np.append(qs, pad)
                padq = np.append(padq, True)
            self._qsizes = qs
            self._qpad_dev = jnp.asarray(~padq, jnp.float32)

    def sample(self, iteration, grad, hess, rng):
        freq = max(1, self.config.bagging_freq)
        if iteration % freq == 0:
            self._mask = self._fresh_mask(rng)
        return self._mask, grad, hess

    def _fresh_mask(self, rng):
        cfg = self.config
        if self._qsizes is not None:
            nq = len(self._qsizes)
            qmask = jax.random.bernoulli(
                rng, cfg.bagging_fraction, (nq,)
            ).astype(jnp.float32)
            qmask = qmask * self._qpad_dev
            return jnp.repeat(
                qmask, self._qsizes, total_repeat_length=self.num_data
            )
        if self._is_pos is not None:
            p = jnp.where(
                self._is_pos, cfg.pos_bagging_fraction, cfg.neg_bagging_fraction
            )
            return (jax.random.uniform(rng, (self.num_data,)) < p).astype(
                jnp.float32
            )
        return jax.random.bernoulli(
            rng, cfg.bagging_fraction, (self.num_data,)
        ).astype(jnp.float32)

    def scan_sample(self, iteration, grad, hess, rng, carried_mask):
        freq = max(1, self.config.bagging_freq)
        fresh = self._fresh_mask(rng)
        mask = jnp.where(iteration % freq == 0, fresh, carried_mask)
        return mask, grad, hess, mask


class GOSSStrategy(SampleStrategy):
    """Gradient-based One-Side Sampling (src/boosting/goss.hpp)."""

    is_hessian_change = True

    def __init__(self, config: Config, num_data: int):
        super().__init__(config, num_data)
        if config.top_rate + config.other_rate > 1.0:
            raise ValueError("top_rate + other_rate must be <= 1.0")
        if config.top_rate <= 0 or config.other_rate <= 0:
            raise ValueError("top_rate and other_rate must be > 0 for GOSS")
        self._warmup = int(1.0 / max(config.learning_rate, 1e-12))
        self._counters = None

    def sample(self, iteration, grad, hess, rng):
        if iteration < self._warmup:
            return self._ones, grad, hess
        return self._goss(iteration, grad, hess)[:3]

    def _goss(self, iteration, grad, hess):
        """(mask, grad', hess', top_rows, threshold_ties) of a sampled
        iteration (``iteration``: a host int or a traced i32)."""
        cfg = self.config
        # with a fixed row mask the excluded rows reach us as exact zeros
        # (|g*h| = 0, never in the top set); sizing against the live count
        # keeps the effective top/other rates right for the subset
        n = self._live_count if self._live_count is not None else self.num_data
        return goss_sample(
            grad, hess, jnp.asarray(iteration, jnp.uint32),
            np.uint32(cfg.bagging_seed & 0xFFFFFFFF),
            n=n, top_k=max(1, int(n * cfg.top_rate)),
            other_k=max(1, int(n * cfg.other_rate)),
        )

    def scan_sample(self, iteration, grad, hess, rng, carried_mask):
        mask, g, h, top_rows, ties = self._goss(iteration, grad, hess)
        warm = iteration < self._warmup
        ones = jnp.ones((self.num_data,), jnp.float32)
        self._counters = (
            jnp.where(warm, 0, top_rows), jnp.where(warm, 0, ties),
        )
        return (
            jnp.where(warm, ones, mask),
            jnp.where(warm, grad, g),
            jnp.where(warm, hess, h),
            carried_mask,
        )

    def scan_counters(self):
        counters, self._counters = self._counters, None
        return counters


def is_goss(config: Config) -> bool:
    return (
        config.boosting == "goss"
        or (config.raw or {}).get("data_sample_strategy") == "goss"
    )


def sampling_is_active(config: Config) -> bool:
    """Whether trees are grown on a subset of the rows (GOSS, bagging of
    any kind, rf): from the Config alone, so the Booster can ask before its
    sampler exists (``GrowerParams.bag_window``)."""
    return is_goss(config) or bagging_is_active(config)


def bagging_is_active(config: Config) -> bool:
    """Whether any bagging mask will ever be drawn (used by the factory AND
    by the Booster to decide whether query info must be collected)."""
    need_balanced = (
        config.pos_bagging_fraction < 1.0 or config.neg_bagging_fraction < 1.0
    )
    return (
        config.bagging_freq > 0
        and (config.bagging_fraction < 1.0 or need_balanced)
    ) or config.boosting == "rf"


def create_sample_strategy(
    config: Config, num_data: int, is_pos=None, query_sizes=None,
    pad_query_mask=None,
) -> SampleStrategy:
    """Factory (reference: SampleStrategy::CreateSampleStrategy,
    src/boosting/sample_strategy.cpp)."""
    goss = is_goss(config)
    need_balanced = (
        config.pos_bagging_fraction < 1.0 or config.neg_bagging_fraction < 1.0
    )
    bagging_active = bagging_is_active(config)
    qs = query_sizes if config.bagging_by_query else None
    if config.bagging_by_query and bagging_active:
        # by-query sampling can't be combined with row-level strategies:
        # both would partially sample queries, the exact thing it forbids
        if goss:
            raise ValueError(
                "bagging_by_query cannot be combined with GOSS (GOSS "
                "samples individual rows, splitting queries)"
            )
        if need_balanced:
            raise ValueError(
                "bagging_by_query cannot be combined with pos/neg "
                "balanced bagging (balanced bagging samples individual "
                "rows, splitting queries)"
            )
        if query_sizes is None:
            raise ValueError(
                "bagging_by_query=True needs query information (set "
                "`group` on the train Dataset)"
            )
    if goss:
        return GOSSStrategy(config, num_data)
    pq = pad_query_mask if config.bagging_by_query else None
    if config.bagging_freq > 0 and (config.bagging_fraction < 1.0 or need_balanced):
        return BaggingStrategy(
            config, num_data, is_pos if need_balanced else None,
            query_sizes=qs, pad_query_mask=pq,
        )
    if config.boosting == "rf":
        # RF requires bagging (reference rf.hpp:25 CHECK)
        return BaggingStrategy(config, num_data, query_sizes=qs,
                               pad_query_mask=pq)
    return SampleStrategy(config, num_data)
