"""Integer histogram kernel for quantized-gradient training.

Reference analog: the 16/32-bit packed integer histogram accumulation that
quantized training enables in the reference
(src/treelearner/gradient_discretizer.cpp + feature_histogram.hpp's
PACKED_HIST_BIN_T int paths).

With ``use_quantized_grad`` the per-row (g, h) are small integers times a
scale (ops/quantize.py). This kernel recovers the grid integers as a
2-DIGIT int8 pair (q = hi*128 + lo, |hi| <= 127, |lo| <= 64 — histogram
engine v2's shared convention, see seg.py), one-hots the bins as int8, and
contracts int8 x int8 -> int32 on the MXU — EXACT integer accumulation on
the quantized grid (no bf16 hi/lo split needed) at twice the bf16 MXU
rate.  The kernel emits the RAW [8, F*bpad] i32 accumulator planes (the
i32 VMEM tile height — GL005-clean); the digit recombine/dequantize runs
outside in seg.combine_hist_raw, so the [3, F, B] f32 histogram drops into
the existing split search unchanged.

Selected explicitly via ``hist_method='pallas_int8'`` (grower params); the
seg fast path engages the same 2-digit accumulation by DEFAULT via
``hist_acc`` (ops/grower.py), with an f32 re-accumulate for near ties.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...obs.jit import instrumented_jit
from jax.experimental import pallas as pl

from .histogram import tile_pallas_histogram
from .seg import QMAX, combine_hist_raw


def _hist_kernel_int8(
    bins_ref,
    ghc_ref,  # [TR, 8] int8 2-digit rows (already masked; built outside)
    out_ref,  # [8, F*bpad] int32 — RAW accumulator planes
    onehot_ref,  # [TR, FG*bpad] int8 scratch
    *,
    num_features: int,
    bpad: int,
    group: int,
):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ghc_t = ghc_ref[...]  # [TR, 8] int8
    bins_t = bins_ref[...].astype(jnp.int32)
    tr = ghc_t.shape[0]
    iota = jax.lax.broadcasted_iota(jnp.int32, (tr, bpad), 1)
    ngroups = (num_features + group - 1) // group
    for gi in range(ngroups):
        base = gi * group
        nf = min(group, num_features - base)
        for j in range(nf):
            col = bins_t[:, base + j]
            onehot_ref[:, j * bpad : (j + 1) * bpad] = (
                col[:, None] == iota
            ).astype(jnp.int8)
        if nf < group:
            onehot_ref[:, nf * bpad :] = jnp.zeros(
                (tr, (group - nf) * bpad), jnp.int8
            )
        part = jax.lax.dot_general(
            ghc_t,
            onehot_ref[...],
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )  # [8, FG*bpad] int32 — exact
        width = nf * bpad
        out_ref[:, base * bpad : base * bpad + width] += part[:, :width]


def int8_digit_rows(grad, hess, mask, g_scale, h_scale):
    """[N, 8] int8 2-digit stat rows (g_hi, h_hi, m, g_lo, h_lo, 0, 0, 0):
    q = round(stat/scale) clipped to +-QMAX, split q = hi*128 + lo with the
    +64 bias so both digits are int8-safe (|hi| <= 127, |lo| <= 64).  On
    the quantized-training grid (|q| <= 127) the split is exact."""
    n = grad.shape[0]
    m = (mask > 0).astype(jnp.int32)
    qg = jnp.clip(jnp.round(grad / g_scale), -QMAX, QMAX).astype(jnp.int32) * m
    qh = jnp.clip(jnp.round(hess / h_scale), -QMAX, QMAX).astype(jnp.int32) * m
    g_hi = (qg + 64) >> 7
    g_lo = qg - (g_hi << 7)
    h_hi = (qh + 64) >> 7
    h_lo = qh - (h_hi << 7)
    return jnp.stack(
        [g_hi, h_hi, m, g_lo, h_lo, jnp.zeros_like(m), jnp.zeros_like(m),
         jnp.zeros_like(m)],
        axis=1,
    ).astype(jnp.int8)


@functools.partial(
    instrumented_jit, static_argnames=("num_bins", "interpret")
)
def histogram_pallas_int8(
    bins: jnp.ndarray,  # [N, F] integer bins
    grad: jnp.ndarray,  # [N] f32 — QUANTIZED grid values (k * g_scale)
    hess: jnp.ndarray,  # [N] f32 — quantized grid values (k * h_scale)
    mask: jnp.ndarray,  # [N] f32 in {0, 1}
    num_bins: int,
    g_scale: jnp.ndarray,  # scalar f32
    h_scale: jnp.ndarray,  # scalar f32
    interpret: bool = False,
) -> jnp.ndarray:
    """[3, F, B] (sum_g, sum_h, count planes) from 2-digit int8 MXU
    accumulation."""
    n, f = bins.shape
    if f == 0:
        return jnp.zeros((3, 0, num_bins), jnp.float32)
    ghc = int8_digit_rows(grad, hess, mask, g_scale, h_scale)
    out, bpad = tile_pallas_histogram(
        bins, ghc, num_bins, _hist_kernel_int8, jnp.int8, jnp.int32, interpret
    )
    scales = jnp.stack(
        [g_scale.astype(jnp.float32), h_scale.astype(jnp.float32)]
    )
    return combine_hist_raw(
        out[None, None], scales, f=f, bpad=bpad, group=f, num_bins=num_bins,
        quantized=True,
    )[0]
