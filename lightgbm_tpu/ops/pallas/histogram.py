"""Pallas TPU histogram kernel — the framework's hottest op.

Reference analogs: the scalar gather loop ``DenseBin::ConstructHistogramInner``
(src/io/dense_bin.hpp:99) and the CUDA shared-memory kernel
(src/treelearner/cuda/cuda_histogram_constructor.cu:19-130,
NUM_DATA_PER_THREAD/SHARED_HIST_SIZE tuning in the .hpp).

TPU formulation: TPUs have no fast scatter-add, so the per-row bin increment
becomes a dense one-hot contraction on the MXU.  The naive per-feature matmul
``[TR,B] x [TR,3]`` has a 3-wide output — ~2% of the MXU lane width — so this
kernel instead:

  * tiles rows into VMEM (grid over row tiles, accumulating across steps);
  * builds the one-hot for a GROUP of features at once into a VMEM scratch
    ``[TR, FG*B_pad]`` via per-feature iota compares (VPU work, one [TR,B]
    block store per feature — no MXU involvement);
  * contracts ``ghc8[TR, 8] x onehot[TR, FG*B_pad] -> [8, FG*B_pad]`` — the
    contraction (TR) and lane (FG*B_pad ~ 2048) dims are both MXU-sized, so
    one wide matmul replaces FG narrow ones;
  * ghc8 packs (g, h) as a THREE-term bf16 split plus count hi/lo (the
    one-hot factor is exact in bf16 and the residuals carry ~16 extra
    mantissa bits — the 8-row operand is exactly the MXU's output sublane
    tile, so the extra residual rows are free; histogram engine v2 made
    the third term and the 8-row layout the default);
  * emits the RAW [8, F*bpad] accumulator planes — 8 sublanes is the
    f32/i32 VMEM tile height (GL005-clean, no baselined layout needed) —
    and the term recombine runs OUTSIDE the kernel in plain XLA
    (seg.combine_hist_raw, shared with the seg kernels).

HBM traffic is exactly bins + ghc read once; the VMEM-resident accumulation
mirrors the CUDA kernel's shared-memory histogram.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...obs.jit import instrumented_jit
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_TILE_ROWS = 1024
_TARGET_LANES = 2048  # FG*B_pad per matmul


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _hist_kernel(
    bins_ref,
    ghc_ref,
    out_ref,
    onehot_ref,
    *,
    num_features: int,
    bpad: int,
    group: int,
):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ghc_t = ghc_ref[...]  # [TR, 3] f32 (mask already folded in)
    bins_t = bins_ref[...].astype(jnp.int32)  # [TR, F]
    tr = ghc_t.shape[0]
    # THREE-term bf16 split of g/h (count's residual is zero) packed as one
    # [TR, 8] operand -> single wide matmul.  Row convention (shared with
    # seg._hist_window / combine_hist_raw): 0 g_hi, 1 h_hi, 2 count,
    # 3 g_lo, 4 h_lo, 5 c_lo, 6 g_lo2, 7 h_lo2.
    ghc_hi = ghc_t.astype(jnp.bfloat16)
    r1 = ghc_t - ghc_hi.astype(jnp.float32)
    ghc_lo = r1.astype(jnp.bfloat16)
    ghc_lo2 = (r1[:, :2] - ghc_lo[:, :2].astype(jnp.float32)).astype(
        jnp.bfloat16
    )
    ghc8 = jnp.concatenate([ghc_hi, ghc_lo, ghc_lo2], axis=1)  # [TR, 8]

    iota = jax.lax.broadcasted_iota(jnp.int32, (tr, bpad), 1)
    ngroups = (num_features + group - 1) // group
    for gi in range(ngroups):
        base = gi * group
        nf = min(group, num_features - base)
        for j in range(nf):
            col = bins_t[:, base + j]
            onehot_ref[:, j * bpad : (j + 1) * bpad] = (
                col[:, None] == iota
            ).astype(jnp.bfloat16)
        if nf < group:  # tail group: clear stale columns
            onehot_ref[:, nf * bpad :] = jnp.zeros(
                (tr, (group - nf) * bpad), jnp.bfloat16
            )
        part = jax.lax.dot_general(
            ghc8,
            onehot_ref[...],
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [8, FG*bpad]
        width = nf * bpad  # tail group writes only its live columns
        out_ref[:, base * bpad : base * bpad + width] += part[:, :width]


def tile_pallas_histogram(
    bins, ghc, num_bins, kernel_body, scratch_dtype, out_dtype, interpret
):
    """Shared tile/pad/group machinery for the histogram kernels (bf16
    3-term and 2-digit int8): rows tiled into VMEM, features grouped to
    ~_TARGET_LANES lanes, accumulation across row tiles.  Returns the RAW
    accumulator planes ([8, F*bpad], bpad) — callers recombine outside the
    kernel via seg.combine_hist_raw."""
    n, f = bins.shape
    bpad = _round_up(max(num_bins, 1), 128)
    group = min(max(1, _TARGET_LANES // bpad), f)
    tr = min(_TILE_ROWS, max(256, 1 << (n - 1).bit_length() if n > 1 else 256))
    pad = (-n) % tr
    if pad:
        bins = jnp.pad(bins, ((0, pad), (0, 0)))
        ghc = jnp.pad(ghc, ((0, pad), (0, 0)))
    tiles = (n + pad) // tr
    kernel = functools.partial(
        kernel_body, num_features=f, bpad=bpad, group=group
    )
    out = pl.pallas_call(
        kernel,
        grid=(tiles,),
        in_specs=[
            pl.BlockSpec((tr, f), lambda i: (i, 0)),
            pl.BlockSpec((tr, ghc.shape[1]), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((8, f * bpad), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((8, f * bpad), out_dtype),
        scratch_shapes=[pltpu.VMEM((tr, group * bpad), scratch_dtype)],
        interpret=interpret,
        compiler_params=(
            pltpu.CompilerParams(dimension_semantics=("arbitrary",))
            if not interpret
            else None
        ),
    )(bins, ghc)
    return out, bpad


@functools.partial(instrumented_jit, static_argnames=("num_bins", "interpret"))
def histogram_pallas(
    bins: jnp.ndarray,  # [N, F] integer bins (int8/uint8/int32 ...)
    grad: jnp.ndarray,  # [N] f32
    hess: jnp.ndarray,  # [N] f32
    mask: jnp.ndarray,  # [N] f32
    num_bins: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Masked histogram [3, F, B] = (sum_g, sum_h, count) planes per
    (feature, bin)."""
    n, f = bins.shape
    if f == 0:  # all-constant datasets: platform_dependent traces all branches
        return jnp.zeros((3, 0, num_bins), jnp.float32)
    from .seg import combine_hist_raw

    ghc = jnp.stack([grad * mask, hess * mask, mask], axis=1)  # [N, 3]
    out, bpad = tile_pallas_histogram(
        bins, ghc, num_bins, _hist_kernel, jnp.bfloat16, jnp.float32, interpret
    )
    # raw [8, F*bpad] planes -> recombined [3, F, B] outside the kernel
    return combine_hist_raw(
        out[None, None],
        jnp.ones((2,), jnp.float32),
        f=f, bpad=bpad, group=f, num_bins=num_bins, quantized=False,
    )[0]
