"""Histogram construction — the hottest kernel of GBDT training.

Reference analogs: ``DenseBin::ConstructHistogramInner`` (src/io/dense_bin.hpp:99,
the scalar gather loop), ``MultiValBinWrapper::ConstructHistograms``
(include/LightGBM/train_share_states.h:48, thread-block histograms + merge)
and the CUDA shared-memory kernel (src/treelearner/cuda/
cuda_histogram_constructor.cu:19-130).

TPU-native formulation: TPUs have no fast random scatter, so the
scatter-add becomes either
  * a ``segment_sum`` over flattened (feature, bin) ids (XLA sorted-scatter),
    or
  * a chunked one-hot matmul ``one_hot(bins) @ (g,h,c)`` that runs on the
    MXU — the dense-masked analog of the CUDA shared-mem accumulation.
Rows outside the target leaf contribute zeros via the mask (dense masked
ops instead of the reference's ordered_gradients gather).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..obs.collectives import timed_psum


@functools.lru_cache(maxsize=None)
def _segment_hist_fn(num_bins: int):
    """Per-``num_bins`` segment-sum histogram with a fleet-aware vmap rule.

    Under ``jax.vmap`` (model-fleet training batches grad/hess/mask over a
    leading member axis M) the default batching of ``segment_sum`` emits one
    scatter per member.  The custom rule instead folds the member axis into
    the segment ids — ``id += member * (F * B)`` — so all M histograms
    accumulate in a single segment_sum launch over ``M * F * B`` segments.
    Float adds happen in the same per-(row, feature, bin) order as the
    unbatched kernel, so each member's [3, F, B] planes are byte-identical
    to its solo run.  ``num_bins`` is closed over (lru_cached) because
    custom_vmap arguments must all be array operands.
    """

    @jax.custom_batching.custom_vmap
    def impl(bins, grad, hess, mask):
        n, f = bins.shape
        ids = (bins + jnp.arange(f, dtype=jnp.int32)[None, :] * num_bins).reshape(-1)
        g = (grad * mask)[:, None]
        h = (hess * mask)[:, None]
        c = mask[:, None]
        data = jnp.broadcast_to(
            jnp.concatenate([g, h, c], axis=1)[:, None, :], (n, f, 3)
        ).reshape(-1, 3)
        hist = jax.ops.segment_sum(data, ids, num_segments=f * num_bins)
        return hist.T.reshape(3, f, num_bins)

    @impl.def_vmap
    def impl_vmap(axis_size, in_batched, bins, grad, hess, mask):
        m = axis_size

        def bcast(x, batched):
            return x if batched else jnp.broadcast_to(x[None], (m,) + x.shape)

        bins_b = bcast(bins, in_batched[0])
        grad_b = bcast(grad, in_batched[1])
        hess_b = bcast(hess, in_batched[2])
        mask_b = bcast(mask, in_batched[3])
        _, n, f = bins_b.shape
        ids = bins_b + jnp.arange(f, dtype=jnp.int32)[None, None, :] * num_bins
        ids = ids + (jnp.arange(m, dtype=jnp.int32) * (f * num_bins))[:, None, None]
        ghc = jnp.stack(
            [grad_b * mask_b, hess_b * mask_b, mask_b], axis=-1
        )  # [M, N, 3]
        data = jnp.broadcast_to(ghc[:, :, None, :], (m, n, f, 3)).reshape(-1, 3)
        hist = jax.ops.segment_sum(
            data, ids.reshape(-1), num_segments=m * f * num_bins
        )
        planes = hist.reshape(m, f * num_bins, 3).transpose(0, 2, 1)
        return planes.reshape(m, 3, f, num_bins), True

    return impl


def leaf_histogram_segment(
    bins: jnp.ndarray,  # [N, F] int32 bin indices
    grad: jnp.ndarray,  # [N] f32
    hess: jnp.ndarray,  # [N] f32
    mask: jnp.ndarray,  # [N] f32 — 1 for rows of the target leaf (in-bag), else 0
    num_bins: int,
) -> jnp.ndarray:
    """Masked histogram via segment_sum. Returns [3, F, B] (g, h, count
    planes, stat axis first).

    Vmapping over a leading member axis (fleet training) collapses into one
    flattened segment_sum launch — see ``_segment_hist_fn``."""
    return _segment_hist_fn(int(num_bins))(bins, grad, hess, mask)


def leaf_histogram_onehot(
    bins: jnp.ndarray,
    grad: jnp.ndarray,
    hess: jnp.ndarray,
    mask: jnp.ndarray,
    num_bins: int,
    chunk: int = 16384,
) -> jnp.ndarray:
    """Masked histogram as chunked one-hot matmuls (MXU-friendly).

    hist[k, f, b] = sum_n ghc[n, k] * [bins[n, f] == b]
    computed as a dot_general with the row axis contracted, scanning over
    fixed-size row chunks to bound memory.
    """
    n, f = bins.shape
    ghc = jnp.stack([grad * mask, hess * mask, mask], axis=1)  # [N, 3]
    pad = (-n) % chunk
    if pad:
        bins = jnp.pad(bins, ((0, pad), (0, 0)))
        ghc = jnp.pad(ghc, ((0, pad), (0, 0)))
    nchunks = (n + pad) // chunk
    bins_c = bins.reshape(nchunks, chunk, f)
    ghc_c = ghc.reshape(nchunks, chunk, 3)

    def body(acc, xs):
        b_c, v_c = xs
        onehot = jax.nn.one_hot(b_c, num_bins, dtype=jnp.float32)  # [chunk, F, B]
        # contract over rows: [3, chunk] x [chunk, F, B] -> [3, F, B]
        part = jax.lax.dot_general(
            v_c,
            onehot,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        return acc + part, None

    init = jnp.zeros((3, f, num_bins), dtype=jnp.float32)
    hist, _ = jax.lax.scan(body, init, (bins_c, ghc_c))
    return hist


def leaf_histogram(
    bins: jnp.ndarray,
    grad: jnp.ndarray,
    hess: jnp.ndarray,
    mask: jnp.ndarray,
    num_bins: int,
    *,
    method: str = "auto",
    axis_name: Optional[str] = None,
    quant_scales=None,  # (g_scale, h_scale) for the pallas_int8 methods
    measure: bool = False,  # timed-psum instrumentation (obs/collectives)
    psum_site: str = "hist",  # measured-site label (hist | hist_db0 | hist_db1)
) -> jnp.ndarray:
    """Dispatch histogram impl; psum across the data mesh axis if given.

    The psum is the TPU-native replacement for the reference's histogram
    ReduceScatter (src/treelearner/data_parallel_tree_learner.cpp:286, XLA
    collective over ICI instead of hand-rolled TCP recursive-halving).
    ``measure`` (static, from ``GrowerParams.measure_collectives``) swaps
    the bare psum for the timed/byte-counted wrapper.  ``psum_site``
    lets double-buffered callers label which buffer this reduction feeds
    (the grower's overlap path psums half the frontier under
    ``hist_db0`` while building the other half, then ``hist_db1``).
    """
    if method == "auto":
        # Dispatch on the LOWERING platform: lax.platform_dependent lowers
        # only the branch of the platform the computation is placed on.
        from .pallas.histogram import histogram_pallas

        if jax.default_backend() != "tpu":
            # no TPU in this process: nothing can lower the Pallas branch,
            # so don't trace it
            hist = leaf_histogram_segment(bins, grad, hess, mask, num_bins)
        else:
            hist = jax.lax.platform_dependent(
                bins,
                grad,
                hess,
                mask,
                tpu=functools.partial(histogram_pallas, num_bins=num_bins),
                default=functools.partial(leaf_histogram_segment, num_bins=num_bins),
            )
        if axis_name is not None:
            hist = timed_psum(hist, axis_name, site=psum_site, measure=measure)
        return hist
    if method == "pallas":
        from .pallas.histogram import histogram_pallas

        hist = histogram_pallas(bins, grad, hess, mask, num_bins)
    elif method == "pallas_interpret":
        from .pallas.histogram import histogram_pallas

        hist = histogram_pallas(bins, grad, hess, mask, num_bins, interpret=True)
    elif method in ("pallas_int8", "pallas_int8_interpret"):
        # quantized-gradient integer kernel: exact int32 accumulation of the
        # int8 grid (requires use_quantized_grad so the scales exist)
        if quant_scales is None:
            raise ValueError(
                f"method={method!r} needs quantized gradients "
                "(use_quantized_grad=True provides the scales)"
            )
        from .pallas.histogram_int8 import histogram_pallas_int8

        hist = histogram_pallas_int8(
            bins, grad, hess, mask, num_bins,
            quant_scales[0], quant_scales[1],
            interpret=method.endswith("interpret"),
        )
    elif method == "onehot":
        hist = leaf_histogram_onehot(bins, grad, hess, mask, num_bins)
    elif method == "segment":
        hist = leaf_histogram_segment(bins, grad, hess, mask, num_bins)
    else:
        raise ValueError(f"unknown histogram method {method!r}")
    if axis_name is not None:
        hist = timed_psum(hist, axis_name, site=psum_site, measure=measure)
    return hist
