"""Quantized-gradient training (reference: GradientDiscretizer,
src/treelearner/gradient_discretizer.cpp; LightGBM 4's use_quantized_grad).

Per tree the gradients and hessians are discretized to a few integer levels
(``num_grad_quant_bins``), histograms are integer sums, split gains multiply
the integer sums by the two per-tree scales, and leaf outputs are renewed
from the TRUE gradients after the tree is grown (RenewIntGradTreeOutput,
gradient_discretizer.cpp:209) when ``quant_train_renew_leaf``.

The values travel as f32 grid MULTIPLES (qg = k * g_scale, integer k): the
segment kernels recover k exactly and accumulate it on the int8 MXU path in
int32 (ops/pallas/seg.py, ``quantized=True``); every other histogram
producer sums the multiples in f32.  The stochastic-rounding offsets are a
stateless integer mix of (seed, tree, row, stream) — :func:`rounding_uniforms`
— so the launch scan (boosting/launch.py), the per-iteration loop and a mesh
of any layout round every row alike.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..obs.collectives import timed_psum
from ..obs.jit import instrumented_jit

from .split import leaf_output


def _fmix32(x: jnp.ndarray) -> jnp.ndarray:
    """MurmurHash3's 32-bit finalizer (Appleby, public domain): a bijection
    of uint32 whose every output bit depends on every input bit."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def hashed_uniforms(seed, word, n: int) -> jnp.ndarray:
    """[n] f32 in [0, 1), 24 bits each: ``u = (fmix32(row ^ fmix32(seed ^
    word * 0x9E3779B9)) >> 8) * 2**-24`` for the rows 0..n-1 of the global
    [N] array — a pure function of (seed, word, row index).  No key, no host
    state: row r draws the same value inside the launch scan and out of it,
    on one device and under any mesh layout (the iota is the global row
    index under SPMD partitioning), and an independent reference can write
    the same 24 bits in NumPy.  ``word`` names the stream: the rounding
    offsets of quantized training take the words below 2**31
    (:func:`rounding_uniforms`), GOSS's rest draws those above
    (boosting/sampling.py)."""
    u32 = jnp.uint32
    word = jnp.asarray(word).astype(u32)
    key = _fmix32(jnp.asarray(seed).astype(u32) ^ (word * u32(0x9E3779B9)))
    rows = jnp.arange(n, dtype=jnp.uint32)
    x = _fmix32(rows ^ key)
    # 24 bits: every value is an exact f32 and u < 1
    return (x >> 8).astype(jnp.int32).astype(jnp.float32) * jnp.float32(2.0**-24)


def rounding_uniforms(seed, tree_index, n: int, stream: int) -> jnp.ndarray:
    """[n] f32 in [0, 1): the stochastic-rounding offset of every row, a
    pure function of (seed, tree index, row index, stream 0 = gradient |
    1 = hessian) and of nothing else (:func:`hashed_uniforms` on the word
    ``2 * tree_index + stream``; benchmark/reference/criteo67-quant.py writes
    the same bits in NumPy).  ``tree_index`` is iteration *
    trees-per-iteration + class."""
    u32 = jnp.uint32
    word = jnp.asarray(tree_index).astype(u32) * u32(2) + u32(stream)
    return hashed_uniforms(seed, word, n)


@functools.partial(
    instrumented_jit, static_argnames=("num_bins", "stochastic", "constant_hessian")
)
def quantize_gradients(
    grad: jnp.ndarray,  # [N] f32
    hess: jnp.ndarray,  # [N] f32
    seed,  # scalar: the booster's seed
    tree_index,  # scalar: iteration * trees-per-iteration + class
    num_bins: int = 4,
    stochastic: bool = True,
    constant_hessian: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Quantize (grad, hess) onto the reference's integer grid
    (DiscretizeGradients, gradient_discretizer.cpp:70-160: scales from the
    max |value|, truncation toward zero, optional stochastic rounding with
    the offsets of :func:`rounding_uniforms`).

    Returns (qg, qh, g_scale, h_scale): qg/qh are f32 grid MULTIPLES
    (qg = k * g_scale with integer k, |k| <= num_bins), and the scales let
    the integer kernels recover k exactly (ops/pallas/seg.py)."""
    if num_bins > 127:
        raise ValueError(
            "num_grad_quant_bins must be <= 127 (int8 grid)"
        )
    with jax.named_scope("quantize"):
        max_g = jnp.max(jnp.abs(grad))
        max_h = jnp.max(jnp.abs(hess))
        g_scale = jnp.maximum(max_g / (num_bins // 2), 1e-30)
        h_scale = jnp.maximum(
            max_h if constant_hessian else max_h / num_bins, 1e-30
        )
        gi = grad / g_scale
        hi = hess / h_scale
        if stochastic:
            rg = rounding_uniforms(seed, tree_index, grad.shape[0], 0)
            rh = rounding_uniforms(seed, tree_index, hess.shape[0], 1)
        else:
            rg = jnp.float32(0.5)
            rh = jnp.float32(0.5)
        # C's int8 cast truncates toward zero; rounding offset follows the sign
        qg = jnp.trunc(jnp.where(gi >= 0, gi + rg, gi - rg))
        qh = jnp.trunc(hi + rh)  # hessians are non-negative
        if constant_hessian:
            qh = jnp.ones_like(qh)
        return qg * g_scale, qh * h_scale, g_scale, h_scale


def hist_acc_scales(
    grad: jnp.ndarray,  # [N] f32 TRUE gradients
    hess: jnp.ndarray,  # [N] f32
    mask: Optional[jnp.ndarray] = None,  # [N] in-bag mask (None = all)
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-iteration scales for the DEFAULT int8 histogram accumulator
    (histogram engine v2): unlike ``quantize_gradients`` — which changes
    the training values themselves — these scales only parameterize how
    the seg kernels accumulate UNCHANGED f32 gradients on the int8 MXU
    path.  The grid is the kernels' 2-digit ceiling (seg.QMAX = 16256), so
    every in-bag |g| maps to at most QMAX with a relative quantization
    step of ~1/16256 ~= 6e-5 — inside the near-tie tolerance the grower's
    f32 re-accumulate pass covers (GrowerParams.near_tie_tol).

    Computed ONCE per boosting iteration (the max is over the in-bag
    rows), reused by every histogram launch of the tree."""
    from .pallas.seg import QMAX

    if mask is not None:
        grad = grad * mask
        hess = hess * mask
    g_scale = jnp.maximum(jnp.max(jnp.abs(grad)) / QMAX, 1e-30)
    h_scale = jnp.maximum(jnp.max(jnp.abs(hess)) / QMAX, 1e-30)
    return g_scale.astype(jnp.float32), h_scale.astype(jnp.float32)


def _bf16_head(v: jnp.ndarray) -> jnp.ndarray:
    """f32 ``v`` truncated to its leading 8 significant bits (a bfloat16
    value, still f32), by masking: a bf16 round trip inside a fusion is the
    TPU compiler's to elide (excess precision), and then the remainder below
    is zero and the sums are those of bf16 gradients (read on the chip,
    PR 33: leaf values 2.3e-3 off, the bfloat16 control's own reading)."""
    bits = jax.lax.bitcast_convert_type(v, jnp.uint32) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _leaf_sums(
    leaf_id: jnp.ndarray, g: jnp.ndarray, h: jnp.ndarray, num_leaves: int
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """[num_leaves] f32 sums of g and of h over the rows of each leaf, as
    one contraction of the rows against a one-hot of ``leaf_id`` (the TPU
    compiler builds the one-hot inside the matmul: 0.3 GB of temporaries at
    8M rows by its own memory analysis, not the 4 GB of the operand), where
    a scatter-add of N rows into 255 leaves serializes on the chip.  Each
    f32 addend goes in as three bf16 terms of 8 significant bits each, which
    add up to it exactly, so every product is exact and the sums are f32
    accumulations of the f32 values."""
    lp = -(-num_leaves // 128) * 128
    onehot = (
        leaf_id[:, None] == jnp.arange(lp, dtype=jnp.int32)[None, :]
    ).astype(jnp.bfloat16)
    terms = []
    for v in (g, h):
        hi = _bf16_head(v)
        lo = _bf16_head(v - hi)
        terms += [hi, lo, v - hi - lo]
    acc = jnp.dot(
        jnp.stack(terms).astype(jnp.bfloat16), onehot,
        preferred_element_type=jnp.float32,
    )[:, :num_leaves]
    return acc[2] + acc[1] + acc[0], acc[5] + acc[4] + acc[3]


@functools.partial(
    instrumented_jit,
    static_argnames=(
        "num_leaves",
        "lambda_l1",
        "lambda_l2",
        "max_delta_step",
        "axis_name",
        "measure",
    ),
)
def renew_leaf_values(
    leaf_id: jnp.ndarray,  # [N] int32 from grow_tree
    grad: jnp.ndarray,  # [N] TRUE (unquantized) gradients
    hess: jnp.ndarray,
    mask: jnp.ndarray,  # [N] in-bag mask
    num_leaves_used: jnp.ndarray,  # scalar from TreeArrays.num_leaves
    num_leaves: int,
    lambda_l1: float,
    lambda_l2: float,
    max_delta_step: float,
    axis_name: Optional[str] = None,
    measure: bool = False,
) -> jnp.ndarray:
    """Per-leaf outputs from true gradient sums
    (RenewIntGradTreeOutput, gradient_discretizer.cpp:209; the data-parallel
    branch GlobalSums the per-leaf stats — here a psum when axis_name,
    routed through the timed wrapper so ``collective_measured/*`` and the
    perf contract see the quantized-training path)."""
    with jax.named_scope("renew_leaf"):
        sum_g, sum_h = _leaf_sums(leaf_id, grad * mask, hess * mask, num_leaves)
        if axis_name is not None:
            sum_g = timed_psum(sum_g, axis_name, site="quant", measure=measure)
            sum_h = timed_psum(sum_h, axis_name, site="quant", measure=measure)
        out = leaf_output(sum_g, sum_h, lambda_l1, lambda_l2, max_delta_step)
        active = jnp.arange(num_leaves, dtype=jnp.int32) < num_leaves_used
        return jnp.where(active & (num_leaves_used > 1), out, 0.0).astype(
            jnp.float32
        )
