"""Oracle tests for the Pallas histogram kernel — the production TPU path.

The kernel (ops/pallas/histogram.py) must match leaf_histogram_segment within
f32 tolerance, including masked/bagged rows and padded (non-multiple-of-tile)
row counts.  Runs in interpret mode everywhere; natively when a TPU is
attached (the bf16 hi/lo MXU decomposition is only exercised natively).
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from lightgbm_tpu.ops.histogram import leaf_histogram_segment  # noqa: E402
from lightgbm_tpu.ops.pallas.histogram import histogram_pallas  # noqa: E402

def _problem(n, f, b, seed=0, mask_frac=0.8, grad_scale=1.0):
    rng = np.random.default_rng(seed)
    bins = rng.integers(0, b, size=(n, f), dtype=np.int32)
    grad = (rng.normal(size=n) * grad_scale).astype(np.float32)
    hess = rng.uniform(0.1, 2.0, size=n).astype(np.float32)
    mask = (rng.uniform(size=n) < mask_frac).astype(np.float32)
    return bins, grad, hess, mask


CASES = [
    (512, 6, 16),  # single tile, tiny
    (1000, 28, 256),  # padded rows (1000 % tile != 0), full Higgs shape
    (5000, 28, 64),  # multiple tiles + padding
    (2048, 1, 4),  # degenerate single feature
    (300, 33, 255),  # odd feature count (not a multiple of any group), odd B
]


@pytest.mark.parametrize("n,f,b", CASES)
def test_pallas_interpret_matches_segment(n, f, b):
    bins, grad, hess, mask = _problem(n, f, b)
    ref = np.asarray(leaf_histogram_segment(jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess), jnp.asarray(mask), b))
    got = np.asarray(
        histogram_pallas(
            jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess), jnp.asarray(mask), b, interpret=True
        )
    )
    assert got.shape == (3, f, b)
    # the interpreter evaluates the dot at bf16 precision (the hi/lo residual
    # is lost), so interpret-mode accuracy is ~2^-9 relative; the native MXU
    # path keeps f32 accumulation and is tested at 5e-5 below
    scale = np.abs(ref).max() + 1e-9
    np.testing.assert_allclose(got / scale, ref / scale, atol=4e-3)
    # counts are integral sums of 0/1 — must be exact
    np.testing.assert_allclose(got[2], ref[2], rtol=0, atol=1e-3)


@pytest.mark.native_tpu
@pytest.mark.parametrize("n,f,b", CASES)
def test_pallas_native_matches_segment(n, f, b):
    bins, grad, hess, mask = _problem(n, f, b, seed=7)
    ref = np.asarray(leaf_histogram_segment(jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess), jnp.asarray(mask), b))
    got = np.asarray(histogram_pallas(jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess), jnp.asarray(mask), b))
    # bf16 hi/lo split: each element carries ~2^-16 relative error; sums over
    # n rows stay within a few ulps of the f32 oracle
    scale = np.abs(ref).max() + 1e-9
    np.testing.assert_allclose(got / scale, ref / scale, atol=5e-5)
    np.testing.assert_allclose(got[2], ref[2], rtol=0, atol=0.01)


@pytest.mark.native_tpu
def test_pallas_native_all_masked_and_large_grads():
    n, f, b = 1024, 8, 32
    bins, grad, hess, _ = _problem(n, f, b, seed=3, grad_scale=1e3)
    zero = jnp.zeros(n, jnp.float32)
    got = np.asarray(
        histogram_pallas(jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess), zero, b)
    )
    assert np.all(got == 0.0)
    # large-magnitude grads exercise the hi/lo split
    ones = jnp.ones(n, jnp.float32)
    ref = np.asarray(leaf_histogram_segment(jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess), ones, b))
    got = np.asarray(histogram_pallas(jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess), ones, b))
    scale = np.abs(ref).max() + 1e-9
    np.testing.assert_allclose(got / scale, ref / scale, atol=5e-5)


def test_uint8_bins_accepted():
    n, f, b = 700, 5, 64
    bins, grad, hess, mask = _problem(n, f, b, seed=11)
    ref = np.asarray(leaf_histogram_segment(jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess), jnp.asarray(mask), b))
    got = np.asarray(
        histogram_pallas(
            jnp.asarray(bins.astype(np.uint8)), jnp.asarray(grad), jnp.asarray(hess), jnp.asarray(mask), b, interpret=True
        )
    )
    scale = np.abs(ref).max() + 1e-9
    np.testing.assert_allclose(got / scale, ref / scale, atol=4e-3)
    np.testing.assert_allclose(got[2], ref[2], rtol=0, atol=1e-3)
