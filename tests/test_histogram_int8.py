"""Int8 quantized histogram kernel vs the exact oracle (interpret mode —
numerics identical to the native TPU lowering since accumulation is integer).
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from lightgbm_tpu.ops.histogram import leaf_histogram_segment  # noqa: E402
from lightgbm_tpu.ops.pallas.histogram_int8 import histogram_pallas_int8  # noqa: E402
from lightgbm_tpu.ops.quantize import quantize_gradients  # noqa: E402


def test_int8_training_path_matches_segment():
    """End-to-end: hist_method='pallas_int8_interpret' trains the identical
    model to the exact segment path on the same quantized gradients (integer
    accumulation is exact)."""
    import lightgbm_tpu as lgb

    rng = np.random.default_rng(0)
    X = rng.normal(size=(1500, 6))
    y = X[:, 0] * 2 - X[:, 1] + rng.normal(scale=0.1, size=1500)
    base = {
        "objective": "regression",
        "verbosity": -1,
        "use_quantized_grad": True,
        "num_grad_quant_bins": 16,
        "quant_train_renew_leaf": True,
        "num_leaves": 15,
    }
    b_int8 = lgb.train(
        {**base, "hist_method": "pallas_int8_interpret"}, lgb.Dataset(X, y), 6
    )
    assert b_int8._grower_params.hist_method == "pallas_int8_interpret"
    b_seg = lgb.train({**base, "hist_method": "segment"}, lgb.Dataset(X, y), 6)
    np.testing.assert_allclose(
        b_int8.predict(X), b_seg.predict(X), rtol=1e-6, atol=1e-7
    )


def test_int8_method_requires_quantization():
    import lightgbm_tpu as lgb

    rng = np.random.default_rng(0)
    X = rng.normal(size=(300, 3))
    y = X[:, 0]
    with pytest.raises(ValueError, match="quantized"):
        lgb.train(
            {"objective": "regression", "verbosity": -1,
             "hist_method": "pallas_int8_interpret"},
            lgb.Dataset(X, y),
            1,
        )


@pytest.mark.parametrize("n,f,b", [(500, 7, 16), (1200, 3, 64), (300, 30, 255)])
def test_int8_kernel_matches_oracle(n, f, b):
    rng = np.random.default_rng(n + f)
    bins = rng.integers(0, b, size=(n, f)).astype(np.int32)
    g = rng.normal(size=n).astype(np.float32)
    h = np.abs(rng.normal(size=n)).astype(np.float32) + 0.1
    mask = (rng.random(n) < 0.8).astype(np.float32)

    qg, qh, g_scale, h_scale = quantize_gradients(
        jnp.asarray(g), jnp.asarray(h), 0, 0,
        num_bins=8, stochastic=False,
    )

    got = histogram_pallas_int8(
        jnp.asarray(bins), qg, qh, jnp.asarray(mask), b,
        g_scale, h_scale, interpret=True,
    )
    want = leaf_histogram_segment(
        jnp.asarray(bins), qg, qh, jnp.asarray(mask), b
    )
    # integer accumulation is exact; only the final scale multiply rounds
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )
    # counts are exactly the masked row counts
    np.testing.assert_array_equal(
        np.asarray(got)[2].sum(axis=1), np.full(f, mask.sum())
    )
