"""Objective functions: (score, label, weight) -> (grad, hess), vmapped JAX.

Reference analogs: include/LightGBM/objective_function.h (GetGradients
contract), src/objective/*.hpp (per-loss math), factory
src/objective/objective_function.cpp:22.

TPU-native design: every objective exposes ``get_gradients(score, rng)`` as a
pure JAX function over a ``[num_class, N]`` score array — the reference's
per-row OpenMP loops become whole-array vectorized expressions that XLA fuses
into the boosting step.  Ranking objectives pre-pack queries into padded
``[num_queries, Q]`` segments so the per-query OpenMP loop
(rank_objective.hpp:73) becomes a vmap; the CUDA per-query bitonic sort
(cuda_rank_objective.cu) becomes ``jnp.argsort`` inside the vmap.

Host-side (setup-time) work — label validation, class priors, max-DCG
normalizers — stays NumPy, exactly as it is setup-time C++ in the reference.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ..config import Config
from ..obs.trace import traced_transfer

_EPS = 1e-15


def _weighted_percentile(values: np.ndarray, weights: Optional[np.ndarray], alpha: float) -> float:
    """Percentile used by l1/quantile/mape boost-from-score and leaf renewal.

    Follows the reference's PercentileFun / WeightedPercentileFun
    (src/objective/regression_objective.hpp:18-88): linear interpolation
    between the two order statistics around the alpha position.
    """
    values = np.asarray(values, dtype=np.float64)
    cnt = len(values)
    if cnt == 0:
        return 0.0
    if cnt == 1:
        return float(values[0])
    if weights is None:
        sorted_v = np.sort(values)
        float_pos = (cnt - 1) * alpha  # position from the low end
        pos = int(float_pos)
        bias = float_pos - pos
        if pos + 1 < cnt:
            return float(sorted_v[pos] * (1 - bias) + sorted_v[pos + 1] * bias)
        return float(sorted_v[pos])
    order = np.argsort(values, kind="stable")
    sv = values[order]
    sw = np.asarray(weights, dtype=np.float64)[order]
    cdf = np.cumsum(sw)
    threshold = cdf[-1] * alpha
    pos = int(np.searchsorted(cdf, threshold, side="right"))
    pos = min(pos, cnt - 1)
    if pos == 0 or pos == cnt - 1:
        return float(sv[pos])
    v1, v2 = sv[pos - 1], sv[pos]
    if pos + 1 < cnt and cdf[pos + 1] - cdf[pos] >= 1.0:
        return float((threshold - cdf[pos]) / (cdf[pos + 1] - cdf[pos]) * (v2 - v1) + v1)
    return float(v2)


class ObjectiveFunction:
    """Base objective (reference: include/LightGBM/objective_function.h:37)."""

    name: str = "custom"
    is_constant_hessian: bool = False
    is_renew_tree_output: bool = False
    need_query: bool = False

    def __init__(self, config: Config):
        self.config = config
        self.num_class = 1
        self.label: Optional[jnp.ndarray] = None
        self.weight: Optional[jnp.ndarray] = None
        self._label_np: Optional[np.ndarray] = None
        self._weight_np: Optional[np.ndarray] = None
        self.num_data = 0

    # ------------------------------------------------------------------ init
    def init(self, label: np.ndarray, weight: Optional[np.ndarray], query_boundaries=None, position=None) -> None:
        self._label_np = np.asarray(label, dtype=np.float64)
        self._weight_np = None if weight is None else np.asarray(weight, dtype=np.float64)
        self.num_data = len(self._label_np)
        self.label = traced_transfer(
            "objective.label", lambda: jnp.asarray(self._label_np, dtype=jnp.float32)
        )
        self.weight = None if weight is None else traced_transfer(
            "objective.weight", lambda: jnp.asarray(self._weight_np, dtype=jnp.float32)
        )

    def per_row_device_arrays(self):
        """Per-row DEVICE arrays consumed by ``get_gradients``, as
        (holder, attr_name, row_axis) triples.

        The distributed Booster pads these with zero rows and re-places them
        sharded over the data mesh; host-side statistics (``_label_np`` /
        ``_weight_np``, class priors, percentiles) stay UNPADDED so
        boost_from_score / renew_tree_output remain exact.  Padded rows carry
        zero weight, which zeroes their gradients in every objective."""
        return [(self, "label", 0), (self, "weight", 0)]

    # ------------------------------------------------------------- gradients
    def get_gradients(self, score: jnp.ndarray, rng: Optional[jax.Array] = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """score: [num_class, N] raw scores -> (grad, hess) of the same shape."""
        raise NotImplementedError

    def _apply_weight(self, grad, hess):
        if self.weight is None:
            return grad, hess
        return grad * self.weight, hess * self.weight

    # ----------------------------------------------------------------- misc
    def boost_from_score(self, class_id: int = 0) -> float:
        """Init score (reference BoostFromScore); 0.0 when not applicable."""
        return 0.0

    def convert_output(self, raw: jnp.ndarray) -> jnp.ndarray:
        """Raw score -> output space (sigmoid/softmax/exp); identity default."""
        return raw

    def class_need_train(self, class_id: int) -> bool:
        return True

    def renew_tree_output(
        self,
        score: np.ndarray,  # [N] current score (before adding this tree)
        leaf_id: np.ndarray,  # [N] leaf index per row
        leaf_values: np.ndarray,  # [L] current leaf outputs (no shrinkage yet)
        mask: Optional[np.ndarray],  # in-bag mask or None
    ) -> np.ndarray:
        """Per-leaf output renewal for order-statistic losses (host-side)."""
        return leaf_values

    def to_string(self) -> str:
        return self.name

    @property
    def num_tree_per_iteration(self) -> int:
        return self.num_class


# =========================================================== regression family
class RegressionL2(ObjectiveFunction):
    """L2 loss (reference: RegressionL2loss, regression_objective.hpp:95)."""

    name = "regression"

    def __init__(self, config: Config):
        super().__init__(config)
        self.sqrt = bool(config.reg_sqrt)
        self.is_constant_hessian = True

    def init(self, label, weight, query_boundaries=None, position=None):
        super().init(label, weight)
        if self.sqrt:
            t = np.sign(self._label_np) * np.sqrt(np.abs(self._label_np))
            self._label_np = t
            self.label = traced_transfer(
                "objective.label", lambda: jnp.asarray(t, dtype=jnp.float32)
            )
        self.is_constant_hessian = weight is None

    def get_gradients(self, score, rng=None):
        grad = score[0] - self.label
        hess = jnp.ones_like(grad)
        g, h = self._apply_weight(grad, hess)
        return g[None], h[None]

    def boost_from_score(self, class_id: int = 0) -> float:
        if self._weight_np is None:
            return float(np.mean(self._label_np))
        return float(np.average(self._label_np, weights=self._weight_np))

    def convert_output(self, raw):
        if self.sqrt:
            return jnp.sign(raw) * raw * raw
        return raw

    def to_string(self):
        return f"{self.name} sqrt" if self.sqrt else self.name


class RegressionL1(RegressionL2):
    """L1 loss (reference: RegressionL1loss, regression_objective.hpp:205)."""

    name = "regression_l1"
    is_renew_tree_output = True

    def __init__(self, config: Config):
        super().__init__(config)
        self.sqrt = False
        self._renew_alpha = 0.5

    def get_gradients(self, score, rng=None):
        diff = score[0] - self.label
        grad = jnp.sign(diff)
        hess = jnp.ones_like(grad)
        g, h = self._apply_weight(grad, hess)
        return g[None], h[None]

    def boost_from_score(self, class_id: int = 0) -> float:
        return _weighted_percentile(self._label_np, self._weight_np, 0.5)

    def _renew_weights(self) -> Optional[np.ndarray]:
        return self._weight_np

    def renew_tree_output(self, score, leaf_id, leaf_values, mask):
        """Weighted median of residual per leaf (regression_objective.hpp:252)."""
        out = np.array(leaf_values, dtype=np.float64)
        residual = self._label_np - score
        w = self._renew_weights()
        sel_all = np.ones(len(residual), bool) if mask is None else mask > 0
        for leaf in range(len(out)):
            sel = (leaf_id == leaf) & sel_all
            if sel.any():
                out[leaf] = _weighted_percentile(
                    residual[sel], None if w is None else w[sel], self._renew_alpha
                )
        return out

    def convert_output(self, raw):
        return raw

    def to_string(self):
        return self.name


class RegressionHuber(RegressionL2):
    """Huber loss (reference: RegressionHuberLoss, regression_objective.hpp:292)."""

    name = "huber"

    def __init__(self, config: Config):
        super().__init__(config)
        self.sqrt = False
        self.alpha = float(config.alpha)

    def get_gradients(self, score, rng=None):
        diff = score[0] - self.label
        grad = jnp.clip(diff, -self.alpha, self.alpha)
        hess = jnp.ones_like(grad)
        g, h = self._apply_weight(grad, hess)
        return g[None], h[None]

    def convert_output(self, raw):
        return raw

    def to_string(self):
        return self.name


class RegressionFair(RegressionL2):
    """Fair loss (reference: RegressionFairLoss, regression_objective.hpp:351)."""

    name = "fair"

    def __init__(self, config: Config):
        super().__init__(config)
        self.sqrt = False
        self.c = float(config.fair_c)

    def init(self, label, weight, query_boundaries=None, position=None):
        super().init(label, weight)
        self.is_constant_hessian = False

    def get_gradients(self, score, rng=None):
        x = score[0] - self.label
        denom = jnp.abs(x) + self.c
        grad = self.c * x / denom
        hess = self.c * self.c / (denom * denom)
        g, h = self._apply_weight(grad, hess)
        return g[None], h[None]

    def convert_output(self, raw):
        return raw

    def to_string(self):
        return self.name


class RegressionPoisson(RegressionL2):
    """Poisson loss (reference: RegressionPoissonLoss, regression_objective.hpp:398)."""

    name = "poisson"

    def __init__(self, config: Config):
        super().__init__(config)
        self.sqrt = False
        self.max_delta_step = float(config.poisson_max_delta_step)

    def init(self, label, weight, query_boundaries=None, position=None):
        super().init(label, weight)
        self.is_constant_hessian = False
        if np.min(self._label_np) < 0:
            raise ValueError(f"[{self.name}]: at least one target label is negative")
        if np.sum(self._label_np) == 0:
            raise ValueError(f"[{self.name}]: sum of labels is zero")

    def get_gradients(self, score, rng=None):
        exp_score = jnp.exp(score[0])
        grad = exp_score - self.label
        hess = exp_score * math.exp(self.max_delta_step)
        g, h = self._apply_weight(grad, hess)
        return g[None], h[None]

    def boost_from_score(self, class_id: int = 0) -> float:
        mean = RegressionL2.boost_from_score(self)
        return math.log(max(mean, 1e-300))

    def convert_output(self, raw):
        return jnp.exp(raw)

    def to_string(self):
        return self.name


class RegressionQuantile(RegressionL2):
    """Quantile loss (reference: RegressionQuantileloss, regression_objective.hpp:478)."""

    name = "quantile"
    is_renew_tree_output = True

    def __init__(self, config: Config):
        super().__init__(config)
        self.sqrt = False
        self.alpha = float(config.alpha)
        if not (0.0 < self.alpha < 1.0):
            raise ValueError("alpha must be in (0, 1) for quantile objective")

    def get_gradients(self, score, rng=None):
        delta = score[0] - self.label
        grad = jnp.where(delta >= 0, 1.0 - self.alpha, -self.alpha)
        hess = jnp.ones_like(grad)
        g, h = self._apply_weight(grad, hess)
        return g[None], h[None]

    def boost_from_score(self, class_id: int = 0) -> float:
        return _weighted_percentile(self._label_np, self._weight_np, self.alpha)

    def renew_tree_output(self, score, leaf_id, leaf_values, mask):
        out = np.array(leaf_values, dtype=np.float64)
        residual = self._label_np - score
        w = self._weight_np
        sel_all = np.ones(len(residual), bool) if mask is None else mask > 0
        for leaf in range(len(out)):
            sel = (leaf_id == leaf) & sel_all
            if sel.any():
                out[leaf] = _weighted_percentile(
                    residual[sel], None if w is None else w[sel], self.alpha
                )
        return out

    def convert_output(self, raw):
        return raw

    def to_string(self):
        return f"{self.name} alpha:{self.alpha:g}"


class RegressionMAPE(RegressionL1):
    """MAPE loss (reference: RegressionMAPELOSS, regression_objective.hpp:578)."""

    name = "mape"

    def init(self, label, weight, query_boundaries=None, position=None):
        super().init(label, weight)
        lw = 1.0 / np.maximum(1.0, np.abs(self._label_np))
        if self._weight_np is not None:
            lw = lw * self._weight_np
        self._label_weight_np = lw
        self._label_weight = traced_transfer(
            "objective.label_weight", lambda: jnp.asarray(lw, dtype=jnp.float32)
        )
        self.is_constant_hessian = True

    def per_row_device_arrays(self):
        return super().per_row_device_arrays() + [(self, "_label_weight", 0)]

    def get_gradients(self, score, rng=None):
        diff = score[0] - self.label
        grad = jnp.sign(diff) * self._label_weight
        hess = jnp.ones_like(grad) if self.weight is None else self.weight
        return grad[None], hess[None]

    def boost_from_score(self, class_id: int = 0) -> float:
        return _weighted_percentile(self._label_np, self._label_weight_np, 0.5)

    def _renew_weights(self) -> Optional[np.ndarray]:
        return self._label_weight_np


class RegressionGamma(RegressionPoisson):
    """Gamma loss (reference: RegressionGammaLoss, regression_objective.hpp:682)."""

    name = "gamma"

    def get_gradients(self, score, rng=None):
        exp_neg = jnp.exp(-score[0])
        grad = 1.0 - self.label * exp_neg
        hess = self.label * exp_neg
        g, h = self._apply_weight(grad, hess)
        return g[None], h[None]


class RegressionTweedie(RegressionPoisson):
    """Tweedie loss (reference: RegressionTweedieLoss, regression_objective.hpp:718)."""

    name = "tweedie"

    def __init__(self, config: Config):
        super().__init__(config)
        self.rho = float(config.tweedie_variance_power)

    def get_gradients(self, score, rng=None):
        s = score[0]
        exp1 = jnp.exp((1.0 - self.rho) * s)
        exp2 = jnp.exp((2.0 - self.rho) * s)
        grad = -self.label * exp1 + exp2
        hess = -self.label * (1.0 - self.rho) * exp1 + (2.0 - self.rho) * exp2
        g, h = self._apply_weight(grad, hess)
        return g[None], h[None]


# =============================================================== binary family
class BinaryLogloss(ObjectiveFunction):
    """Binary log-loss (reference: BinaryLogloss, binary_objective.hpp:20)."""

    name = "binary"

    def __init__(self, config: Config, is_pos=None):
        super().__init__(config)
        self.sigmoid = float(config.sigmoid)
        if self.sigmoid <= 0:
            raise ValueError("sigmoid parameter must be > 0")
        self.is_unbalance = bool(config.is_unbalance)
        self.scale_pos_weight = float(config.scale_pos_weight)
        self._is_pos = is_pos if is_pos is not None else (lambda y: y > 0)
        self.need_train = True

    def init(self, label, weight, query_boundaries=None, position=None):
        super().init(label, weight)
        pos = self._is_pos(self._label_np)
        cnt_pos = int(pos.sum())
        cnt_neg = self.num_data - cnt_pos
        self.num_pos_data = cnt_pos
        self.need_train = cnt_pos > 0 and cnt_neg > 0
        label_weights = [1.0, 1.0]
        if self.is_unbalance and cnt_pos > 0 and cnt_neg > 0:
            if cnt_pos > cnt_neg:
                label_weights[0] = cnt_pos / cnt_neg
            else:
                label_weights[1] = cnt_neg / cnt_pos
        label_weights[1] *= self.scale_pos_weight
        self._label_weights = label_weights
        self._pos_np = pos
        pos_dev = traced_transfer("objective.is_pos", lambda: jnp.asarray(pos))
        self._y = jnp.where(pos_dev, 1.0, -1.0)  # label in {-1, +1}
        self._lw = jnp.where(pos_dev, label_weights[1], label_weights[0])

    def per_row_device_arrays(self):
        return super().per_row_device_arrays() + [
            (self, "_y", 0),
            (self, "_lw", 0),
        ]

    def get_gradients(self, score, rng=None):
        if not self.need_train:
            z = jnp.zeros_like(score)
            return z, z
        s = score[0]
        sig = self.sigmoid
        response = -self._y * sig / (1.0 + jnp.exp(self._y * sig * s))
        abs_resp = jnp.abs(response)
        grad = response * self._lw
        hess = abs_resp * (sig - abs_resp) * self._lw
        g, h = self._apply_weight(grad, hess)
        return g[None], h[None]

    def boost_from_score(self, class_id: int = 0) -> float:
        if self._weight_np is None:
            pavg = float(self._pos_np.mean())
        else:
            pavg = float(np.average(self._pos_np.astype(np.float64), weights=self._weight_np))
        pavg = min(max(pavg, _EPS), 1.0 - _EPS)
        return math.log(pavg / (1.0 - pavg)) / self.sigmoid

    def class_need_train(self, class_id: int) -> bool:
        return self.need_train

    def convert_output(self, raw):
        return 1.0 / (1.0 + jnp.exp(-self.sigmoid * raw))

    def to_string(self):
        return f"{self.name} sigmoid:{self.sigmoid:g}"


# =========================================================== multiclass family
class MulticlassSoftmax(ObjectiveFunction):
    """Softmax multiclass (reference: MulticlassSoftmax, multiclass_objective.hpp:24)."""

    name = "multiclass"

    def __init__(self, config: Config):
        super().__init__(config)
        self.num_class = int(config.num_class)
        if self.num_class < 2:
            raise ValueError("multiclass objective requires num_class >= 2")
        # rescales the redundant K-output parameterization (Friedman GBDT paper)
        self.factor = self.num_class / (self.num_class - 1.0)

    def init(self, label, weight, query_boundaries=None, position=None):
        super().init(label, weight)
        li = self._label_np.astype(np.int64)
        if li.min() < 0 or li.max() >= self.num_class:
            raise ValueError(f"label must be in [0, {self.num_class})")
        if self._weight_np is None:
            probs = np.bincount(li, minlength=self.num_class).astype(np.float64)
            probs /= self.num_data
        else:
            probs = np.zeros(self.num_class)
            np.add.at(probs, li, self._weight_np)
            probs /= self._weight_np.sum()
        self.class_init_probs = probs
        label_int = traced_transfer(
            "objective.label_int", lambda: jnp.asarray(li, dtype=jnp.int32)
        )
        self._onehot = jax.nn.one_hot(label_int, self.num_class, dtype=jnp.float32).T  # [K, N]

    def per_row_device_arrays(self):
        return super().per_row_device_arrays() + [(self, "_onehot", 1)]

    def get_gradients(self, score, rng=None):
        p = jax.nn.softmax(score, axis=0)  # [K, N]
        grad = p - self._onehot
        hess = self.factor * p * (1.0 - p)
        if self.weight is not None:
            grad = grad * self.weight[None]
            hess = hess * self.weight[None]
        return grad, hess

    def boost_from_score(self, class_id: int = 0) -> float:
        return math.log(max(_EPS, self.class_init_probs[class_id]))

    def class_need_train(self, class_id: int) -> bool:
        p = self.class_init_probs[class_id]
        return _EPS < abs(p) < 1.0 - _EPS

    def convert_output(self, raw):
        """raw: [..., K] -> softmax over the last axis."""
        return jax.nn.softmax(raw, axis=-1)

    def to_string(self):
        return f"{self.name} num_class:{self.num_class}"


class MulticlassOVA(ObjectiveFunction):
    """One-vs-all multiclass (reference: MulticlassOVA, multiclass_objective.hpp:178)."""

    name = "multiclassova"

    def __init__(self, config: Config):
        super().__init__(config)
        self.num_class = int(config.num_class)
        self.sigmoid = float(config.sigmoid)
        self._binary = [BinaryLogloss(config) for _ in range(self.num_class)]

    def init(self, label, weight, query_boundaries=None, position=None):
        super().init(label, weight)
        for k, b in enumerate(self._binary):
            b._is_pos = (lambda kk: (lambda y: y == kk))(k)
            b.init(label, weight)

    def per_row_device_arrays(self):
        out = super().per_row_device_arrays()
        for b in self._binary:
            out.extend(b.per_row_device_arrays())
        return out

    def get_gradients(self, score, rng=None):
        gs, hs = [], []
        for k, b in enumerate(self._binary):
            g, h = b.get_gradients(score[k][None])
            gs.append(g[0])
            hs.append(h[0])
        return jnp.stack(gs), jnp.stack(hs)

    def boost_from_score(self, class_id: int = 0) -> float:
        return self._binary[class_id].boost_from_score(0)

    def class_need_train(self, class_id: int) -> bool:
        return self._binary[class_id].need_train

    def convert_output(self, raw):
        return 1.0 / (1.0 + jnp.exp(-self.sigmoid * raw))

    def to_string(self):
        return f"{self.name} num_class:{self.num_class} sigmoid:{self.sigmoid:g}"


# ============================================================ xentropy family
class CrossEntropy(ObjectiveFunction):
    """Cross-entropy with labels in [0,1] (reference: xentropy_objective.hpp:38)."""

    name = "cross_entropy"

    def init(self, label, weight, query_boundaries=None, position=None):
        super().init(label, weight)
        if self._label_np.min() < 0 or self._label_np.max() > 1:
            raise ValueError(f"[{self.name}]: labels must be in [0, 1]")
        if self._weight_np is not None:
            if self._weight_np.min() < 0:
                raise ValueError(f"[{self.name}]: at least one weight is negative")
            if self._weight_np.sum() == 0:
                raise ValueError(f"[{self.name}]: sum of weights is zero")

    def get_gradients(self, score, rng=None):
        s = score[0]
        z = jax.nn.sigmoid(s)
        grad = z - self.label
        hess = z * (1.0 - z)
        g, h = self._apply_weight(grad, hess)
        return g[None], h[None]

    def boost_from_score(self, class_id: int = 0) -> float:
        if self._weight_np is None:
            pavg = float(self._label_np.mean())
        else:
            pavg = float(np.average(self._label_np, weights=self._weight_np))
        pavg = min(max(pavg, _EPS), 1.0 - _EPS)
        return math.log(pavg / (1.0 - pavg))

    def convert_output(self, raw):
        return jax.nn.sigmoid(raw)


class CrossEntropyLambda(ObjectiveFunction):
    """Weighted cross-entropy, alternative parameterization
    (reference: CrossEntropyLambda, xentropy_objective.hpp:180)."""

    name = "cross_entropy_lambda"

    def init(self, label, weight, query_boundaries=None, position=None):
        super().init(label, weight)
        if self._label_np.min() < 0 or self._label_np.max() > 1:
            raise ValueError(f"[{self.name}]: labels must be in [0, 1]")
        if self._weight_np is not None and self._weight_np.min() <= 0:
            raise ValueError(f"[{self.name}]: at least one weight is non-positive")

    def get_gradients(self, score, rng=None):
        s = score[0]
        if self.weight is None:
            z = jax.nn.sigmoid(s)
            grad = z - self.label
            hess = z * (1.0 - z)
            return grad[None], hess[None]
        w = self.weight
        y = self.label
        epf = jnp.exp(s)
        hhat = jnp.log1p(epf)
        z = 1.0 - jnp.exp(-w * hhat)
        enf = jnp.exp(-s)
        grad = (1.0 - y / z) * w / (1.0 + enf)
        c = 1.0 / (1.0 - z)
        d = 1.0 + epf
        a = w * epf / (d * d)
        d2 = c - 1.0
        b = (c / (d2 * d2)) * (1.0 + w * epf - c)
        hess = a * (1.0 + y * b)
        return grad[None], hess[None]

    def boost_from_score(self, class_id: int = 0) -> float:
        if self._weight_np is None:
            pavg = float(self._label_np.mean())
        else:
            pavg = float(np.average(self._label_np, weights=self._weight_np))
        pavg = min(max(pavg, _EPS), 1.0 - _EPS)
        return math.log(pavg / (1.0 - pavg))

    def convert_output(self, raw):
        # output is the normalized exponential parameter, not a probability
        return jnp.log1p(jnp.exp(raw))


# ============================================================= ranking family
def _default_label_gain(max_label: int = 31) -> np.ndarray:
    return (2.0 ** np.arange(max_label + 1)) - 1.0


def _pad_queries(query_boundaries: np.ndarray) -> Tuple[np.ndarray, int]:
    """Query sizes -> (per-query row index matrix [num_q, Q], Q) with -1 pad."""
    sizes = np.diff(query_boundaries)
    q = int(sizes.max()) if len(sizes) else 1
    # round up to a power of two to limit recompiles across datasets
    q = max(8, 1 << (q - 1).bit_length())
    idx = np.full((len(sizes), q), -1, dtype=np.int32)
    for i, (b, e) in enumerate(zip(query_boundaries[:-1], query_boundaries[1:])):
        idx[i, : e - b] = np.arange(b, e, dtype=np.int32)
    return idx, q


class RankingObjective(ObjectiveFunction):
    """Base for per-query ranking objectives (reference: rank_objective.hpp:30)."""

    need_query = True

    def init(self, label, weight, query_boundaries=None, position=None):
        super().init(label, weight)
        if query_boundaries is None:
            raise ValueError(f"[{self.name}]: query data (group) is required")
        self.query_boundaries = np.asarray(query_boundaries, dtype=np.int64)
        self.num_queries = len(self.query_boundaries) - 1
        idx, self.q_pad = _pad_queries(self.query_boundaries)
        self._qidx = jnp.asarray(idx)  # [num_q, Q] row ids, -1 = pad
        self._qvalid = jnp.asarray(idx >= 0)
        lab = np.zeros(idx.shape, dtype=np.float32)
        lab[idx >= 0] = self._label_np[idx[idx >= 0]]
        self._qlabel = jnp.asarray(lab)

    def _scatter_back(self, per_query: jnp.ndarray) -> jnp.ndarray:
        """[num_q, Q] padded per-row values -> [N] row vector."""
        idx = self._qidx.reshape(-1)
        vals = per_query.reshape(-1)
        safe = jnp.where(idx >= 0, idx, 0)
        return jnp.zeros((self.num_data,), jnp.float32).at[safe].add(
            jnp.where(idx >= 0, vals, 0.0)
        )

    def _gather_scores(self, score: jnp.ndarray) -> jnp.ndarray:
        safe = jnp.where(self._qidx >= 0, self._qidx, 0)
        s = score[0][safe]
        return jnp.where(self._qvalid, s, -jnp.inf)


class LambdarankNDCG(RankingObjective):
    """Pairwise LambdaRank with NDCG (reference: LambdarankNDCG,
    rank_objective.hpp:137; per-query math :180-272).

    The per-query OpenMP loop + stable sort becomes a vmapped function over
    padded [num_q, Q] segments; the O(Q^2) pair loop becomes dense [Q, Q]
    masked matrices (chunked over queries to bound memory).  The sigmoid
    lookup table (rank_objective.hpp:287) is replaced by direct computation —
    on TPU the exp is cheaper than the gather.
    """

    name = "lambdarank"

    def __init__(self, config: Config):
        super().__init__(config)
        self.sigmoid = float(config.sigmoid)
        if self.sigmoid <= 0:
            raise ValueError("sigmoid parameter must be > 0")
        self.norm = bool(config.lambdarank_norm)
        self.truncation_level = int(config.lambdarank_truncation_level)
        lg = config.label_gain
        self.label_gain = np.asarray(lg, dtype=np.float64) if lg else _default_label_gain()

    def init(self, label, weight, query_boundaries=None, position=None):
        super().init(label, weight, query_boundaries)
        if self._label_np.max() >= len(self.label_gain):
            raise ValueError("label exceeds label_gain size")
        # position debias (reference: positions_/pos_biases_,
        # rank_objective.hpp:44-56; Newton update :302-341): scores are
        # adjusted by a learned per-position bias before the lambda
        # computation, and the biases update each iteration from the
        # accumulated lambdas/hessians per position.
        self._pos_inv = None
        if position is not None:
            pos = np.asarray(position)
            uniq, inv = np.unique(pos, return_inverse=True)
            self.position_ids = uniq
            self.num_position_ids = len(uniq)
            self._pos_inv = jnp.asarray(inv.astype(np.int32))
            self._pos_counts = jnp.asarray(
                np.bincount(inv, minlength=len(uniq)).astype(np.float32)
            )
            self.pos_biases = jnp.zeros((len(uniq),), jnp.float32)
            self._pos_reg = float(
                self.config.lambdarank_position_bias_regularization
            )
            self._pos_lr = float(self.config.learning_rate)
        # per-query inverse max DCG at truncation level (host, setup-time)
        inv = np.zeros(self.num_queries)
        disc = 1.0 / np.log2(np.arange(2, self.q_pad + 2))
        for i in range(self.num_queries):
            b, e = self.query_boundaries[i], self.query_boundaries[i + 1]
            ls = np.sort(self._label_np[b:e])[::-1][: self.truncation_level]
            m = (self.label_gain[ls.astype(np.int64)] * disc[: len(ls)]).sum()
            inv[i] = 1.0 / m if m > 0 else 0.0
        self._inv_max_dcg = jnp.asarray(inv, dtype=jnp.float32)
        self._gain_table = jnp.asarray(self.label_gain, dtype=jnp.float32)
        self._discount = jnp.asarray(disc, dtype=jnp.float32)

    def _update_position_bias(self, grad_row, hess_row) -> None:
        """Newton-Raphson step on the per-position bias factors
        (UpdatePositionBiasFactors, rank_objective.hpp:302)."""
        p = self.num_position_ids
        fd = -jax.ops.segment_sum(grad_row, self._pos_inv, num_segments=p)
        sd = -jax.ops.segment_sum(hess_row, self._pos_inv, num_segments=p)
        fd = fd - self.pos_biases * self._pos_reg * self._pos_counts
        sd = sd - self._pos_reg * self._pos_counts
        self.pos_biases = self.pos_biases + self._pos_lr * fd / (
            jnp.abs(sd) + 0.001
        )

    def _one_query(self, s, lab, valid, inv_max_dcg):
        """Lambdas/hessians for one padded query. s/lab/valid: [Q]."""
        q = s.shape[0]
        order = jnp.argsort(-jnp.where(valid, s, -jnp.inf), stable=True)
        ss = s[order]
        ll = lab[order]
        vv = valid[order]
        gain = self._gain_table[jnp.clip(ll.astype(jnp.int32), 0, len(self.label_gain) - 1)]
        disc = self._discount[:q] * vv
        best = jnp.max(jnp.where(vv, ss, -jnp.inf))
        worst = jnp.min(jnp.where(vv, ss, jnp.inf))

        i_idx = jnp.arange(q)
        pair_valid = (
            vv[:, None]
            & vv[None, :]
            & (i_idx[:, None] < i_idx[None, :])
            & (i_idx[:, None] < self.truncation_level)
            & (ll[:, None] != ll[None, :])
        )
        hi_is_i = ll[:, None] > ll[None, :]
        dcg_gap = jnp.abs(gain[:, None] - gain[None, :])
        paired_disc = jnp.abs(disc[:, None] - disc[None, :])
        delta_ndcg = dcg_gap * paired_disc * inv_max_dcg
        s_hi = jnp.where(hi_is_i, ss[:, None], ss[None, :])
        s_lo = jnp.where(hi_is_i, ss[None, :], ss[:, None])
        delta_score = s_hi - s_lo
        if self.norm:
            delta_ndcg = jnp.where(
                best != worst, delta_ndcg / (0.01 + jnp.abs(delta_score)), delta_ndcg
            )
        sig = self.sigmoid
        p_sig = 1.0 / (1.0 + jnp.exp(sig * delta_score))
        p_hess = p_sig * (1.0 - p_sig) * sig * sig * delta_ndcg
        p_lambda = -sig * delta_ndcg * p_sig  # contribution with the 'high' sign
        p_lambda = jnp.where(pair_valid, p_lambda, 0.0)
        p_hess = jnp.where(pair_valid, p_hess, 0.0)

        # lambdas[high] += p_lambda; lambdas[low] -= p_lambda
        contrib_i = jnp.where(hi_is_i, p_lambda, -p_lambda)
        lam_sorted = contrib_i.sum(axis=1) - contrib_i.sum(axis=0)
        hess_sorted = p_hess.sum(axis=1) + p_hess.sum(axis=0)
        sum_lambdas = -2.0 * p_lambda.sum()
        if self.norm:
            norm_factor = jnp.where(
                sum_lambdas > 0,
                jnp.log2(1.0 + sum_lambdas) / jnp.maximum(sum_lambdas, _EPS),
                1.0,
            )
            lam_sorted = lam_sorted * norm_factor
            hess_sorted = hess_sorted * norm_factor
        inv_order = jnp.argsort(order)
        return lam_sorted[inv_order], hess_sorted[inv_order]

    def get_gradients(self, score, rng=None):
        if self._pos_inv is not None:
            # bias-adjusted scores feed the lambda computation
            # (rank_objective.hpp:68-73)
            score = (score[0] + self.pos_biases[self._pos_inv])[None]
        qs = self._gather_scores(score)  # [num_q, Q]
        qq = self.q_pad
        # chunk queries so the [chunk, Q, Q] intermediate stays ~16M elements
        chunk = max(1, min(self.num_queries, (1 << 24) // max(1, qq * qq)))
        nq = qs.shape[0]
        pad_q = (-nq) % chunk

        def padq(a, fill):
            return jnp.pad(a, ((0, pad_q),) + ((0, 0),) * (a.ndim - 1), constant_values=fill)

        qs_c = padq(qs, -jnp.inf).reshape(-1, chunk, qq)
        lab_c = padq(self._qlabel, 0.0).reshape(-1, chunk, qq)
        val_c = padq(self._qvalid, False).reshape(-1, chunk, qq)
        inv_c = padq(self._inv_max_dcg, 0.0).reshape(-1, chunk)

        f = jax.vmap(self._one_query)

        def body(_, xs):
            s, l, v, im = xs
            return None, f(s, l, v, im)

        _, (lam, hes) = jax.lax.scan(body, None, (qs_c, lab_c, val_c, inv_c))
        lam = lam.reshape(-1, qq)[:nq]
        hes = hes.reshape(-1, qq)[:nq]
        grad = self._scatter_back(lam)
        hess = self._scatter_back(hes)
        if self.weight is not None:
            grad = grad * self.weight
            hess = hess * self.weight
        if self._pos_inv is not None:
            self._update_position_bias(grad, hess)
        return grad[None], hess[None]

    def to_string(self):
        return self.name


class RankXENDCG(RankingObjective):
    """Listwise XE-NDCG (reference: RankXENDCG, rank_objective.hpp:386;
    arxiv.org/abs/1911.09798)."""

    name = "rank_xendcg"

    def __init__(self, config: Config):
        super().__init__(config)
        self.seed = int(config.objective_seed)

    def _one_query(self, s, lab, valid, gamma):
        rho = jax.nn.softmax(jnp.where(valid, s, -jnp.inf))
        rho = jnp.where(valid, rho, 0.0)
        params = jnp.where(valid, 2.0 ** jnp.floor(lab) - gamma, 0.0)
        inv_denominator = 1.0 / jnp.maximum(_EPS, params.sum())
        # first-order terms
        term1 = jnp.where(valid, -params * inv_denominator + rho, 0.0)
        lambdas = term1
        params1 = jnp.where(valid, term1 / jnp.maximum(1.0 - rho, _EPS), 0.0)
        sum_l1 = params1.sum()
        # second-order terms
        term2 = jnp.where(valid, rho * (sum_l1 - params1), 0.0)
        lambdas = lambdas + term2
        params2 = jnp.where(valid, term2 / jnp.maximum(1.0 - rho, _EPS), 0.0)
        sum_l2 = params2.sum()
        lambdas = lambdas + jnp.where(valid, rho * (sum_l2 - params2), 0.0)
        hessians = jnp.where(valid, rho * (1.0 - rho), 0.0)
        keep = valid.sum() > 1  # skip groups with a single item
        return jnp.where(keep & valid, lambdas, 0.0), jnp.where(keep & valid, hessians, 0.0)

    def get_gradients(self, score, rng=None):
        if rng is None:
            rng = jax.random.PRNGKey(self.seed)
        qs = self._gather_scores(score)
        gamma = jax.random.uniform(rng, (self.num_queries, self.q_pad))
        lam, hes = jax.vmap(self._one_query)(qs, self._qlabel, self._qvalid, gamma)
        grad = self._scatter_back(lam)
        hess = self._scatter_back(hes)
        if self.weight is not None:
            grad = grad * self.weight
            hess = hess * self.weight
        return grad[None], hess[None]

    def to_string(self):
        return self.name


# ================================================================== factory
_OBJECTIVES = {
    "regression": RegressionL2,
    "regression_l1": RegressionL1,
    "huber": RegressionHuber,
    "fair": RegressionFair,
    "poisson": RegressionPoisson,
    "quantile": RegressionQuantile,
    "mape": RegressionMAPE,
    "gamma": RegressionGamma,
    "tweedie": RegressionTweedie,
    "binary": BinaryLogloss,
    "multiclass": MulticlassSoftmax,
    "multiclassova": MulticlassOVA,
    "cross_entropy": CrossEntropy,
    "cross_entropy_lambda": CrossEntropyLambda,
    "lambdarank": LambdarankNDCG,
    "rank_xendcg": RankXENDCG,
}


def create_objective(config: Config) -> Optional[ObjectiveFunction]:
    """Factory (reference: ObjectiveFunction::CreateObjectiveFunction,
    src/objective/objective_function.cpp:22)."""
    name = config.objective
    if name in ("none", "null", "custom", "na", ""):
        return None
    if name not in _OBJECTIVES:
        raise ValueError(f"unknown objective: {name!r}")
    return _OBJECTIVES[name](config)
