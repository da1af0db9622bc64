"""Random-forest mode (reference: src/boosting/rf.hpp).

Semantics kept from the reference: no shrinkage; gradients computed ONCE from
the constant per-class init score (not the evolving ensemble); bagging (row or
feature) is mandatory; the running score is the AVERAGE of tree outputs
(``MultiplyScore`` dance, rf.hpp:111-160); every tree absorbs the init score
via AddBias so the saved model divides cleanly by tree count
(``average_output`` flag in the model header).
"""

from __future__ import annotations

from typing import Optional

import numpy as np
import jax.numpy as jnp

from ..dataset import Dataset
from ..obs.jit import instrumented_jit
from ..ops.grower import fetch_tree_arrays, grow_tree
from ..ops.score_lookup import leaf_lookup
from ..predict import add_tree_to_score
from ..tree import Tree
from .gbdt import Booster, _EPS


def _add_leaf_values_impl(row, leaf_value, leaf_id):
    """``row + leaf_value[leaf_id]`` ([N] f32) as ONE program: only under jit
    does the compiler build ``leaf_lookup``'s one-hot inside the matmul's
    fusion; dispatched eagerly it is an [Lp, N] array (3 * Lp bytes a row:
    6 GB at 8M rows and 255 leaves)."""
    return row + leaf_lookup(leaf_value, leaf_id)


_add_leaf_values = instrumented_jit(
    _add_leaf_values_impl, label="rf/score_update", donate_argnums=(0,)
)


class RFBooster(Booster):
    def _init_train(self, train_set: Dataset) -> None:
        super()._init_train(train_set)
        cfg = self.config
        ok_bag = cfg.bagging_freq > 0 and 0.0 < cfg.bagging_fraction < 1.0
        ok_feat = 0.0 < cfg.feature_fraction < 1.0
        if not (ok_bag or ok_feat):
            raise ValueError(
                "random forest requires bagging (bagging_freq > 0 and "
                "bagging_fraction < 1.0) or feature_fraction < 1.0"
            )
        self.average_output = True
        self._shrinkage_rate = 1.0
        # constant init scores and one-time gradients (rf.hpp Boosting())
        k = self.num_tree_per_iteration
        n = train_set.num_data
        self._init_scores = [
            self.objective.boost_from_score(kk) if self.objective else 0.0
            for kk in range(k)
        ]
        base = jnp.asarray(
            np.tile(np.asarray(self._init_scores, dtype=np.float32)[:, None], (1, n))
        )
        self._rf_grad, self._rf_hess = self.objective.get_gradients(base, self._next_rng())

    def update(self, train_set: Optional[Dataset] = None, fobj=None) -> bool:
        if fobj is not None:
            raise ValueError("RF mode does not support custom objective functions")
        cfg = self.config
        k = self.num_tree_per_iteration
        mask, grad, hess = self._sampler.sample(
            self._iter, self._rf_grad, self._rf_hess, self._bagging_rng()
        )
        feature_mask = self._feature_mask_for_iter()

        any_tree = False
        for kk in range(k):
            if self._class_need_train[kk] and self._bins.shape[1] > 0:
                ta, leaf_id = grow_tree(
                    self._bins,
                    grad[kk],
                    hess[kk],
                    mask,
                    self._num_bins,
                    self._nan_bins,
                    feature_mask,
                    self._grower_params,
                )
                ta_host = fetch_tree_arrays(ta)
                n_leaves = int(ta_host.num_leaves)
            else:
                n_leaves = 1

            if n_leaves > 1:
                any_tree = True
                leaf_value = ta.leaf_value
                if self.objective is not None and self.objective.is_renew_tree_output:
                    init = self._init_scores[kk]
                    lv = self.objective.renew_tree_output(
                        np.full(self.train_set.num_data, init),
                        np.asarray(leaf_id),
                        np.asarray(ta_host.leaf_value, dtype=np.float64),
                        np.asarray(mask),
                    )
                    leaf_value = jnp.asarray(lv, dtype=jnp.float32)
                    ta = ta._replace(leaf_value=leaf_value)
                    ta_host = ta_host._replace(leaf_value=lv)
                if abs(self._init_scores[kk]) > _EPS:
                    leaf_value = leaf_value + self._init_scores[kk]
                    ta = ta._replace(leaf_value=leaf_value)
                    ta_host = ta_host._replace(
                        leaf_value=np.asarray(ta_host.leaf_value, dtype=np.float64)
                        + self._init_scores[kk]
                    )
                # running average: score = (score*t + tree)/(t+1)  (rf.hpp:149);
                # scaled, added and divided in three steps, as the validation
                # scores below are, so both round the same way
                t = float(self._iter)
                summed = _add_leaf_values(self._score[kk] * t, leaf_value, leaf_id)
                self._score = self._score.at[kk].set(summed / (t + 1.0))
                for entry in self._valid:
                    updated = add_tree_to_score(
                        entry.score[kk] * t,
                        entry.dataset.device_bins(),
                        self._nan_bins,
                        ta.split_feature,
                        ta.split_bin,
                        ta.default_left,
                        ta.left_child,
                        ta.right_child,
                        leaf_value,
                    )
                    entry.score = entry.score.at[kk].set(updated / (t + 1.0))
                tree = Tree.from_device_arrays(
                    ta_host,
                    self.train_set.bin_mappers,
                    self.train_set.used_features,
                )
                nn = n_leaves - 1
                self._bin_records.append(
                    {
                        "split_feature": np.asarray(ta_host.split_feature)[:nn],
                        "split_bin": np.asarray(ta_host.split_bin)[:nn],
                        "default_left": np.asarray(ta_host.default_left)[:nn],
                        "left_child": np.asarray(ta_host.left_child)[:nn],
                        "right_child": np.asarray(ta_host.right_child)[:nn],
                        "leaf_value": np.asarray(tree.leaf_value, dtype=np.float32),
                    }
                )
                self.models_.append(tree)
                self._bump_model_version()
            else:
                output = 0.0
                if len(self.models_) < k and not self._class_need_train[kk]:
                    output = (
                        self.objective.boost_from_score(kk) if self.objective else 0.0
                    )
                tree = Tree.constant_tree(output)
                self._bin_records.append(
                    {
                        "split_feature": np.zeros(0, np.int32),
                        "split_bin": np.zeros(0, np.int32),
                        "default_left": np.zeros(0, bool),
                        "left_child": np.zeros(0, np.int32),
                        "right_child": np.zeros(0, np.int32),
                        "leaf_value": np.asarray(tree.leaf_value, dtype=np.float32),
                    }
                )
                self.models_.append(tree)
                self._bump_model_version()
        self._iter += 1
        return not any_tree
