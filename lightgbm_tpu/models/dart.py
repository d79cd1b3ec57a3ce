"""DART boosting (reference: /root/reference/src/boosting/dart.hpp:20-211).

Dropout trees: each iteration a random subset of existing trees is dropped
(``DroppingTrees``), gradients are computed against the score without them
(``GetTrainingScore`` override, dart.hpp:74-85), and after the new tree is
added both it and the dropped trees are re-normalized (``Normalize``):
standard mode scales the new tree by 1/(k+1) and dropped trees by k/(k+1);
xgboost_dart_mode uses lr/(k+lr) and k/(k+lr).
"""

from __future__ import annotations

from typing import List, Optional

import jax.numpy as jnp
import numpy as np

from .gbdt import GBDTModel
from ..predict_device import add_tree_score


class DARTModel(GBDTModel):
    def __init__(self, config, train_set, objective, hist_reduce=None,
                 obs=None):
        super().__init__(config, train_set, objective, hist_reduce, obs)
        self._rng_drop = np.random.RandomState(config.drop_seed)
        self._drop_idx: List[int] = []
        self._drop_contrib_train = None     # [N, K] score of dropped trees
        self._drop_contrib_valid = []

    def _select_drop(self) -> List[int]:
        n_trees = len(self.device_trees) // self.num_class
        if n_trees == 0 or self._rng_drop.rand() < self.config.skip_drop:
            return []
        rate = self.config.drop_rate
        if self.config.uniform_drop:
            mask = self._rng_drop.rand(n_trees) < rate
        else:
            w = np.asarray(self.tree_weights[::self.num_class])
            p = np.clip(rate * w * n_trees / max(w.sum(), 1e-12), 0, 1)
            mask = self._rng_drop.rand(n_trees) < p
        drop = list(np.nonzero(mask)[0])
        if len(drop) > self.config.max_drop > 0:
            drop = list(self._rng_drop.choice(drop, self.config.max_drop,
                                              replace=False))
        return sorted(drop)

    def _tree_contrib(self, binned, ti: int, k: int):
        from .gbdt import _apply_tree
        dt = self.device_trees[ti * self.num_class + k]
        w = self.tree_weights[ti * self.num_class + k]
        zero = jnp.zeros(binned.shape[0], jnp.float32)
        return _apply_tree(zero, binned, dt, self.na_bin_dev, w,
                           self.efb_maps)

    def train_one_iter(self, grad=None, hess=None) -> bool:
        self._drop_idx = self._select_drop()
        k_drop = len(self._drop_idx)
        if k_drop > 0:
            contrib = jnp.zeros_like(self.score)
            for ti in self._drop_idx:
                for k in range(self.num_class):
                    contrib = contrib.at[:, k].add(
                        self._tree_contrib(self.binned_dev, ti, k))
            self._drop_contrib_train = contrib
            self._drop_contrib_valid = []
            for (vds, vbinned, _vs) in self.valid_sets:
                # zeros_like: the valid score may carry row-bucket
                # padding (gbdt.add_valid_set), so size off the score
                vc = jnp.zeros_like(_vs)
                for ti in self._drop_idx:
                    for k in range(self.num_class):
                        vc = vc.at[:, k].add(self._tree_contrib(vbinned, ti, k))
                self._drop_contrib_valid.append(vc)
            # drop: gradients see score minus dropped trees
            self.score = self.score - contrib
            for vi in range(len(self.valid_sets)):
                vds, vb, vs = self.valid_sets[vi]
                self.valid_sets[vi] = (vds, vb, vs - self._drop_contrib_valid[vi])

        stopped = super().train_one_iter(grad, hess)

        # Normalize (dart.hpp:120-170)
        if k_drop > 0:
            lr = self.learning_rate
            if self.config.xgboost_dart_mode:
                new_factor = lr / (k_drop + lr)
                old_factor = k_drop / (k_drop + lr)
            else:
                new_factor = 1.0 / (k_drop + 1.0)
                old_factor = k_drop / (k_drop + 1.0)
            # scale the just-added trees
            for k in range(self.num_class):
                ti = len(self.tree_weights) - self.num_class + k
                self.tree_weights[ti] *= new_factor
                st = self._last_iter_state
                delta = jnp.take(st["leaf_values"][k], st["leaf_of_rows"][k])
                self.score = self.score.at[:, k].add((new_factor - 1.0) * delta)
                for vi in range(len(self.valid_sets)):
                    vds, vb, vs = self.valid_sets[vi]
                    dt = st["trees"][k]
                    from .gbdt import _apply_tree
                    ns = _apply_tree(vs[:, k], vb, dt, self.na_bin_dev,
                                     new_factor - 1.0, self.efb_maps)
                    self.valid_sets[vi] = (vds, vb, vs.at[:, k].set(ns))
            # scale dropped trees and restore their (rescaled) contribution
            for ti in self._drop_idx:
                for k in range(self.num_class):
                    self.tree_weights[ti * self.num_class + k] *= old_factor
            self.score = self.score + self._drop_contrib_train * old_factor
            for vi in range(len(self.valid_sets)):
                vds, vb, vs = self.valid_sets[vi]
                self.valid_sets[vi] = (
                    vds, vb, vs + self._drop_contrib_valid[vi] * old_factor)
            self._drop_contrib_train = None
            self._drop_contrib_valid = []
        return stopped
