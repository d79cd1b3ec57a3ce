"""Random forest mode (reference: /root/reference/src/boosting/rf.hpp:217).

No shrinkage, bagging required; every tree fits the full gradient computed
at the constant init score (rf.hpp ``GetTrainingScore`` returns the
boost-from-average score only), the init bias is folded into every tree
(rf.hpp:137 ``AddBias``), and predictions are averaged over iterations
(``average_output_`` flag, rf.hpp:28).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from .gbdt import GBDTModel


class RFModel(GBDTModel):
    _bias_in_every_tree = True
    average_output = True

    def __init__(self, config, train_set, objective, hist_reduce=None,
                 obs=None):
        if config.bagging_freq <= 0 or not (0.0 < config.bagging_fraction < 1.0):
            raise ValueError("rf requires bagging (bagging_freq>0, "
                             "0<bagging_fraction<1)")
        super().__init__(config, train_set, objective, hist_reduce, obs)
        self._const_score = None

    def _score_for_gradients(self):
        if self._const_score is None:
            init = [0.0] * self.num_class
            if self.objective is not None and self.config.boost_from_average:
                init = [self.objective.boost_from_score(k)
                        for k in range(self.num_class)]
            self._init_scores = init
            self._const_score = jnp.broadcast_to(
                jnp.asarray(init, jnp.float32),
                (self.num_data, self.num_class))
        return self._const_score

    def train_one_iter(self, grad=None, hess=None) -> bool:
        self._score_for_gradients()  # ensure _init_scores exists at iter 0
        self._init_applied_backup = self._init_applied
        # prevent the base from also adding init to the scorers
        self._init_applied = True
        try:
            return super().train_one_iter(grad, hess)
        finally:
            self._init_applied = self._init_applied_backup
