"""Evaluation metrics (reference: /root/reference/src/metric/*.hpp).

Host-side NumPy implementations — metrics run once per ``metric_freq``
iterations on score arrays pulled from device (the reference's metrics are
likewise CPU-side, metric.cpp:16-66 factory).  All support sample weights;
AUC / NDCG / MAP are rank-based O(n log n) like the reference.

Each metric reports ``(name, value, is_higher_better)``.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import Config
from .dataset import Metadata


class Metric:
    name = "metric"
    is_higher_better = False

    def __init__(self, config: Config):
        self.config = config

    def init(self, metadata: Metadata, num_data: int) -> None:
        self.label = np.asarray(metadata.label)
        self.weight = (np.asarray(metadata.weight)
                       if metadata.weight is not None else None)
        self.boundaries = metadata.query_boundaries
        self.num_data = num_data

    def _avg(self, per_row: np.ndarray) -> float:
        if self.weight is not None:
            return float(np.sum(per_row * self.weight) / np.sum(self.weight))
        return float(np.mean(per_row))

    def eval(self, score: np.ndarray) -> List[Tuple[str, float, bool]]:
        raise NotImplementedError


# ---- regression metrics (regression_metric.hpp:322) -----------------------

class _PointwiseMetric(Metric):
    def point(self, y, s):
        raise NotImplementedError

    def transform(self, s):
        return s

    def eval(self, score):
        s = self.transform(score)
        return [(self.name, self._avg(self.point(self.label, s)),
                 self.is_higher_better)]


class L2Metric(_PointwiseMetric):
    name = "l2"
    def point(self, y, s): return (y - s) ** 2


class RMSEMetric(_PointwiseMetric):
    name = "rmse"
    def point(self, y, s): return (y - s) ** 2
    def eval(self, score):
        return [(self.name, float(np.sqrt(self._avg(self.point(self.label, score)))),
                 False)]


class L1Metric(_PointwiseMetric):
    name = "l1"
    def point(self, y, s): return np.abs(y - s)


class QuantileMetric(_PointwiseMetric):
    name = "quantile"
    def point(self, y, s):
        a = self.config.alpha
        d = y - s
        return np.where(d >= 0, a * d, (a - 1.0) * d)


class HuberMetric(_PointwiseMetric):
    name = "huber"
    def point(self, y, s):
        a = self.config.alpha
        d = np.abs(y - s)
        return np.where(d <= a, 0.5 * d * d, a * (d - 0.5 * a))


class FairMetric(_PointwiseMetric):
    name = "fair"
    def point(self, y, s):
        c = self.config.fair_c
        d = np.abs(y - s)
        return c * c * (d / c - np.log1p(d / c))


class PoissonMetric(_PointwiseMetric):
    name = "poisson"
    def transform(self, s): return np.exp(s)
    def point(self, y, s):
        eps = 1e-10
        return s - y * np.log(np.maximum(s, eps))


class MAPEMetric(_PointwiseMetric):
    name = "mape"
    def point(self, y, s):
        return np.abs(y - s) / np.maximum(np.abs(y), 1.0)


class GammaMetric(_PointwiseMetric):
    name = "gamma"
    def transform(self, s): return np.exp(s)
    def point(self, y, s):
        eps = 1e-10
        psi = y / np.maximum(s, eps)
        theta = -1.0 / np.maximum(s, eps)
        a = -np.log(-theta)
        return -np.log(np.maximum(y, eps)) - theta * y + a + psi * 0  # deviance core
    def eval(self, score):
        s = self.transform(score)
        eps = 1e-10
        ll = (self.label / np.maximum(s, eps) + np.log(np.maximum(s, eps)))
        return [(self.name, self._avg(ll), False)]


class GammaDevianceMetric(_PointwiseMetric):
    name = "gamma_deviance"
    def transform(self, s): return np.exp(s)
    def point(self, y, s):
        eps = 1e-10
        f = y / np.maximum(s, eps)
        return 2.0 * (np.log(np.maximum(1.0 / np.maximum(f, eps), eps)) + f - 1.0)


class TweedieMetric(_PointwiseMetric):
    name = "tweedie"
    def transform(self, s): return np.exp(s)
    def point(self, y, s):
        rho = self.config.tweedie_variance_power
        eps = 1e-10
        s = np.maximum(s, eps)
        a = y * np.power(s, 1.0 - rho) / (1.0 - rho)
        b = np.power(s, 2.0 - rho) / (2.0 - rho)
        return -a + b


# ---- binary metrics (binary_metric.hpp:388) -------------------------------

def _sigmoid(x, k=1.0):
    return 1.0 / (1.0 + np.exp(-k * x))


class BinaryLoglossMetric(Metric):
    name = "binary_logloss"

    def eval(self, score):
        p = np.clip(_sigmoid(score, self.config.sigmoid), 1e-15, 1 - 1e-15)
        ll = -(self.label * np.log(p) + (1 - self.label) * np.log(1 - p))
        return [(self.name, self._avg(ll), False)]


class BinaryErrorMetric(Metric):
    name = "binary_error"

    def eval(self, score):
        pred = (score > 0).astype(np.float64)
        return [(self.name, self._avg((pred != self.label).astype(np.float64)),
                 False)]


def _auc(label: np.ndarray, score: np.ndarray,
         weight: Optional[np.ndarray]) -> float:
    """Rank-based weighted AUC (binary_metric.hpp AUCMetric, O(n log n))."""
    order = np.argsort(score, kind="mergesort")
    s, y = score[order], label[order]
    w = weight[order] if weight is not None else np.ones_like(y)
    # tie-aware: average rank within tied score groups
    pos_w = (y > 0) * w
    neg_w = (y <= 0) * w
    cum_neg = np.cumsum(neg_w)
    # group by unique score: within a tie group use half of the group's negatives
    _, first_idx, inv = np.unique(s, return_index=True, return_inverse=True)
    grp_neg = np.bincount(inv, weights=neg_w)
    cum_before = np.concatenate([[0.0], np.cumsum(grp_neg)[:-1]])
    rank_neg = cum_before[inv] + 0.5 * grp_neg[inv]
    area = float(np.sum(pos_w * rank_neg))
    tot_pos, tot_neg = float(pos_w.sum()), float(neg_w.sum())
    if tot_pos <= 0 or tot_neg <= 0:
        return 1.0
    return area / (tot_pos * tot_neg)


class AUCMetric(Metric):
    name = "auc"
    is_higher_better = True

    def eval(self, score):
        return [(self.name, _auc(self.label, score, self.weight), True)]


class AveragePrecisionMetric(Metric):
    name = "average_precision"
    is_higher_better = True

    def eval(self, score):
        order = np.argsort(-score, kind="mergesort")
        y = self.label[order]
        w = self.weight[order] if self.weight is not None else np.ones_like(y)
        tp = np.cumsum(y * w)
        all_ = np.cumsum(w)
        precision = tp / np.maximum(all_, 1e-15)
        ap = float(np.sum(precision * y * w) / max(np.sum(y * w), 1e-15))
        return [(self.name, ap, True)]


# ---- multiclass metrics (multiclass_metric.hpp:368) -----------------------

class MultiLoglossMetric(Metric):
    name = "multi_logloss"

    def eval(self, score):
        # score: [N, K] raw; softmax here
        s = score - score.max(axis=1, keepdims=True)
        p = np.exp(s)
        p /= p.sum(axis=1, keepdims=True)
        idx = self.label.astype(np.int64)
        ll = -np.log(np.clip(p[np.arange(len(idx)), idx], 1e-15, None))
        return [(self.name, self._avg(ll), False)]


class MultiErrorMetric(Metric):
    name = "multi_error"

    def eval(self, score):
        k = self.config.multi_error_top_k
        idx = self.label.astype(np.int64)
        true_score = score[np.arange(len(idx)), idx]
        rank = (score >= true_score[:, None]).sum(axis=1)
        err = (rank > k).astype(np.float64)
        # top-k > 1 reports as multi_error@k (multiclass_metric.hpp
        # MultiErrorMetric::Name)
        name = self.name if k <= 1 else f"{self.name}@{k}"
        return [(name, self._avg(err), False)]


class AucMuMetric(Metric):
    """auc_mu (multiclass_metric.hpp auc_mu): mean pairwise-class AUC."""
    name = "auc_mu"
    is_higher_better = True

    def eval(self, score):
        k = score.shape[1]
        idx = self.label.astype(np.int64)
        aucs = []
        for a in range(k):
            for b in range(a + 1, k):
                m = (idx == a) | (idx == b)
                if not m.any():
                    continue
                y = (idx[m] == a).astype(np.float64)
                s = score[m, a] - score[m, b]
                w = self.weight[m] if self.weight is not None else None
                aucs.append(_auc(y, s, w))
        return [(self.name, float(np.mean(aucs)) if aucs else 1.0, True)]


# ---- ranking metrics (rank_metric.hpp:169, dcg_calculator.cpp) ------------

class NDCGMetric(Metric):
    name = "ndcg"
    is_higher_better = True

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        lg = self.config.label_gain
        max_label = int(self.label.max()) if len(self.label) else 0
        if lg is None:
            lg = [(1 << i) - 1 for i in range(max_label + 2)]
        self.label_gain = np.asarray(lg, np.float64)

    def eval(self, score):
        if self.boundaries is None:
            raise ValueError("ndcg metric requires query information")
        eval_at = [int(k) for k in self.config.eval_at]
        b = self.boundaries
        sums = np.zeros(len(eval_at))
        cnt = 0
        for qi in range(len(b) - 1):
            y = self.label[b[qi]:b[qi + 1]].astype(np.int64)
            s = score[b[qi]:b[qi + 1]]
            order = np.argsort(-s, kind="mergesort")
            ideal = np.sort(y)[::-1]
            cnt += 1
            for j, k in enumerate(eval_at):
                kk = min(k, len(y))
                disc = 1.0 / np.log2(np.arange(2, kk + 2))
                dcg = float((self.label_gain[y[order[:kk]]] * disc).sum())
                idcg = float((self.label_gain[ideal[:kk]] * disc).sum())
                sums[j] += dcg / idcg if idcg > 0 else 1.0
        return [(f"ndcg@{k}", sums[j] / max(cnt, 1), True)
                for j, k in enumerate(eval_at)]


class MAPMetric(Metric):
    name = "map"
    is_higher_better = True

    def eval(self, score):
        if self.boundaries is None:
            raise ValueError("map metric requires query information")
        eval_at = [int(k) for k in self.config.eval_at]
        b = self.boundaries
        sums = np.zeros(len(eval_at))
        cnt = 0
        for qi in range(len(b) - 1):
            y = (self.label[b[qi]:b[qi + 1]] > 0).astype(np.float64)
            s = score[b[qi]:b[qi + 1]]
            order = np.argsort(-s, kind="mergesort")
            ys = y[order]
            cnt += 1
            hits = np.cumsum(ys)
            prec = hits / np.arange(1, len(ys) + 1)
            for j, k in enumerate(eval_at):
                kk = min(k, len(ys))
                npos = ys[:kk].sum()
                sums[j] += (prec[:kk] * ys[:kk]).sum() / npos if npos > 0 else 0.0
        return [(f"map@{k}", sums[j] / max(cnt, 1), True)
                for j, k in enumerate(eval_at)]


# ---- cross-entropy metrics (xentropy_metric.hpp:358) ----------------------

class CrossEntropyMetric(Metric):
    name = "cross_entropy"

    def eval(self, score):
        p = np.clip(_sigmoid(score), 1e-15, 1 - 1e-15)
        ll = -(self.label * np.log(p) + (1 - self.label) * np.log(1 - p))
        return [(self.name, self._avg(ll), False)]


class CrossEntropyLambdaMetric(Metric):
    name = "cross_entropy_lambda"

    def eval(self, score):
        lam = np.log1p(np.exp(score))
        p = np.clip(-np.expm1(-lam), 1e-15, 1 - 1e-15)
        ll = -(self.label * np.log(p) + (1 - self.label) * np.log(1 - p))
        return [(self.name, self._avg(ll), False)]


class KLDivMetric(Metric):
    name = "kldiv"

    def eval(self, score):
        p = np.clip(_sigmoid(score), 1e-15, 1 - 1e-15)
        y = np.clip(self.label, 1e-15, 1 - 1e-15)
        kl = (y * np.log(y / p) + (1 - y) * np.log((1 - y) / (1 - p)))
        return [(self.name, self._avg(kl), False)]


# ---- traced (jit-able) metric forms ---------------------------------------
#
# Device-resident evaluation for the super-epoch trainer
# (models/gbdt.py train_superepoch) and the booster's fused_eval path:
# each factory returns a pure ``(score, label, weight) -> f32 scalar``
# that jits into the training scan (or a standalone eval program) over
# PADDED valid buckets.  Padding rows carry weight 0.0, so every traced
# metric is a weighted mean/ratio that ignores them by construction —
# the caller always passes a weight vector (ones where the user gave
# none, zeros on the pad tail).  Metrics without a traced form return
# None from traced_metric_fn, which gates the engine back to the
# per-iteration host path.  Traced values are f32 (the host metrics
# compute in f64): the byte-identity contract is traced-vs-traced
# (superepoch vs fused_eval="true" per-iteration — docs/Fused-
# Training.md), while the clip floor is widened to 1e-7 because
# ``1 - 1e-15`` rounds to 1.0 in f32 and would emit inf on saturated
# scores.

def _t_wavg(per_row, w):
    return jnp.sum(per_row * w) / jnp.sum(w)


def _t_binary_logloss(config: Config):
    sig = float(config.sigmoid)

    def fn(score, label, weight):
        p = 1.0 / (1.0 + jnp.exp(-sig * score))
        p = jnp.clip(p, 1e-7, 1.0 - 1e-7)
        ll = -(label * jnp.log(p) + (1.0 - label) * jnp.log(1.0 - p))
        return _t_wavg(ll, weight)
    return fn


def _t_auc(config: Config):
    # exact tie-aware weighted AUC, the _auc() recurrence in traced
    # form: stable ascending sort, tie groups via a cumsum of
    # score-change flags, per-group negative mass via segment_sum —
    # pad rows have weight 0 so joining a tie group changes nothing
    def fn(score, label, weight):
        order = jnp.argsort(score, stable=True)
        s, y, w = score[order], label[order], weight[order]
        pos_w = jnp.where(y > 0, w, 0.0)
        neg_w = jnp.where(y <= 0, w, 0.0)
        newgrp = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32),
             (s[1:] != s[:-1]).astype(jnp.int32)])
        gid = jnp.cumsum(newgrp)
        grp_neg = jax.ops.segment_sum(neg_w, gid,
                                      num_segments=s.shape[0])
        cum_before = jnp.cumsum(grp_neg) - grp_neg
        rank_neg = cum_before[gid] + 0.5 * grp_neg[gid]
        area = jnp.sum(pos_w * rank_neg)
        tp, tn = jnp.sum(pos_w), jnp.sum(neg_w)
        return jnp.where((tp > 0) & (tn > 0), area / (tp * tn),
                         jnp.float32(1.0))
    return fn


def _t_l2(config: Config):
    def fn(score, label, weight):
        return _t_wavg((label - score) ** 2, weight)
    return fn


def _t_rmse(config: Config):
    def fn(score, label, weight):
        return jnp.sqrt(_t_wavg((label - score) ** 2, weight))
    return fn


def _t_l1(config: Config):
    def fn(score, label, weight):
        return _t_wavg(jnp.abs(label - score), weight)
    return fn


def _t_multi_logloss(config: Config):
    # score: [N, K] raw — parity partner for MultiLoglossMetric; the
    # scan path never reaches it (num_class > 1 is unfusable) but the
    # fused_eval="true" per-iteration path does
    def fn(score, label, weight):
        s = score - jnp.max(score, axis=1, keepdims=True)
        p = jnp.exp(s)
        p = p / jnp.sum(p, axis=1, keepdims=True)
        idx = label.astype(jnp.int32)
        picked = jnp.take_along_axis(p, idx[:, None], axis=1)[:, 0]
        ll = -jnp.log(jnp.clip(picked, 1e-7, None))
        return _t_wavg(ll, weight)
    return fn


_TRACED_METRICS: Dict[str, Callable[[Config], Callable]] = {
    "binary_logloss": _t_binary_logloss,
    "auc": _t_auc,
    "l2": _t_l2,
    "rmse": _t_rmse,
    "l1": _t_l1,
    "multi_logloss": _t_multi_logloss,
}


def traced_metric_fn(name: str, config: Config) -> Optional[Callable]:
    """Jit-able ``(score, label, weight) -> f32 scalar`` for ``name``,
    or None when the metric has no traced form (engine falls back to
    per-iteration host eval)."""
    mk = _TRACED_METRICS.get(name)
    return mk(config) if mk is not None else None


def build_traced_eval(eval_spec: Sequence[Tuple],
                      config: Config) -> Optional[Callable]:
    """The ONE jitted eval program both fused paths report through.

    ``eval_spec`` is a tuple of ``(valid_idx, set_name, metric_name,
    higher_better)`` entries in ``booster.eval_valid()`` order; the
    returned ``teval(svecs, ops)`` evaluates every entry over device
    score VECTORS (``svecs[vi]``: f32 ``[rows]``) and padded
    ``(label, weight)`` pairs (``ops[vi]``), returning an f32 ``[E]``
    stack.  Returns None when any metric lacks a traced form.

    Why a shared program instead of evaluating inside the training
    scan: XLA may fuse a reduction differently depending on the
    surrounding program, and different fusion can round the last ulp
    differently even on bitwise-identical inputs.  The super-epoch
    trainer therefore evaluates its in-scan metrics only to drive the
    early-stop VOTE, and recomputes the REPORTED values post-scan
    through this program — the same one ``fused_eval="true"``
    per-iteration runs use — so record_evals are bit-identical across
    the two paths by construction (docs/Fused-Training.md)."""
    spec = tuple(eval_spec)
    fns = tuple(traced_metric_fn(mn, config)
                for (_vi, _n, mn, _h) in spec)
    if any(f is None for f in fns):
        return None
    from .obs.flops import eval_flops_bytes, note_traced
    from .utils.compile_cache import trace_event

    @jax.jit
    @jax.named_scope("lgbtpu.eval")
    def teval(svecs, ops):
        trace_event("traced_eval")
        if not spec:
            return jnp.zeros((0,), jnp.float32)
        note_traced("fused_eval",
                    *eval_flops_bytes(
                        sum(int(s.shape[0]) for s in svecs)
                        // max(len(svecs), 1), len(spec)),
                    phase="eval", cadence="iter")
        return jnp.stack([
            f(svecs[vi], ops[vi][0], ops[vi][1])
            for f, (vi, _n, _mn, _h) in zip(fns, spec)])
    return teval


_METRICS = {
    "l1": L1Metric, "l2": L2Metric, "rmse": RMSEMetric,
    "quantile": QuantileMetric, "huber": HuberMetric, "fair": FairMetric,
    "poisson": PoissonMetric, "mape": MAPEMetric, "gamma": GammaMetric,
    "gamma_deviance": GammaDevianceMetric, "tweedie": TweedieMetric,
    "binary_logloss": BinaryLoglossMetric, "binary_error": BinaryErrorMetric,
    "auc": AUCMetric, "average_precision": AveragePrecisionMetric,
    "multi_logloss": MultiLoglossMetric, "multi_error": MultiErrorMetric,
    "auc_mu": AucMuMetric,
    "ndcg": NDCGMetric, "map": MAPMetric,
    "cross_entropy": CrossEntropyMetric,
    "cross_entropy_lambda": CrossEntropyLambdaMetric,
    "kldiv": KLDivMetric,
}


def create_metric(name: str, config: Config) -> Optional[Metric]:
    """Metric factory (metric.cpp:16-66)."""
    if name in ("custom", "none", ""):
        return None
    cls = _METRICS.get(name)
    if cls is None:
        raise ValueError(f"Unknown metric: {name}")
    return cls(config)
