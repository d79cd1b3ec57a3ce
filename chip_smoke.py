#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system starts on the chip.

One process, the public entry points, full width, depth cut:

    python chip_smoke.py             # one TPU chip
    python chip_smoke.py --chips 4   # one host, four chips, one process

One-chip stages (each prints one JSON line with platform, device_kind,
device count, stage seconds, this stage's compile count / seconds /
persistent-cache hits and misses, and the device's peak bytes in use):

- ``train255``  ``lgb.train`` on HIGGS-shaped seeded data, 1,000,000 x 28
  train + 100,000 held-out in ``valid_sets``, binary, 255 leaves, 63 bins,
  32 rounds — the reference's own tree shape, no dimension cut.  Names the
  training loop that ran, checks the learner, the split batch, the tree
  sizes and the held-out AUC, and checks tree 0's root split against a
  float64 NumPy gain scan over the same binned matrix.
- ``train31``   31 leaves, ``split_batch=1``, 25 rounds, no valid set: the
  strict grower inside the super-epoch scan, its eval tail empty.
- ``serve``     ``serve.Server`` at defaults answers 20 requests of 1..4096
  rows byte-equal to ``Booster.predict``; a second ``Server`` with
  ``serve_device_binning`` answers them through the fused program within
  1e-6, with no demotion to the host walk.
- ``numerics``  the histogram contraction at 28 features x 64 padded bins
  against float64 NumPy: a probe that is exact in f32 but not in bf16 must
  be reproduced *exactly* (dense and k-hot), random data must meet the f32
  summation bound, the int8 path must equal an int64 reference.  The same
  probe and bound at the benchmark cell's width (2,000 features, 16 slots,
  32,768 rows), and the count of contractions traced by implementation
  (on the chip the f32 passes take the VMEM kernel, the int8 one the scan).
- ``facts``     three bring-up observations: blocking 4-byte fetch latency,
  ``block_until_ready`` against ``obs.trace.fence``, and whether
  ``telemetry_profile_iters`` leaves a non-empty xplane file.

The script fails (non-zero exit, no result line) when
``jax.devices()[0].platform`` is not ``tpu``, and no stage is wrapped in a
handler that would let a failure end with 0.  The last line of stdout is
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
import time

import numpy as np

N_TRAIN, N_VALID, N_FEAT = 1_000_000, 100_000, 28
N_WIDE, F_WIDE = 32_768, 2_000     # the benchmark cell's width, few rows
MAX_BIN = 63
ROUNDS_255, ROUNDS_31, ROUNDS_4CHIP = 32, 25, 4
REQUEST_ROWS = (1, 7, 64, 1000, 4096)
REQUEST_ROUNDS = 4

PARAMS_255 = {
    "objective": "binary", "num_leaves": 255, "max_bin": MAX_BIN,
    "learning_rate": 0.1, "min_data_in_leaf": 20, "metric": "auc",
    "telemetry": True, "verbosity": -1,
}
PARAMS_31 = dict(PARAMS_255, num_leaves=31, split_batch=1)


def make_higgs_like(n: int, f: int, seed: int = 0):
    """HIGGS-shaped seeded data."""
    rng = np.random.RandomState(seed)
    x = rng.randn(n, f).astype(np.float32)
    logit = (1.2 * x[:, 0] - 0.8 * x[:, 1] + 0.6 * x[:, 2] * x[:, 3]
             + 0.4 * np.abs(x[:, 4]) + 0.5 * rng.randn(n))
    y = (logit > 0).astype(np.float32)
    return x, y


def auc(y: np.ndarray, score: np.ndarray) -> float:
    """Rank-sum AUC in float64 (continuous scores: ties are negligible)."""
    rank = np.empty(len(score), np.float64)
    rank[np.argsort(score, kind="stable")] = np.arange(1, len(score) + 1)
    pos = y > 0.5
    n_pos, n_neg = int(pos.sum()), int((~pos).sum())
    return float((rank[pos].sum() - n_pos * (n_pos + 1) / 2.0)
                 / (n_pos * n_neg))


def numpy_root_split(binned: np.ndarray, y: np.ndarray,
                     min_data: int = 20, min_hess: float = 1e-3):
    """The first tree's root split by a plain float64 gain scan.

    Binary logloss with boost_from_average starts every row at
    p = mean(y), so grad = p - y and hess = p (1 - p) for all rows.  For
    each feature and each threshold bin t (left = bins <= t) the gain is
    G_L^2 / H_L + G_R^2 / H_R (lambda_l1 = lambda_l2 = 0), admissible
    when both sides hold min_data_in_leaf rows and min_sum_hessian.
    Returns (feature, threshold_bin, best_gain, runner_up_gain)."""
    n = len(y)
    p = float(np.mean(y, dtype=np.float64))
    g = p - y.astype(np.float64)
    h = p * (1.0 - p)
    g_tot, h_tot = g.sum(), h * n
    gains = []
    for f in range(binned.shape[1]):
        col = binned[:, f]
        cnt_l = np.cumsum(np.bincount(col, minlength=MAX_BIN))[:-1]
        g_l = np.cumsum(np.bincount(col, weights=g, minlength=MAX_BIN))[:-1]
        h_l = cnt_l * h
        cnt_r, g_r, h_r = n - cnt_l, g_tot - g_l, h_tot - h_l
        ok = (cnt_l >= min_data) & (cnt_r >= min_data) \
            & (h_l >= min_hess) & (h_r >= min_hess)
        with np.errstate(divide="ignore", invalid="ignore"):
            gain = np.where(ok, g_l ** 2 / h_l + g_r ** 2 / h_r, -np.inf)
        gains.append(gain)
    gains = np.stack(gains)                               # [F, B-1]
    f_best, t_best = np.unravel_index(int(np.argmax(gains)), gains.shape)
    top2 = np.sort(gains.ravel())[-2:]
    return int(f_best), int(t_best), float(top2[1]), float(top2[0])


def peak_bytes_in_use(devices) -> int:
    return max(d.memory_stats()["peak_bytes_in_use"] for d in devices)


class Stages:
    """Prints one line per stage; holds no exception handler."""

    def __init__(self, devices):
        self.devices = devices
        self.dev = {"platform": devices[0].platform,
                    "kind": devices[0].device_kind,
                    "count": len(devices)}

    def run(self, name: str, fn, *args):
        from lightgbm_tpu.utils.compile_cache import compile_stats
        c0, t0 = compile_stats(), time.perf_counter()
        result = fn(*args)
        seconds = time.perf_counter() - t0
        c1 = compile_stats()
        info = result[-1] if isinstance(result, tuple) else result
        print(json.dumps({
            "stage": name, "platform": self.dev["platform"],
            "device_kind": self.dev["kind"],
            "device_count": self.dev["count"],
            "seconds": round(seconds, 3),
            "compile.count": c1["count"] - c0["count"],
            "compile.seconds": round(c1["seconds"] - c0["seconds"], 3),
            "compile.cache_hits": c1["cache_hits"] - c0["cache_hits"],
            "compile.cache_misses":
                c1["cache_misses"] - c0["cache_misses"],
            "peak_bytes_in_use": peak_bytes_in_use(self.devices),
            **(info or {})}), flush=True)
        return result


# -- stages -----------------------------------------------------------------

def stage_data():
    import lightgbm_tpu as lgb
    x, y = make_higgs_like(N_TRAIN + N_VALID, N_FEAT)
    xt, yt, xv, yv = x[:N_TRAIN], y[:N_TRAIN], x[N_TRAIN:], y[N_TRAIN:]
    t0 = time.perf_counter()
    ds = lgb.Dataset(xt, label=yt, params=PARAMS_255).construct()
    dv = lgb.Dataset(xv, label=yv, reference=ds).construct()
    assert ds.binned.shape == (N_TRAIN, N_FEAT), ds.binned.shape
    return ds, dv, yt, xv, yv, {
        "binning_seconds": round(time.perf_counter() - t0, 3),
        "binned_dtype": str(ds.binned.dtype)}


def counter(snap: dict, key: str) -> int:
    """A metrics-snapshot counter; one that never fired is absent."""
    return int(snap.get(key, {}).get("value", 0))


def engaged_loop(bst) -> dict:
    """Which of the two training loops ran, from the telemetry counters."""
    snap = bst.telemetry_snapshot()
    out = {"superepochs": counter(snap, "train.superepochs"),
           "iterations": counter(snap, "train.iterations")}
    if out["superepochs"]:
        out["loop"] = "superepoch"
    else:
        out["loop"] = "per_iteration"
        out["fused_reasons"] = bst._model.fused_reasons()
    return out


def assert_main_path(model, split_batch: int) -> None:
    """None of the give-way branches of GBDTModel was taken: auto picked
    the one-program learner, on one chip no mesh was resolved."""
    assert model._learner_kind == "masked", model._learner_kind
    assert model._split_batch == split_batch, model._split_batch
    assert model._dist is None and model._mesh is None, model._dist
    assert not model.config.dist_fallback_serial


def stage_train255(ds, dv, yt):
    import lightgbm_tpu as lgb
    evals = {}
    bst = lgb.train(PARAMS_255, ds, num_boost_round=ROUNDS_255,
                    valid_sets=[dv],
                    callbacks=[lgb.record_evaluation(evals)])
    loop = engaged_loop(bst)
    assert_main_path(bst._model, split_batch=16)
    assert loop["loop"] == "superepoch", loop
    leaves = [t.num_leaves for t in bst.trees]
    assert len(leaves) == ROUNDS_255 and min(leaves) > 100, leaves
    curve = evals["valid_0"]["auc"]
    assert len(curve) == ROUNDS_255 and np.isfinite(curve).all(), curve
    assert curve[-1] > curve[0] and curve[-1] > 0.85, curve

    t0 = bst.trees[0]
    assert int(t0.leaf_count.sum()) == N_TRAIN, int(t0.leaf_count.sum())
    f_ref, t_ref, g_best, g_next = numpy_root_split(ds.binned, yt)
    root = (int(t0.split_feature[0]), int(t0.threshold_bin[0]))
    assert root == (f_ref, t_ref), (root, (f_ref, t_ref))
    return bst, {**loop, "learner": bst._model._learner_kind,
                 "split_batch": bst._model._split_batch,
                 "leaves_min": min(leaves), "leaves_max": max(leaves),
                 "steps_per_tree_max": max(bst._model.step_counts),
                 "auc_first": round(curve[0], 5),
                 "auc_last": round(curve[-1], 5),
                 "root_split": root,
                 "root_gain_margin": round((g_best - g_next) / g_best, 6)}


def stage_train31(ds, xv, yv):
    import lightgbm_tpu as lgb
    bst = lgb.train(PARAMS_31, ds, num_boost_round=ROUNDS_31)
    loop = engaged_loop(bst)
    assert_main_path(bst._model, split_batch=1)
    assert loop["loop"] == "superepoch", loop
    leaves = [t.num_leaves for t in bst.trees]
    assert len(leaves) == ROUNDS_31 and min(leaves) > 15, leaves
    pred = bst.predict(xv)
    assert pred.shape == (len(xv),) and np.isfinite(pred).all()
    held_out = auc(yv, pred)
    assert held_out > 0.85, held_out
    return {**loop, "split_batch": bst._model._split_batch,
            "leaves_min": min(leaves), "leaves_max": max(leaves),
            "steps_per_tree_max": max(bst._model.step_counts),
            "auc_held_out": round(held_out, 5)}


def stage_serve(bst, xv):
    from lightgbm_tpu import serve
    requests, off = [], 0
    for _ in range(REQUEST_ROUNDS):
        for n in REQUEST_ROWS:
            requests.append(np.asarray(xv[off:off + n], np.float64))
            off += n
    # the reference first: Booster.predict before any Server installs its
    # engine as the booster's predictor (the four small sizes walk the
    # host trees, 4096 rows ride the bucketed engine)
    ref = [bst.predict(r) for r in requests]
    info = {"requests": len(requests)}
    for name, params in (("default", {}),
                         ("fused", {"serve_device_binning": True})):
        srv = serve.Server(params=params, booster=bst)
        try:
            out = []
            for lo in range(0, len(requests), len(REQUEST_ROWS)):
                futs = [srv.submit(r)
                        for r in requests[lo:lo + len(REQUEST_ROWS)]]
                out += [f.result(600.0) for f in futs]
            snap = srv.metrics_snapshot()
            served = srv.registry.current()
        finally:
            srv.close()
        # the load-time self-check demotes a disagreeing engine to the
        # host walk; that is safety code, and here it must not have fired
        assert served.engine is not None and not served.self_check_failed
        assert counter(snap, "serve.host_fallback_batches") == 0, snap
        assert counter(snap, "serve.requests") == len(requests), snap
        err = max(float(np.max(np.abs(o - r))) for o, r in zip(out, ref))
        assert all(o.shape == r.shape and np.isfinite(o).all()
                   for o, r in zip(out, ref))
        if name == "default":
            assert all(np.array_equal(o, r) for o, r in zip(out, ref)), err
        else:
            assert counter(snap, "serve.fused_batches") > 0, snap
            # fused scores accumulate in f32 in tree order where
            # Booster.predict accumulates in f64: <= 32 trees of |leaf|
            # <~ 0.1 round to ~1e-7 in the raw score, and the sigmoid
            # (slope <= 1/4) cannot widen that
            assert err <= 1e-6, err
        assert counter(snap, "serve.errors") == 0, snap
        info[name] = {"max_abs_diff": err,
                      "rows": counter(snap, "serve.rows"),
                      "fused_batches": counter(snap, "serve.fused_batches"),
                      "host_fallback_batches":
                          counter(snap, "serve.host_fallback_batches"),
                      "threshold_dtype":
                          snap["serve.engine"]["threshold_dtype"],
                      "table_bytes": snap["serve.engine"]["table_bytes"]}
    return info


def _probe_bins(n: int) -> np.ndarray:
    """[n, 28] bins where every feature sees every bin equally often."""
    return ((np.arange(n)[:, None] + 7 * np.arange(N_FEAT)[None, :])
            % MAX_BIN).astype(np.uint8)


def _bincount_hist(bins: np.ndarray, vals: np.ndarray) -> np.ndarray:
    """float64 reference: hist[f, b, c] = sum of vals[n, c] over rows with
    bins[n, f] == b."""
    out = np.zeros((bins.shape[1], MAX_BIN, vals.shape[1]), np.float64)
    for f in range(bins.shape[1]):
        for c in range(vals.shape[1]):
            out[f, :, c] = np.bincount(
                bins[:, f], weights=vals[:, c].astype(np.float64),
                minlength=MAX_BIN)
    return out


def _bincount_hist_slotted(bins: np.ndarray, vals: np.ndarray,
                           slot: np.ndarray, slots: int) -> np.ndarray:
    """float64 reference of the slotted contraction: hist[f, b, c*slots + s]
    sums vals[n, c] over rows with bins[n, f] == b and slot[n] == s; a row
    whose slot is negative adds nothing."""
    live = slot >= 0
    bins, vals, slot = bins[live].astype(np.int64), vals[live], slot[live]
    out = np.zeros((bins.shape[1], MAX_BIN, vals.shape[1], slots),
                   np.float64)
    for f in range(bins.shape[1]):
        cell = bins[:, f] * slots + slot
        for c in range(vals.shape[1]):
            out[f, :, c, :] = np.bincount(
                cell, weights=vals[:, c].astype(np.float64),
                minlength=MAX_BIN * slots).reshape(MAX_BIN, slots)
    return out.reshape(bins.shape[1], MAX_BIN, vals.shape[1] * slots)


def stage_numerics():
    import jax.numpy as jnp
    from lightgbm_tpu import sparse_data
    from lightgbm_tpu.ops.histogram import compute_histogram
    from lightgbm_tpu.ops.quantize import (QuantSpec, quant_scales,
                                           quantize_stack)
    info = {}

    # (a) the discriminating probe.  grad = 1 + 2^-12 and hess = 1 - 2^-11
    # are exact in f32 and round to 1.0 in bf16; with 2048 rows in every
    # bin each partial sum m * 4097/4096 (m <= 2048) is exact in f32 in
    # any order, so an f32 contraction must return 2048.5 / 2047 / 2048
    # EXACTLY and one that rounds its operands to bf16 returns 2048.
    n_a = MAX_BIN * 2048
    bins_a = _probe_bins(n_a)
    vals_a = np.tile(np.asarray([1 + 2.0 ** -12, 1 - 2.0 ** -11, 1.0],
                                np.float32), (n_a, 1))
    got = np.asarray(compute_histogram(jnp.asarray(bins_a),
                                       jnp.asarray(vals_a),
                                       num_bins=MAX_BIN))
    ref = _bincount_hist(bins_a, vals_a)
    assert got.dtype == np.float32 and got.shape == ref.shape
    assert np.array_equal(got.astype(np.float64), ref), \
        ("f32 histogram lost operand bits", got[0, 0], ref[0, 0])
    # ...and with ONE full-mantissa accumuland per bin (every other row
    # zeroed, as rows outside the leaf are) the histogram must return
    # each value bit for bit: all 24 bits of the operand reach the sum
    vals_m = np.zeros((n_a, 3), np.float32)
    vals_m[:MAX_BIN] = (1.0 + np.random.RandomState(5).rand(MAX_BIN, 3)) \
        .astype(np.float32)
    got = np.asarray(compute_histogram(jnp.asarray(bins_a),
                                       jnp.asarray(vals_m),
                                       num_bins=MAX_BIN))
    assert np.array_equal(got.astype(np.float64),
                          _bincount_hist(bins_a, vals_m)), \
        "f32 histogram lost low mantissa bits"
    info["probe_exact"] = True

    # the k-hot layout under the same probe, slotted so that the
    # per-slot totals ride its dot_general: 16 slots x 2048 rows
    n_k, slots = 16 * 2048, 16
    bins_k = _probe_bins(n_k)
    rows, feats = np.nonzero(bins_k != 0)            # default bin 0
    sp = sparse_data.build_khot(
        rows, (feats * 64 + bins_k[rows, feats]).astype(np.int32),
        np.zeros(N_FEAT, np.int32), n_k, 64, N_FEAT).to_device()
    slot = (np.arange(n_k) // 2048).astype(np.int32)
    got = np.asarray(sparse_data.histogram(
        sp, jnp.asarray(vals_a[:n_k]), num_bins=MAX_BIN,
        slot=jnp.asarray(slot), num_slots=slots))
    ref = np.stack([_bincount_hist(bins_k[slot == s], vals_a[:2048])
                    for s in range(slots)], axis=-1)   # [F, B, 3, S]
    assert np.array_equal(got.astype(np.float64),
                          ref.reshape(N_FEAT, MAX_BIN, 3 * slots)), \
        "k-hot histogram lost operand bits"
    info["khot_probe_exact"] = True

    # (b) seeded random grad/hess at 1M rows.  Bound: any order of f32
    # summation of n terms satisfies |err| <= (n - 1) u sum|v_i| with
    # u = 2^-24 (Higham, Accuracy and Stability, eq. 4.4), n the rows in
    # the bin.  It is the bound f32 accumulation can promise and no
    # tighter: bf16 operand rounding (~2^-9 per row, cancelling to ~1e-5
    # of sum|v|) hides inside it, which is why (a) exists.
    rng = np.random.RandomState(11)
    bins_b = rng.randint(0, MAX_BIN, size=(N_TRAIN, N_FEAT)).astype(np.uint8)
    vals_b = np.stack([rng.randn(N_TRAIN), rng.rand(N_TRAIN) * 0.25,
                       np.ones(N_TRAIN)], axis=1).astype(np.float32)
    bins_dev, vals_dev = jnp.asarray(bins_b), jnp.asarray(vals_b)
    got = np.asarray(compute_histogram(bins_dev, vals_dev,
                                       num_bins=MAX_BIN))
    ref = _bincount_hist(bins_b, vals_b)
    mass = _bincount_hist(bins_b, np.abs(vals_b))
    bound = (ref[:, :, 2:3] - 1.0) * 2.0 ** -24 * mass
    err = np.abs(got.astype(np.float64) - ref)
    assert (err <= bound).all(), float((err / np.maximum(bound, 1e-300)).max())
    info["random_max_err_over_mass"] = float((err / mass).max())
    info["random_max_err_over_bound"] = float((err / bound).max())

    # (c) the width of the benchmark's cell: 2,000 features, 16 slots (the
    # batched grower's contraction), rows without a slot among them.  On
    # the chip this is the kernel that builds the one-hot in VMEM
    # (ops/hist_kernel.py) at the tiles it takes there: 16 feature tiles,
    # the last one ragged.  First (a)'s probe: at ~30 rows a (bin, slot)
    # every partial sum of 1 + 2^-12 is exact in f32 in any order and in
    # any split of the accumuland, so the sums are exact, and a split that
    # drops its low pieces returns the row count.  Then seeded accumulands
    # under (b)'s bound, with two more roundings for the adds that join
    # the three pieces.
    n_w, f_w, slots = N_WIDE, F_WIDE, 16
    rng = np.random.RandomState(13)
    bins_w = rng.randint(0, MAX_BIN, size=(n_w, f_w)).astype(np.uint8)
    slot_w = rng.randint(-1, slots, size=n_w).astype(np.int32)
    bins_w_dev, slot_w_dev = jnp.asarray(bins_w), jnp.asarray(slot_w)
    vals_w = np.stack([rng.randn(n_w), rng.rand(n_w) * 0.25,
                       np.ones(n_w)], axis=1).astype(np.float32)

    def wide(vals):
        got = np.asarray(compute_histogram(
            bins_w_dev, jnp.asarray(vals), num_bins=MAX_BIN,
            slot=slot_w_dev, num_slots=slots))
        ref = _bincount_hist_slotted(bins_w, vals, slot_w, slots)
        assert got.dtype == np.float32 and got.shape == ref.shape
        return got.astype(np.float64), ref

    got, ref = wide(vals_a[:n_w])
    assert np.array_equal(got, ref), \
        ("wide f32 histogram lost operand bits", float(np.abs(got - ref).max()))
    info["wide_probe_exact"] = True
    got, ref = wide(vals_w)
    mass = _bincount_hist_slotted(bins_w, np.abs(vals_w), slot_w, slots)
    count = np.tile(ref[:, :, 2 * slots:], (1, 1, 3))
    bound = np.maximum((count + 1.0) * 2.0 ** -24 * mass, 1e-300)
    err = np.abs(got - ref)
    assert (err <= bound).all(), float((err / bound).max())
    info["wide_random_max_err_over_bound"] = float((err / bound).max())

    # int8: the shipped quantizer's output through the integer
    # contraction equals an int64 bincount of the same int8 values
    spec = QuantSpec(bits=8)
    q = quantize_stack(vals_dev, quant_scales(vals_dev, spec.qmax), spec,
                       iter_key=0, row_offset=0)
    got = np.asarray(compute_histogram(bins_dev, q, num_bins=MAX_BIN))
    q_host = np.asarray(q)
    assert q_host.dtype == np.int8 and got.dtype == np.int32
    assert np.array_equal(got.astype(np.int64),
                          _bincount_hist(bins_b, q_host).astype(np.int64)), \
        "int8 histogram is not exact"
    info["int8_exact"] = True
    # which contraction each of the passes above traced: on the chip the
    # f32 ones the kernel, the int8 one the scan
    from lightgbm_tpu.obs.flops import traced_impls
    info["contraction_traces"] = {impl: n for (_, impl), n
                                  in traced_impls().items()}
    return bins_dev, vals_dev, info


def stage_facts(bins_dev, vals_dev):
    """Three observations for CHANGES.md (one run each, not metrics)."""
    import jax
    import jax.numpy as jnp
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs.trace import fence
    from lightgbm_tpu.ops.histogram import compute_histogram

    bump = jax.jit(lambda a: a + 1.0)
    a = jax.device_get(bump(jnp.zeros((), jnp.float32)))
    fetch = []
    for _ in range(51):
        t0 = time.perf_counter()
        a = jax.device_get(bump(a))
        fetch.append(time.perf_counter() - t0)

    def one_pass():
        return compute_histogram(bins_dev, vals_dev, num_bins=MAX_BIN)

    fence(one_pass())                                  # compiled, drained
    bur, fence_after_bur, fence_alone = [], [], []
    for _ in range(5):
        t0 = time.perf_counter()
        h = jax.block_until_ready(one_pass())
        t1 = time.perf_counter()
        fence(h)
        t2 = time.perf_counter()
        fence(one_pass())
        t3 = time.perf_counter()
        bur.append(t1 - t0)
        fence_after_bur.append(t2 - t1)
        fence_alone.append(t3 - t2)

    # a short run of its own, so the capture holds a few small programs
    x, y = make_higgs_like(65_536, N_FEAT, seed=3)
    with tempfile.TemporaryDirectory() as tmp:
        params = dict(PARAMS_31, telemetry_profile_iters=[4, 2],
                      telemetry_trace_file=os.path.join(tmp, "trace.jsonl"))
        bst = lgb.train(params, lgb.Dataset(x, label=y, params=params),
                        num_boost_round=8)
        bst.telemetry_finish()
        planes = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                           recursive=True)
        plane_bytes = sum(os.path.getsize(p) for p in planes)
    assert planes and plane_bytes > 0, \
        "telemetry_profile_iters left no xplane file"
    ms = lambda v: round(float(np.median(v)) * 1e3, 3)  # noqa: E731
    return {"fetch_4_bytes_ms_median": ms(fetch),
            "hist_pass_block_until_ready_ms": ms(bur),
            "hist_pass_fence_after_block_until_ready_ms":
                ms(fence_after_bur),
            "hist_pass_fence_ms": ms(fence_alone),
            "xplane_files": len(planes), "xplane_bytes": plane_bytes}


def stage_four_chips(ds, dv, xv, yv, devices):
    """tree_learner=data on four real devices against the one-chip run."""
    import lightgbm_tpu as lgb

    def fit(**extra):
        return lgb.train(dict(PARAMS_255, **extra), ds,
                         num_boost_round=ROUNDS_4CHIP, valid_sets=[dv])

    one, dp = fit(), fit(tree_learner="data")
    m = dp._model
    assert m._dist == "data" and m._mesh.size == 4, (m._dist, m._mesh)
    assert m._learner_kind == "masked" and m._split_batch == 16
    assert getattr(m.grower, "owner_shard", False)
    shards = m.binned_dev.addressable_shards
    assert {s.device for s in shards} == set(devices), shards
    assert all(s.data.shape == (N_TRAIN // 4, N_FEAT) for s in shards), \
        [s.data.shape for s in shards]
    # Structure must be identical, thresholds and counts included.  Leaf
    # values meet dryrun_multichip's tolerance except where a leaf's
    # totals are differences of much larger f32 sums (larger child =
    # parent - smaller child, down a chain from the root): at 1M rows that
    # cancellation reaches ~1e-3 of such a leaf's totals on EVERY path
    # (against float64 NumPy one 3045-row leaf of tree 0 was off by 2.3e-4
    # on one chip and 6e-5 on four; PERF.md), and the paths round
    # differently.  So: at most two leaves per tree past the dryrun
    # tolerance, none past 1e-3.
    worst, outside = 0.0, []
    for t1, t4 in zip(one.trees, dp.trees):
        assert t1.num_leaves == t4.num_leaves > 100
        np.testing.assert_array_equal(t1.split_feature, t4.split_feature)
        np.testing.assert_array_equal(t1.threshold_bin, t4.threshold_bin)
        np.testing.assert_array_equal(t1.leaf_count, t4.leaf_count)
        off = ~np.isclose(t4.leaf_value, t1.leaf_value, rtol=2e-4, atol=1e-5)
        outside.append(int(off.sum()))
        worst = max(worst, float(np.abs(t4.leaf_value - t1.leaf_value).max()))
    assert max(outside) <= 2 and worst <= 1e-3, (outside, worst)
    p1, p4 = (b.predict(xv, raw_score=True) for b in (one, dp))
    pred_diff = float(np.abs(p4 - p1).max())
    assert pred_diff <= 1e-3 and abs(auc(yv, p4) - auc(yv, p1)) <= 1e-5
    info = {"trees": len(dp.trees),
            "leaves": [t.num_leaves for t in dp.trees],
            "mesh": list(m._mesh.devices.shape),
            "leaves_past_dryrun_tolerance": outside,
            "leaf_value_max_abs_diff": worst,
            "held_out_raw_score_max_abs_diff": pred_diff}
    for learner in ("feature", "voting"):
        b = lgb.train(dict(PARAMS_255, tree_learner=learner), ds,
                      num_boost_round=1)
        assert b._model._dist == learner and b._model._mesh.size == 4
        assert b.trees[0].num_leaves > 100
        info[learner + "_leaves"] = b.trees[0].num_leaves
    return info


# -- driver -----------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    chips = ap.parse_args().chips

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: jax.devices()[0].platform is {platform!r}, not "
              "'tpu'; this script only passes on the chip", file=sys.stderr)
        return 1
    if len(devices) != chips:
        print(f"chip_smoke: --chips {chips} but jax sees {len(devices)} "
              "device(s)", file=sys.stderr)
        return 1

    from lightgbm_tpu.obs.attrib import device_peaks
    from lightgbm_tpu.utils.compile_cache import compile_stats
    stages = Stages(devices)
    assert None not in device_peaks(devices), \
        f"obs.attrib.PEAKS does not know {devices[0].device_kind!r}"

    ds, dv, yt, xv, yv, _ = stages.run("data", stage_data)
    if chips == 4:
        stages.run("four_chips", stage_four_chips, ds, dv, xv, yv, devices)
    else:
        bst, _ = stages.run("train255", stage_train255, ds, dv, yt)
        stages.run("train31", stage_train31, ds, xv, yv)
        stages.run("serve", stage_serve, bst, xv)
        bins_dev, vals_dev, _ = stages.run("numerics", stage_numerics)
        stages.run("facts", stage_facts, bins_dev, vals_dev)

    # a renamed jax.monitoring event would leave these at zero silently
    total = compile_stats()
    assert total["count"] > 0, total
    assert total["cache_hits"] + total["cache_misses"] > 0, total
    print(json.dumps({"stage": "total", **total}), flush=True)
    print(json.dumps({"ok": True, "device": stages.dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
