"""Wide MXU-shaped histogram contraction (ISSUE 15).

The multi-leaf one-hot contraction grew past the shipped K<=16
super-step widths: C = 3K channel axes lane-pad to MXU 128-multiples
(utils/shapes.bucket_channels, exact zeros sliced off in-kernel), the
split_batch set extends to {32, 64} with budget-aware snapping
(fit_split_batch), the strict grower's masked smaller-child pass rides
the same slot mechanism (hist_overlap — byte-identical by
construction), the block-rows budget accounts the wide accumulator,
and an on-device autotuner (ops/hist_tune.py) picks (K, block_rows) by
measured ms per leaf slot.  These tests pin: kernel exactness at every
width, the overlap path's byte-identity, metric parity of the wide
widths vs strict across sampling/categorical/monotone/quantized
configs, dp==serial through the owner-shard reduce at K=32, the
pad-excluded MFU accounting, and the tuner's persistence.
"""

import json
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb


def _strip_params(model_text: str) -> str:
    """Model bytes minus the dumped parameter block (a toggled param
    name prints there even when the trees are identical)."""
    return model_text.split("parameters:")[0]


@pytest.fixture(scope="module")
def data():
    rs = np.random.RandomState(11)
    n, f = 900, 10
    x = rs.randn(n, f)
    x[rs.rand(n, f) < 0.03] = np.nan
    logit = (np.nan_to_num(x[:, 0]) * 1.5 - np.nan_to_num(x[:, 1])
             + 0.4 * np.nan_to_num(x[:, 2]) + 0.3 * rs.randn(n))
    y = (logit > 0).astype(np.float32)
    return x, y


def _train(x, y, rounds=3, **over):
    p = {"objective": "binary", "verbosity": -1, "min_data_in_leaf": 5,
         "max_bin": 31, "tpu_learner": "masked", "fused_chunk": 0,
         "num_leaves": 33}
    p.update(over)
    ds = lgb.Dataset(x, label=y, params=p)
    return lgb.train(p, ds, num_boost_round=rounds)


def _auc(y, s):
    order = np.argsort(s)
    r = np.empty(len(s))
    r[order] = np.arange(1, len(s) + 1)
    pos = y > 0
    n1, n0 = pos.sum(), (~pos).sum()
    return (r[pos].sum() - n1 * (n1 + 1) / 2) / (n1 * n0)


# ---------------------------------------------------------------------------
# shape policy units
# ---------------------------------------------------------------------------

class TestShapePolicy:
    def test_bucket_channels(self):
        from lightgbm_tpu.utils.shapes import (HIST_CHANNEL_EXACT_MAX,
                                               bucket_channels)
        # shipped widths stay exact (C=3 strict, 24/48 for K=8/16)
        for c in (3, 6, 24, 48):
            assert bucket_channels(c) == c
        assert HIST_CHANNEL_EXACT_MAX == 48
        # wide widths pad to 128-lane multiples
        assert bucket_channels(96) == 128       # K=32
        assert bucket_channels(192) == 256      # K=64
        assert bucket_channels(129) == 256

    def test_split_batch_set_extended(self):
        from lightgbm_tpu.utils.shapes import (SPLIT_BATCH_SET,
                                               snap_split_batch)
        assert SPLIT_BATCH_SET == (1, 8, 16, 32, 64)
        assert snap_split_batch(20) == 32
        assert snap_split_batch(33) == 64
        assert snap_split_batch(999) == 64
        assert snap_split_batch(16) == 16
        assert snap_split_batch(1) == 1

    def test_fit_split_batch_budget(self):
        from lightgbm_tpu.utils.shapes import fit_split_batch
        assert fit_split_batch(32, 31) == 16    # steps DOWN the set
        assert fit_split_batch(32, 33) == 32
        assert fit_split_batch(64, 40) == 32
        assert fit_split_batch(64, 65) == 64
        assert fit_split_batch(8, 31) == 8      # shipped widths pass
        assert fit_split_batch(1, 31) == 1
        assert fit_split_batch(64, 2) == 1      # nothing fits -> strict

    def test_block_rows_budget_accounts_wide_channels(self):
        from lightgbm_tpu.ops.histogram import hist_block_rows
        # shipped widths: formula byte-identical to the historic one
        assert hist_block_rows(28, 64) == hist_block_rows(28, 64,
                                                          channels=48)
        assert hist_block_rows(968, 256) == \
            hist_block_rows(968, 256, channels=24)
        # wide channels on a wide dataset: the [C, F*Bp] accumulator
        # carry alone exceeds the budget -> block floors at 8 instead
        # of silently overshooting (the pre-fix behavior)
        assert hist_block_rows(968, 256, channels=256) == 8
        # narrow dataset: wide channels only trim the block a little
        assert hist_block_rows(28, 64, channels=256) >= 4096


# ---------------------------------------------------------------------------
# kernel exactness at the new widths
# ---------------------------------------------------------------------------

class TestKernelWidths:
    @pytest.mark.parametrize("k", [32, 64])
    def test_slotted_matches_masked_per_slot(self, k):
        import jax.numpy as jnp
        from lightgbm_tpu.ops.histogram import compute_histogram
        rs = np.random.RandomState(0)
        n, f, B = 3000, 5, 31
        binned = jnp.asarray(rs.randint(0, B, size=(n, f),
                                        dtype=np.uint8))
        vals = jnp.asarray(rs.randn(n, 3).astype(np.float32))
        slot = jnp.asarray(rs.randint(-1, k, size=n, dtype=np.int32))
        h = compute_histogram(binned, vals, num_bins=B, slot=slot,
                              num_slots=k)
        assert h.shape == (f, B, 3 * k)
        for s in (0, k // 2, k - 1):
            m = (slot == s).astype(np.float32)[:, None]
            ref = compute_histogram(binned, vals * m, num_bins=B)
            got = h.reshape(f, B, 3, k)[:, :, :, s]
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=2e-5, atol=2e-4)

    def test_int8_k64_exact(self):
        import jax.numpy as jnp
        from lightgbm_tpu.ops.histogram import compute_histogram
        rs = np.random.RandomState(1)
        n, f, B, k = 2500, 4, 31, 64
        binned = jnp.asarray(rs.randint(0, B, size=(n, f),
                                        dtype=np.uint8))
        vi = jnp.asarray(rs.randint(-50, 50, size=(n, 3),
                                    dtype=np.int8))
        slot = jnp.asarray(rs.randint(0, k, size=n, dtype=np.int32))
        h = compute_histogram(binned, vi, num_bins=B, slot=slot,
                              num_slots=k)
        assert h.dtype == jnp.int32
        s = 9
        ref = np.zeros((f, B, 3), np.int64)
        bn, vn = np.asarray(binned), np.asarray(vi, np.int64)
        for i in np.nonzero(np.asarray(slot) == s)[0]:
            for ff in range(f):
                ref[ff, bn[i, ff]] += vn[i]
        np.testing.assert_array_equal(
            np.asarray(h.reshape(f, B, 3, k)[:, :, :, s]), ref)

    def test_padded_channel_flops_excluded_from_hist_site(self):
        """The in-kernel trace note for ``hist`` carries the USEFUL
        channel flops only; the 128-lane pad lands in ``hist_pad``
        under phase="pad" (the MFU-excluded channel)."""
        import jax.numpy as jnp
        from lightgbm_tpu.obs.flops import (hist_flops_bytes,
                                            padded_bins, traced_sites)
        from lightgbm_tpu.ops.histogram import compute_histogram
        rs = np.random.RandomState(2)
        n, f, B, k = 1000, 3, 15, 32
        binned = jnp.asarray(rs.randint(0, B, size=(n, f),
                                        dtype=np.uint8))
        vals = jnp.asarray(rs.randn(n, 3).astype(np.float32))
        slot = jnp.asarray(rs.randint(0, k, size=n, dtype=np.int32))
        compute_histogram(binned, vals, num_bins=B, slot=slot,
                          num_slots=k)
        sites = traced_sites()
        useful, _ = hist_flops_bytes(n, f, B, channels=3 * k)
        assert sites["hist"].flops == useful
        assert useful == 2 * 3 * k * n * f * padded_bins(B)
        pad = sites["hist_pad"]
        assert pad.phase == "pad"
        # 96 useful channels pad to 128: 32 dead lanes
        assert pad.flops == 2 * (128 - 96) * n * f * padded_bins(B)


# ---------------------------------------------------------------------------
# strict-grower overlap path: byte-identical to the serialized baseline
# ---------------------------------------------------------------------------

class TestStrictOverlap:
    def test_kernel_slot_mask_bitwise_equals_masked(self):
        import jax.numpy as jnp
        from lightgbm_tpu.ops.histogram import compute_histogram
        rs = np.random.RandomState(3)
        n, f, B = 4000, 6, 63
        binned = jnp.asarray(rs.randint(0, B, size=(n, f),
                                        dtype=np.uint8))
        vals = jnp.asarray(rs.randn(n, 3).astype(np.float32))
        mask = jnp.asarray(rs.rand(n) < 0.4)
        sl = jnp.where(mask, jnp.int32(0), jnp.int32(-1))
        h_slot = compute_histogram(binned, vals, num_bins=B, slot=sl,
                                   num_slots=1)
        h_mask = compute_histogram(
            binned, vals * mask.astype(np.float32)[:, None], num_bins=B)
        np.testing.assert_array_equal(np.asarray(h_slot),
                                      np.asarray(h_mask))

    @pytest.mark.parametrize("extra", [
        {},
        {"bagging_fraction": 0.7, "bagging_freq": 1},
        {"quant_train": True},
    ])
    def test_overlap_model_byte_identical(self, data, extra):
        x, y = data
        a = _train(x, y, num_leaves=15, split_batch=1,
                   hist_overlap=True, **extra)
        b = _train(x, y, num_leaves=15, split_batch=1,
                   hist_overlap=False, **extra)
        assert _strip_params(a.model_to_string()) == \
            _strip_params(b.model_to_string())


# ---------------------------------------------------------------------------
# wide-width parity matrix vs strict growth
# ---------------------------------------------------------------------------

_WIDE_CONFIGS = {
    "plain": {},
    "bagging": {"bagging_fraction": 0.7, "bagging_freq": 1},
    "goss": {"data_sample_strategy": "goss"},
    "monotone": {"monotone_constraints": [1, -1] + [0] * 8},
    "quant": {"quant_train": True},
}


@pytest.mark.slow   # exhaustive sweep tier, like test_split_batch.py
class TestWideParity:
    @pytest.mark.parametrize("name", sorted(_WIDE_CONFIGS))
    def test_k32_metric_parity_vs_strict(self, data, name):
        """K=32 changes growth ORDER, not model quality: AUC within a
        small epsilon of strict leaf-wise on every config family."""
        x, y = data
        over = _WIDE_CONFIGS[name]
        strict = _train(x, y, rounds=5, split_batch=1, **over)
        wide = _train(x, y, rounds=5, split_batch=32, **over)
        a1 = _auc(y, strict.predict(x))
        a32 = _auc(y, wide.predict(x))
        assert a32 > a1 - 0.03, (name, a1, a32)

    def test_k64_trains_and_matches(self, data):
        x, y = data
        strict = _train(x, y, rounds=4, num_leaves=65, split_batch=1)
        wide = _train(x, y, rounds=4, num_leaves=65, split_batch=64)
        assert _auc(y, wide.predict(x)) > \
            _auc(y, strict.predict(x)) - 0.03

    def test_k32_categorical(self, data):
        x, y = data
        rs = np.random.RandomState(5)
        xc = np.nan_to_num(x).copy()
        cat = rs.randint(0, 8, x.shape[0]).astype(float)
        y2 = ((cat >= 4) & (np.nan_to_num(x[:, 0]) > -0.5)) \
            .astype(np.float32)
        xc[:, 5] = cat
        aucs = {}
        for sb in (1, 32):
            p = {"objective": "binary", "verbosity": -1,
                 "num_leaves": 33, "min_data_in_leaf": 5,
                 "min_data_per_group": 5, "tpu_learner": "masked",
                 "fused_chunk": 0, "split_batch": sb}
            ds = lgb.Dataset(xc, label=y2, params={"max_bin": 31},
                             categorical_feature=[5])
            bst = lgb.train(p, ds, num_boost_round=6)
            aucs[sb] = _auc(y2, bst.predict(xc))
        assert aucs[32] > 0.9
        assert aucs[32] > aucs[1] - 0.03


class TestWidthContracts:
    """The cheap byte-level pins of the width contract (tier-1; the
    exhaustive parity sweeps above are slow-tier)."""

    def test_over_budget_width_fits_down_byte_identical(self, data):
        """num_leaves=31 at K=32 must run the K=16 program — the same
        bytes an explicit split_batch=16 trains."""
        x, y = data
        a = _train(x, y, num_leaves=31, split_batch=32)
        b = _train(x, y, num_leaves=31, split_batch=16)
        assert _strip_params(a.model_to_string()) == \
            _strip_params(b.model_to_string())

    def test_fused_chunk_carries_k32(self, data):
        """The fused super-step scan threads the wide K: fused ==
        per-iteration byte-identically at split_batch=32."""
        x, y = data
        a = _train(x, y, split_batch=32, fused_chunk=0)
        b = _train(x, y, split_batch=32, fused_chunk=3)
        assert _strip_params(a.model_to_string()) == \
            _strip_params(b.model_to_string())


# ---------------------------------------------------------------------------
# distributed: the owner-shard reduce carries the wide K
# ---------------------------------------------------------------------------

@pytest.mark.slow   # mirrors test_split_batch.py::TestDistributedBatched
class TestDistributedWide:
    def _structure(self, bst):
        return [(list(np.asarray(t.split_feature)),
                 list(np.asarray(t.left_child)))
                for t in bst.trees]

    @pytest.fixture(scope="class")
    def clean_data(self):
        # NaN-free, well-separated data: the f32 dp comparison needs
        # gains without near-ties (psum reorder moves ulps, and the
        # wide top-K ORDER is tie-sensitive — the same caveat the
        # shipped K<=16 dp tests carry); the quant variant below is
        # exact by int32 construction
        rs = np.random.RandomState(3)
        n, f = 1600, 12
        x = rs.randn(n, f)
        y = (x[:, 0] - x[:, 1] + 0.3 * rs.randn(n) > 0) \
            .astype(np.float32)
        return x, y

    def test_dp_owner_shard_structure_equals_serial_at_k32(
            self, clean_data):
        import jax
        if len(jax.devices()) < 4:
            pytest.skip("needs a multi-device mesh")
        x, y = clean_data
        ser = _train(x, y, split_batch=32)
        dp = _train(x, y, split_batch=32, tree_learner="data",
                    mesh_shape=[4])
        assert self._structure(ser) == self._structure(dp)

    def test_dp_quant_int32_reduce_at_k32(self, clean_data):
        """Quantized training's exact int32 histograms through the
        wide owner-shard psum_scatter: structure parity dp == serial
        (the shipped quant contract, test_quant.py, at the new K)."""
        import jax
        if len(jax.devices()) < 4:
            pytest.skip("needs a multi-device mesh")
        x, y = clean_data
        ser = _train(x, y, split_batch=32, quant_train=True)
        dp = _train(x, y, split_batch=32, quant_train=True,
                    tree_learner="data", mesh_shape=[4])
        assert self._structure(ser) == self._structure(dp)

    def test_feature_parallel_carries_k32(self, clean_data):
        import jax
        if len(jax.devices()) < 4:
            pytest.skip("needs a multi-device mesh")
        x, y = clean_data
        ser = _train(x, y, split_batch=32)
        fp = _train(x, y, split_batch=32, tree_learner="feature",
                    mesh_shape=[4])
        assert self._structure(ser) == self._structure(fp)


# ---------------------------------------------------------------------------
# pad-truthful accounting (obs/flops.py + obs/attrib.py)
# ---------------------------------------------------------------------------

class TestPadAccounting:
    def test_ledger_pad_site_only_for_wide_widths(self):
        from lightgbm_tpu.obs.flops import FlopLedger
        led16 = FlopLedger.for_training(10000, 28, 63, split_batch=16)
        assert "hist_pad" not in {s.site for s in led16.sites()}
        led32 = FlopLedger.for_training(10000, 28, 63, split_batch=32)
        sites = {s.site: s for s in led32.sites()}
        assert sites["hist_pad"].phase == "pad"
        from lightgbm_tpu.obs.flops import padded_bins
        assert sites["hist_pad"].flops == \
            2 * (128 - 96) * 10000 * 28 * padded_bins(63)

    def test_intensity_rises_with_k(self):
        """More channels per binned-operand load is the direct
        arithmetic-intensity lever — the acceptance instrument."""
        from lightgbm_tpu.obs.flops import FlopLedger
        inten = {}
        for k in (16, 32, 64):
            led = FlopLedger.for_training(100000, 28, 63, split_batch=k)
            s = {x.site: x for x in led.sites()}["hist"]
            inten[k] = s.flops / s.hbm_bytes
        assert inten[32] > inten[16]
        assert inten[64] > inten[32]

    def test_perf_summary_excludes_pad_from_mfu(self):
        """perf.hist_pad.* is visible, but phase/total aggregation —
        the MFU denominator's numerator — never includes pad FLOPs."""
        from lightgbm_tpu.obs.attrib import perf_summary
        snap = {
            "flops.total{phase=grow,site=hist}": {"value": 1000.0},
            "flops.hbm_bytes{phase=grow,site=hist}": {"value": 100.0},
            "flops.total{phase=pad,site=hist_pad}": {"value": 333.0},
            "flops.hbm_bytes{phase=pad,site=hist_pad}": {"value": 0.0},
            "train.phase_seconds{phase=grow}": {"sum": 1.0},
        }
        out = perf_summary(snap, peaks=(1e4, 1e3))
        assert out["perf.hist_pad.flops"] == 333.0
        assert out["perf.grow.flops"] == 1000.0
        assert out["perf.total.flops"] == 1000.0
        assert out["perf.grow.mfu"] == pytest.approx(1000.0 / 1.0 / 1e4)
        assert "perf.pad.flops" not in out

    def test_booster_perf_keys_at_k32(self, data):
        x, y = data
        bst = _train(x, y, split_batch=32, telemetry=True)
        snap = bst.telemetry_snapshot()
        pad = snap.get("perf.hist_pad.flops", 0.0)
        assert pad > 0
        # the grow phase's flops must be EXACTLY the sum of its own
        # phase=grow counters — i.e. the pad counters (phase=pad) are
        # excluded from the MFU numerator, not merely small
        grow_counters = sum(
            float(v.get("value", 0.0)) for k, v in snap.items()
            if k.startswith("flops.total{") and "phase=grow" in k)
        assert snap["perf.grow.flops"] == pytest.approx(grow_counters)
        pad_counters = sum(
            float(v.get("value", 0.0)) for k, v in snap.items()
            if k.startswith("flops.total{") and "phase=pad" in k)
        assert pad_counters == pytest.approx(pad) and pad_counters > 0
        # ...and the total aggregates PHASES only (a phase block emits
        # .seconds, a site block does not) — no "pad" phase exists
        phase_flops = sum(
            float(snap[k]) for k in snap
            if k.startswith("perf.") and k.endswith(".flops")
            and k != "perf.total.flops"
            and (k[:-len("flops")] + "seconds") in snap)
        assert snap["perf.total.flops"] == pytest.approx(phase_flops)
        assert "perf.pad.flops" not in snap
        assert snap.get("perf.hist.intensity_flops_per_byte", 0) > 0


# ---------------------------------------------------------------------------
# autotuner (ops/hist_tune.py)
# ---------------------------------------------------------------------------

class TestAutotuner:
    def test_sweep_and_persistence(self, tmp_path):
        from lightgbm_tpu.ops import hist_tune
        rec = hist_tune.tune(2000, 4, 15, kmax=32, reps=2,
                             sample_rows=1024)
        assert rec["k"] in (8, 16, 32)
        assert rec["block_rows"] >= 8
        assert rec["ms_per_leaf"] <= rec["ms_per_pass"]
        # ensure(): sweep once, then table hits (memory and disk)
        d = str(tmp_path / "tune")
        c0 = hist_tune.tune_counts()
        r1 = hist_tune.ensure(2000, 4, 15, kmax=32, dir_path=d)
        c1 = hist_tune.tune_counts()
        assert c1["sweeps"] == c0["sweeps"] + 1
        path = os.path.join(d, hist_tune.TUNE_FILE)
        assert os.path.exists(path)
        r2 = hist_tune.ensure(2000, 4, 15, kmax=32, dir_path=d)
        c2 = hist_tune.tune_counts()
        assert c2["sweeps"] == c1["sweeps"] and r2 == r1
        # a fresh process-view miss still resolves from DISK, no sweep
        with hist_tune._LOCK:
            hist_tune._MEM.clear()
        r3 = hist_tune.ensure(2000, 4, 15, kmax=32, dir_path=d)
        assert r3 == r1
        assert hist_tune.tune_counts()["sweeps"] == c2["sweeps"]
        table = json.load(open(path))
        key = next(iter(table))
        assert "kmax32" in key and table[key]["k"] == r1["k"]

    def test_booster_hist_tune_on_uses_choice(self, data, tmp_path,
                                              monkeypatch):
        from lightgbm_tpu.ops import hist_tune
        x, y = data
        # a private directory: the param only places the table where
        # JAX_COMPILATION_CACHE_DIR (set by conftest) does not
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        d = str(tmp_path / "cache")
        c0 = hist_tune.tune_counts()["sweeps"]
        bst = _train(x, y, rounds=2, hist_tune="on",
                     compile_cache_dir=d)
        assert hist_tune.tune_counts()["sweeps"] == c0 + 1
        assert os.path.exists(os.path.join(d, hist_tune.TUNE_FILE))
        assert _auc(y, bst.predict(x)) > 0.8
        # second booster on the same shape bucket: zero re-tune
        _train(x, y, rounds=2, hist_tune="on", compile_cache_dir=d)
        assert hist_tune.tune_counts()["sweeps"] == c0 + 1

    def test_hist_tune_off_is_default_and_exact(self, data):
        """hist_tune=off must never consult the tuner — identical
        bytes to a run with the param unset."""
        from lightgbm_tpu.ops import hist_tune
        x, y = data
        c0 = hist_tune.tune_counts()["sweeps"]
        a = _train(x, y, num_leaves=15)
        b = _train(x, y, num_leaves=15, hist_tune="off")
        assert hist_tune.tune_counts()["sweeps"] == c0
        assert _strip_params(a.model_to_string()) == \
            _strip_params(b.model_to_string())

    def test_bad_hist_tune_value_rejected(self, data):
        x, y = data
        with pytest.raises(Exception):
            _train(x, y, rounds=1, hist_tune="sometimes")

    def test_explicit_split_batch_wins_over_tuner(self, data, tmp_path,
                                                  monkeypatch):
        """An explicit width is the user's choice: the tuner engages
        only for split_batch=0 — with an explicit width it must not
        even sweep (a tuned block_rows paired to a different K would
        re-partition the f32 scan against the explicit-width byte
        pins)."""
        from lightgbm_tpu.ops import hist_tune
        x, y = data
        # a private directory: the param only places the table where
        # JAX_COMPILATION_CACHE_DIR (set by conftest) does not
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        d = str(tmp_path / "cache")
        c0 = hist_tune.tune_counts()["sweeps"]
        a = _train(x, y, split_batch=16, hist_tune="on",
                   compile_cache_dir=d)
        assert hist_tune.tune_counts()["sweeps"] == c0
        assert not os.path.exists(os.path.join(d, hist_tune.TUNE_FILE))
        b = _train(x, y, split_batch=16)
        assert _strip_params(a.model_to_string()) == \
            _strip_params(b.model_to_string())

    def test_tiny_budget_skips_sweep_cleanly(self, data, tmp_path,
                                             monkeypatch):
        """num_leaves <= 8 admits no set width: hist_tune=on must skip
        the sweep (not crash-and-warn every fit) and train strict."""
        from lightgbm_tpu.ops import hist_tune
        x, y = data
        # a private directory: the param only places the table where
        # JAX_COMPILATION_CACHE_DIR (set by conftest) does not
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        d = str(tmp_path / "cache")
        c0 = hist_tune.tune_counts()["sweeps"]
        a = _train(x, y, rounds=2, num_leaves=5, split_batch=0,
                   hist_tune="on", compile_cache_dir=d)
        assert hist_tune.tune_counts()["sweeps"] == c0
        b = _train(x, y, rounds=2, num_leaves=5, split_batch=0)
        assert _strip_params(a.model_to_string()) == \
            _strip_params(b.model_to_string())
