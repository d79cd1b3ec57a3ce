"""Quantized low-precision training (ISSUE 13, docs/Quantized-Training.md).

The acceptance bars, as tests:

- **metric-parity harness** — quant vs f32 training on all four
  objective families (regression / binary / multiclass / lambdarank)
  stays within a pinned epsilon; this gate is the feature's contract;
- **default off is byte-identical** — ``quant_train=false`` trains the
  exact pre-quantization trees (only the echoed parameter line moves);
- **dp==serial int32 histogram identity** — the quantized histogram is
  an exact integer accumulation, so the sharded reduce is BITWISE equal
  to the serial pass (stronger than the f32 path's per-program
  determinism), and the trained tree structure matches serial;
- **kill+resume byte identity** — the stochastic-rounding stream is
  iteration-keyed, so crash+resume replays a straight run exactly;
- **fused == per-iteration** — the chunked ``lax.scan`` path quantizes
  with the same in-graph scales and keys;
- **ledger-proven HBM cut** — the static ledger (obs/flops.py) shows
  >= 2x lower histogram HBM bytes for int8 at a narrow shape, rising
  intensity, and the quantize/dequant sites; ``perf.hist.*`` keys carry
  the moved bound;
- **comm re-accounting** — the owner-shard reduce-scatter payload is
  recorded at its true int32 width, plus the quant-scale pmax site.
"""

import glob
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.ops.quantize import (QuantSpec, counter_uniform,
                                       quant_scales, quantize_stack)

_rs = np.random.RandomState(11)
X = _rs.randn(600, 6)
YREG = (2.0 * X[:, 0] - X[:, 1] + 0.1 * _rs.randn(600)).astype(np.float32)
YBIN = (X[:, 0] - X[:, 1] + 0.2 * _rs.randn(600) > 0).astype(np.float32)

BASE = {"objective": "binary", "num_leaves": 15, "max_bin": 31,
        "min_data_in_leaf": 5, "verbosity": -1, "tpu_learner": "masked",
        "fused_chunk": 0}


def _train(p, x=X, y=YBIN, rounds=3, **dskw):
    ds = lgb.Dataset(x, label=y, params=dict(p), **dskw)
    return lgb.train(dict(p), ds, num_boost_round=rounds)


def _strip_params(model_text: str) -> str:
    """Tree sections only: the parameters echo legitimately differs
    when a param is passed explicitly."""
    return model_text.split("parameters:")[0]


def _auc(y, s):
    r = np.argsort(np.argsort(s)) + 1
    npos = int((y > 0).sum())
    nneg = len(y) - npos
    return float((r[y > 0].sum() - npos * (npos + 1) / 2)
                 / max(npos * nneg, 1))


def _ndcg_at(y, s, groups, k=5):
    out, off = [], 0
    for g in groups:
        yy, ss = y[off:off + g], s[off:off + g]
        off += g
        order = np.argsort(-ss)[:k]
        dcg = ((2.0 ** yy[order] - 1)
               / np.log2(np.arange(len(order)) + 2)).sum()
        ideal = np.sort(yy)[::-1][:k]
        idcg = ((2.0 ** ideal - 1)
                / np.log2(np.arange(len(ideal)) + 2)).sum()
        out.append(dcg / idcg if idcg > 0 else 1.0)
    return float(np.mean(out))


# ---------------------------------------------------------------------------
# quantizer units (ops/quantize.py)
# ---------------------------------------------------------------------------

class TestQuantizer:
    def test_zero_rows_stay_zero(self):
        """Out-of-bag / padded rows carry exact zeros; stochastic
        rounding must never push them off zero."""
        import jax.numpy as jnp
        spec = QuantSpec(bits=8, stochastic=True, seed=3)
        vals = jnp.zeros((64, 3), jnp.float32)
        scales = jnp.full(3, 0.01, jnp.float32)
        q = quantize_stack(vals, scales, spec, 5, 0)
        assert q.dtype == jnp.int8
        assert not np.asarray(q).any()

    def test_stochastic_rounding_is_unbiased(self):
        import jax.numpy as jnp
        spec = QuantSpec(bits=8, stochastic=True, seed=0)
        v = jnp.full((4000, 3), 0.3, jnp.float32)
        scales = jnp.ones(3, jnp.float32)
        q = np.asarray(quantize_stack(v, scales, spec, 1, 0), np.float64)
        assert set(np.unique(q)) <= {0.0, 1.0}
        assert abs(q.mean() - 0.3) < 0.02

    def test_nearest_mode_deterministic(self):
        import jax.numpy as jnp
        spec = QuantSpec(bits=16, stochastic=False, seed=0)
        v = jnp.asarray(_rs.randn(100, 3).astype(np.float32))
        s = quant_scales(v, spec.qmax)
        q1 = np.asarray(quantize_stack(v, s, spec, 1, 0))
        q2 = np.asarray(quantize_stack(v, s, spec, 99, 0))
        np.testing.assert_array_equal(q1, q2)   # iteration key unused
        assert q1.dtype == np.int16

    def test_rounding_stream_slices_by_global_row(self):
        """The dp==serial identity's core: rows quantized on a shard
        with a global offset draw the SAME uniforms as the serial pass
        draws for those rows."""
        import jax.numpy as jnp
        full = np.asarray(counter_uniform(
            jnp.arange(300, dtype=jnp.int32), 3, 7, 42))
        part = np.asarray(counter_uniform(
            100 + jnp.arange(50, dtype=jnp.int32), 3, 7, 42))
        np.testing.assert_array_equal(full[100:150], part)
        assert (full >= 0).all() and (full < 1).all()

    def test_scale_covers_range(self):
        import jax.numpy as jnp
        spec = QuantSpec(bits=8)
        v = jnp.asarray(_rs.randn(500, 3).astype(np.float32)) * 37.0
        s = quant_scales(v, spec.qmax)
        q = np.asarray(quantize_stack(v, s, spec, 0, 0), np.int32)
        assert q.min() >= -127 and q.max() <= 127
        # dequantized extremum reproduces the true extremum to one step
        err = np.abs(q * np.asarray(s)[None, :] - np.asarray(v))
        assert (err <= np.asarray(s)[None, :] + 1e-7).all()


# ---------------------------------------------------------------------------
# config surface
# ---------------------------------------------------------------------------

class TestQuantConfig:
    def test_bad_bits_rejected(self):
        with pytest.raises(ValueError, match="quant_bits"):
            lgb.train(dict(BASE, quant_train=True, quant_bits=12),
                      lgb.Dataset(X, label=YBIN), num_boost_round=1)

    def test_bad_round_rejected(self):
        with pytest.raises(ValueError, match="quant_round"):
            lgb.train(dict(BASE, quant_train=True, quant_round="up"),
                      lgb.Dataset(X, label=YBIN), num_boost_round=1)

    def test_default_off_is_byte_identical(self):
        a = _train(BASE)
        b = _train(dict(BASE, quant_train=False))
        assert _strip_params(a.model_to_string()) \
            == _strip_params(b.model_to_string())


# ---------------------------------------------------------------------------
# the metric-parity harness: the feature's acceptance gate
# ---------------------------------------------------------------------------

# (params, metric fn on (model, x, y, groups), pinned epsilon).
# Epsilons are deliberately tight for trees this small: int8 stochastic
# rounding perturbs leaf values by ~1/127 of the grad scale, which these
# shallow ensembles absorb almost entirely.
_FAMILIES = {
    "regression": (dict(objective="regression"), "l2", 0.12),
    "binary": (dict(objective="binary"), "auc", 0.02),
    "multiclass": (dict(objective="multiclass", num_class=3), "mlogloss",
                   0.10),
    "lambdarank": (dict(objective="lambdarank"), "ndcg", 0.05),
}


def _family_data(family):
    if family == "multiclass":
        y = (np.digitize(X[:, 0] + 0.3 * X[:, 1], [-0.5, 0.5])
             ).astype(np.float32)
        return X, y, None
    if family == "lambdarank":
        groups = [20] * 30
        y = np.clip(np.round(X[:, 0] - X[:, 1]
                             + 0.3 * _rs.randn(600)), 0, 3).astype(
            np.float32)
        return X, y, groups
    if family == "binary":
        return X, YBIN, None
    return X, YREG, None


def _family_metric(kind, model, x, y, groups):
    pred = model.predict(x)
    if kind == "l2":
        return float(np.mean((pred - y) ** 2))
    if kind == "auc":
        return _auc(y, pred)
    if kind == "mlogloss":
        p = np.clip(pred[np.arange(len(y)), y.astype(int)], 1e-9, 1.0)
        return float(-np.mean(np.log(p)))
    return _ndcg_at(y, pred, groups)


class TestMetricParityHarness:
    @pytest.mark.parametrize("family", sorted(_FAMILIES))
    @pytest.mark.parametrize("bits", [8, 16])
    def test_quant_within_epsilon_of_f32(self, family, bits):
        over, kind, eps = _FAMILIES[family]
        x, y, groups = _family_data(family)
        dskw = {"group": groups} if groups else {}
        p = dict(BASE, **over)
        m_f32 = _train(p, x, y, rounds=5, **dskw)
        m_q = _train(dict(p, quant_train=True, quant_bits=bits),
                     x, y, rounds=5, **dskw)
        v_f32 = _family_metric(kind, m_f32, x, y, groups)
        v_q = _family_metric(kind, m_q, x, y, groups)
        if kind == "l2":
            # scale-dependent: compare relatively
            assert abs(v_q - v_f32) <= eps * max(v_f32, 1e-9), \
                (family, bits, v_f32, v_q)
        else:
            assert abs(v_q - v_f32) <= eps, (family, bits, v_f32, v_q)


# ---------------------------------------------------------------------------
# exactness properties
# ---------------------------------------------------------------------------

class TestInt32HistogramIdentity:
    def test_dp_reduce_bitwise_equals_serial(self):
        """The int32 accumulation is exact and order-independent, so
        the sharded psum of per-shard quantized histograms equals the
        serial pass BITWISE — the dp==serial histogram identity."""
        import jax
        if len(jax.devices()) < 8:
            pytest.skip("needs the 8-device CPU mesh")
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import PartitionSpec as P
        from lightgbm_tpu.ops.histogram import compute_histogram
        from lightgbm_tpu.parallel import make_mesh

        n, f, b = 512, 5, 16
        binned = _rs.randint(0, b, size=(n, f)).astype(np.uint8)
        vals = _rs.randn(n, 3).astype(np.float32)
        spec = QuantSpec(bits=8, stochastic=True, seed=9)
        scales = quant_scales(jnp.asarray(vals), spec.qmax)
        q = quantize_stack(jnp.asarray(vals), scales, spec, 4, 0)
        serial = np.asarray(compute_histogram(
            jnp.asarray(binned), q, num_bins=b))
        assert serial.dtype == np.int32

        mesh = make_mesh((8,), ("data",), jax.devices()[:8])

        def shard_fn(bb, vv):
            # per-shard rows quantized with the GLOBAL row offset:
            # identical ints to the serial pass, then an exact psum
            off = lax.axis_index("data") * (n // 8)
            qq = quantize_stack(vv, scales, spec, 4, off)
            return lax.psum(compute_histogram(bb, qq, num_bins=b),
                            "data")

        fn = jax.jit(jax.shard_map(
            shard_fn, mesh=mesh, in_specs=(P("data"), P("data")),
            out_specs=P(), check_vma=False))
        sharded = np.asarray(fn(binned, vals))
        np.testing.assert_array_equal(serial, sharded)

    def test_dp_trains_serial_structure(self):
        import jax
        if len(jax.devices()) < 8:
            pytest.skip("needs the 8-device CPU mesh")
        p = dict(BASE, quant_train=True)
        ser = _train(p)
        dp = _train(dict(p, tree_learner="data"))
        for a, b in zip(ser.dump_model()["tree_info"],
                        dp.dump_model()["tree_info"]):
            sa, sb = a["tree_structure"], b["tree_structure"]
            assert sa.get("split_feature") == sb.get("split_feature")
            assert sa.get("threshold") == sb.get("threshold")
        np.testing.assert_allclose(ser.predict(X), dp.predict(X),
                                   rtol=1e-5, atol=1e-6)

    def test_fused_chunk_matches_per_iteration(self):
        """The fused lax.scan path quantizes with the same in-graph
        scales and iteration keys — byte-identical trees."""
        p = dict(BASE, objective="regression", quant_train=True)
        a = _train(p, y=YREG, rounds=4)
        b = _train(dict(p, fused_chunk=2), y=YREG, rounds=4)
        assert _strip_params(a.model_to_string()) \
            == _strip_params(b.model_to_string())

    def test_partitioned_matches_masked_structure(self):
        p = dict(BASE, quant_train=True)
        m = _train(p)
        pt = _train(dict(p, tpu_learner="partitioned"))
        for a, b in zip(m.dump_model()["tree_info"],
                        pt.dump_model()["tree_info"]):
            assert a["tree_structure"].get("split_feature") \
                == b["tree_structure"].get("split_feature")

    def test_voting_and_feature_learners_train(self):
        import jax
        if len(jax.devices()) < 8:
            pytest.skip("needs the 8-device CPU mesh")
        for tl in ("voting", "feature"):
            m = _train(dict(BASE, quant_train=True, tree_learner=tl),
                       rounds=2)
            assert m.num_trees() == 2
            assert _auc(YBIN, m.predict(X)) > 0.8
            if tl == "voting":
                # the scale pmax is recorded under the VOTING learner's
                # own label, not misattributed to dp
                sites = {s.site for s in m._model.grower.comm.sites()}
                assert "voting.quant_scale" in sites
                assert "dp.quant_scale" not in sites

    def test_int32_accumulator_overflow_refused(self):
        """rows * qmax must stay under 2^31 (a degenerate feature can
        put every row in ONE bin, wrapping the int32 histogram
        silently): quant_bits=16 is refused past ~65k rows with an
        actionable error; the same rows fit easily under quant_bits=8
        (bound ~16.9M)."""
        n = 66_000                       # > (2^31-1) // 32767 == 65538
        xb = _rs.randn(n, 2).astype(np.float32)
        yb = (xb[:, 0] > 0).astype(np.float32)
        p = dict(BASE, quant_train=True, quant_bits=16)
        with pytest.raises(ValueError, match="int32 histogram"):
            lgb.train(dict(p), lgb.Dataset(xb, label=yb, params=dict(p)),
                      num_boost_round=1)
        m = _train(dict(BASE, quant_train=True, quant_bits=8,
                        num_leaves=4), x=xb, y=yb, rounds=1)
        assert m.num_trees() == 1

    def test_sparse_storage_refused(self):
        sp = pytest.importorskip("scipy.sparse")
        dense = _rs.randn(400, 50)
        dense[_rs.rand(400, 50) > 0.04] = 0.0    # ~2 nnz/row, 50 cols
        xs = sp.csr_matrix(dense)
        y = (dense[:, 0] + 0.1 * _rs.randn(400) > 0).astype(np.float32)
        p = dict(BASE, quant_train=True, enable_sparse=True,
                 enable_bundle=False)
        with pytest.raises(ValueError, match="quant_train"):
            lgb.train(dict(p), lgb.Dataset(xs, label=y, params=dict(p)),
                      num_boost_round=1)


# ---------------------------------------------------------------------------
# crash+resume byte identity under quantized stochastic rounding
# ---------------------------------------------------------------------------

class TestQuantResume:
    def test_kill_and_resume_byte_identical(self, tmp_path):
        from lightgbm_tpu.utils import faultinject
        from lightgbm_tpu.utils.faultinject import InjectedKill
        out = str(tmp_path / "m.txt")
        p = dict(BASE, objective="regression", quant_train=True,
                 snapshot_freq=3, output_model=out)

        def ds():
            return lgb.Dataset(X, label=YREG, params=dict(p))

        straight = lgb.train(dict(p), ds(), num_boost_round=7)
        s_straight = straight.model_to_string()
        for f in glob.glob(out + "*"):
            os.unlink(f)
        faultinject.configure("snapshot_kill:4")
        try:
            with pytest.raises(InjectedKill):
                lgb.train(dict(p), ds(), num_boost_round=7)
        finally:
            faultinject.clear()
        resumed = lgb.train(dict(p, resume=True), ds(),
                            num_boost_round=7)
        # iteration-keyed rounding: the resumed run replays the exact
        # stochastic stream of the straight run
        assert _strip_params(resumed.model_to_string()) \
            == _strip_params(s_straight)


# ---------------------------------------------------------------------------
# the ledger-proven HBM cut + perf.* instrument + comm re-accounting
# ---------------------------------------------------------------------------

class TestLedgerAndPerfKeys:
    def test_hist_hbm_bytes_drop_2x_and_intensity_rises(self):
        """The acceptance criterion: >= 2x lower perf.hist.hbm_bytes
        for quant_bits=8 vs f32 at identical shapes, with intensity
        rising accordingly (narrow feature count: the vals stream is
        the dominant histogram read there)."""
        from lightgbm_tpu.obs.flops import FlopLedger
        n, f, b = 1_000_000, 4, 63
        led8 = FlopLedger.for_training(n, f, b, vals_itemsize=1,
                                       quant=True)
        led16 = FlopLedger.for_training(n, f, b, vals_itemsize=2,
                                        quant=True)
        led32 = FlopLedger.for_training(n, f, b)
        s8 = {s.site: s for s in led8.sites()}
        s16 = {s.site: s for s in led16.sites()}
        s32 = {s.site: s for s in led32.sites()}
        assert s32["hist"].hbm_bytes >= 2 * s8["hist"].hbm_bytes
        assert s32["hist"].hbm_bytes > s16["hist"].hbm_bytes
        # FLOPs unchanged -> intensity rises by the byte ratio
        assert s8["hist"].flops == s32["hist"].flops
        i8 = s8["hist"].flops / s8["hist"].hbm_bytes
        i32 = s32["hist"].flops / s32["hist"].hbm_bytes
        assert i8 >= 2 * i32
        # the new sites exist only under quant
        assert "quantize" in s8 and "dequant" in s8
        assert "quantize" not in s32 and "dequant" not in s32

    def test_perf_hist_keys_show_the_bound(self):
        """perf.hist.* in the telemetry snapshot (per-site roofline
        join, obs/attrib.py): quant halves-or-better the histogram
        bytes vs an f32 run of the SAME narrow shape."""
        # enough rows that the per-pass vals read dominates the [F,B,3]
        # histogram write in the byte formula (as it does at real scale)
        xn = _rs.randn(4000, 4)
        yn = (2.0 * xn[:, 0] - xn[:, 1]
              + 0.1 * _rs.randn(4000)).astype(np.float32)

        def snap_for(extra):
            # pinned peaks put the ridge point (150 FLOP/byte) between
            # the f32 (~92) and int8 (~198) histogram intensities, so
            # the roofline verdict itself must flip memory -> compute
            p = dict(BASE, objective="regression", telemetry=True,
                     telemetry_peak_flops=1.5e13,
                     telemetry_peak_hbm_gbs=100.0, **extra)
            m = _train(p, x=xn, y=yn, rounds=2)
            return m.telemetry_snapshot()

        s_f32 = snap_for({})
        s_q8 = snap_for({"quant_train": True})
        assert s_q8["perf.hist.hbm_bytes"] * 2 \
            <= s_f32["perf.hist.hbm_bytes"]
        assert s_q8["perf.hist.intensity_flops_per_byte"] \
            >= 2 * s_f32["perf.hist.intensity_flops_per_byte"]
        assert s_f32["perf.hist.bound"] == "memory"
        assert s_q8["perf.hist.bound"] == "compute"   # the bound moved
        assert "perf.quantize.flops" in s_q8
        assert "perf.dequant.flops" in s_q8

    def test_dp_comm_ledger_reaccounts_quant(self):
        """The owner-shard reduce-scatter payload is recorded at its
        true int32 width (4-byte lanes — half the reference's f64
        ReduceScatter format), and the quant-scale pmax site appears."""
        import jax
        if len(jax.devices()) < 8:
            pytest.skip("needs the 8-device CPU mesh")
        from lightgbm_tpu.obs.comm import dp_hist_bytes_per_iter
        m = _train(dict(BASE, quant_train=True, tree_learner="data"),
                   rounds=2)
        comm = m._model.grower.comm
        sites = {s.site: s for s in comm.sites()}
        assert "dp.quant_scale" in sites
        assert sites["dp.quant_scale"].collective == "pmax"
        assert sites["dp.quant_scale"].payload_bytes == 3 * 4
        plan = m._model.grower.plan
        hr = sites["dp.hist_reduce"]
        # [n_shards * chunk, B, 3] int32
        assert hr.payload_bytes == 8 * plan.chunk * 31 * 3 * 4
        assert hr.wire_bytes == dp_hist_bytes_per_iter(
            8, plan.chunk, 31, n_steps=1, itemsize=4) \
            // 1  # one step

    def test_block_rows_scale_with_vals_width(self):
        """Satellite: hist_block_rows sizes the row block by the actual
        vals dtype width — int8 packs get 4x the f32 block (until the
        global cap)."""
        from lightgbm_tpu.ops.histogram import (HIST_BLOCK_ROWS,
                                                hist_block_rows)
        f, bp = 968, 256
        b4 = hist_block_rows(f, bp, 4)
        b1 = hist_block_rows(f, bp, 1)
        assert b1 >= 2 * b4           # wide shape: budget-bound
        assert b1 == min(4 * b4, HIST_BLOCK_ROWS) or b1 >= 2 * b4
        # narrow shapes stay at the measured cap either way
        assert hist_block_rows(28, 64, 1) == HIST_BLOCK_ROWS
        assert hist_block_rows(28, 64, 4) == HIST_BLOCK_ROWS
