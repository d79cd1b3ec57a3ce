"""Survivable out-of-core ingest (lightgbm_tpu/ingest.py) + the sketch
binning substrate (binning.QuantileSketch) + dist_data payload framing.

Pinned contracts:

- While a sketch never compacts (distinct values <= capacity) the
  sketch-fitted bin bounds are BYTE-IDENTICAL to in-memory FindBin over
  the same rows, and streaming-ingest training is byte-identical to
  in-memory training (the dense small-bin regime of docs/Ingest.md).
- After compaction each greedy boundary's rank displacement is bounded
  by 2*n*compactions/capacity (the documented sketch epsilon).
- A loader killed between chunk commits resumes from the manifests and
  trains a byte-identical model vs an uninterrupted run.
- Transient read errors retry; corrupt chunks quarantine per
  ``ingest_bad_chunk``; a hung reader classifies as
  ``ElasticFailure("ingest")`` within the deadline; a torn allgather
  payload raises a classified PayloadIntegrityError, never raw
  unpickle behavior.

All fault specs go through ``faultinject.configure`` and are cleared by
the autouse fixture.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import ingest as ing
from lightgbm_tpu.binning import BinMapper, QuantileSketch
from lightgbm_tpu.config import Config
from lightgbm_tpu.data_io import load_text, parse_csv_block
from lightgbm_tpu.parallel import dist_data, elastic
from lightgbm_tpu.utils import faultinject
from lightgbm_tpu.utils.faultinject import InjectedKill

_WORKER = os.path.join(os.path.dirname(__file__), "ingest_worker.py")


@pytest.fixture(autouse=True)
def _clean_faults():
    faultinject.clear()
    ing.reset_metrics()
    yield
    faultinject.clear()


def _write_csv(path, x, y, fmt="%.6g"):
    with open(path, "w", encoding="utf-8") as f:
        for i in range(len(x)):
            f.write(",".join([f"{y[i]:g}"]
                             + [fmt % v for v in x[i]]) + "\n")


def _toy(n=1200, f=5, seed=3, decimals=None):
    rs = np.random.RandomState(seed)
    x = rs.randn(n, f)
    x[::9, 1] = 0.0
    if decimals is not None:
        x = np.round(x, decimals)
    y = (x[:, 0] + 0.25 * rs.randn(n) > 0).astype(np.float64)
    return x, y


_PARAMS = {"objective": "binary", "num_leaves": 8, "max_bin": 31,
           "min_data_in_leaf": 5, "verbosity": -1,
           "ingest_chunk_rows": 200}


# ---------------------------------------------------------------------------
# QuantileSketch contracts
# ---------------------------------------------------------------------------

class TestSketch:
    def test_lossless_exact_vs_findbin(self):
        x, _ = _toy(n=3000)
        col = x[:, 0].copy()
        col[::11] = np.nan
        sk = QuantileSketch(4096)
        for i in range(0, len(col), 500):
            sk.update(col[i:i + 500])
        assert sk.compactions == 0
        exact = BinMapper()
        exact.find_bin(col, len(col), 255, 3)
        got = BinMapper()
        got.find_bin_from_sketch(sk, 255, 3)
        assert np.array_equal(exact.bin_upper_bound, got.bin_upper_bound)
        for attr in ("num_bin", "missing_type", "default_bin",
                     "most_freq_bin", "sparse_rate", "bin0_frac",
                     "is_trivial"):
            assert getattr(exact, attr) == getattr(got, attr), attr

    def test_compacted_rank_displacement_bound(self):
        rng = np.random.RandomState(7)
        n, cap = 30000, 512
        col = rng.lognormal(size=n)
        sk = QuantileSketch(cap)
        for i in range(0, n, 3000):
            sk.update(col[i:i + 3000])
        assert sk.compactions > 0
        exact = BinMapper()
        exact.find_bin(col, n, 63, 3)
        got = BinMapper()
        got.find_bin_from_sketch(sk, 63, 3)
        xs = np.sort(col)
        k = min(exact.num_bin, got.num_bin) - 1
        r_exact = np.searchsorted(xs, exact.bin_upper_bound[:k])
        r_got = np.searchsorted(xs, got.bin_upper_bound[:k])
        disp = int(np.abs(r_exact - r_got).max())
        # the documented epsilon (docs/Ingest.md): 2n/capacity rows per
        # compaction generation
        assert disp <= 2 * n * sk.compactions / cap

    def test_merge_equals_one_shot_and_is_deterministic(self):
        x, _ = _toy(n=4000)
        col = np.round(x[:, 2], 2)        # dense: stays lossless
        whole = QuantileSketch(2048).update(col)
        parts = [QuantileSketch(2048).update(c)
                 for c in np.array_split(col, 7)]
        merged = QuantileSketch(2048)
        for p in parts:
            merged.merge(p)
        assert np.array_equal(whole.values, merged.values)
        assert np.array_equal(whole.counts, merged.counts)
        assert whole.n == merged.n
        # deterministic under repetition (the fleet-wide rank-order
        # merge must be byte-stable)
        merged2 = QuantileSketch(2048)
        for p in parts:
            merged2.merge(p)
        assert np.array_equal(merged.values, merged2.values)
        assert np.array_equal(merged.counts, merged2.counts)

    def test_state_roundtrip_and_version_gate(self):
        sk = QuantileSketch(64).update(np.arange(200, dtype=np.float64))
        st = sk.to_state()
        back = QuantileSketch.from_state(st)
        assert np.array_equal(back.values, sk.values)
        assert back.compactions == sk.compactions
        st["version"] = 99
        with pytest.raises(ValueError, match="version"):
            QuantileSketch.from_state(st)

    def test_categorical_never_compacts(self):
        cats = np.repeat(np.arange(500, dtype=np.float64), 3)
        sk = QuantileSketch(64, categorical=True).update(cats)
        assert sk.compactions == 0
        uniq, counts = sk.categorical_counts()
        assert len(uniq) == 500 and counts.sum() == 1500


# ---------------------------------------------------------------------------
# Streaming ingest end-to-end
# ---------------------------------------------------------------------------

class TestIngestE2E:
    def test_dense_regime_byte_identical_model(self, tmp_path):
        x, y = _toy(decimals=1)
        path = str(tmp_path / "train.csv")
        _write_csv(path, x, y, fmt="%.1f")
        ds = lgb.ingest_dataset(path, _PARAMS)
        bst = lgb.train(_PARAMS, ds, num_boost_round=6)
        x2, y2 = load_text(path)
        bst2 = lgb.train(_PARAMS, lgb.Dataset(x2, label=y2,
                                              params=_PARAMS),
                         num_boost_round=6)
        assert bst.model_to_string() == bst2.model_to_string()
        assert ds.ingest_report["dropped_rows"] == 0
        snap = ing.metrics_snapshot()
        assert snap["ingest.chunks{outcome=ok}"]["value"] == 6

    def test_directory_of_chunks_source(self, tmp_path):
        x, y = _toy(n=900, decimals=1)
        d = tmp_path / "shards"
        d.mkdir()
        for i, (xc, yc) in enumerate(zip(np.array_split(x, 3),
                                         np.array_split(y, 3))):
            _write_csv(str(d / f"part-{i:03d}.csv"), xc, yc, fmt="%.1f")
        ds = lgb.Dataset.from_ingest(str(d), _PARAMS)
        bst = lgb.train(_PARAMS, ds, num_boost_round=4)
        x2, y2 = load_text(str(d / "part-000.csv"))
        assert bst.num_trees() == 4
        assert ds.ingest_report["num_rows"] == 900
        assert x2.shape[1] == x.shape[1]

    def test_in_process_resume_after_kill(self, tmp_path):
        x, y = _toy(decimals=1)
        path = str(tmp_path / "train.csv")
        _write_csv(path, x, y, fmt="%.1f")
        spool = str(tmp_path / "spool")
        # die at the 4th chunk read: 3 chunks committed manifest-last
        faultinject.configure("ingest_read:4:kill")
        with pytest.raises(InjectedKill):
            lgb.ingest_dataset(path, _PARAMS, spool_dir=spool)
        committed = [f for f in os.listdir(spool)
                     if f.endswith(".manifest.json")]
        assert len(committed) == 3
        faultinject.clear()
        ds = lgb.ingest_dataset(path, _PARAMS, spool_dir=spool)
        assert ds.ingest_report["resumed_chunks"] == 3
        bst = lgb.train(_PARAMS, ds, num_boost_round=5)
        clean = lgb.ingest_dataset(path, _PARAMS,
                                   spool_dir=str(tmp_path / "spool2"))
        bst2 = lgb.train(_PARAMS, clean, num_boost_round=5)
        assert bst.model_to_string() == bst2.model_to_string()

    def test_bounded_residency_one_chunk_in_flight(self, tmp_path):
        # the bounded-memory contract, structurally: however many chunks
        # the spool holds, the sequence keeps at most ONE decoded — RSS
        # cannot scale with chunk count
        x, y = _toy(n=2000, decimals=1)
        path = str(tmp_path / "train.csv")
        _write_csv(path, x, y, fmt="%.1f")
        res = ing.IngestRunner(
            path, Config(dict(_PARAMS, ingest_chunk_rows=100))).run()
        seq = res.sequence
        assert len(seq._meta) == 20
        for gidx in (0, 150, 1999, 42):
            seq[gidx]
            assert seq._cache is not None
            assert len(seq._cache[0]) == 100     # one chunk, not the file
        # a cross-chunk slice still leaves a single chunk resident
        seq[180:220]
        assert len(seq._cache[0]) == 100

    def test_plan_change_invalidates_spool(self, tmp_path):
        x, y = _toy(n=600, decimals=1)
        path = str(tmp_path / "train.csv")
        _write_csv(path, x, y, fmt="%.1f")
        spool = str(tmp_path / "spool")
        lgb.ingest_dataset(path, _PARAMS, spool_dir=spool)
        p2 = dict(_PARAMS, ingest_chunk_rows=100)
        ds = lgb.ingest_dataset(path, p2, spool_dir=spool)
        # different chunking cuts different byte spans: nothing resumes
        assert ds.ingest_report["resumed_chunks"] == 0


# ---------------------------------------------------------------------------
# Failure policy: retry / quarantine / hang
# ---------------------------------------------------------------------------

class TestIngestFaults:
    def test_transient_read_error_retries(self, tmp_path):
        x, y = _toy(n=600, decimals=1)
        path = str(tmp_path / "train.csv")
        _write_csv(path, x, y, fmt="%.1f")
        faultinject.configure("ingest_read:2")   # 2nd read raises once
        ds = lgb.ingest_dataset(path, dict(_PARAMS, ingest_retries=2,
                                           ingest_retry_backoff_s=0.01),
                                spool_dir=str(tmp_path / "s"))
        assert ds.ingest_report["num_rows"] == 600
        assert ds.ingest_report["dropped_rows"] == 0
        snap = ing.metrics_snapshot()
        assert snap["ingest.retries"]["value"] >= 1

    def test_retry_exhaustion_classifies_as_elastic_ingest(self, tmp_path):
        x, y = _toy(n=600, decimals=1)
        path = str(tmp_path / "train.csv")
        _write_csv(path, x, y, fmt="%.1f")
        faultinject.configure("ingest_read:1-")   # every read fails
        with pytest.raises(elastic.ElasticFailure) as ei:
            lgb.ingest_dataset(path, dict(_PARAMS, ingest_retries=1,
                                          ingest_retry_backoff_s=0.01),
                               spool_dir=str(tmp_path / "s"))
        assert ei.value.kind == "ingest"
        assert elastic.failure_kind(ei.value) == "ingest"

    def test_corrupt_chunk_raise_policy(self, tmp_path):
        x, y = _toy(n=600, decimals=1)
        path = str(tmp_path / "train.csv")
        _write_csv(path, x, y, fmt="%.1f")
        faultinject.configure("ingest_checksum:2")
        with pytest.raises(ing.ChunkCorrupt):
            lgb.ingest_dataset(path, _PARAMS,
                               spool_dir=str(tmp_path / "s"))

    def test_corrupt_chunk_skip_policy_accounts_dropped_rows(
            self, tmp_path):
        x, y = _toy(n=600, decimals=1)
        path = str(tmp_path / "train.csv")
        _write_csv(path, x, y, fmt="%.1f")
        spool = str(tmp_path / "s")
        faultinject.configure("ingest_checksum:2")
        ds = lgb.ingest_dataset(path, dict(_PARAMS,
                                           ingest_bad_chunk="skip"),
                                spool_dir=spool)
        rep = ds.ingest_report
        assert rep["dropped_rows"] == 200          # one full chunk
        assert rep["num_rows"] == 400
        assert len(rep["quarantined"]) == 1
        assert rep["quarantined"][0]["index"] == 1
        qdir = os.path.join(spool, "quarantine")
        assert os.path.exists(
            os.path.join(qdir, "chunk_000001.json"))
        with open(os.path.join(qdir, "chunk_000001.json"),
                  encoding="utf-8") as f:
            assert "injected fault" in json.load(f)["reason"]
        # the degraded dataset still trains
        bst = lgb.train(_PARAMS, ds, num_boost_round=3)
        assert bst.num_trees() == 3

    def test_malformed_chunk_quarantines_not_retries(self, tmp_path):
        x, y = _toy(n=600, decimals=1)
        path = str(tmp_path / "train.csv")
        _write_csv(path, x, y, fmt="%.1f")
        with open(path, "a", encoding="utf-8") as f:
            f.write("1.0,not_a_number,0.1,0.2,0.3,0.4\n")
        with pytest.raises(ing.ChunkCorrupt, match="malformed"):
            lgb.ingest_dataset(path, _PARAMS,
                               spool_dir=str(tmp_path / "s"))

    def test_hang_classifies_within_deadline(self, tmp_path, monkeypatch):
        x, y = _toy(n=600, decimals=1)
        path = str(tmp_path / "train.csv")
        _write_csv(path, x, y, fmt="%.1f")
        monkeypatch.setenv(faultinject.HANG_ENV_VAR, "20")
        faultinject.configure("ingest_hang:1-")
        t0 = time.monotonic()
        with pytest.raises(elastic.ElasticFailure) as ei:
            lgb.ingest_dataset(
                path, dict(_PARAMS, ingest_read_timeout_s=0.5,
                           ingest_retries=1,
                           ingest_retry_backoff_s=0.01),
                spool_dir=str(tmp_path / "s"))
        wall = time.monotonic() - t0
        assert ei.value.kind == "ingest"
        # two 0.5 s deadlines + backoff, NOT the 20 s hang
        assert wall < 10.0


# ---------------------------------------------------------------------------
# kill -9 between chunk commits (subprocess, the real os._exit death)
# ---------------------------------------------------------------------------

class TestKillResume:
    def test_kill9_mid_ingest_resume_byte_identical(self, tmp_path):
        x, y = _toy(n=900, decimals=1)
        _write_csv(str(tmp_path / "train.csv"), x, y, fmt="%.1f")
        env = dict(os.environ, LGBM_TPU_FAULTS="ingest_read:4:exit")
        p = subprocess.run(
            [sys.executable, _WORKER, str(tmp_path), "spool", "dead"],
            env=env, capture_output=True, text=True, timeout=240)
        assert p.returncode == 23, p.stderr[-2000:]
        committed = [f for f in os.listdir(tmp_path / "spool")
                     if f.endswith(".manifest.json")]
        assert len(committed) == 3          # chunks 1-3 landed
        env.pop("LGBM_TPU_FAULTS")
        p2 = subprocess.run(
            [sys.executable, _WORKER, str(tmp_path), "spool", "resumed"],
            env=env, capture_output=True, text=True, timeout=240)
        assert p2.returncode == 0, p2.stderr[-2000:]
        assert "WORKER_DONE resumed=3" in p2.stdout
        p3 = subprocess.run(
            [sys.executable, _WORKER, str(tmp_path), "spool_clean",
             "clean"],
            env=env, capture_output=True, text=True, timeout=240)
        assert p3.returncode == 0, p3.stderr[-2000:]
        assert "WORKER_DONE resumed=0" in p3.stdout
        resumed = (tmp_path / "model_resumed.txt").read_text("utf-8")
        clean = (tmp_path / "model_clean.txt").read_text("utf-8")
        assert resumed == clean and len(resumed) > 100


# ---------------------------------------------------------------------------
# dist_data framing + sketch allgather
# ---------------------------------------------------------------------------

class TestDistFraming:
    def test_frame_roundtrip(self):
        body = b"x" * 1000
        assert dist_data.unframe_payload(
            dist_data.frame_payload(body)) == body

    @pytest.mark.parametrize("mutate", [
        lambda b: b[:-3],                               # truncated body
        lambda b: b[:20],                               # truncated header
        lambda b: b"XXXX" + b[4:],                      # bad magic
        lambda b: b[:50] + bytes([b[50] ^ 0xFF]) + b[51:],  # bit flip
        lambda b: b[:4] + (9).to_bytes(2, "little") + b[6:],  # version
    ])
    def test_tamper_raises_classified(self, mutate):
        blob = mutate(dist_data.frame_payload(b"payload" * 100))
        with pytest.raises(dist_data.PayloadIntegrityError) as ei:
            dist_data.unframe_payload(blob)
        # classifiable by the elastic ladder, not a crash
        assert elastic.failure_kind(ei.value) is not None

    def test_sketch_allgather_matches_in_memory_findbin(self):
        x, _ = _toy(n=2000, decimals=1)
        cfg = Config({"max_bin": 31, "min_data_in_leaf": 5})
        mappers = dist_data.distributed_bin_mappers(
            x, cfg, process_index=0, process_count=1,
            allgather=lambda b: [b])
        for f in range(x.shape[1]):
            exact = BinMapper()
            exact.find_bin(x[:, f], len(x), 31, cfg.min_data_in_bin,
                           min_split_data=cfg.min_data_in_leaf)
            assert np.array_equal(exact.bin_upper_bound,
                                  mappers[f].bin_upper_bound), f

    def test_wire_bytes_accounting(self):
        x, _ = _toy(n=500, decimals=1)
        cfg = Config({"max_bin": 31})
        dist_data.reset_wire_bytes()
        dist_data.distributed_bin_mappers(
            x, cfg, process_index=0, process_count=1,
            allgather=lambda b: [b])
        assert dist_data.wire_bytes_sent() > 0


# ---------------------------------------------------------------------------
# data_io hardening (satellite: BOM / CRLF / trailing delimiters)
# ---------------------------------------------------------------------------

class TestDataIOHardening:
    def _clean_and_dirty(self, tmp_path):
        rows = ["1,2.5,3", "0,1.5,4", "1,0.5,5"]
        clean = tmp_path / "clean.csv"
        clean.write_text("\n".join(rows) + "\n", encoding="utf-8")
        dirty = tmp_path / "dirty.csv"
        dirty.write_bytes(
            b"\xef\xbb\xbf" + "\r\n".join(r + "," for r in rows).encode()
            + b"\r\n")
        return str(clean), str(dirty)

    def test_bom_crlf_trailing_delim_parse_identically(self, tmp_path):
        clean, dirty = self._clean_and_dirty(tmp_path)
        xc, yc = load_text(clean)
        xd, yd = load_text(dirty)
        assert np.array_equal(xc, xd) and np.array_equal(yc, yd)

    def test_malformed_line_reports_path_and_lineno(self, tmp_path):
        p = tmp_path / "bad.csv"
        p.write_text("1,2,3\n1,zap,3\n", encoding="utf-8")
        with pytest.raises(ValueError, match=r"bad\.csv:2.*'zap'"):
            load_text(str(p))

    def test_width_drift_reports_lineno(self, tmp_path):
        with pytest.raises(ValueError, match=r"w\.csv:3"):
            parse_csv_block(["1,2", "3,4", "5,6,7"], ",",
                            path="w.csv")

    def test_empty_fields_are_nan(self):
        out = parse_csv_block(["1,,3"], ",")
        assert np.isnan(out[0, 1]) and out[0, 2] == 3.0

    def test_libsvm_malformed_reports_lineno(self, tmp_path):
        p = tmp_path / "bad.svm"
        p.write_text("1 0:1.5 1:2.0\n0 0:x\n", encoding="utf-8")
        with pytest.raises(ValueError, match=r"bad\.svm:2"):
            load_text(str(p), fmt="libsvm")

    def test_libsvm_ingest_matches_load_text(self, tmp_path):
        rng = np.random.RandomState(5)
        lines = []
        for i in range(400):
            feats = sorted(rng.choice(8, size=4, replace=False))
            lines.append(f"{i % 2} " + " ".join(
                f"{k}:{round(float(rng.randn()), 1)}" for k in feats))
        p = tmp_path / "t.svm"
        p.write_text("\n".join(lines) + "\n", encoding="utf-8")
        ds = lgb.ingest_dataset(str(p), dict(_PARAMS,
                                             ingest_chunk_rows=150),
                                spool_dir=str(tmp_path / "s"))
        x2, y2 = load_text(str(p), fmt="libsvm")
        assert ds.ingest_report["num_rows"] == 400
        assert ds.ingest_report["num_features"] == x2.shape[1]
        bst = lgb.train(_PARAMS, ds, num_boost_round=3)
        bst2 = lgb.train(_PARAMS, lgb.Dataset(x2, label=y2,
                                              params=_PARAMS),
                         num_boost_round=3)
        assert bst.model_to_string() == bst2.model_to_string()
