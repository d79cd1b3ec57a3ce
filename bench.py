"""Benchmark: HIGGS-shaped binary classification training throughput.

Mirrors the reference's headline experiment (docs/Experiments.rst: HIGGS,
500 iterations, num_leaves=255 -> 130.094 s on 2x E5-2690v4, i.e. 3.843
iters/s; GPU docs recommend 63 bins for accelerator runs,
docs/GPU-Performance.rst:108-124).

Primary metric: steady-state iters/s on a 1M-row slice at 31 leaves /
63 bins; ``vs_baseline`` is against the reference's full-size 3.843
iters/s.  ``extra`` carries the baseline-shaped points: strict leaf-wise
growth, a 255-leaf run (the baseline's own tree shape), a 10M-row
scaling point, and an Epsilon-shaped wide point (400k x 2000 dense,
GPU-Performance.rst:63).

One process on ``jax.devices()``: a chip belongs to one process at a
time, so nothing is spawned.  The run exits non-zero when the platform
is not ``tpu`` (a CPU timing is not a speed number) or when any point
raised; the points that did land are still merged into the ONE final
JSON line {"metric", "value", "unit", "vs_baseline"[, "extra"]}, and each
point is mirrored to stderr the moment it lands.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np

BASELINE_IPS = 500.0 / 130.094  # reference HIGGS CPU (Experiments.rst:113)
METRIC = "higgs1m_binary_train_iters_per_sec"
N_ROWS, N_FEAT = 1_000_000, 28
PRIMARY_LEAVES, PRIMARY_MAX_BIN = 31, 63
PRIMARY_PADDED_BIN = 64          # ops/histogram.py pads the bin axis to 64
_DIR = os.path.dirname(os.path.abspath(__file__))

# FLOP accounting and the per-device peak table live in the library
# (obs/flops.py formulas + obs/attrib.py PEAKS — the measurement
# substrate telemetry, serving and this bench all share).

_POINTS = []          # every measured point of this run, in order
_FAILED = []          # names of the points that raised
_PROVENANCE = None


def _provenance():
    """Self-describing point metadata (device, library versions, host,
    git sha) so bench records can be compared by tools/bench_diff.py
    without external context."""
    global _PROVENANCE
    if _PROVENANCE is None:
        import platform
        from importlib import metadata as _md

        import jax
        import jaxlib
        devs = jax.devices()
        prov = {"hostname": platform.node(),
                "py": platform.python_version(),
                "jax": jax.__version__, "jaxlib": jaxlib.__version__,
                "platform": devs[0].platform,
                "device_kind": devs[0].device_kind,
                "device_count": len(devs)}
        try:
            prov["libtpu"] = _md.version("libtpu")
        except _md.PackageNotFoundError:
            pass
        try:
            out = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                                 capture_output=True, text=True, cwd=_DIR,
                                 timeout=10)
            if out.returncode == 0:
                prov["git_sha"] = out.stdout.strip()
        except (OSError, subprocess.SubprocessError):
            pass
        _PROVENANCE = prov
    return dict(_PROVENANCE)


def _record_point(name, **kv):
    """Keep one measured point and mirror it to stderr the moment it
    lands.  Every point carries its provenance (device + versions + git
    sha) so the record is self-describing for tools/bench_diff.py."""
    rec = {"point": name, "t": time.strftime("%Y-%m-%dT%H:%M:%S"),
           "prov": _provenance(), **kv}
    _POINTS.append(rec)
    print(f"[bench] point {rec}", file=sys.stderr, flush=True)


def _point_failed(name, exc):
    """A point that raised: recorded, and the run will exit non-zero."""
    _FAILED.append(name)
    _record_point(name, error=f"{type(exc).__name__}: {exc}"[:200])


def make_higgs_like(n: int, f: int, seed: int = 0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, f).astype(np.float32)
    logit = (1.2 * x[:, 0] - 0.8 * x[:, 1] + 0.6 * x[:, 2] * x[:, 3]
             + 0.4 * np.abs(x[:, 4]) + 0.5 * rng.randn(n))
    y = (logit > 0).astype(np.float32)
    return x, y


def make_epsilon_like(n: int, f: int, seed: int = 3):
    """Epsilon-shaped wide dense data (400k x 2000), generated in f32
    row-chunks so the host never holds an f64 copy (~6.4 GB)."""
    rng = np.random.RandomState(seed)
    x = np.empty((n, f), dtype=np.float32)
    chunk = max(1, 50_000_000 // f)
    for lo in range(0, n, chunk):
        hi = min(n, lo + chunk)
        x[lo:hi] = rng.standard_normal((hi - lo, f)).astype(np.float32)
    w = rng.standard_normal(16).astype(np.float32)
    logit = x[:, :16] @ w + 0.5 * rng.standard_normal(n).astype(np.float32)
    y = (logit > 0).astype(np.float32)
    return x, y


def _train_point(lgb, x, y, num_leaves, chunk, n_chunks, tag, ds=None,
                 split_batch=0, max_bin=PRIMARY_MAX_BIN):
    """Train one config; returns (ips, auc, ds, steps) steady-state over
    n_chunks fused chunks (or per-iter updates when fusion is
    unavailable).  ``steps`` is the per-tree grower loop count
    (super-steps for split_batch>1) from the last chunk.  Pass ``ds`` to
    reuse an already-binned dataset (num_leaves is a Booster param;
    binning is identical across points on the same data).
    split_batch: 0 = config auto (strict below 64 leaves, batched above),
    explicit K pins the grower's super-step width (grower.py).

    The returned ``stats`` dict carries the first-class compile
    metrics (ROADMAP item 4): ``compile_s`` — wall time of the first
    chunk/iteration including XLA trace+compile (warm-started by the
    persistent cache when enabled), ``trace_count`` — library jit
    traces this point added, and the process compile/cache counters
    delta (utils/compile_cache.py)."""
    from lightgbm_tpu.utils.compile_cache import compile_stats, trace_total
    params = {
        "objective": "binary", "num_leaves": num_leaves,
        "learning_rate": 0.1, "max_bin": max_bin,
        "min_data_in_leaf": 20, "verbosity": 0,
        "split_batch": split_batch,
    }
    t0 = time.time()
    if ds is None:
        ds = lgb.Dataset(x, label=y, params=params)
        ds.construct()
    t_bin = time.time() - t0

    traces0, cs0 = trace_total(), compile_stats()
    bst = lgb.Booster(params=dict(params, fused_chunk=chunk),
                      train_set=ds)
    m = bst._model
    fused = m.supports_fused() and chunk > 1

    t0 = time.time()
    if fused:
        m.train_chunk(chunk)          # includes XLA compile
    else:
        bst.update()
    np.asarray(m.score)
    t_compile = time.time() - t0

    t0 = time.time()
    start_iter = m.iter_
    if fused:
        for _ in range(n_chunks):
            if m.train_chunk(chunk):
                break                 # no-split stop: count only real iters
    else:
        for _ in range(n_chunks * chunk):
            if bst.update():
                break
    np.asarray(m.score)               # hard sync
    dt = time.time() - t0
    iters = m.iter_ - start_iter
    ips = iters / max(dt, 1e-9)
    cs1 = compile_stats()
    stats = {
        "compile_s": round(t_compile, 2),
        "trace_count": trace_total() - traces0,
        "backend_compiles": cs1["count"] - cs0["count"],
        "compile_cache_hits": cs1["cache_hits"] - cs0["cache_hits"],
    }
    if not fused:
        # provenance: WHY this point measured the per-iteration path
        # (GBDTModel.fused_reasons — specific blockers, never a guess)
        stats["fused_reasons"] = "; ".join(m.fused_reasons())[:200]

    from lightgbm_tpu.metrics import _auc
    auc = _auc(y, np.asarray(m.train_score())[:, 0], None)
    steps = m.step_counts[-min(len(m.step_counts), 8):]
    print(f"[bench] {tag}: bin={t_bin:.1f}s compile+warm={t_compile:.1f}s "
          f"(traces={stats['trace_count']}, "
          f"cache_hits={stats['compile_cache_hits']}) "
          f"steady={dt:.1f}s/{iters} iters -> {ips:.3f} iters/s "
          f"(train-AUC={auc:.4f}, fused={fused}, steps/tree={steps[-1] if steps else '?'})",
          file=sys.stderr, flush=True)
    return ips, auc, ds, steps, stats


def run_primary(lgb, devs, x, y) -> dict:
    """The primary measurement; returns the metric record."""
    n = N_ROWS

    # primary: 1M x 28, 31 leaves, 8-way batched super-steps (the
    # framework's fast growth mode; AUC reported alongside so quality is
    # auditable against the strict point below)
    ips1, auc1, ds1, steps1, stats1 = _train_point(
        lgb, x, y, num_leaves=PRIMARY_LEAVES,
        chunk=25, n_chunks=4, tag="1M/31leaf/sb8", split_batch=8)
    rec = {
        "metric": METRIC,
        "value": round(ips1, 3),
        "unit": ("iters/s (1M rows x 28 feat, 31 leaves, 63 bins, "
                 "split_batch=8)"),
        "vs_baseline": round(ips1 / BASELINE_IPS, 3),
    }
    # roofline attribution from the library ledger (obs/flops.py /
    # obs/attrib.py — the same formulas telemetry_snapshot uses):
    # achieved histogram FLOP/s, MFU against the claimed device's peak,
    # and the static per-phase FLOP share — first-class in the point
    from lightgbm_tpu.obs.attrib import device_peaks
    from lightgbm_tpu.obs.flops import (FlopLedger,
                                        train_hist_flops_per_iter)
    achieved = train_hist_flops_per_iter(
        n, N_FEAT, PRIMARY_MAX_BIN, PRIMARY_LEAVES) * ips1
    peak, _bw = device_peaks(devs)
    mfu = round(achieved / peak, 4) if peak else None
    share = FlopLedger.for_training(
        n, N_FEAT, PRIMARY_MAX_BIN, split_batch=8).flop_share(
        steps1[-1] if steps1 else PRIMARY_LEAVES - 1)
    _record_point("primary", auc=round(float(auc1), 4),
                  steps_per_tree=steps1[-1] if steps1 else None,
                  hist_tflops=round(achieved / 1e12, 3), mfu=mfu,
                  flop_share=share, **stats1, **rec)
    print(f"[bench] primary {ips1:.2f} iters/s train-AUC={auc1:.4f} "
          f"hist~{achieved / 1e12:.2f} TFLOP/s "
          f"(MFU~{f'{mfu:.1%}' if mfu is not None else 'n/a'} of "
          f"{devs[0].device_kind})", file=sys.stderr, flush=True)

    # strict leaf-wise growth (split_batch=1): the AUC quality anchor
    # for the batched primary above
    try:
        ips0, auc0, _, _, st0 = _train_point(lgb, x, y,
                                             num_leaves=PRIMARY_LEAVES,
                                             chunk=25, n_chunks=2,
                                             tag="1M/31leaf/strict",
                                             ds=ds1, split_batch=1)
        _record_point("higgs1m_31leaf_strict", value=round(ips0, 3),
                      auc=round(float(auc0), 4), **st0)
    except Exception as e:
        _point_failed("higgs1m_31leaf_strict", e)
    return rec


def run_extras(lgb, devs, x, y) -> None:
    """The non-primary points, each recorded as it lands.  A point that
    raises is recorded with its error and fails the run at the end; the
    points after it still run."""
    n = N_ROWS

    # the baseline's own 255-leaf tree shape (VERDICT r2 task 3a; the
    # vs_baseline that matters most — 3.843 iters/s IS this shape).
    # auto split_batch=16 -> M=3K=48 of the MXU's 128 rows; the achieved
    # histogram FLOP/s double as the MFU evidence for VERDICT r3 task 3.
    # steps_per_tree is the while-loop super-step count: ~16-20 for a
    # balanced 255-leaf tree at K=16 (vs 254 for the old static loop).
    ds2 = ips2 = None
    try:
        ips2, auc2, ds2, st2, cst2 = _train_point(
            lgb, x, y, num_leaves=255, chunk=4,
            n_chunks=2, tag="1M/255leaf")
        from lightgbm_tpu.obs.attrib import device_peaks
        from lightgbm_tpu.obs.flops import (FlopLedger,
                                            train_hist_flops_per_iter)
        flops = train_hist_flops_per_iter(
            n, N_FEAT, PRIMARY_MAX_BIN, 255) * ips2
        peak, _bw = device_peaks(devs)
        share255 = FlopLedger.for_training(
            n, N_FEAT, PRIMARY_MAX_BIN, split_batch=16).flop_share(
            st2[-1] if st2 else 254)
        _record_point("higgs1m_255leaf", value=round(ips2, 3),
                      auc=round(float(auc2), 4),
                      steps_per_tree=st2[-1] if st2 else None,
                      vs_baseline=round(ips2 / BASELINE_IPS, 3),
                      hist_tflops=round(flops / 1e12, 2),
                      mfu=round(flops / peak, 4) if peak else None,
                      flop_share=share255,
                      **cst2)
    except Exception as e:
        _point_failed("higgs1m_255leaf", e)

    # Epsilon-shaped wide point (VERDICT r3 task 6: 400k x 2000 dense).
    # Runs BEFORE the slow strict point below so a timeout starves the
    # least important measurement, not this one.
    try:
        ne, fe = 400_000, 2000
        xe, ye = make_epsilon_like(ne, fe)
        ipse, auce, _, _, cste = _train_point(
            lgb, xe, ye, num_leaves=PRIMARY_LEAVES, chunk=4, n_chunks=2,
            tag=f"{ne//1000}k/{fe}f/31leaf", split_batch=8)
        _record_point("epsilon400k_2000f", value=round(ipse, 3),
                      shape=f"{ne}x{fe}", auc=round(float(auce), 4),
                      **cste)
        del xe, ye
    except Exception as e:
        _point_failed("epsilon400k_2000f", e)

    # strict (split_batch=1) 255-leaf on the same data: the measured
    # K=16-vs-1 super-step ratio — the empirical record for round 4's
    # two structural claims (while-loop growers + auto split_batch).
    # ~254 passes/tree makes this the slowest point; it runs last.
    if ds2 is not None:
        try:
            ips2s, _, _, st2s, cst2s = _train_point(
                lgb, x, y, num_leaves=255, chunk=2, n_chunks=1,
                tag="1M/255leaf/strict", ds=ds2, split_batch=1)
            _record_point("higgs1m_255leaf_strict", value=round(ips2s, 3),
                          steps_per_tree=st2s[-1] if st2s else None,
                          batched_over_strict=round(
                              ips2 / max(ips2s, 1e-9), 2), **cst2s)
        except Exception as e:
            _point_failed("higgs1m_255leaf_strict", e)

    # owner-shard dp histogram state (ISSUE 1 / VERDICT #63): per-shard
    # histogram bytes per leaf after the psum_scatter, vs the full-psum
    # replication — the memory shape tools/bench_hist.py --sharded times
    try:
        from lightgbm_tpu.parallel.mesh import owner_shard_plan
        pts = {}
        for wname, f in (("higgs28", 28), ("bosch968", 968),
                         ("allstate4228", 4228)):
            for s in (8, 16):
                plan = owner_shard_plan(np.arange(f), s)
                pts[f"{wname}_x{s}"] = plan.hist_bytes(1, 64)
            pts[f"{wname}_full"] = f * 64 * 3 * 4
        _record_point("dp_owner_shard_hist_bytes_per_leaf", **pts)
    except Exception as e:
        _point_failed("dp_owner_shard_hist_bytes_per_leaf", e)

    # serving microbench (ISSUE 4 / tools/bench_serve.py): in-process
    # serve stack (micro-batcher + bucketed predictor engine) driven by
    # concurrent clients — rows/s and client-observed p50/p99 latency.
    # Keyed-payload point: the keys fold into extras as serve_rows_per_s
    # / serve_p99_ms etc.
    try:
        sys.path.insert(0, os.path.join(_DIR, "tools"))
        import bench_serve
        sp = bench_serve.run_bench(
            duration_s=4.0, clients=4, rows_per_request=64,
            n_train=50_000)
        _record_point("serve",
                      **{k: v for k, v in sp.items()
                         if k in ("rows_per_s", "p50_ms", "p99_ms",
                                  "requests", "batch_occupancy_mean",
                                  "compile_bound")})
    except Exception as e:
        _point_failed("serve", e)

    # fused device-resident serve path (ISSUE 10): the same drive with
    # serve_device_binning — one jitted bin/traverse/accumulate program,
    # one sync per batch.  Folds into extras as serve_device_rows_per_s
    # / serve_device_p99_ms, gated by tools/bench_diff.py next to the
    # host-accumulation numbers above
    try:
        import bench_serve
        spd = bench_serve.run_bench(
            duration_s=4.0, clients=4, rows_per_request=64,
            n_train=50_000, device_binning=True)
        _record_point("serve_device",
                      **{k: v for k, v in spd.items()
                         if k in ("rows_per_s", "p50_ms", "p99_ms",
                                  "requests", "batch_occupancy_mean",
                                  "compile_bound", "fused_batches",
                                  "host_fallback_batches",
                                  "table_bytes")})
    except Exception as e:
        _point_failed("serve_device", e)

    # continual-pipeline microbench (ISSUE 11, pipeline/continual.py):
    # two fault-free generations of the train->publish->gate->promote
    # loop against a live in-process serving registry under client
    # traffic.  The gated numbers are chunk-arrival-to-serving lag
    # (continual_freshness_lag_s) and mean wall time per generation
    # (continual_gen_s) — the freshness guarantee as a perf metric
    try:
        import soak_serve
        cr = soak_serve.run_continual_soak(
            duration_s=4.0, clients=2, generations=2,
            gate_failure=False)
        _record_point(
            "continual",
            freshness_lag_s=cr.get("freshness_lag_s"),
            gen_s=cr.get("gen_s"),
            published=(cr.get("freshness") or {}).get(
                "generations_published"),
            violations=len(cr.get("violations") or []))
    except Exception as e:
        _point_failed("continual", e)

    # quantized-training histogram sweep (ISSUE 13, ops/quantize.py):
    # f32 vs int8/int16 packed accumulands through the SHIPPED
    # contraction across split_batch slot widths K in {16,32,64}
    # (tools/bench_hist.run_quant_bench — ms/pass AND ms/leaf-slot per
    # width, plus the autotuner's chosen (K, block_rows) as
    # provenance), folded into extras as hist_quant_*.  Gated keys
    # (tools/perf_budget.txt): hist_hbm_bytes_per_iter — the static
    # ledger's histogram HBM bytes for ONE canonical 255-leaf K=16
    # iteration under quant_bits=8 (lower-better, the ledger-proven
    # cut of ISSUE 13) — and hist_ms_per_pass / hist_ms_per_leaf_wide
    # — the measured shipped-shape pass cost and the best wide-width
    # per-leaf cost (the MXU-widening win of ISSUE 15)
    try:
        sys.path.insert(0, os.path.join(_DIR, "tools"))
        import bench_hist
        qp = bench_hist.run_quant_bench(
            n_rows=500_000, reps=10)
        _record_point("hist_quant", **qp)
        from lightgbm_tpu.obs.flops import FlopLedger
        steps = -(-254 // 16)        # canonical 255-leaf K=16 iteration
        led_q8 = FlopLedger.for_training(
            n, N_FEAT, PRIMARY_MAX_BIN, split_batch=16,
            vals_itemsize=1, quant=True)
        led_f32 = FlopLedger.for_training(
            n, N_FEAT, PRIMARY_MAX_BIN, split_batch=16)
        site_q8 = {s.site: s for s in led_q8.sites()}
        site_f32 = {s.site: s for s in led_f32.sites()}
        wide = [v for k, v in qp.items()
                if k.startswith("qoff_k") and k.endswith("_ms_per_leaf")
                and not k.startswith("qoff_k16")]
        _record_point(
            "hist",
            hbm_bytes_per_iter=site_q8["hist"].hbm_bytes * steps
            + site_q8["hist_root"].hbm_bytes,
            hbm_bytes_per_iter_f32=site_f32["hist"].hbm_bytes * steps
            + site_f32["hist_root"].hbm_bytes,
            ms_per_pass=qp.get("qoff_k16_ms_per_pass"),
            ms_per_leaf_k16=qp.get("qoff_k16_ms_per_leaf"),
            ms_per_leaf_wide=min(wide) if wide else None,
            tuned_k=qp.get("tuned_k"),
            tuned_block_rows=qp.get("tuned_block_rows"))
    except Exception as e:
        _point_failed("hist_quant", e)

    # super-epoch sweep (ISSUE 16, tools/bench_fused.sweep): k in
    # {1, 8, 32} x {valid, novalid} end-to-end lgb.train runs — k=1 is
    # the per-iteration baseline — counting jax.device_get syncs during
    # the timed run.  Headline keys fold as superepoch_iters_per_s /
    # superepoch_sync_count_per_iter (the k=32 + one-valid + ES
    # acceptance shape, pinned in tools/perf_budget.txt: the sync count
    # is structural, 1/k, near-zero tolerance)
    try:
        sys.path.insert(0, os.path.join(_DIR, "tools"))
        import bench_fused
        sp = bench_fused.sweep(n_rows=400_000, ks=(1, 8, 32))
        _record_point("superepoch", **sp)
    except Exception as e:
        _point_failed("superepoch", e)

    # fleet sweep (ISSUE 19, tools/bench_fleet.run_bench): warm
    # aggregate iters/s of ONE vmapped N-member fleet_train vs N warm
    # sequential solo runs, N in {1, 4, 8, 16}.  The shape is the
    # fleet's home regime — a small-data hyperparameter sweep, where
    # per-epoch dispatch dominates and batching members into one
    # program wins.  Headline keys fold as fleet_agg_iters_per_s (the
    # N=8 vmapped aggregate, pinned in tools/perf_budget.txt) and
    # fleet_speedup_x8 (the >=2x acceptance ratio vs 8 solos)
    try:
        sys.path.insert(0, os.path.join(_DIR, "tools"))
        import bench_fleet
        fp = bench_fleet.run_bench(
            n_rows=500, rounds=32, sizes=(1, 4, 8, 16))
        _record_point("fleet", **fp)
    except Exception as e:
        _point_failed("fleet", e)

    # out-of-core ingest microbench (ISSUE 17, lightgbm_tpu/ingest.py):
    # streaming rows/s through the chunked reader + quantile sketcher,
    # peak RSS of a SUBPROCESS ingesting a many-chunk file (the
    # bounded-memory claim: one chunk in flight regardless of chunk
    # count — gated lower-better in tools/perf_budget.txt), and the
    # serialized-sketch allgather wire bytes from parallel/dist_data.py
    # (what crosses the fleet instead of raw sample rows).  Keyed
    # points: fold as ingest_rows_per_s / ingest_peak_rss_mb /
    # binning_wire_bytes
    try:
        import tempfile
        n_i, f_i = 200_000, 8
        tmpd = tempfile.mkdtemp(prefix="bench_ingest_")
        src = os.path.join(tmpd, "train.csv")
        rng = np.random.RandomState(11)
        xi = np.round(rng.randn(n_i, f_i), 3)
        yi = (xi[:, 0] > 0).astype(np.float64)
        np.savetxt(src, np.column_stack([yi, xi]), fmt="%.3f",
                   delimiter=",")
        child = (
            "import sys,json,time,resource;"
            f"sys.path.insert(0,{_DIR!r});"
            "import lightgbm_tpu as lgb;"
            f"p={{'verbosity':-1,'ingest_chunk_rows':{max(n_i // 64, 1)}}};"
            "t0=time.time();"
            f"ds=lgb.ingest_dataset({src!r},p,"
            f"spool_dir={os.path.join(tmpd, 'spool')!r});"
            "dt=time.time()-t0;"
            "print(json.dumps({"
            "'rows_per_s':ds.ingest_report['num_rows']/max(dt,1e-9),"
            "'peak_rss_mb':resource.getrusage("
            "resource.RUSAGE_SELF).ru_maxrss/1024.0}))")
        # peak RSS needs a process of its own; it is pinned to the CPU
        # through its environment and never claims the chip
        out = subprocess.run([sys.executable, "-c", child],
                             env=dict(os.environ, JAX_PLATFORMS="cpu"),
                             capture_output=True, text=True, timeout=600)
        ip = json.loads(out.stdout.strip().splitlines()[-1])
        from lightgbm_tpu.config import Config
        from lightgbm_tpu.parallel import dist_data
        cfg_i = Config({"max_bin": PRIMARY_MAX_BIN, "verbosity": -1})
        dist_data.reset_wire_bytes()
        dist_data.distributed_bin_mappers(
            xi[:20_000], cfg_i, process_index=0, process_count=1,
            allgather=lambda b: [b])
        _record_point("ingest",
                      rows_per_s=round(ip["rows_per_s"], 1),
                      peak_rss_mb=round(ip["peak_rss_mb"], 1),
                      chunk_rows=max(n_i // 64, 1))
        _record_point("binning",
                      wire_bytes=dist_data.wire_bytes_sent())
    except Exception as e:
        _point_failed("ingest", e)

    # integrity-layer overhead (ISSUE 20, lightgbm_tpu/integrity.py):
    # checked (integrity_check_freq=16: shadow re-execution every 16th
    # iteration + traced invariants riding the consolidated fetch) vs
    # unchecked iters/s on the per-iteration masked path, same binned
    # data.  Folds into extras as integrity_overhead_pct — pinned
    # lower-better in tools/perf_budget.txt: the "pay only on check
    # iterations" contract, measured
    try:
        n_g = 200_000
        xg, yg = make_higgs_like(n_g, N_FEAT, seed=5)
        pg = {"objective": "binary", "num_leaves": 31,
              "max_bin": PRIMARY_MAX_BIN,
              "min_data_in_leaf": 20,
              "verbosity": -1, "tpu_learner": "masked"}
        dsg = lgb.Dataset(xg, label=yg, params=pg)
        dsg.construct()

        def _ips_at(freq):
            bst = lgb.Booster(params=dict(pg, integrity_check_freq=freq),
                              train_set=dsg)
            m = bst._model
            for _ in range(max(freq, 1) + 1):   # warm: compile primary
                bst.update()                    # AND the shadow's first
            np.asarray(m.score)                 # check iteration
            t0 = time.time()
            n0 = m.iter_
            for _ in range(32):
                bst.update()
            np.asarray(m.score)
            return (m.iter_ - n0) / max(time.time() - t0, 1e-9)

        ips_off = _ips_at(0)
        ips_on = _ips_at(16)
        overhead = max(0.0, (ips_off / max(ips_on, 1e-9) - 1.0) * 100.0)
        _record_point("integrity", check_freq=16,
                      unchecked_ips=round(ips_off, 3),
                      checked_ips=round(ips_on, 3),
                      overhead_pct=round(overhead, 1))
    except Exception as e:
        _point_failed("integrity", e)

    # comm wire bytes per boosting iteration (obs/comm.py static model,
    # same math the telemetry counters use at train time): the in-flight
    # number arXiv:1706.08359 instruments to validate scaling — one
    # reduce-scattered hist pass per split, (leaves-1) splits/tree
    try:
        from lightgbm_tpu.obs.comm import dp_hist_bytes_per_iter
        from lightgbm_tpu.parallel.mesh import owner_shard_plan
        pts = {}
        for wname, f in (("higgs28", 28), ("bosch968", 968),
                         ("allstate4228", 4228)):
            for s in (8, 16):
                plan = owner_shard_plan(np.arange(f), s)
                pts[f"{wname}_x{s}"] = dp_hist_bytes_per_iter(
                    s, plan.chunk, PRIMARY_PADDED_BIN,
                    n_steps=PRIMARY_LEAVES - 1)
        _record_point("comm_bytes_per_iter",
                      leaves=PRIMARY_LEAVES, **pts)
    except Exception as e:
        _point_failed("comm_bytes_per_iter", e)

    # 10M-row scaling point (VERDICT r2 task 3b)
    try:
        x10 = np.concatenate([x] * 10, axis=0)
        rng = np.random.RandomState(7)
        for i in range(10):     # chunked f32 noise: no 2 GB f64 spike
            sl = slice(i * N_ROWS, (i + 1) * N_ROWS)
            x10[sl] += (rng.standard_normal(
                (N_ROWS, N_FEAT)).astype(np.float32) * 1e-3)
        y10 = np.concatenate([y] * 10)
        ips3, auc3, _, _, cst3 = _train_point(lgb, x10, y10, num_leaves=31,
                                              chunk=8, n_chunks=2,
                                              tag="10M/31leaf/sb8",
                                              split_batch=8)
        _record_point("higgs10m", value=round(ips3, 3),
                      auc=round(float(auc3), 4), **cst3)
    except Exception as e:
        _point_failed("higgs10m", e)


def _merge(rec: dict) -> dict:
    """Fold every recorded point into the primary record's ``extra``."""
    extra = {}
    for p in _POINTS:
        name = p["point"]
        if name == "primary":
            extra["higgs1m_31leaf_sb8_auc"] = p["auc"]
            if p.get("steps_per_tree") is not None:
                extra["higgs1m_31leaf_sb8_steps"] = p["steps_per_tree"]
            for k_src in ("compile_s", "trace_count", "hist_tflops",
                          "mfu", "flop_share"):
                if p.get(k_src) is not None:
                    extra[f"higgs1m_31leaf_sb8_{k_src}"] = p[k_src]
            rec["prov"] = p["prov"]
            continue
        if "value" not in p and "error" not in p:
            # keyed payload points (hist-bytes shapes, comm_bytes_per_iter
            # from the obs/comm static model): fold every data key
            for k_src, v in p.items():
                if k_src not in ("point", "t", "prov"):
                    extra[f"{name}_{k_src}"] = v
            continue
        if "value" in p:
            extra[name + "_iters_per_sec"] = p["value"]
            for k_src, k_dst in (("auc", "_auc"),
                                 ("vs_baseline", "_vs_baseline"),
                                 ("steps_per_tree", "_steps"),
                                 ("batched_over_strict", "_speedup"),
                                 ("hist_tflops", "_hist_tflops"),
                                 ("mfu", "_mfu"),
                                 ("flop_share", "_flop_share"),
                                 ("compile_s", "_compile_s"),
                                 ("trace_count", "_trace_count"),
                                 ("compile_cache_hits", "_cache_hits"),
                                 ("shape", "_shape")):
                if p.get(k_src) is not None:
                    extra[name + k_dst] = p[k_src]
        elif "error" in p:
            extra[name + "_error"] = p["error"]
    if extra:
        rec["extra"] = extra
    return rec


def main() -> int:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"[bench] jax.devices()[0].platform is {devs[0].platform!r}, "
              "not 'tpu': a timing taken here is not a speed number",
              file=sys.stderr)
        return 1
    print(f"[bench] devices={devs}", file=sys.stderr, flush=True)
    import lightgbm_tpu as lgb

    x, y = make_higgs_like(N_ROWS, N_FEAT)
    rec = run_primary(lgb, devs, x, y)
    run_extras(lgb, devs, x, y)
    rec = _merge(rec)
    if _FAILED:
        rec["error"] = "points failed: " + ", ".join(_FAILED)
    print(json.dumps(rec), flush=True)
    return 1 if _FAILED else 0


if __name__ == "__main__":
    sys.exit(main())
