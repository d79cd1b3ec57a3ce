"""Per-phase wall-clock attribution of one training iteration on real TPU.

Attribute every millisecond of a steady-state iteration to a named
phase.  Since the obs subsystem this
script is a THIN consumer: it enables ``telemetry=true`` on the booster
and reads the per-phase spans the training loop itself emits
(grad / sample / grow / fetch / tree_host / score / valid_score,
models/gbdt.py) — the same spans a
production run records — plus a couple of raw-latency probes timed with
``obs.trace.timed_fenced``.

All fencing goes through ``obs.trace.fence`` (a device_get of a scalar
derived from the timed work).

Output: a table on stderr + the JSONL trace (convertible to Perfetto
via ``python -c "from lightgbm_tpu.obs.trace import jsonl_to_chrome;
jsonl_to_chrome('profile_iter_trace.jsonl', 'trace.json')"``).

Run: python tools/profile_iter.py [n_rows] [num_leaves]
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
    num_leaves = int(sys.argv[2]) if len(sys.argv) > 2 else 31

    rng = np.random.RandomState(0)
    f = 28
    x = rng.randn(n, f).astype(np.float32)
    logit = (1.2 * x[:, 0] - 0.8 * x[:, 1] + 0.6 * x[:, 2] * x[:, 3]
             + 0.4 * np.abs(x[:, 4]) + 0.5 * rng.randn(n))
    y = (logit > 0).astype(np.float32)

    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.obs.trace import Tracer, fence, timed_fenced

    devs = jax.devices()
    print(f"devices={devs}", file=sys.stderr)

    tracer = Tracer(sink_path="profile_iter_trace.jsonl")

    # raw host round trip: dispatch + fetch of a 4-byte scalar — the
    # latency floor every blocking call pays
    one = fence(jnp.float32(1.0) + 0.0)
    t_rt_min, t_rt_avg = timed_fenced(
        lambda: jnp.float32(1.0) + one, iters=20, tracer=tracer,
        name="host_roundtrip")
    print(f"host round trip (scalar op + fence): "
          f"min {t_rt_min*1e3:.1f} ms avg {t_rt_avg*1e3:.1f} ms",
          file=sys.stderr)

    import lightgbm_tpu as lgb

    params = {"objective": "binary", "num_leaves": num_leaves,
              "learning_rate": 0.1, "max_bin": 63, "min_data_in_leaf": 20,
              "verbosity": 0, "telemetry": True,
              "telemetry_trace_file": "profile_iter_trace.jsonl",
              "fused_chunk": 0}   # per-iteration path: that's what we attribute
    ds = lgb.Dataset(x, label=y, params=params)   # bin at the CLAIMED max_bin
    ds.construct()
    bst = lgb.Booster(params=params, train_set=ds)
    m = bst._model

    # one full update to compile everything
    t0 = time.perf_counter()
    bst.update()
    print(f"compile+iter1: {time.perf_counter()-t0:.1f} s", file=sys.stderr)

    # steady-state reps: the training loop's own phase spans do the
    # attribution — no replicated pipeline, no hand-rolled fences
    reps = 8
    obs = m._obs
    phases = ("grad", "sample", "grow", "fetch", "tree_host", "score",
              "valid_score")
    skip = {k: len(obs.tracer.durations("lgbtpu." + k)) for k in phases}
    t0 = time.perf_counter()
    for _ in range(reps):
        bst.update()
    fence(m.score)
    total = time.perf_counter() - t0

    print(f"\nper-phase (over {reps} reps), n={n} leaves={num_leaves}:",
          file=sys.stderr)
    phase_sum = 0.0
    for k in phases:
        v = obs.tracer.durations("lgbtpu." + k)[skip[k]:]
        if not v:
            continue
        phase_sum += min(v)
        print(f"  {k:9s} min {min(v)*1e3:8.1f} ms   avg "
              f"{np.mean(v)*1e3:8.1f} ms", file=sys.stderr)
    print(f"  (sum of phase mins: {phase_sum*1e3:.1f} ms; measured "
          f"{total/reps*1e3:.1f} ms/iter)", file=sys.stderr)

    snap = bst.telemetry_finish()
    it = snap.get("train.iterations", {}).get("value", 0)
    isec = snap.get("train.iter_seconds", {})
    if isec.get("count"):
        print(f"\nmetrics: {it:g} iters, "
              f"mean {isec['sum']/isec['count']*1e3:.1f} ms/iter; "
              f"trace -> profile_iter_trace.jsonl", file=sys.stderr)


if __name__ == "__main__":
    main()
