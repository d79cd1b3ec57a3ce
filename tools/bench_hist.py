"""Microbenchmark histogram formulations on the real TPU.

Each variant is applied R times IN-GRAPH (chained through a dummy
dependency) so one dispatch and one fetch are amortized over R passes, and
we report device-time-per-pass = wall / R.

Run: python tools/bench_hist.py [n_rows] [R]

--quant {off,8,16}: quantized-training sweep instead — the SHIPPED
``compute_histogram`` (f32 vs int8/int16 packed accumulands,
ops/quantize.py) across split_batch-shaped slot widths K in {16,32,64},
reporting ms/pass, achieved TFLOP/s, and the static per-pass HBM bytes
from the shared ledger formula (obs/flops.py).  ``run_quant_bench`` is
the importable entry bench.py folds into its extras as ``hist_quant_*``
keys.  Default (no value) runs all three.

Run: python tools/bench_hist.py --quant [8] [n_rows] [R]

--sharded: microbench the data-parallel histogram REDUCTION instead —
owner-shard ``psum_scatter`` (each shard keeps [ceil(F/n), B, 3] of global
histograms) vs the legacy full ``psum`` ([F, B, 3] replicated to every
shard) at HIGGS (28) and Allstate (4228) feature widths over >= 2 shard
counts.  Reports ms/pass and per-shard histogram bytes as JSON lines,
with the measuring platform recorded in every record.  By default the
bench runs on a virtual 8-device CPU mesh (a count of bytes and calls,
not a speed number); ``--sharded-tpu`` keeps the real backend instead for
hosts that have >= 2 accelerators, so recorded numbers are real ICI
collectives there.
Per-shard byte counts are platform-independent either way.

Run: python tools/bench_hist.py --sharded [R] [--sharded-tpu]
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, ".")

SHARDED_REAL = "--sharded-tpu" in sys.argv
SHARDED = "--sharded" in sys.argv or SHARDED_REAL
if SHARDED and not SHARDED_REAL \
        and "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
from jax import lax

if SHARDED and not SHARDED_REAL:
    jax.config.update("jax_platforms", "cpu")


def amortized(make_one, R):
    """make_one(binned, vals, salt) -> [F, B, 3]; returns jitted R-rep fn."""
    @jax.jit
    def rep(binned, vals):
        def body(i, acc):
            # salt the vals with i so XLA can't hoist the pass out of the loop
            h = make_one(binned, vals + (i * 1e-12), i)
            return acc + h
        return lax.fori_loop(0, R, body, jnp.zeros_like(make_one(binned, vals, 0)))
    return rep


def timeit(fn, *args, reps=3):
    # obs.trace.fence: the repo's one fence (docs/Observability.md)
    from lightgbm_tpu.obs.trace import fence
    fence(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fence(fn(*args))
        ts.append(time.perf_counter() - t0)
    return min(ts)


def hist_variant(block_rows, dtype, orient, num_bins, f):
    def one(binned, vals, salt):
        n = binned.shape[0]
        pad = (-n) % block_rows
        if pad:
            binned = jnp.pad(binned, ((0, pad), (0, 0)))
            vals = jnp.pad(vals, ((0, pad), (0, 0)))
        nblocks = (n + pad) // block_rows
        binned_b = binned.reshape(nblocks, block_rows, f)
        vals_b = vals.reshape(nblocks, block_rows, 3)
        iota = jnp.arange(num_bins, dtype=jnp.int32)

        def body(acc, chunk):
            bins_blk, vals_blk = chunk
            onehot = (bins_blk.astype(jnp.int32)[:, :, None] == iota) \
                .astype(dtype).reshape(block_rows, f * num_bins)
            if orient == "fb3":
                h = lax.dot_general(
                    onehot, vals_blk.astype(dtype),
                    dimension_numbers=(((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            else:
                h = lax.dot_general(
                    vals_blk.astype(dtype), onehot,
                    dimension_numbers=(((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32).T
            return acc + h, None

        acc0 = jnp.zeros((f * num_bins, 3), dtype=jnp.float32)
        acc, _ = lax.scan(body, acc0, (binned_b, vals_b))
        return acc.reshape(f, num_bins, 3)
    return one


def sharded_main():
    """Owner-shard ``psum_scatter`` vs full ``psum`` of the reduced
    histogram tensor (the dp learner's one heavy collective) — isolated
    from the histogram build so the Allstate width stays benchable on a
    CPU mesh.  Per-shard histogram bytes are the RESULT state each chip
    must hold per leaf: chunk*B*3*4 (owner-shard) vs F*B*3*4 (psum)."""
    import json

    from lightgbm_tpu.parallel import make_mesh, owner_shard_plan
    from lightgbm_tpu.parallel.data_parallel import owner_hist_reduce
    from jax.sharding import PartitionSpec as P

    args = [a for a in sys.argv[1:] if not a.startswith("--sharded")]
    R = int(args[0]) if args else 50
    platform = jax.devices()[0].platform
    B = 64
    widths = (("higgs", 28), ("allstate", 4228))
    shard_counts = [s for s in (2, 4, 8) if s <= len(jax.devices())]
    assert len(shard_counts) >= 2, \
        f"need >=2 benchable shard counts, have {len(jax.devices())} devices"

    for n_shards in shard_counts:
        mesh = make_mesh((n_shards,), ("data",),
                         jax.devices()[:n_shards])
        for name, f in widths:
            plan = owner_shard_plan(np.arange(f), n_shards)
            scatter_red = owner_hist_reduce("data", n_shards, plan.chunk)
            full_red = lambda h: lax.psum(h, "data")
            rng = np.random.RandomState(0)
            h_local = rng.rand(f, B, 3).astype(np.float32)

            def bench(red):
                def body(h):
                    def step(i, acc):
                        r = red(h + i * jnp.float32(1e-9))
                        return acc + lax.psum(r.sum(), "data")
                    return lax.fori_loop(0, R, step, jnp.float32(0.0))
                fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(),),
                                           out_specs=P(), check_vma=False))
                return timeit(fn, h_local) / R

            t_scatter = bench(scatter_red)
            t_psum = bench(full_red)
            rec = {
                "bench": "dp_hist_reduce", "platform": platform,
                "width": name, "F": f, "B": B,
                "n_shards": n_shards, "owner_chunk": plan.chunk,
                "per_shard_hist_bytes_owner": plan.hist_bytes(1, B),
                "per_shard_hist_bytes_psum": f * B * 3 * 4,
                "ms_per_pass_psum_scatter": round(t_scatter * 1e3, 3),
                "ms_per_pass_full_psum": round(t_psum * 1e3, 3),
            }
            print(json.dumps(rec), flush=True)
            print(f"  shards={n_shards} {name}(F={f}): owner-shard "
                  f"{rec['per_shard_hist_bytes_owner']/1e3:.1f} kB/shard "
                  f"@ {rec['ms_per_pass_psum_scatter']:.3f} ms vs full-psum "
                  f"{rec['per_shard_hist_bytes_psum']/1e3:.1f} kB/shard "
                  f"@ {rec['ms_per_pass_full_psum']:.3f} ms",
                  file=sys.stderr, flush=True)


def run_quant_bench(n_rows: int = 200_000, reps: int = 5,
                    quants=("off", "8", "16"), ks=(16, 32, 64),
                    f: int = 28, num_bins: int = 63,
                    tune: bool = True) -> dict:
    """Quantized-vs-f32 histogram contraction sweep over the
    split_batch slot widths K in {16, 32, 64} — the SHIPPED kernel
    (compute_histogram), not a bench-local variant, so dtype dispatch,
    block sizing (hist_block_rows by vals itemsize AND the wide
    channel/accumulator budget), the MXU lane padding of the wide
    widths (C=96 -> 128, C=192 -> 256) and the int32 accumulation are
    exactly what training runs.  Per width both the raw ``ms_per_pass``
    and the decision metric ``ms_per_leaf`` (= ms/pass / K — a wider
    pass may cost more wall and still win per split) are recorded;
    with ``tune`` the REAL autotuner (ops/hist_tune.py, in-memory
    table only — the bench must not poison the training cache) runs on
    the same shape and its chosen (K, block_rows) lands in the record
    as ``tuned_k`` / ``tuned_block_rows``.  Returns a flat dict
    bench.py folds into extras as ``hist_quant_<key>``."""
    import jax as _jax
    import jax.numpy as _jnp
    from lightgbm_tpu.obs.flops import hist_flops_bytes, padded_bins
    from lightgbm_tpu.obs.trace import fence
    from lightgbm_tpu.ops.histogram import compute_histogram
    from lightgbm_tpu.ops.quantize import (QuantSpec, quant_scales,
                                           quantize_stack)

    rng = np.random.RandomState(0)
    binned = _jnp.asarray(rng.randint(0, num_bins, size=(n_rows, f),
                                      dtype=np.uint8))
    vals_f32 = _jnp.asarray(rng.randn(n_rows, 3).astype(np.float32))
    out = {}
    for q in quants:
        if q == "off":
            vals, isz = vals_f32, 4
        else:
            spec = QuantSpec(bits=int(q))
            scales = quant_scales(vals_f32, spec.qmax)
            vals = quantize_stack(vals_f32, scales, spec,
                                  _jnp.int32(0), 0)
            isz = spec.itemsize
        for k in ks:
            slot = _jnp.asarray(
                rng.randint(0, k, size=n_rows, dtype=np.int32))

            @_jax.jit
            def rep(b, v, s, _k=k):
                def body(i, acc):
                    h = compute_histogram(b, v, num_bins=num_bins,
                                          slot=s + 0 * i, num_slots=_k)
                    return acc + h.astype(_jnp.float32)
                z = compute_histogram(b, v, num_bins=num_bins, slot=s,
                                      num_slots=_k)
                return lax.fori_loop(0, reps, body,
                                     jnp.zeros_like(z, jnp.float32))

            fence(rep(binned, vals, slot))
            t0 = time.perf_counter()
            fence(rep(binned, vals, slot))
            t = (time.perf_counter() - t0) / reps
            fl, hb = hist_flops_bytes(n_rows, f, num_bins,
                                      channels=3 * k, vals_itemsize=isz)
            out[f"q{q}_k{k}_ms_per_pass"] = round(t * 1e3, 3)
            out[f"q{q}_k{k}_ms_per_leaf"] = round(t * 1e3 / k, 4)
            out[f"q{q}_k{k}_tflops"] = round(fl / t / 1e12, 4)
            out[f"q{q}_k{k}_intensity"] = round(fl / hb, 2)
        _, hb1 = hist_flops_bytes(n_rows, f, num_bins, channels=3,
                                  vals_itemsize=isz)
        out[f"q{q}_hbm_bytes_per_pass"] = hb1
        if tune:
            # the autotuner's own verdict for this (shape, dtype): an
            # in-memory sweep (no table writes) so every bench point
            # carries the chosen (K, block_rows) as provenance
            try:
                from lightgbm_tpu.ops.hist_tune import tune as _tune
                rec = _tune(n_rows, f, num_bins, itemsize=isz,
                            kmax=max(ks), reps=max(2, reps // 2))
                out[f"q{q}_tuned_k"] = rec["k"]
                out[f"q{q}_tuned_block_rows"] = rec["block_rows"]
                if q == "off":
                    out["tuned_k"] = rec["k"]
                    out["tuned_block_rows"] = rec["block_rows"]
            except Exception as e:      # bench never dies on the tuner
                out[f"q{q}_tuned_error"] = f"{type(e).__name__}: {e}"[:80]
    out.update(n_rows=n_rows, f=f, num_bins=num_bins,
               padded_bins=padded_bins(num_bins), reps=reps)
    return out


def quant_main():
    import json
    args = [a for a in sys.argv[1:] if a != "--quant"]
    quants = ("off", "8", "16")
    if args and args[0] in ("off", "8", "16"):
        quants = (args.pop(0),)
    n = int(args[0]) if args else 200_000
    reps = int(args[1]) if len(args) > 1 else 5
    rec = run_quant_bench(n_rows=n, reps=reps, quants=quants)
    rec["bench"] = "hist_quant"
    rec["platform"] = jax.devices()[0].platform
    print(json.dumps(rec), flush=True)
    for k in sorted(rec):
        if k.endswith("_ms_per_pass"):
            print(f"  {k} = {rec[k]} ms "
                  f"({rec[k.replace('_ms_per_pass', '_tflops')]} TF/s)",
                  file=sys.stderr, flush=True)


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
    R = int(sys.argv[2]) if len(sys.argv) > 2 else 20
    f, B = 28, 64
    rng = np.random.RandomState(0)
    binned = jnp.asarray(rng.randint(0, B, size=(n, f), dtype=np.uint8))
    vals = jnp.asarray(rng.randn(n, 3).astype(np.float32))
    from lightgbm_tpu.obs.trace import fence
    fence((binned, vals))
    print(f"n={n} f={f} B={B} R={R}; flops/pass = {2*3*n*f*B/1e9:.1f} GFLOP",
          file=sys.stderr, flush=True)

    ref = None
    for block in (888, 8192, 32768, 131072):
        for dtype, dname in ((jnp.float32, "f32"), (jnp.bfloat16, "bf16")):
            for orient in ("fb3", "3fb"):
                one = hist_variant(block, dtype, orient, B, f)
                try:
                    fn = amortized(one, R)
                    t = timeit(fn, binned, vals) / R
                    out = np.asarray(one(jnp.asarray(binned), vals, 0))
                    if ref is None:
                        ref = out
                    err = np.abs(out - ref).max() / max(np.abs(ref).max(), 1)
                    gfs = 2 * 3 * n * f * B / t / 1e12
                    print(f"block={block:7d} {dname:4s} {orient}: "
                          f"{t*1e3:8.2f} ms/pass  {gfs:6.2f} TF/s  "
                          f"relerr={err:.2e}", file=sys.stderr, flush=True)
                except Exception as e:
                    print(f"block={block:7d} {dname:4s} {orient}: FAIL "
                          f"{type(e).__name__}: {str(e)[:100]}",
                          file=sys.stderr, flush=True)

    # child-pass strategies at 25% occupancy
    leaf_of_row = jnp.asarray((rng.rand(n) < 0.25).astype(np.int32))
    cap = max(1 << int(np.ceil(np.log2(max(n // 4, 1)))), 8)
    base = hist_variant(8192, jnp.float32, "fb3", B, f)

    def masked_one(binned, vals, salt):
        m = (leaf_of_row == 1).astype(vals.dtype)[:, None]
        return base(binned, vals * m, salt)

    def gathered_one(binned, vals, salt):
        idx = jnp.nonzero(leaf_of_row == 1, size=cap, fill_value=n)[0]
        safe = jnp.minimum(idx, n - 1)
        b_g = jnp.take(binned, safe, axis=0)
        v_g = jnp.take(vals, safe, axis=0) \
            * (idx < n)[:, None].astype(vals.dtype)
        return base(b_g, v_g, salt)

    tm = timeit(amortized(masked_one, R), binned, vals) / R
    tg = timeit(amortized(gathered_one, R), binned, vals) / R
    print(f"child 25%: masked-full {tm*1e3:.2f} ms vs gather(cap={cap}) "
          f"{tg*1e3:.2f} ms", file=sys.stderr, flush=True)

    # isolate nonzero / take / partition-style ops
    def nz_one(binned, vals, salt):
        idx = jnp.nonzero((leaf_of_row + 0 * salt) == 1, size=cap,
                          fill_value=n)[0]
        return idx.astype(jnp.float32).sum().reshape(1, 1, 1) \
            * jnp.ones((1, 1, 1))

    def take_one(binned, vals, salt):
        idx = (jnp.arange(cap) * 3 + salt) % n
        return jnp.take(binned, idx, axis=0).astype(jnp.float32) \
            .sum().reshape(1, 1, 1)

    def part_one(binned, vals, salt):
        fcol = jnp.take(binned, 3, axis=1).astype(jnp.int32)
        go_left = fcol <= (16 + salt * 0)
        out = jnp.where((leaf_of_row == 1) & (~go_left), 7, leaf_of_row)
        return out.astype(jnp.float32).sum().reshape(1, 1, 1)

    for name, one in (("nonzero", nz_one), ("take[cap,F]", take_one),
                      ("partition-update", part_one)):
        t = timeit(amortized(one, R), binned, vals) / R
        print(f"  {name}: {t*1e3:.3f} ms", file=sys.stderr, flush=True)


if __name__ == "__main__":
    if SHARDED:
        sharded_main()
    elif "--quant" in sys.argv:
        quant_main()
    else:
        main()
