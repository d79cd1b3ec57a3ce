"""A step's contraction is handed the rows it needs (PR 35): the target rows
of a step are compacted into the smallest row bucket of a static ladder that
holds them (grower.py ``contract_compacted``, ``compact_ladder``), so that
histogram work follows the rows in the smaller children as the reference's
smaller-leaf discipline does
(/root/reference/src/treelearner/serial_tree_learner.cpp:283-323, CUDA
leaf-indexed construction cuda_histogram_constructor.cu).  Every product and
every float32 sum of the pass over all rows is made, in another order of the
blocks: the tree's structure, counts and row assignment are the full pass's
exactly, its values to float32 rounding, and with accumulands whose sums are
exact the whole tree to the byte.

The ladder follows from the shapes and is empty at a CPU test's widths (a
row costs less to contract than to gather), so the tests that want rungs
put a ladder in ``compact_ladder``'s place; who builds the grower cannot."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import grower as grower_mod
from lightgbm_tpu import sparse_data
from lightgbm_tpu.grower import (RUNGS, compact_ladder, contract_compacted,
                                 contract_ns_per_row, make_grower, pick_rung,
                                 rows_contracted, rung_capacity)
from lightgbm_tpu.models import gbdt as gbdt_mod
from lightgbm_tpu.ops import hist_kernel
from lightgbm_tpu.ops.histogram import compute_histogram
from lightgbm_tpu.ops.quantize import QuantSpec
from lightgbm_tpu.ops.split import SplitParams
from lightgbm_tpu.parallel import (make_dp_grower, make_mesh,
                                   make_voting_grower, shard_rows)
from lightgbm_tpu.parallel.feature_parallel import _build as build_fp
from lightgbm_tpu.predict_device import traverse_tree_binned
from lightgbm_tpu.utils.shapes import round_up_pow2

from test_wide_bins import PARTITION_PINNED, digest, partition_case

FORCED = (1, 2, 3, 4)       # buckets of a half down to a sixteenth


@pytest.fixture()
def forced_ladder(monkeypatch):
    """Every dense shape gets the rungs ``FORCED``.  The memos of jitted
    growers and loop bodies are emptied around the test: a program traced
    under this ladder must not serve a test outside it, nor the reverse."""
    def clear():
        with grower_mod._SHARED_GROWERS_LOCK:
            grower_mod._SHARED_GROWERS.clear()
        gbdt_mod._SE_CACHE.clear()
    clear()
    monkeypatch.setattr(grower_mod, "compact_ladder",
                        lambda n, *shape: FORCED)
    yield FORCED
    clear()


def _data(n, f=10, b=32, seed=0):
    rng = np.random.RandomState(seed)
    binned = rng.randint(0, b, size=(n, f)).astype(np.uint8)
    y = (binned[:, 2] >= b // 2).astype(np.float32) \
        + 0.3 * rng.randn(n).astype(np.float32)
    g = (0.5 - y).astype(np.float32)
    vals = np.stack([g, np.ones(n, np.float32), np.ones(n, np.float32)], 1)
    return binned, vals


def _grow(binned, vals, L=15, b=32, **kw):
    """A grower jitted for this call alone (no memo), so that it is traced
    under whatever ladder stands now."""
    f = binned.shape[1]
    grow = jax.jit(make_grower(
        num_leaves=L, num_bins=b, params=SplitParams(min_data_in_leaf=5),
        jit=False, **kw))
    return grow(jnp.asarray(binned), jnp.asarray(vals),
                jnp.ones(f, bool), jnp.full(f, b, jnp.int32),
                jnp.full(f, -1, jnp.int32))


def _assert_same_tree(a, b):
    assert int(a.num_leaves) == int(b.num_leaves) > 2
    for field in ("split_feature", "threshold_bin", "left_child",
                  "right_child", "leaf_of_row",
                  # ``count_gap``-style exactness: a leaf's rows are counted
                  # in float32 ones, exact in any order
                  "leaf_count", "internal_count"):
        np.testing.assert_array_equal(np.asarray(getattr(a, field)),
                                      np.asarray(getattr(b, field)), field)
    # values differ only by float summation order (the bucket's blocks
    # against the full pass's)
    np.testing.assert_allclose(np.asarray(a.leaf_value),
                               np.asarray(b.leaf_value),
                               rtol=2e-3, atol=5e-5)


GROWERS = {"strict": dict(split_batch=1),
           "strict_overlap": dict(split_batch=1, hist_overlap=True),
           "batched_k8": dict(split_batch=8, L=31)}


class TestGatherTiers:
    @pytest.mark.parametrize("kind", list(GROWERS))
    def test_tiers_match_full_pass(self, kind, monkeypatch):
        # 6k rows under the rungs 3000 / 1500 / 750 / 375: all of them
        # taken across the leaf-size distribution of a strict tree
        binned, vals = _data(6000)
        t_full = _grow(binned, vals, **GROWERS[kind])
        assert np.asarray(t_full.rung_steps)[1:].sum() == 0
        monkeypatch.setattr(grower_mod, "compact_ladder",
                            lambda n, *shape: FORCED)
        t_tier = _grow(binned, vals, **GROWERS[kind])
        _assert_same_tree(t_full, t_tier)
        steps = np.asarray(t_tier.rung_steps)
        assert steps.sum() == 1 + int(t_tier.n_steps) and steps[0] >= 1
        assert (steps[1:5] > 0).sum() >= (2 if "batched" in kind else 4)
        assert rows_contracted(6000, steps) \
            < rows_contracted(6000, np.asarray(t_full.rung_steps)) / 2

    @pytest.mark.parametrize("kind", list(GROWERS))
    def test_bagged_rows_gathered(self, kind, monkeypatch):
        # zero-weight (out-of-bag) rows still occupy leaves and must be
        # gathered with zero accumulands
        binned, vals = _data(6000, seed=3)
        vals[::3, :] = 0.0
        t_full = _grow(binned, vals, **GROWERS[kind])
        monkeypatch.setattr(grower_mod, "compact_ladder",
                            lambda n, *shape: FORCED)
        t_tier = _grow(binned, vals, **GROWERS[kind])
        _assert_same_tree(t_full, t_tier)
        assert np.asarray(t_tier.rung_steps)[1:].sum() > 0

    @pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")
    def test_dp_keeps_the_full_pass_and_matches_serial(self, forced_ladder):
        # the row-sharded learner contracts all of a shard's rows at every
        # step, whatever the ladder, and grows the serial grower's tree
        binned, vals = _data(8192)
        b, L = 32, 15
        t_ser = _grow(binned, vals)
        assert np.asarray(t_ser.rung_steps)[1:].sum() > 0
        mesh = make_mesh((8,), ("data",))
        dp = make_dp_grower(mesh, num_leaves=L, num_bins=b,
                            params=SplitParams(min_data_in_leaf=5))
        f = binned.shape[1]
        t_dp = dp(shard_rows(mesh, binned), shard_rows(mesh, vals),
                  jnp.ones(f, bool), jnp.full(f, b, jnp.int32),
                  jnp.full(f, -1, jnp.int32))
        _assert_same_tree(t_ser, t_dp)
        np.testing.assert_array_equal(
            np.asarray(t_dp.rung_steps),
            [1 + int(t_dp.n_steps)] + [0] * (RUNGS - 1))


# -- one contraction, rung by rung ---------------------------------------------

N_STEP, F_STEP, BINS_STEP, K_STEP = 1024, 20, 63, 4
# the rungs 1, 2, 3 of 1,024 rows hold 512, 256, 128: a count and the rung
# that must take it, at every boundary
BOUNDARIES = [(0, 3), (1, 3), (128, 3), (129, 2), (256, 2), (257, 1),
              (512, 1), (513, 0), (1024, 0)]


def _scan_pass(b, v, s):
    return compute_histogram(b, v, num_bins=BINS_STEP, slot=s,
                             num_slots=K_STEP, channel_major=True)


def _kernel_pass(b, v, s):
    """The TPU's kernel in Pallas's interpreter, several row blocks."""
    plan = hist_kernel.tile_plan(b.shape[0], F_STEP, BINS_STEP, 3 * K_STEP,
                                 rows=128)
    return hist_kernel.hist_vmem(b, v, num_bins=BINS_STEP, plan=plan, slot=s,
                                 num_slots=K_STEP, interpret=True,
                                 channel_major=True)


@pytest.mark.parametrize("impl", ["scan", "kernel"])
@pytest.mark.parametrize("count,rung", BOUNDARIES)
def test_a_compacted_steps_histogram_is_the_full_passes(count, rung, impl):
    rng = np.random.default_rng(count)
    binned = jnp.asarray(rng.integers(0, BINS_STEP, (N_STEP, F_STEP))
                         .astype(np.uint8))
    vals = np.stack([rng.standard_normal(N_STEP), rng.random(N_STEP),
                     np.ones(N_STEP)], axis=1).astype(np.float32)
    tslot = np.full(N_STEP, -1, np.int32)
    on = rng.permutation(N_STEP)[:count]
    tslot[on] = rng.integers(0, K_STEP, count)
    contract = _scan_pass if impl == "scan" else _kernel_pass
    want = np.asarray(contract(binned, jnp.asarray(vals), jnp.asarray(tslot)))
    got, took = jax.jit(lambda b, v, s: contract_compacted(
        contract, b, v, s, (1, 2, 3)))(
        binned, jnp.asarray(vals), jnp.asarray(tslot))
    assert int(took) == rung
    got = np.asarray(got)
    assert got.shape == want.shape == (3 * K_STEP, F_STEP, BINS_STEP)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # the count channel (ones) is exact in any order, slot by slot
    np.testing.assert_array_equal(got[2 * K_STEP:], want[2 * K_STEP:])
    assert got[2 * K_STEP:, 0].sum() == count


def test_no_rung_is_the_plain_pass():
    binned, vals = _data(512, f=F_STEP, b=BINS_STEP)
    tslot = jnp.asarray(np.arange(512) % (K_STEP + 1) - 1, jnp.int32)
    text = str(jax.make_jaxpr(lambda b, v, s: contract_compacted(
        _scan_pass, b, v, s, ()))(jnp.asarray(binned), jnp.asarray(vals),
                                  tslot))
    assert "cond" not in text and "gather" not in text


@pytest.mark.parametrize("seed", range(8))
def test_a_bucket_is_never_smaller_than_its_rows(seed):
    """Over random ``tslot`` of random density and random ladders: the
    bucket chosen holds the count, and the next rung down would not."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(300, 5000))
    rungs = tuple(sorted(rng.choice(np.arange(1, RUNGS),
                                    int(rng.integers(1, 5)), replace=False)))
    caps = [rung_capacity(n, r) for r in rungs]
    sizes = [n] + caps
    assert sizes == sorted(sizes, reverse=True)
    pick = jax.jit(lambda t: pick_rung(jnp.sum(t >= 0, dtype=jnp.int32),
                                       caps))
    for density in list(rng.random(6)) + [0.0, 1.0] \
            + [c / n for c in caps]:
        tslot = np.where(rng.random(n) < density, 0, -1).astype(np.int32)
        for count in {int((tslot >= 0).sum())} | {
                c + d for c in caps for d in (0, 1) if c + d <= n}:
            tslot[:] = -1
            tslot[rng.permutation(n)[:count]] = 0
            which = int(pick(jnp.asarray(tslot)))
            assert sizes[which] >= count
            assert which == len(caps) or sizes[which + 1] < count


# -- the rule ----------------------------------------------------------------

@pytest.mark.parametrize("name,shape,least,most", [
    # rows, columns, bytes a row, bins, channels of the benchmark's cells
    ("epsilon-b255.cv5", (320_000, 2_000, 2_000, 255, 48), 5, RUNGS - 1),
    ("epsilon-l255.cv5", (320_000, 2_000, 2_000, 63, 48), 5, RUNGS - 1),
    ("epsilon-b255-fp4.cv5, a worker's columns",
     (320_000, 500, 500, 255, 48), 4, RUNGS - 1),
    # a narrow table's rungs start where the bucket is small: a row costs
    # 10.5 ns to contract and more than twice that to gather
    ("higgs-l255.cv5", (8_400_000, 28, 28, 255, 48), 0, 5),
    ("chip_smoke.py's table", (1_000_000, 28, 28, 63, 48), 0, 0),
    ("a test's narrow table", (6_000, 10, 10, 32, 3), 0, 0),
    ("too few rows for a bucket", (3_000, 2_000, 2_000, 255, 48), 0, 0),
])
def test_the_ladder_follows_from_the_shapes(name, shape, least, most):
    rungs = compact_ladder(*shape)
    assert least <= len(rungs) <= most, (name, rungs)
    # halvings in rising order from the first that pays, none left out,
    # none under the least bucket
    assert list(rungs) == list(range(rungs[0], rungs[0] + len(rungs))) \
        if rungs else True
    assert all(rung_capacity(shape[0], r) >= grower_mod.LEAST_BUCKET_ROWS
               for r in rungs)
    assert not rungs or rungs[0] == (1 if shape[1] >= 500 else 3)


def test_the_contractions_cost_a_row_is_tile_plans_arithmetic():
    # PERF.md section 6, PR 27: MXU floors of 245.8 / 59.9 / 88 ms a pass
    for shape, rows, floor_ms in (((2_000, 255, 48), 320_000, 245.8),
                                  ((2_000, 63, 48), 320_000, 59.9),
                                  ((28, 255, 48), 8_400_000, 88.0)):
        assert contract_ns_per_row(*shape) * rows / 1e6 \
            == pytest.approx(floor_ms, rel=0.03)


EXCLUDED = ["row_sharded_data", "row_sharded_voting", "sparse_binned",
            "integer_accumulands", "vmapped_body", "callers_reduce_hook"]


@pytest.mark.parametrize("kind", EXCLUDED)
def test_the_rules_exclusions_take_no_rung(kind, forced_ladder):
    binned, vals = _data(4096)
    f, b, p = binned.shape[1], 32, SplitParams(min_data_in_leaf=5)
    meta = (jnp.ones(f, bool), jnp.full(f, b, jnp.int32),
            jnp.full(f, -1, jnp.int32))
    if kind.startswith("row_sharded"):
        mesh = make_mesh((8,), ("data",))
        make = make_dp_grower if kind.endswith("data") else make_voting_grower
        extra = {} if kind.endswith("data") else {"top_k": 4}
        t = make(mesh, num_leaves=15, num_bins=b, params=p, **extra)(
            shard_rows(mesh, binned), shard_rows(mesh, vals), *meta)
    elif kind == "sparse_binned":
        opts, args, kw = partition_case("sparse_binned_k8")
        assert isinstance(args[0], sparse_data.SparseBinned)
        t = jax.jit(make_grower(jit=False, **opts))(*args, **kw)
        assert digest(t) == PARTITION_PINNED["sparse_binned_k8"]
    elif kind == "integer_accumulands":
        t = _grow(binned, vals, quant=QuantSpec(bits=8), split_batch=8)
    elif kind == "vmapped_body":
        grow = make_grower(num_leaves=15, num_bins=b, params=p,
                           split_batch=8, vmapped=True, jit=False)
        members = jnp.stack([jnp.asarray(vals), jnp.asarray(vals) * 0.5])
        t = jax.jit(jax.vmap(lambda v: grow(jnp.asarray(binned), v, *meta)))(
            members)
        t = jax.tree.map(lambda x: x[0], t)
    else:
        t = _grow(binned, vals, hist_reduce=lambda h: h, split_batch=8)
    steps = np.asarray(t.rung_steps)
    assert steps[0] >= 2 and steps[1:].sum() == 0
    # and the same grower with nothing in the rule's way does take rungs
    if kind in ("integer_accumulands", "vmapped_body", "callers_reduce_hook"):
        assert np.asarray(_grow(binned, vals, split_batch=8)
                          .rung_steps)[1:].sum() > 0


# -- exact accumulands: the tree to the byte -----------------------------------

DENSE_PINNED = [name for name in PARTITION_PINNED if "sparse" not in name]


@pytest.mark.parametrize("name", DENSE_PINNED)
def test_exact_accumulands_grow_the_pinned_trees_under_a_ladder(
        name, forced_ladder):
    """The pinned shapes (missing-value bins, categorical features, bundles,
    padded leaves, no subtraction, the strict grower) carry accumulands whose
    float32 sums are exact in any order: under a ladder the trees are the
    pinned ones to the byte, and rungs were taken."""
    opts, args, kw = partition_case(name)
    t = jax.jit(make_grower(jit=False, **opts))(*args, **kw)
    assert digest(t) == PARTITION_PINNED[name]
    steps = np.asarray(t.rung_steps)
    calls = int(t.n_steps) * (1 if opts.get("subtract", True) else 2)
    if int(opts["split_batch"]) > 1 and not opts.get("subtract", True):
        calls = int(t.n_steps)          # both children in one contraction
    assert steps.sum() == 1 + calls and steps[1:].sum() > 0


def test_followers_are_untouched_by_compaction(forced_ladder):
    """The held-out rows ride the partition, not the contraction: under a
    ladder their leaves are a walk's of the finished tree."""
    opts, args, kw = partition_case("nan_bins_k16")
    rng = np.random.default_rng(3)
    followers = tuple(jnp.asarray(np.asarray(args[0])[
        rng.integers(0, args[0].shape[0], rows)]) for rows in (700, 1300))
    t, leaves = jax.jit(make_grower(jit=False, **opts))(
        *args, followers=followers, **kw)
    assert digest(t) == PARTITION_PINNED["nan_bins_k16"]
    assert np.asarray(t.rung_steps)[1:].sum() > 0
    depth = int(np.asarray(t.leaf_depth)[:int(t.num_leaves)].max())
    for fol, got in zip(followers, leaves):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(
            traverse_tree_binned(
                fol, t.split_feature, t.threshold_bin, t.default_left,
                t.left_child, t.right_child, args[4], t.is_cat_node,
                t.cat_rank, None, steps=round_up_pow2(max(depth, 1)))))


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 devices")
@pytest.mark.parametrize("k", [1, 8])
def test_the_feature_sharded_grower_grows_the_serial_trees_to_the_byte(
        k, forced_ladder):
    """On the virtual mesh of four, with compaction on in both and
    accumulands that do round: every worker holds all rows and compacts them
    alike, and a feature's rows meet its accumulator in the same order
    whatever the columns beside it.  (Row blocks of 64: so short a block
    XLA:CPU's matmul sums in row order whatever its width, as the TPU's
    kernel sums a grid step's rows; from 256 rows a block its blocking
    follows the width, and a worker's sums differ from the serial grower's
    in the last bit with a ladder or without.)"""
    rng = np.random.default_rng(35)
    n, f, b, leaves = 4096, 16, 32, 31
    binned = rng.integers(0, b, (n, f)).astype(np.uint8)
    g = (0.5 - (binned[:, 5] / b + 0.3 * rng.standard_normal(n) > 0.5)) \
        * rng.random(n)
    vals = np.stack([g, rng.random(n) * 0.25, np.ones(n)], 1) \
        .astype(np.float32)
    p = SplitParams(min_data_in_leaf=5)
    meta = (jnp.ones(f, bool), jnp.full(f, b, jnp.int32),
            jnp.full(f, -1, jnp.int32))
    serial = jax.jit(make_grower(num_leaves=leaves, num_bins=b, params=p,
                                 split_batch=k, hist_overlap=True,
                                 block_rows=64, jit=False))(
        jnp.asarray(binned), jnp.asarray(vals), *meta)
    mesh = make_mesh((4,), ("feature",), jax.devices()[:4])
    sharded, _ = build_fp(mesh, num_features=f, num_leaves=leaves,
                          num_bins=b, params=p, max_depth=-1, block_rows=64,
                          axis="feature", split_batch=k, hist_overlap=True)
    fp = sharded(jnp.asarray(binned), jnp.asarray(vals), *meta, meta[2],
                 None, jnp.int32(leaves), jnp.int32(0), None)
    assert np.asarray(serial.rung_steps)[1:].sum() > 0
    for name in serial._fields:
        np.testing.assert_array_equal(np.asarray(getattr(serial, name)),
                                      np.asarray(getattr(fp, name)), name)


# -- what the booster records ------------------------------------------------

def _hand_count(node, n, rungs, want):
    """``rung_steps`` of a strict tree from the counts of its dumped nodes:
    for every split the rung whose bucket is the smallest that holds the
    smaller child (``want[0]``: the pass over all rows)."""
    if "leaf_index" in node:
        return node["leaf_count"]
    small = min(_hand_count(node[side], n, rungs, want)
                for side in ("left_child", "right_child"))
    fits = [r for r in rungs if rung_capacity(n, r) >= small]
    want[max(fits) if fits else 0] += 1
    return node["internal_count"]


@pytest.mark.parametrize("loop", ["per_iteration", "scan"])
def test_telemetry_reads_a_hand_count_of_a_small_tree(loop, forced_ladder):
    rng = np.random.default_rng(7)
    n, rounds = 3000, 3
    x = rng.standard_normal((n, 6))
    y = (x[:, 0] + 0.5 * x[:, 1] * x[:, 2]
         + 0.3 * rng.standard_normal(n) > 0).astype(np.float64)
    bst = lgb.train(
        dict(objective="binary", num_leaves=12, min_data_in_leaf=5,
             tpu_learner="masked", split_batch=1, verbose=-1, telemetry=True,
             superepoch=-1 if loop == "per_iteration" else 0),
        lgb.Dataset(x, label=y), num_boost_round=rounds)
    snap = bst.telemetry_snapshot()
    assert ("train.superepochs" in snap) == (loop == "scan")
    want = np.zeros(RUNGS, np.int64)
    for tree in bst.dump_model()["tree_info"]:
        want[0] += 1                                    # the root's pass
        assert _hand_count(tree["tree_structure"], n, FORCED, want) == n
    got = {key: int(entry["value"]) for key, entry in snap.items()
           if key.startswith("hist.compact_steps{")}
    assert got == {f"hist.compact_steps{{rung=N/{2 ** r}}}": int(want[r])
                   for r in range(1, RUNGS) if want[r]}
    assert sum(got.values()) > rounds
    rows = snap["hist.rows_contracted"]
    assert rows["count"] == rounds
    assert rows["sum"] == rows_contracted(n, want)
    assert rows["sum"] < n * want.sum() / 2


@pytest.mark.parametrize("features", [20, 130])
def test_rows_are_read_alike_from_a_narrow_and_a_wide_matrix(features):
    """Under 128 columns the reader gathers from a copy turned once, lane by
    lane; from 128 on from the matrix as it lies."""
    rng = np.random.default_rng(features)
    binned = rng.integers(0, 255, (700, features)).astype(np.uint8)
    idx = np.sort(rng.integers(0, 700, 300)).astype(np.int32)
    text = str(jax.make_jaxpr(
        lambda b, i: grower_mod.row_reader(b)(i))(binned, idx))
    assert ("transpose" in text) == (features < grower_mod.LANES)
    np.testing.assert_array_equal(
        np.asarray(grower_mod.row_reader(jnp.asarray(binned))(
            jnp.asarray(idx))), binned[idx])


def test_prefix_sums_over_more_than_a_registers_lanes(monkeypatch):
    """On a TPU the split scan's prefix sums over more than 128 bins are the
    128-lane pieces' and their carry, written out so that every operation
    carries the program's scope; elsewhere and up to 128 bins ``cumsum``."""
    from lightgbm_tpu.ops import split
    rng = np.random.default_rng(1)
    exact = jnp.asarray(rng.integers(-64, 65, (3, 5, 300)) / 8, jnp.float32)
    rounds = jnp.asarray(rng.standard_normal((2, 3, 7, 255)), jnp.float32)
    def traced(x):      # a function of its own a call: no trace is reused
        return str(jax.make_jaxpr(lambda v: split.prefix_sums(v))(x))
    plain = traced(rounds)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for x in (exact, rounds, rounds[..., :129]):
        got = np.asarray(split.prefix_sums(x))
        want = np.cumsum(np.asarray(x, np.float64), axis=-1)
        assert got.shape == x.shape
        np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)
    np.testing.assert_array_equal(
        np.asarray(split.prefix_sums(exact)),
        np.cumsum(np.asarray(exact), axis=-1))
    assert "pad" in traced(rounds) and "pad" not in plain
    assert "pad" not in traced(rounds[..., :128])
