"""The masked grower's channel-major histograms (PR 30): state ``[L, 3, F,
B]``, the step's slot histograms and the split scan never hold the 3
channels as an array's minor axis, which the TPU's compiler pads to 128 lanes
where it tiles it (at 2,000 features x 255 bins one such copy was 15.6 GB and
the grower did not compile).  On the CPU the trees are, byte for byte, the ones
the ``[L, F, B, 3]`` grower grew; the compile for the chip itself is in
tests/test_hist_kernel.py, beside the kernel's."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu import efb as efb_mod
from lightgbm_tpu import sparse_data
from lightgbm_tpu.grower import make_grower, slot_histograms
from lightgbm_tpu.ops import hist_kernel
from lightgbm_tpu.ops.histogram import compute_histogram
from lightgbm_tpu.ops.split import SplitParams, find_best_split

# rows, features, bins, leaves, slots, seed -> leaves grown, steps, and the
# first 16 hex digits of the sha256 over every field of the grower's
# TreeArrays, as the grower of the parent commit (935c82f, histograms
# ``[F, B, 3]``) gave them.  The accumulands are multiples of 2**-10 whose
# sums stay under 2**12, so every float32 sum is exact whatever its order and
# the pin does not hang on how many threads the host gives XLA.
PINNED = {
    "wide_255_bins_k16": ((4096, 512, 255, 255, 16, 5),
                          (255, 19, "15e858dd4412cd30")),
    "epsilon_63_bins_k16": ((3000, 40, 63, 255, 16, 6),
                            (255, 20, "b2219eb4fef2dda9")),
    "higgs_28_features_k8": ((3000, 28, 255, 63, 8, 7),
                             (63, 10, "54ebddf996729bc4")),
    "strict_31_leaves": ((2000, 30, 63, 31, 1, 8),
                         (31, 30, "84a166aa1c25dc05")),
}


def exact_inputs(n, f, bins, seed):
    rng = np.random.default_rng(seed)
    binned = rng.integers(0, bins, (n, f)).astype(np.uint8)
    y = (binned[:, 0] / bins + 0.3 * rng.standard_normal(n) > 0.5)
    g = (0.5 - y) + rng.integers(-128, 129, n) / 1024
    vals = np.stack([g, np.full(n, 0.25), np.ones(n)], axis=1)
    return binned, vals.astype(np.float32)


def grown(n, f, bins, leaves, k, seed):
    binned, vals = exact_inputs(n, f, bins, seed)
    grow = make_grower(num_leaves=leaves, num_bins=bins, split_batch=k,
                       params=SplitParams(min_data_in_leaf=1,
                                          min_sum_hessian_in_leaf=1e-3))
    return digest(grow(
        jnp.asarray(binned), jnp.asarray(vals), jnp.ones(f, bool),
        jnp.full(f, bins, jnp.int32), jnp.full(f, -1, jnp.int32)))


def digest(t):
    """Of every field the pins were taken of: ``rung_steps`` (PR 35) came
    later and says what the contractions were handed, not what was grown."""
    h = hashlib.sha256()
    for name in t._fields:
        if name == "rung_steps":
            continue
        h.update(np.ascontiguousarray(np.asarray(getattr(t, name))).tobytes())
    return int(t.num_leaves), int(t.n_steps), h.hexdigest()[:16]


@pytest.mark.parametrize("name", list(PINNED))
def test_trees_are_byte_for_byte_the_ones_pinned_before_the_layout_change(
        name):
    spec, pinned = PINNED[name]
    assert grown(*spec) == pinned


def test_a_steps_slot_histograms_are_the_strict_growers_masked_ones():
    """The hand-over of a batched step at a wide 255-bin shape: slot ``s`` of
    one 16-slot contraction, as the grower takes it into its state, is the
    histogram the strict grower builds of the same rows by masking, bit for
    bit, and channel-major like it."""
    n, f, bins, k = 1024, 96, 255, 16
    binned, vals = exact_inputs(n, f, bins, 9)
    rng = np.random.default_rng(9)
    slot = rng.integers(-1, k, n).astype(np.int32)      # -1: in no slot
    got = slot_histograms(compute_histogram(
        jnp.asarray(binned), jnp.asarray(vals), num_bins=bins,
        slot=jnp.asarray(slot), num_slots=k, channel_major=True), k)
    assert got.shape == (k, 3, f, bins)
    for s in (0, 7, 15):
        mask = (slot == s).astype(np.float32)[:, None]
        strict = compute_histogram(jnp.asarray(binned),
                                   jnp.asarray(vals * mask), num_bins=bins,
                                   channel_major=True)
        np.testing.assert_array_equal(np.asarray(got[s]), np.asarray(strict))
        # and the public layout is the same numbers with the channels last
        np.testing.assert_array_equal(
            np.asarray(strict).transpose(1, 2, 0),
            np.asarray(compute_histogram(
                jnp.asarray(binned), jnp.asarray(vals * mask),
                num_bins=bins)))


def test_no_array_of_the_split_scan_has_the_channels_minor():
    """The scan's jaxpr at 255 bins: no value of it, however small, ends in an
    axis of 3 behind a bin or feature axis (the gather of the three left sums
    is three gathers of a scalar: one gather of the 3-channel slice made the
    compiler lay out the whole ``[slots, 2, 3, F, B]`` operand channels-minor)."""
    f, b = 64, 255
    closed = jax.make_jaxpr(lambda h, t: find_best_split(
        h, t, jnp.full(f, b, jnp.int32), jnp.full(f, -1, jnp.int32),
        jnp.ones(f, bool), SplitParams()))(
        jnp.zeros((3, f, b)), jnp.zeros(3))

    def shapes(jaxpr):
        for eqn in jaxpr.eqns:
            for v in eqn.outvars:
                yield tuple(v.aval.shape)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from shapes(sub)
    wide = [s for s in shapes(closed.jaxpr) if len(s) >= 2 and s[-1] == 3]
    assert not wide, wide


def test_tile_plan_at_256_bins_16_slots_2000_features_is_in_equal_parts():
    """``bp=256``, ``cp=48``: a feature's accumulator is 147 KB and a block of
    128 does not fit ``ACC_BYTES``, the regime ``epsilon-b255.cv5`` runs.  The
    parts are as few as fit and alike, so that none contracts features the
    block does not have (four of 40 contracted 160 for 128)."""
    p = hist_kernel.tile_plan(320_000, 2_000, 255, 48)
    assert (p.bp, p.cp, p.ft) == (256, 48, 128)
    assert p.parts > 1 and p.parts * p.fpart == p.ft
    assert p.fpart * 3 * p.cp * p.bp * 4 <= hist_kernel.ACC_BYTES
    assert p.fpart % 8 == 0 and p.vmem <= hist_kernel.VMEM_LIMIT
    # the accepted cells' plans are what they were: one part
    assert hist_kernel.tile_plan(320_000, 2_000, 63, 48).parts == 1
    assert hist_kernel.tile_plan(8_400_000, 28, 255, 48).parts == 1


def test_kernel_in_parts_equals_the_scan_at_256_bins_16_slots():
    """Interpret mode at the cell's bins and slots, two feature blocks (the
    second ragged), two row blocks: the kernel's result is the scan's, and
    with whole-number accumulands bit for bit."""
    n, f, bins, k = 200, 136, 255, 16
    rng = np.random.default_rng(12)
    binned = rng.integers(0, bins, (n, f)).astype(np.uint8)
    vals = rng.integers(-1000, 1000, (n, 3)).astype(np.float32)
    slot = rng.integers(-2, k, n).astype(np.int32)
    plan = hist_kernel.tile_plan(n, f, bins, 3 * k, rows=128)
    assert plan.parts == 4 and plan.fpart == 32
    kw = dict(num_bins=bins, slot=jnp.asarray(slot), num_slots=k)
    got = hist_kernel.hist_vmem(jnp.asarray(binned), jnp.asarray(vals),
                                plan=plan, interpret=True,
                                channel_major=True, **kw)
    want = compute_histogram(jnp.asarray(binned), jnp.asarray(vals),
                             channel_major=True, **kw)
    assert got.shape == (3 * k, f, bins)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_the_kernels_plan_is_counted_beside_its_trace():
    """``hist.kernel_plans{fpart=,parts=}``: one count a trace of the kernel,
    by the tiles its plan chose, in the registry of the session that runs."""
    from lightgbm_tpu.obs import ObsSession
    from lightgbm_tpu.ops import histogram
    session = ObsSession()
    session.activate()
    n, f, bins, k = 256, 136, 255, 16
    plan = hist_kernel.tile_plan(n, f, bins, 3 * k)
    jax.eval_shape(
        lambda b, v, s: histogram._compute_histogram_vmem(
            b, v, num_bins=bins, plan=plan, slot=s, num_slots=k,
            channel_major=True),
        jax.ShapeDtypeStruct((n, f), jnp.uint8),
        jax.ShapeDtypeStruct((n, 3), jnp.float32),
        jax.ShapeDtypeStruct((n,), jnp.int32))
    snap = session.snapshot()
    assert snap["hist.kernel_plans{fpart=32,parts=4}"]["value"] == 1
    assert snap["hist.contraction_traces{impl=vmem}"]["value"] == 1


def test_a_booster_notes_its_compiled_growers_memory_once():
    """``grower.temp_bytes`` (XLA's memory analysis of the executable the
    iteration ran) and ``grower.hist_state_bytes`` (leaf slots x 3 x columns
    x bins x 4): one observation a booster on the per-iteration loop, the
    second booster's from the process-wide memo, and no second trace of the
    grower for either."""
    import lightgbm_tpu as lgb
    rng = np.random.default_rng(3)
    x = rng.standard_normal((1500, 12)).astype(np.float32)
    y = (x[:, 0] + x[:, 1] * x[:, 2] > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 255, "max_bin": 255,
              "min_data_in_leaf": 1, "tpu_learner": "masked",
              "telemetry": True, "verbosity": -1}
    snaps = []
    for _ in range(2):
        bst = lgb.Booster(params, lgb.Dataset(x, label=y))
        for _ in range(2):
            bst.update()
        snaps.append(bst.telemetry_snapshot())
    for snap in snaps:
        assert snap["grower.temp_bytes"]["count"] == 1
        # 255 leaves in a budget of 256, 16 scratch slots, 12 columns
        assert snap["grower.hist_state_bytes"]["sum"] \
            == (256 + 16) * 3 * 12 * 255 * 4
        assert snap["grower.temp_bytes"]["sum"] \
            >= snap["grower.hist_state_bytes"]["sum"]
    assert snaps[0]["grower.temp_bytes"]["sum"] \
        == snaps[1]["grower.temp_bytes"]["sum"]
    # reading the analysis lowers from the trace of the call that ran
    assert snaps[0].get("jax.traces{name=grower}", {"value": 0})["value"] <= 1
    assert "jax.traces{name=grower}" not in snaps[1]


# --- the row partition of a step (PR 31) -----------------------------------
# A row's slot, its split's column and its target slot reach it by K compares
# and selects (``grower._partition_rows``), where they were ten ``[N]``
# look-ups a step.  The four pins above hold unchanged; these cover what they
# lack.  Leaves grown, steps and digest (every field of ``TreeArrays``,
# ``leaf_of_row`` among them) as the grower of the parent commit (741bc37,
# the look-ups) gave them for ``partition_case(name)``, taken before the
# change; the accumulands are exact as above.
PARTITION_PINNED = {
    "nan_bins_k16": (63, 7, "d879421fd15d610e"),
    "categorical_among_numeric_k16": (63, 7, "107178b5cf8aa53a"),
    "nan_and_categorical_k16": (63, 7, "463870ce97aced6d"),
    "efb_bundles_k8": (63, 10, "0b6cb4f7b2c3f710"),
    "padded_leaves_k16": (40, 6, "0cdc729a1e2c1eec"),
    "no_subtraction_k8": (63, 10, "fd3c6254d6a290ea"),
    "sparse_binned_k8": (63, 10, "350ae2c47ec4c0ae"),
    "strict_nan_categorical": (31, 30, "1cd8d151b6cd8b6a"),
}


def efb_case(n, bins):
    """Six plain columns and two bundles of three mutually exclusive
    8-bin features: the bundled matrix ``[n, 8]``, the accumulands and the
    device's bundling state."""
    rng = np.random.default_rng(23)
    plain, vals = exact_inputs(n, 6, bins, 23)
    owner = rng.integers(0, 4, (n, 2))          # 3: the row is in none
    sub = rng.integers(1, 8, (n, 2))            # the owner's bin, 1..7
    grouped = np.concatenate([plain, np.zeros((n, 2), np.uint8)], axis=1)
    off = np.full(12, -1, np.int32)
    for g in range(2):
        for j in range(3):
            off[6 + 3 * g + j] = 1 + 7 * j
            grouped[:, 6 + g] += np.where(owner[:, g] == j,
                                          7 * j + sub[:, g], 0) \
                .astype(np.uint8)
    num_bin = np.array([bins] * 6 + [8] * 6, np.int32)
    info = efb_mod.EFBInfo(
        groups=[[j] for j in range(6)] + [[6, 7, 8], [9, 10, 11]],
        group_of_feat=np.concatenate(
            [np.arange(6), np.repeat([6, 7], 3)]).astype(np.int32),
        off_of_feat=off,
        group_num_bin=np.array([bins] * 6 + [22, 22], np.int32))
    return grouped, vals, num_bin, efb_mod.make_device_efb(info, num_bin,
                                                           bins)


def partition_case(name):
    """Grower options, positional and keyword arguments of one case of
    ``PARTITION_PINNED``: 3,000 rows, 12 features, 32 bins."""
    p = SplitParams(min_data_in_leaf=1, min_sum_hessian_in_leaf=1e-3)
    opts = dict(num_leaves=63, num_bins=32, split_batch=16, params=p)
    n, f, bins = 3000, 12, 32
    kw = {}
    if name == "efb_bundles_k8":
        grouped, vals, num_bin, opts["efb"] = efb_case(n, bins)
        opts["split_batch"] = 8
        return opts, (jnp.asarray(grouped), jnp.asarray(vals),
                      jnp.ones(12, bool), jnp.asarray(num_bin),
                      jnp.full(12, -1, jnp.int32)), kw
    binned, vals = exact_inputs(n, f, bins, sum(map(ord, name)))
    na = np.full(f, -1, np.int32)
    if "nan" in name:
        # the last bin of the first six columns is their missing-value bin;
        # the label leans on two of them from either side, so that the
        # missing rows go left at some nodes and right at others
        na[:6] = bins - 1
        miss = np.random.default_rng(5).random((n, 6)) < 0.2
        binned[:, :6] = np.where(miss, bins - 1,
                                 np.minimum(binned[:, :6], bins - 2))
        g = vals[:, 0] + np.where(miss[:, 1], -0.25, 0.0) \
            + np.where(miss[:, 2], 0.25, 0.0) \
            + (binned[:, 1] > 12) * 0.125 - (binned[:, 2] > 20) * 0.125
        vals = np.stack([g, vals[:, 1], vals[:, 2]], axis=1) \
            .astype(np.float32)
    if "categorical" in name:
        is_cat = np.zeros(f, bool)
        is_cat[[1, 4]] = True
        code = np.array([3, -2, 5, 0, -4, 1, 2, -1])[binned[:, 1] % 8]
        vals[:, 0] += (code / 4).astype(np.float32)
        kw["is_cat"] = jnp.asarray(is_cat)
        opts["params"] = p._replace(cat_smooth=1.0, cat_l2=1.0,
                                    min_data_per_group=4)
    if name == "padded_leaves_k16":
        opts.update(num_leaves=40, padded_leaves=64)
        kw["max_leaves"] = jnp.int32(40)
    if name == "no_subtraction_k8":
        opts.update(split_batch=8, subtract=False)
    if name.startswith("strict"):
        opts.update(split_batch=1, num_leaves=31)
    b = jnp.asarray(binned)
    if name == "sparse_binned_k8":
        opts["split_batch"] = 8
        binned = np.where(np.random.default_rng(4).random((n, f)) < 0.7, 0,
                          binned).astype(np.uint8)
        binned[:, 0] = exact_inputs(n, f, bins, sum(map(ord, name)))[0][:, 0]
        rows, cols = np.nonzero(binned)
        stored = np.bincount(rows, minlength=n)
        flat = np.full((n, int(stored.max())), -1, np.int32)
        flat[rows, np.concatenate([np.arange(c) for c in stored])] = \
            cols * bins + binned[rows, cols]
        b = sparse_data.SparseBinned(jnp.asarray(flat),
                                     jnp.zeros(f, jnp.int32), bins, f)
    return opts, (b, jnp.asarray(vals), jnp.ones(f, bool),
                  jnp.full(f, bins, jnp.int32), jnp.asarray(na)), kw


@pytest.mark.parametrize("name", list(PARTITION_PINNED))
def test_partition_by_selects_grows_the_trees_the_look_ups_grew(name):
    opts, args, kw = partition_case(name)
    t = make_grower(**opts)(*args, **kw)
    assert digest(t) == PARTITION_PINNED[name]
    # the case holds what its name says
    nodes = slice(0, int(t.num_leaves) - 1)
    at_na = np.asarray(args[4])[np.asarray(t.split_feature)[nodes]] >= 0
    cat = np.asarray(t.is_cat_node)[nodes]
    if "nan" in name:
        sent_left = np.asarray(t.default_left)[nodes][at_na & ~cat]
        assert sent_left.any() and not sent_left.all()
    assert cat.any() == ("categorical" in name)
    if "efb" in name:
        assert (np.asarray(t.split_feature)[nodes] >= 6).any()


def row_gathers_of_the_step(opts, args, kw, rows=None):
    """``(operand shape, result shape)`` of every ``gather`` inside the
    grower's loop whose result's leading dimension is the row count (or
    one of ``rows``)."""
    rows = rows or (args[0].shape[0],)
    grow = make_grower(jit=False, **opts)
    shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args[:2]]
    closed = jax.make_jaxpr(lambda b, v: grow(b, v, *args[2:], **kw))(*shapes)

    def walk(jaxpr, inside):
        for eqn in jaxpr.eqns:
            step = inside or eqn.primitive.name == "while"
            shape = tuple(eqn.outvars[0].aval.shape)
            if step and eqn.primitive.name == "gather" \
                    and shape[:1] and shape[0] in rows:
                yield tuple(eqn.invars[0].aval.shape), shape
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub, step)
    return list(walk(closed.jaxpr, False))


def cell_shaped_grower(is_cat=None):
    """The grower the cells run, at a CPU size: 255 leaves, 16 a step, 255
    bins, a dense uint8 matrix."""
    n, f, bins = 1000, 12, 255
    opts = dict(num_leaves=255, num_bins=bins, split_batch=16,
                params=SplitParams())
    args = (jnp.zeros((n, f), jnp.uint8), jnp.zeros((n, 3), jnp.float32),
            jnp.ones(f, bool), jnp.full(f, bins, jnp.int32),
            jnp.full(f, -1, jnp.int32))
    return opts, args, {} if is_cat is None else {"is_cat": is_cat}


def test_the_batched_step_of_a_dense_numeric_input_holds_no_row_gather():
    """The jaxpr of the batched grower for a dense input without a
    categorical feature: no ``gather`` in the loop has an ``[N]`` result (the
    parent's step held ten).  With a categorical feature exactly one is left,
    the rank of the row's bin in its slot's ``[K, B]`` table."""
    assert row_gathers_of_the_step(*cell_shaped_grower()) == []
    assert row_gathers_of_the_step(*cell_shaped_grower(
        jnp.asarray(np.arange(12) == 3))) == [((16, 255), (1000,))]


@pytest.mark.parametrize("name,rule,gathers", [
    ("dense_numeric", "select", 0),
    ("categorical_among_numeric_k16", "select+rank", 1),
    ("sparse_binned_k8", "sparse", 1),
    ("strict_nan_categorical", "select+rank", 1),
])
def test_a_grower_counts_the_partition_rule_it_was_traced_with(
        name, rule, gathers):
    """``grower.partition_rule{rule=}`` and ``grower.partition_row_gathers``
    beside ``jax.traces{name=grower}``, once a trace."""
    from lightgbm_tpu.obs import ObsSession
    opts, args, kw = cell_shaped_grower() if name == "dense_numeric" \
        else partition_case(name)
    session = ObsSession()
    session.activate()
    jax.eval_shape(lambda: make_grower(jit=False, **opts)(*args, **kw))
    snap = session.snapshot()
    assert snap["jax.traces{name=grower}"]["value"] == 1
    assert {k: v["value"] for k, v in snap.items()
            if k.startswith("grower.partition_rule")} \
        == {"grower.partition_rule{rule=%s}" % rule: 1}
    assert snap["grower.partition_row_gathers"]["value"] == gathers
