"""The VMEM one-hot contraction kernel (ops/hist_kernel.py) against the scan,
in Pallas's interpret mode on the CPU; the rule that chooses between them;
and the counter of what was traced."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.obs import ObsSession
from lightgbm_tpu.obs.flops import traced_impls
from lightgbm_tpu.ops import hist_kernel, histogram
from lightgbm_tpu.ops.histogram import compute_histogram, vmem_plan

# name: rows, features, num_bins, bins' dtype, slots (0: no slot), what the
# accumulands are.  Row blocks of 128 (the least) keep the interpreter quick.
CASES = {
    "c3_no_slot": (300, 20, 63, np.uint8, 0, "normal"),
    "k16_negative_slots": (300, 20, 63, np.uint8, 16, "normal"),
    "ragged_rows_and_features": (333, 130, 63, np.uint8, 16, "normal"),
    "uint16_255_bins": (200, 12, 255, np.uint16, 8, "normal"),
    "uint8_255_bins": (200, 12, 255, np.uint8, 0, "normal"),
    "one_slot_mask": (260, 9, 63, np.uint8, 1, "normal"),
    "accumulator_in_parts": (130, 48, 255, np.uint16, 16, "normal"),
    "integers_bit_for_bit": (300, 20, 63, np.uint8, 16, "integers"),
    "integers_no_slot_255": (200, 12, 255, np.uint16, 0, "integers"),
    "rows_of_1_plus_2m12": (1000, 6, 63, np.uint8, 0, "probe"),
    "rows_of_1_plus_2m12_k16": (1000, 6, 63, np.uint8, 16, "probe"),
}


def _inputs(name):
    n, f, num_bins, dtype, slots, kind = CASES[name]
    rng = np.random.default_rng(sum(map(ord, name)))
    bins = rng.integers(0, num_bins, (n, f)).astype(dtype)
    if kind == "normal":
        vals = rng.standard_normal((n, 3)).astype(np.float32)
        vals[:, 2] = 1.0
    elif kind == "integers":
        vals = rng.integers(-1000, 1000, (n, 3)).astype(np.float32)
    else:       # exact in float32, not in bfloat16: a dropped piece shows
        vals = np.full((n, 3), 1 + 2.0 ** -12, np.float32)
    kw = {"num_bins": num_bins}
    slot = None
    if slots:
        slot = rng.integers(-2 if kind != "probe" else 0, slots, n) \
            .astype(np.int32)
        kw.update(slot=jnp.asarray(slot), num_slots=slots)
    return bins, vals, slot, kw


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_agrees_with_the_scan(name):
    n, f, num_bins, _, slots, kind = CASES[name]
    bins, vals, slot, kw = _inputs(name)
    plan = hist_kernel.tile_plan(n, f, num_bins, 3 * max(slots, 1), rows=128)
    assert (plan.parts > 1) == (name == "accumulator_in_parts")
    got = np.asarray(hist_kernel.hist_vmem(
        jnp.asarray(bins), jnp.asarray(vals), plan=plan, interpret=True,
        **kw))
    want = np.asarray(histogram._compute_histogram_matmul(
        jnp.asarray(bins), jnp.asarray(vals), **kw))
    assert got.shape == want.shape == (f, num_bins, 3 * max(slots, 1))
    assert got.dtype == np.float32
    if kind == "integers":
        np.testing.assert_array_equal(got, want)
    elif kind == "probe":
        # every row lands in one bin of each feature: a feature's bins sum
        # to n*(1 + 2^-12); bfloat16 accumulands would give n
        live = n if slot is None else int((slot >= 0).sum())
        totals = got.astype(np.float64).sum(axis=(1, 2)) / 3
        np.testing.assert_allclose(totals, live * (1 + 2.0 ** -12),
                                   rtol=1e-7)
        assert abs(totals[0] - live) > 0.2
    else:
        scale = np.abs(want).max(axis=(0, 1), keepdims=True) + 1e-30
        assert (np.abs(got - want) / scale).max() <= 1e-6
        # the count channel sums ones: exact in any split
        np.testing.assert_array_equal(got[..., 2 * max(slots, 1):],
                                      want[..., 2 * max(slots, 1):])


RULE = {
    # backend, accumulands' dtype, num_bins, slots: does the kernel run?
    "cpu_float32": ("cpu", np.float32, 63, 16, False),
    "tpu_float32": ("tpu", np.float32, 63, 16, True),
    "tpu_float32_no_slot": ("tpu", np.float32, 255, 1, True),
    "tpu_int8": ("tpu", np.int8, 63, 16, False),
    "tpu_int16": ("tpu", np.int16, 63, 1, False),
    "tpu_accumulator_past_vmem": ("tpu", np.float32, 16000, 64, False),
}


@pytest.mark.parametrize("name", list(RULE))
def test_rule_reads_backend_dtype_and_shape(name, monkeypatch):
    backend, dtype, num_bins, slots, kernel = RULE[name]
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    bins = jax.ShapeDtypeStruct((4096, 40), jnp.uint8)
    vals = jax.ShapeDtypeStruct((4096, 3), dtype)
    plan = vmem_plan(bins, vals, num_bins=num_bins, num_slots=slots)
    assert (plan is not None) == kernel
    if kernel:
        assert plan.vmem <= hist_kernel.VMEM_LIMIT


def test_cpu_takes_the_scan_and_the_trace_is_counted():
    """``hist.contraction_traces{impl=}``: one count a trace, process-wide
    and in the registry of the session that runs; a second call of the
    same shapes traces nothing."""
    session = ObsSession()
    session.activate()
    before = traced_impls()
    rng = np.random.default_rng(0)
    bins = jnp.asarray(rng.integers(0, 31, (77, 5)).astype(np.uint8))
    vals = jnp.asarray(rng.standard_normal((77, 3)).astype(np.float32))
    for _ in range(2):
        compute_histogram(bins, vals, num_bins=31)
    after = traced_impls()
    assert after.get(("hist", "scan"), 0) \
        == before.get(("hist", "scan"), 0) + 1
    assert after.get(("hist", "vmem"), 0) == before.get(("hist", "vmem"), 0)
    snap = session.snapshot()
    assert snap["hist.contraction_traces{impl=scan}"]["value"] == 1
    assert "hist.contraction_traces{impl=vmem}" not in snap


def test_kernel_trace_is_counted_as_vmem():
    session = ObsSession()
    session.activate()
    before = traced_impls().get(("hist", "vmem"), 0)
    plan = hist_kernel.tile_plan(64, 4, 15, 3)
    jax.eval_shape(
        lambda b, v: histogram._compute_histogram_vmem(
            b, v, num_bins=15, plan=plan),
        jax.ShapeDtypeStruct((64, 4), jnp.uint8),
        jax.ShapeDtypeStruct((64, 3), jnp.float32))
    assert traced_impls()[("hist", "vmem")] == before + 1
    assert session.snapshot()[
        "hist.contraction_traces{impl=vmem}"]["value"] == 1


def test_kernel_under_vmap_is_each_member_alone():
    """The fleet trainer vmaps the grower over members: shared bins,
    accumulands and slots a member."""
    rng = np.random.default_rng(5)
    bins = jnp.asarray(rng.integers(0, 63, (200, 10)).astype(np.uint8))
    vals = jnp.asarray(rng.standard_normal((3, 200, 3)).astype(np.float32))
    slot = jnp.asarray(rng.integers(-1, 8, (3, 200)).astype(np.int32))
    plan = hist_kernel.tile_plan(200, 10, 63, 24, rows=128)

    def one(v, s):
        return hist_kernel.hist_vmem(bins, v, num_bins=63, plan=plan, slot=s,
                                     num_slots=8, interpret=True)
    got = jax.vmap(one)(vals, slot)
    for m in range(3):
        np.testing.assert_array_equal(got[m], one(vals[m], slot[m]))


# -- the kernel through the TPU's compiler, at real widths.  The compiler is
# installed here and compiles for a chip that is described, not attached;
# nothing runs.  The topology is described inside a fixture, never at import
# (one process at a time may load the TPU's library).

@pytest.fixture(scope="module")
def v5e_host():
    """The four chips of one described v5e host."""
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2").devices
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(v5e_host):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(v5e_host[0])


@pytest.fixture()
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    and cannot be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


REAL = {
    # rows, features, num_bins, bins' dtype, slots
    "cell_k16": (320_000, 2_000, 63, jnp.uint8, 16),
    "cell_root": (320_000, 2_000, 63, jnp.uint8, 0),
    "narrow_k1": (1_000_000, 28, 63, jnp.uint8, 0),
    "groups_uint16_in_parts": (400_000, 300, 700, jnp.uint16, 8),
    # epsilon-b255.cv5's pass: 256 bins x 16 slots, a block in four parts
    "cell_b255_k16_in_parts": (320_000, 2_000, 255, jnp.uint8, 16),
}


@pytest.mark.parametrize("name", list(REAL))
def test_kernel_compiles_for_the_v5e(name, one_chip, no_compile_cache):
    from jax.experimental.layout import Format, Layout
    n, f, num_bins, dtype, slots = REAL[name]

    def placed(shape, dt):      # row-major, as a device array is placed
        return jax.ShapeDtypeStruct(shape, dt, sharding=Format(
            Layout(major_to_minor=tuple(range(len(shape)))), one_chip))
    plan = hist_kernel.tile_plan(n, f, num_bins, 3 * max(slots, 1))
    kw = {"slot": placed((n,), jnp.int32)} if slots else {}
    compiled = jax.jit(
        lambda b, v, slot=None: hist_kernel.hist_vmem(
            b, v, num_bins=num_bins, plan=plan, slot=slot,
            num_slots=slots)).lower(
        placed((n, f), dtype), placed((n, 3), jnp.float32), **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the binned matrix is read as placed: no second copy in another layout
    binned_bytes = n * f * jnp.dtype(dtype).itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < binned_bytes // 2


def test_grower_at_2000_features_255_bins_255_leaves_fits_the_v5e(
        one_chip, no_compile_cache, monkeypatch):
    """The whole batched grower of ``epsilon-b255.cv5`` (a 320,000-row fold,
    2,000 features, 255 bins, 255 leaves in a budget of 256, 16 slots, the
    kernel for its contraction) through the TPU's compiler: arguments and
    temporaries fit the chip's 15.75 GiB, by XLA's own memory analysis.  The
    parent's grower did not compile there: one copy of ``f32[32,2,2000,255,3]``
    padded its minor 3 to 128 lanes, 15.6 GB (PERF.md section 6, PR 30).  With
    the histograms channel-major the temporaries are a few times the
    per-leaf state.  About a minute: one whole-program compile, the only
    guard against a layout that pads coming back."""
    from lightgbm_tpu.grower import make_grower
    from lightgbm_tpu.ops.split import SplitParams
    n, f, bins, leaves, k = 320_000, 2_000, 255, 255, 16
    # the rule asks the backend: on the chip it answers "tpu"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    grow = make_grower(num_leaves=leaves, num_bins=bins, split_batch=k,
                       padded_leaves=256, hist_overlap=True, jit=False,
                       params=SplitParams(min_data_in_leaf=1,
                                          min_sum_hessian_in_leaf=100.0))

    def placed(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    compiled = jax.jit(grow).lower(
        placed((n, f), jnp.uint8), placed((n, 3), jnp.float32),
        placed((f,), jnp.bool_), placed((f,), jnp.int32),
        placed((f,), jnp.int32), max_leaves=placed((), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 15.75 * 2 ** 30
    state = (256 + k) * 3 * f * bins * 4
    assert state < m.temp_size_in_bytes < 4 * state


def test_feature_parallel_grower_of_the_four_chip_cell_fits_a_v5e_host(
        v5e_host, no_compile_cache, monkeypatch):
    """The grower of ``epsilon-b255-fp4.cv5`` (``tree_learner=feature`` over
    the four chips of one host: a 320,000-row fold and its 131,072 bucketed
    held-out rows replicated, 500 of the 2,000 columns' histograms a chip)
    through the TPU's compiler: the kernel contracts a worker's 500 columns,
    a chip's temporaries are about a quarter of the one-chip grower's 5.3
    GiB, no collective moves the binned matrix, and what crosses chips runs
    under ``lgbtpu.sync``.  About twenty seconds."""
    import re
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from lightgbm_tpu.ops.split import SplitParams
    from lightgbm_tpu.parallel.feature_parallel import _build
    n, nv, f, bins, leaves, k = 320_000, 131_072, 2_000, 255, 255, 16
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(v5e_host), ("feature",))
    jitted, ledger = _build(
        mesh, num_features=f, num_leaves=leaves, num_bins=bins,
        params=SplitParams(min_data_in_leaf=1, min_sum_hessian_in_leaf=100.0),
        max_depth=-1, block_rows=0, axis="feature", split_batch=k,
        hist_overlap=True, padded_leaves=256)

    def placed(shape, dt, spec=P()):
        return jax.ShapeDtypeStruct(shape, dt,
                                    sharding=NamedSharding(mesh, spec))
    cols = P("feature")
    compiled = jitted.lower(
        placed((n, f), jnp.uint8), placed((n, 3), jnp.float32),
        placed((f,), jnp.bool_, cols), placed((f,), jnp.int32, cols),
        placed((f,), jnp.int32, cols), placed((f,), jnp.int32), None,
        placed((), jnp.int32), placed((), jnp.int32),
        (placed((nv, f), jnp.uint8),)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    m = compiled.memory_analysis()
    state = (256 + k) * 3 * (f // 4) * bins * 4       # one worker's share
    assert state < m.temp_size_in_bytes < 4 * state
    assert m.temp_size_in_bytes < 1.5 * 2 ** 30
    # the exchange is the candidates' alone, and carries its scope
    gathers = re.findall(r"= (\S+) all-gather\(.*?op_name=\"([^\"]*)\"", text)
    assert gathers
    assert all("lgbtpu.sync" in name for _, name in gathers)
    assert not [shape for shape, _ in gathers if shape.startswith("u8[")]
    assert {s.site: s.wire_bytes for s in ledger.sites()} \
        == {"fp.best_split": 102_336, "fp.root_split": 3_198}
