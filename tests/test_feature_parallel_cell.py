"""The feature-parallel learner as the cell ``epsilon-b255-fp4.cv5`` runs it
(``lgb.cv`` -> ``engine.cv`` -> ``GBDTModel`` -> ``make_fp_grower``), on the
CPU's virtual mesh of four at a small size: the trees and held-out curves are
the serial learner's to the byte, the held-out rows ride the grower's
partition, the matrix goes to the mesh once a booster, and what crosses the
mesh is counted from the grower's static table."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.models import gbdt
from lightgbm_tpu.parallel import make_mesh
from lightgbm_tpu.parallel.feature_parallel import make_fp_grower

N, F, WORKERS = 4000, 40, 4
# the CPU's contraction is a dot whose summation order follows its operands'
# widths; in blocks of 8 rows a worker's 10 columns and the serial learner's
# 40 sum alike (on the chip the kernel's row tile is the same at both widths)
BASE = dict(objective="binary", num_leaves=31, max_bin=63, learning_rate=0.1,
            min_data_in_leaf=1, min_sum_hessian_in_leaf=1.0, verbosity=-1,
            tpu_learner="masked", metric="auc", rows_per_block=8)
SHARDED = dict(tree_learner="feature", num_machines=WORKERS)


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(34)
    x = rng.standard_normal((N, F)).astype(np.float32)
    y = (x[:, 0] + 0.5 * x[:, 3] * x[:, 5]
         + 0.3 * rng.standard_normal(N) > 0).astype(np.float32)
    rows = np.arange(N)
    folds = [(rows[rows % 4 != k], rows[rows % 4 == k]) for k in range(4)]
    return x, y, folds


def trees_of(booster) -> str:
    """The model string up to its parameter block, which names the learner."""
    return booster.model_to_string().split("\nparameters:")[0]


def cv(data, **extra):
    x, y, folds = data
    params = dict(BASE, **extra)
    ds = lgb.Dataset(x, label=y, params=params).construct()
    out = lgb.cv(params, ds, num_boost_round=3, folds=folds,
                 return_cvbooster=True)
    return out["cvbooster"].boosters, out["valid auc-mean"]


@pytest.mark.parametrize("split_batch", [1, 8], ids=["strict", "batched"])
def test_cv_grows_the_serial_learners_trees_and_curve_to_the_byte(
        data, split_batch):
    serial, curve = cv(data, split_batch=split_batch)
    sharded, curve4 = cv(data, split_batch=split_batch, telemetry=True,
                         **SHARDED)
    assert curve4 == curve
    for one, four in zip(serial, sharded):
        assert four._model._dist == "feature"
        assert four._model._mesh.size == WORKERS
        assert trees_of(four) == trees_of(one)
    # every new tree's held-out leaves came with the tree: no walk
    for bst in sharded:
        snap = bst.telemetry_snapshot()
        assert snap["train.valid_leaves{source=partition}"]["value"] == 3
        assert "train.valid_leaves{source=walk}" not in snap
    # four boosters, one mesh, one memoised program: at most the first
    # traced a grower, and its partition looks no rank up by the row
    merged = {}
    for bst in sharded:
        for key, rec in bst.telemetry_snapshot().items():
            if isinstance(rec, dict) and "value" in rec:
                merged[key] = merged.get(key, 0) + rec["value"]
    assert merged.get("jax.traces{name=grower}", 0) <= 1
    assert not [k for k in merged if k.startswith("grower.partition_rule")
                and "rule=select}" not in k]


@pytest.mark.parametrize("learner,follows", [
    ("serial", True), ("feature", True), ("data", False), ("voting", False)])
def test_the_rule_admits_the_learners_whose_workers_hold_every_row(
        data, learner, follows):
    x, y, _ = data
    params = dict(BASE, tree_learner=learner,
                  **({} if learner == "serial" else {"num_machines": 4}))
    ds = lgb.Dataset(x[:3000], label=y[:3000], params=params).construct()
    bst = lgb.Booster(params=params, train_set=ds)
    bst.add_valid(lgb.Dataset(x[3000:], label=y[3000:],
                              reference=ds).construct(), "valid")
    assert (bst._model._valid_followers() is not None) is follows


def booster_with_valid(data, **extra):
    x, y, _ = data
    params = dict(BASE, telemetry=True, **SHARDED, **extra)
    ds = lgb.Dataset(x[:3000], label=y[:3000], params=params).construct()
    bst = lgb.Booster(params=params, train_set=ds)
    bst.add_valid(lgb.Dataset(x[3000:], label=y[3000:],
                              reference=ds).construct(), "valid")
    return bst


@pytest.fixture()
def placements(monkeypatch):
    """Bytes of every array that jax places on devices while the fixture is
    live (``pxla.batched_device_put``: explicit ``device_put``s and the
    re-placements jit makes of arguments that lie elsewhere)."""
    from jax._src.interpreters import pxla
    seen = []
    real = pxla.batched_device_put

    def spy(aval, sharding, xs, devices, *a, **kw):
        seen.append(int(np.prod(aval.shape)) * aval.dtype.itemsize)
        return real(aval, sharding, xs, devices, *a, **kw)
    monkeypatch.setattr(pxla, "batched_device_put", spy)
    return seen


def test_the_matrix_is_placed_once_a_booster(data, placements):
    bst = booster_with_valid(data)
    model = bst._model
    matrix = 3000 * F                      # uint8 bins
    for arr in (model.binned_dev, model.valid_sets[0][1], model.score):
        assert arr.sharding.is_fully_replicated and arr.committed
        assert len(arr.sharding.device_set) == WORKERS
    # every device is sent every row: the counter holds the matrix, the
    # held-out rows and the small row state once for each device
    snap = bst.telemetry_snapshot()
    small = 4 * (3000 + 3000 + 1024)       # score, label, valid score
    assert snap["xfer.h2d_bytes"]["value"] \
        == WORKERS * (matrix + 1024 * F + small)
    assert placements.count(matrix) == 1
    bst.update()
    del placements[:]
    bst.update()
    # the second tree moves no matrix, nor anything of a row's size but the
    # accumulands' explicit placement
    assert not [b for b in placements if b >= matrix]
    # the probe sees a matrix that is left on one device
    model.binned_dev = jnp.asarray(np.asarray(model.binned_dev))
    del placements[:]
    bst.update()
    assert matrix in placements


@pytest.mark.parametrize("learner,float32", [
    (None, True), ("feature", True), ("data", False), ("voting", False)],
    ids=lambda p: str(p))
def test_only_the_feature_sharded_learner_shrinks_as_the_serial_one(
        data, learner, float32):
    """Leaf values are shrunk in float32 where the scan could run the
    booster, and under ``feature``, whose model is the serial learner's to
    the byte; the row-sharded learners keep float64."""
    x, y, _ = data
    extra = {} if learner is None else dict(tree_learner=learner,
                                            num_machines=WORKERS)
    params = dict(BASE, **extra)
    ds = lgb.Dataset(x[:800], label=y[:800], params=params).construct()
    model = lgb.Booster(params=params, train_set=ds)._model
    assert model._dist == learner
    assert model._shrinks_in_float32() is float32
    assert model._fusable_config() is (learner is None)


def test_an_objective_places_its_own_row_state():
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.objectives import create_objective
    from jax.sharding import NamedSharding, PartitionSpec
    from lightgbm_tpu.dataset import Metadata
    mesh = make_mesh((WORKERS,), ("feature",), jax.devices()[:WORKERS])
    obj = create_objective(Config({"objective": "binary"}))
    meta = Metadata(4)
    meta.label = np.array([0., 1., 1., 0.], np.float32)
    meta.weight = np.array([1., 2., 1., 1.], np.float32)
    obj.init(meta, 4)
    obj.place_row_state(lambda a: jax.device_put(
        a, NamedSharding(mesh, PartitionSpec())))
    for arr in (obj.label, obj.weight):
        assert len(arr.sharding.device_set) == WORKERS and arr.committed
    assert obj._cnt_pos == 3.0              # host statistics stay the host's


def test_comm_bytes_are_the_ledgers_table_times_the_steps_run(data):
    bst = booster_with_valid(data, split_batch=8)
    for _ in range(2):
        bst.update()
    model = bst._model
    sites = {s.site: s for s in model.grower.comm.sites()}
    assert set(sites) == {"fp.best_split", "fp.root_split"}
    steps, trees = sum(model.step_counts), len(model.step_counts)
    snap = bst.telemetry_snapshot()

    def counted(name, site):
        return snap[f"{name}{{collective=all_gather,site={site}}}"]["value"]
    assert counted("comm.bytes", "fp.best_split") \
        == sites["fp.best_split"].wire_bytes * steps
    assert counted("comm.calls", "fp.best_split") == steps
    assert counted("comm.bytes", "fp.root_split") \
        == sites["fp.root_split"].wire_bytes * trees
    # a step's exchange carries both children of each of its 8 splits, the
    # root's one candidate: vmap hides the batch from the traced shapes
    assert sites["fp.best_split"].payload_bytes \
        == 2 * 8 * sites["fp.root_split"].payload_bytes
    # a candidate is a SplitResult: five scalars, two flags, two sums of
    # three and the [B] decision ranks
    assert sites["fp.root_split"].payload_bytes == 5 * 4 + 2 + 2 * 12 + 63 * 4


def test_a_sharded_growers_memory_is_noted_as_one_workers_share(data):
    bst = booster_with_valid(data, split_batch=8)
    bst.update()
    snap = bst.telemetry_snapshot()
    model = bst._model
    assert model.grower.state_columns == F // WORKERS
    slots = (model._leaf_pad or 31) + 8
    assert snap["grower.hist_state_bytes"]["sum"] \
        == slots * 3 * (F // WORKERS) * model.max_bin * 4
    assert snap["grower.temp_bytes"]["count"] == 1
    assert snap["grower.temp_bytes"]["sum"] > 0
    assert snap["train.setup_seconds{stage=mesh}"]["count"] == 1


def test_the_exchange_runs_under_its_own_device_scope():
    from lightgbm_tpu.ops.split import SplitParams
    mesh = make_mesh((WORKERS,), ("feature",), jax.devices()[:WORKERS])
    grow = make_fp_grower(mesh, num_features=8, num_leaves=4, num_bins=15,
                          params=SplitParams(min_data_in_leaf=1))
    text = grow.lower(
        jnp.zeros((64, 8), jnp.uint8), jnp.ones((64, 3), jnp.float32),
        jnp.ones(8, bool), jnp.full(8, 15, jnp.int32),
        jnp.full(8, -1, jnp.int32)).compile().as_text()
    gathers = re.findall(r'op_name="([^"]*all_gather[^"]*)"', text)
    assert gathers
    assert all("lgbtpu.sync" in name for name in gathers)


def test_followers_rule_is_one_function_of_what_the_booster_is():
    dense = np.zeros((4, 2), np.uint8)
    assert gbdt._followers(True, False, [dense]) == (dense,)
    assert gbdt._followers(False, False, [dense]) is None
    assert gbdt._followers(True, True, [dense]) is None
    assert gbdt._followers(True, False, []) is None
