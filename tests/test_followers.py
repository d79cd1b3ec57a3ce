"""The held-out rows ride the grower's partition (PR 33): ``make_grower``'s
growers take follower matrices and hand back one ``leaf_of_row`` a follower
with the tree, so a booster's held-out score update is the train score's own
``leaf_value[leaf_of_row]`` and no new tree's node tables are walked row by
row.  The tree must not notice its followers, and their leaves must be the
walk's to the element; at the booster's level the models and the recorded
held-out curves are, byte for byte, the parent commit's (ab57858, the walk)."""

import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.grower import make_grower
from lightgbm_tpu.predict_device import traverse_tree_binned
from lightgbm_tpu.utils.shapes import round_up_pow2

from test_wide_bins import (PARTITION_PINNED, cell_shaped_grower, digest,
                            partition_case, row_gathers_of_the_step)

# what the rule admits of PR 31's shapes: every dense one
FOLLOWED = [name for name in PARTITION_PINNED if "sparse" not in name]


def followers_of(binned, seed):
    """Two held-out sets of different row counts, with the training
    matrix's kinds of rows (its missing-value bins, its bundles)."""
    rng = np.random.default_rng(seed)
    b = np.asarray(binned)
    return tuple(jnp.asarray(b[rng.integers(0, len(b), rows)])
                 for rows in (700, 1300))


def walked(follower, t, num_bin, na_bin, efb):
    maps = None if efb is None else (
        efb.group_of_feat, jnp.asarray(efb.off_host), num_bin - 1)
    depth = int(np.asarray(t.leaf_depth)[:int(t.num_leaves)].max())
    return traverse_tree_binned(
        follower, t.split_feature, t.threshold_bin, t.default_left,
        t.left_child, t.right_child, na_bin, t.is_cat_node, t.cat_rank,
        maps, steps=round_up_pow2(max(depth, 1)))


@pytest.mark.parametrize("name", FOLLOWED)
def test_followers_leaves_are_the_walks_and_the_tree_is_unmoved(name):
    opts, args, kw = partition_case(name)
    followers = followers_of(args[0], 3)
    t, leaves = make_grower(**opts)(*args, followers=followers, **kw)
    # the tree and the training rows' leaves, to the byte, as without
    # followers (the parent's pin)
    assert digest(t) == PARTITION_PINNED[name]
    assert len(leaves) == 2
    for f, got in zip(followers, leaves):
        assert got.shape == (f.shape[0],) and got.dtype == jnp.int32
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(walked(f, t, args[3], args[4],
                                               opts.get("efb"))))
    assert len(np.unique(np.asarray(leaves[1]))) > 8


def test_a_grower_without_followers_returns_the_tree_alone():
    opts, args, kw = partition_case("strict_nan_categorical")
    grow = make_grower(**opts)
    assert digest(grow(*args, **kw)) \
        == PARTITION_PINNED["strict_nan_categorical"]
    t, leaves = grow(*args, followers=(), **kw)
    assert leaves == () and digest(t) \
        == PARTITION_PINNED["strict_nan_categorical"]


# sha256 (16 hex digits) of ``str(jax.make_jaxpr(grow)(...))`` of the
# follower-less grower: what ``lgb.train`` without a held-out set traces is
# the program it traced before followers existed.  Taken at PR 33 from its
# parent commit (ab57858) and held until PR 35, which changed the grower's
# program itself (the count of a tree's contractions by rung rides the loop)
# and re-pinned these from its own tree, beside the trees' digests, which did
# not move.  A later change to the grower does the same.
FOLLOWERLESS_JAXPR = {
    "cell": "204a02ecf454022e",
    "efb_bundles_k8": "6778b60e2593a08d",
    "no_subtraction_k8": "9bdb380046019327",
    "strict_nan_categorical": "a8b4e151a038de58",
}


@pytest.mark.parametrize("name", list(FOLLOWERLESS_JAXPR))
def test_the_followerless_grower_traces_the_pinned_program(name):
    opts, args, kw = cell_shaped_grower() if name == "cell" \
        else partition_case(name)
    grow = make_grower(jit=False, **opts)
    text = str(jax.make_jaxpr(lambda *a: grow(*a, **kw))(*args))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == FOLLOWERLESS_JAXPR[name]


def test_the_followers_step_holds_no_row_gather():
    """The cell-shaped batched grower (255 leaves, 16 a step, 255 bins)
    with a follower: no ``gather`` inside the loop has a result of the
    follower's row count, nor of the training rows'."""
    opts, args, kw = cell_shaped_grower()
    follower = jnp.zeros((777, args[0].shape[1]), jnp.uint8)
    assert row_gathers_of_the_step(
        opts, args, dict(kw, followers=(follower,)),
        rows=(args[0].shape[0], 777)) == []


# --- at the booster's level --------------------------------------------------

PARAMS = dict(objective="binary", num_leaves=15, learning_rate=0.1,
              min_data_in_leaf=5, tpu_learner="masked", verbose=-1,
              metric=["auc", "binary_logloss"], telemetry=True)


def table(seed, n=2400, f=10, cat=False, nan=False):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, f))
    y = (x[:, 0] + 0.5 * x[:, 1] * x[:, 2]
         + 0.3 * rng.standard_normal(n) > 0).astype(np.float64)
    if nan:
        x[rng.random((n, f)) < 0.1] = np.nan
    if cat:
        x[:, 3] = rng.integers(0, 6, n)
        y = np.where(np.isin(x[:, 3], [1, 4]), 1 - y, y)
    return x, y


def bundled_table(seed, n):
    """Three dense columns and a one-hot block that EFB bundles."""
    rs = np.random.RandomState(seed)
    dense = rs.randn(n, 3)
    cat = rs.randint(0, 12, size=n)
    onehot = np.zeros((n, 12))
    onehot[np.arange(n), cat] = 1.0
    y = (dense[:, 0] + (cat % 3 == 0) + 0.2 * rs.randn(n) > 0.5)
    return np.column_stack([dense, onehot]), y.astype(np.float64)


def sha(text):
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def model_digest(bst):
    return sha("\n".join(
        ln for ln in bst.model_to_string().splitlines()
        if not ln.startswith(("[superepoch", "[fused_chunk",
                              "[num_iterations", "[telemetry"))))


def curve_digest(evals):
    return sha(json.dumps(evals, sort_keys=True, default=str))


def run_case(name):
    """``(digests..., boosters)`` of one case of ``BOOSTER_PINNED``."""
    loop = {"iter": -1, "scan": 4}.get(name.rsplit("_", 1)[-1], -1)
    params = dict(PARAMS, superepoch=loop)
    x, y = table(1, nan=True)
    xv, yv = table(2, n=700, nan=True)
    xw, yw = table(3, n=230, nan=True)
    ev = {}
    if name.startswith("train"):
        ds = lgb.Dataset(x, label=y)
        bst = lgb.train(params, ds, num_boost_round=8, valid_sets=[
            lgb.Dataset(xv, label=yv, reference=ds),
            lgb.Dataset(xw, label=yw, reference=ds)],
            callbacks=[lgb.record_evaluation(ev)])
        return (model_digest(bst), curve_digest(ev)), [bst]
    if name.startswith("early_stop"):
        ds = lgb.Dataset(x, label=y)
        bst = lgb.train(dict(params, learning_rate=0.5, num_leaves=31), ds,
                        num_boost_round=40,
                        valid_sets=[lgb.Dataset(xv, label=yv, reference=ds)],
                        callbacks=[lgb.record_evaluation(ev),
                                   lgb.early_stopping(3, verbose=False)])
        return (model_digest(bst), curve_digest(ev), bst.best_iteration), \
            [bst]
    if name in ("cv_categorical", "cv_bundles"):
        if name == "cv_bundles":
            xc, yc = bundled_table(4, 3000)
            ds = lgb.Dataset(xc, label=yc)
        else:
            xc, yc = table(4, cat=True)
            ds = lgb.Dataset(xc, label=yc, categorical_feature=[3])
        res = lgb.cv(params, ds, num_boost_round=5, nfold=3,
                     stratified=False, shuffle=True, seed=7,
                     return_cvbooster=True)
        boosters = res.pop("cvbooster").boosters
        if name == "cv_bundles":
            assert ds.efb is not None
        return (sha("".join(map(model_digest, boosters))),
                curve_digest(res)), boosters
    if name == "rollback":
        ds = lgb.Dataset(x, label=y)
        bst = lgb.Booster(params, ds)
        bst.add_valid(lgb.Dataset(xv, label=yv, reference=ds), "v")
        for _ in range(3):
            bst.update()
        bst.rollback_one_iter()
        for _ in range(2):
            bst.update()
        return (model_digest(bst), curve_digest(bst.eval_valid())), [bst]
    assert name == "multiclass"
    xm, _ = table(5)
    xmv, _ = table(6, n=500)
    ds = lgb.Dataset(xm, label=np.digitize(xm[:, 0], [-0.5, 0.5]) * 1.0)
    bst = lgb.train(
        dict(params, objective="multiclass", num_class=3,
             metric="multi_logloss"), ds, num_boost_round=4,
        valid_sets=[lgb.Dataset(
            xmv, label=np.digitize(xmv[:, 0], [-0.5, 0.5]) * 1.0,
            reference=ds)], callbacks=[lgb.record_evaluation(ev)])
    return (model_digest(bst), curve_digest(ev)), [bst]


# model strings and recorded held-out curves as the parent commit (ab57858)
# gave them for ``run_case(name)``, taken before the change (the cases have
# no ``telemetry`` line in their digests, and the parent ran them with it)
BOOSTER_PINNED = {
    "train_iter": ("7e8af0158334dce2", "43514afc8cdc0b8e"),
    "train_scan": ("7e8af0158334dce2", "cedbc1a2904b136d"),
    "early_stop_iter": ("c524378b3ee84d3d", "12bebb29048136d9", 8),
    "early_stop_scan": ("c524378b3ee84d3d", "578f026f7e33e1dc", 8),
    "cv_categorical": ("6ccb5e5a21b08d7d", "a574e0ef8e3ba5e9"),
    "cv_bundles": ("c73a12cd4159da76", "997fa17a33c09631"),
    "rollback": ("6345aeb89c61dd0c", "8ea91ead2604d38f"),
    "multiclass": ("c452e6038495568c", "7ea3b15b49b6b64a"),
}


def valid_leaves(boosters):
    out = {}
    for bst in boosters:
        for k, v in bst.telemetry_snapshot().items():
            if k.startswith("train.valid_leaves"):
                out[k] = out.get(k, 0) + v["value"]
    return out


@pytest.mark.parametrize("name", list(BOOSTER_PINNED))
def test_models_and_held_out_curves_are_the_parents(name):
    got, boosters = run_case(name)
    assert got == BOOSTER_PINNED[name]
    # every held-out update took the grower's leaves: one count a tree a set
    trees = sum(b.num_trees() for b in boosters)
    if name.startswith("early_stop"):
        trees = None       # the trees past the best one are dropped
    sets = 2 if name.startswith("train") else 1
    counts = valid_leaves(boosters)
    assert list(counts) == ["train.valid_leaves{source=partition}"]
    if trees is not None and name != "rollback":
        assert counts["train.valid_leaves{source=partition}"] == trees * sets


def sparse_case():
    import scipy.sparse as sps
    rng = np.random.default_rng(21)
    n, f, nnz = 2000, 600, 30
    rows = np.repeat(np.arange(n), nnz)
    cols = rng.integers(0, f, size=n * nnz)
    _, first = np.unique(rows.astype(np.int64) * f + cols, return_index=True)
    x = sps.csr_matrix((rng.integers(1, 16, len(first)).astype(np.float64),
                        (rows[first], cols[first])), shape=(n, f))
    y = np.asarray(x[:, :20].sum(axis=1)).ravel() \
        + 0.1 * rng.standard_normal(n)
    ds = lgb.Dataset(x[:1500], label=y[:1500])
    dv = lgb.Dataset(x[1500:], label=y[1500:], reference=ds)
    return dict(objective="regression", metric="l2"), ds, dv


@pytest.mark.parametrize("name", ["sparse_binned", "linear_tree",
                                  "tree_learner_data", "partitioned"])
def test_what_the_rule_leaves_out_walks_the_tree(name):
    """A ``SparseBinned`` held-out matrix, linear trees, a sharded learner
    and the partitioned learner take the walk, and say so."""
    params = dict(PARAMS, superepoch=-1)
    if name == "sparse_binned":
        extra, ds, dv = sparse_case()
        params.update(extra)
    else:
        x, y = table(11, n=1600)
        xv, yv = table(12, n=300)
        ds = lgb.Dataset(x, label=y, free_raw_data=False)
        dv = lgb.Dataset(xv, label=yv, reference=ds, free_raw_data=False)
        params.update({"linear_tree": {"linear_tree": True},
                       "tree_learner_data": {"tree_learner": "data"},
                       "partitioned": {"tpu_learner": "partitioned"}}[name])
    bst = lgb.train(params, ds, num_boost_round=3, valid_sets=[dv])
    if name == "sparse_binned":
        assert dv.binned_sparse is not None
    if name == "tree_learner_data":
        assert bst._model._dist == "data"
    assert valid_leaves([bst]) == {"train.valid_leaves{source=walk}": 3}
