"""Distributed learner tests on a virtual 8-device CPU mesh.

The reference tests distributed training by simulating machines with
localhost sockets (tests/distributed/_test_distributed.py); here the mesh
IS the simulation: data-parallel and feature-parallel growers must produce
exactly the same tree as the serial grower.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.grower import make_grower
from lightgbm_tpu.ops.split import SplitParams
from lightgbm_tpu.parallel import (make_dp_grower, make_fp_grower, make_mesh,
                                   make_voting_grower, owner_hist_reduce,
                                   owner_shard_plan, shard_rows)


@pytest.fixture(scope="module")
def mesh8():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    return make_mesh((8,), ("data",))


@pytest.fixture(scope="module")
def mesh_feat():
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    return make_mesh((4,), ("feature",))


def _data(n=4096, f=8, b=16, seed=0):
    rng = np.random.RandomState(seed)
    binned = rng.randint(0, b, size=(n, f)).astype(np.uint8)
    y = (binned[:, 2] >= b // 2).astype(np.float32) \
        + 0.3 * rng.randn(n).astype(np.float32)
    g = (0.5 - y).astype(np.float32)
    vals = np.stack([g, np.ones(n, np.float32), np.ones(n, np.float32)], axis=1)
    return binned, vals


def _tree_fields(tree, skip=("leaf_of_row",)):
    return {k: np.asarray(v) for k, v in tree._asdict().items()
            if k not in skip}


class TestDataParallel:
    def test_matches_serial(self, mesh8):
        binned, vals = _data()
        F, B, L = binned.shape[1], 16, 8
        p = SplitParams(min_data_in_leaf=5)
        nb = jnp.full(F, B, jnp.int32)
        na = jnp.full(F, -1, jnp.int32)
        fm = jnp.ones(F, bool)

        serial = make_grower(num_leaves=L, num_bins=B, params=p)
        t_ser = serial(jnp.asarray(binned), jnp.asarray(vals), fm, nb, na)

        dp = make_dp_grower(mesh8, num_leaves=L, num_bins=B, params=p)
        t_dp = dp(shard_rows(mesh8, binned), shard_rows(mesh8, vals),
                  fm, nb, na)

        ser_f = _tree_fields(t_ser)
        dp_f = _tree_fields(t_dp)
        assert int(t_ser.num_leaves) == int(t_dp.num_leaves) > 2
        for k in ("split_feature", "threshold_bin", "left_child", "right_child"):
            np.testing.assert_array_equal(ser_f[k], dp_f[k], err_msg=k)
        np.testing.assert_allclose(ser_f["leaf_value"], dp_f["leaf_value"],
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(ser_f["leaf_count"], dp_f["leaf_count"])
        # row partition agrees (dp leaf_of_row is row-sharded, same order)
        np.testing.assert_array_equal(np.asarray(t_ser.leaf_of_row),
                                      np.asarray(t_dp.leaf_of_row))

    def test_uneven_work_masking(self, mesh8):
        # zero-weight rows on some shards (bagging) keep results consistent
        binned, vals = _data(seed=3)
        vals[::3, :] = 0.0  # "out of bag"
        F, B, L = binned.shape[1], 16, 6
        p = SplitParams(min_data_in_leaf=5)
        nb = jnp.full(F, B, jnp.int32)
        na = jnp.full(F, -1, jnp.int32)
        fm = jnp.ones(F, bool)
        serial = make_grower(num_leaves=L, num_bins=B, params=p)
        t_ser = serial(jnp.asarray(binned), jnp.asarray(vals), fm, nb, na)
        dp = make_dp_grower(mesh8, num_leaves=L, num_bins=B, params=p)
        t_dp = dp(shard_rows(mesh8, binned), shard_rows(mesh8, vals), fm, nb, na)
        np.testing.assert_array_equal(np.asarray(t_ser.split_feature),
                                      np.asarray(t_dp.split_feature))
        np.testing.assert_allclose(np.asarray(t_ser.leaf_value),
                                   np.asarray(t_dp.leaf_value),
                                   rtol=1e-4, atol=1e-5)


class TestOwnerShard:
    """The reduce-scatter owner-shard dp learner (ISSUE 1 tentpole):
    per-shard histogram state is the owned chunk of the GLOBAL
    histograms, the split scan runs on that slice, and only the best
    SplitResult is allgathered — the reference's ReduceScatter +
    SyncUpGlobalBestSplit communication shape
    (data_parallel_tree_learner.cpp:174-186)."""

    def test_plan_roundtrip_efb_group_padding(self):
        # uneven EFB groups (G=5 over 4 shards -> padded to 8 group rows):
        # the feature-chunk -> global-feature-id map must cover every
        # feature exactly once, pads must be -1, and each owned feature's
        # group must lie inside its shard's group chunk
        group_of = np.array([0, 0, 0, 1, 2, 2, 3, 4, 4, 4, 4])
        plan = owner_shard_plan(group_of, 4)
        assert plan.chunk == 2          # ceil(5 groups / 4 shards)
        assert plan.n_shards == 4
        sf = plan.shard_feat
        feats = sf[sf >= 0]
        assert sorted(feats.tolist()) == list(range(len(group_of)))
        assert plan.fmax == max((sf[s] >= 0).sum() for s in range(4))
        for s in range(4):
            owned = sf[s][sf[s] >= 0]
            assert ((group_of[owned] >= s * plan.chunk)
                    & (group_of[owned] < (s + 1) * plan.chunk)).all()
            # slots after the owned prefix are all padding
            k = len(owned)
            assert (sf[s][k:] == -1).all()

    def test_plan_identity_when_unbundled(self):
        # without EFB the group axis IS the feature axis: contiguous
        # equal chunks, scan width == chunk
        plan = owner_shard_plan(np.arange(10), 8)
        assert plan.chunk == 2 and plan.fmax == 2
        np.testing.assert_array_equal(plan.shard_feat[0], [0, 1])
        np.testing.assert_array_equal(plan.shard_feat[4], [8, 9])
        assert (plan.shard_feat[5:] == -1).all()

    def test_reduce_scatter_owned_hist_shape(self, mesh8):
        # the per-shard histogram state after the reduce is the owned
        # [3, ceil(F/8), B] chunk of the GLOBAL histogram (channel-major,
        # as the grower holds it) — the shape assertion behind the
        # [L, 3, F/n_shards, B] grower carry
        from jax.sharding import PartitionSpec as P
        F, B = 11, 16
        plan = owner_shard_plan(np.arange(F), 8)
        assert plan.chunk == 2
        red = owner_hist_reduce("data", 8, plan.chunk)
        rng = np.random.RandomState(0)
        local = rng.rand(8, 3, F, B).astype(np.float32)  # per-shard hists

        fn = jax.jit(jax.shard_map(
            lambda h: red(h[0]), mesh=mesh8,
            in_specs=(P("data", None, None, None),),
            out_specs=P(None, "data", None), check_vma=False))
        out = np.asarray(fn(local))
        # global stacked output = 8 shards x chunk rows of GLOBAL sums
        assert out.shape == (3, 8 * plan.chunk, B)
        ref = np.zeros((3, 8 * plan.chunk, B), np.float32)
        ref[:, :F] = local.sum(axis=0)
        np.testing.assert_allclose(out, ref, rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("split_batch", [1, 8])
    @pytest.mark.parametrize("bagging", [False, True])
    def test_matches_serial(self, mesh8, split_batch, bagging):
        binned, vals = _data(n=4096, f=10, seed=5)
        if bagging:
            vals[::3, :] = 0.0                     # "out of bag" rows
        F, B, L = binned.shape[1], 16, 8
        p = SplitParams(min_data_in_leaf=5)
        nb = jnp.full(F, B, jnp.int32)
        na = jnp.full(F, -1, jnp.int32)
        fm = jnp.ones(F, bool)

        serial = make_grower(num_leaves=L, num_bins=B, params=p,
                             split_batch=split_batch)
        t_ser = serial(jnp.asarray(binned), jnp.asarray(vals), fm, nb, na)
        dp = make_dp_grower(mesh8, num_leaves=L, num_bins=B, params=p,
                            split_batch=split_batch, owner_shard=True)
        t_dp = dp(shard_rows(mesh8, binned), shard_rows(mesh8, vals),
                  fm, nb, na)
        # F=10 over 8 shards: ceil(10/8)=2 owned histogram rows per shard
        assert dp.plan.chunk == 2 and dp.plan.fmax == 2
        assert int(t_ser.num_leaves) == int(t_dp.num_leaves) > 2
        for k in ("split_feature", "threshold_bin", "default_left",
                  "left_child", "right_child"):
            np.testing.assert_array_equal(
                np.asarray(getattr(t_ser, k)), np.asarray(getattr(t_dp, k)),
                err_msg=k)
        np.testing.assert_allclose(np.asarray(t_ser.leaf_value),
                                   np.asarray(t_dp.leaf_value),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(t_ser.leaf_of_row),
                                      np.asarray(t_dp.leaf_of_row))

    @pytest.mark.parametrize("split_batch", [1, 8])
    def test_categorical_matches_serial(self, mesh8, split_batch):
        rng = np.random.RandomState(9)
        n, f, B, L = 4096, 9, 16, 8
        binned = rng.randint(0, B, size=(n, f)).astype(np.uint8)
        # feature 4 is categorical: the label keys on category membership
        y = np.isin(binned[:, 4], [1, 5, 9]).astype(np.float32) \
            + 0.25 * rng.randn(n).astype(np.float32)
        g = (0.5 - y).astype(np.float32)
        vals = np.stack([g, np.ones(n, np.float32),
                         np.ones(n, np.float32)], axis=1)
        p = SplitParams(min_data_in_leaf=5, min_data_per_group=1,
                        cat_smooth=1.0)
        nb = jnp.full(f, B, jnp.int32)
        na = jnp.full(f, -1, jnp.int32)
        fm = jnp.ones(f, bool)
        ic = jnp.zeros(f, bool).at[4].set(True)

        serial = make_grower(num_leaves=L, num_bins=B, params=p,
                             split_batch=split_batch)
        t_ser = serial(jnp.asarray(binned), jnp.asarray(vals), fm, nb, na,
                       is_cat=ic)
        dp = make_dp_grower(mesh8, num_leaves=L, num_bins=B, params=p,
                            split_batch=split_batch, owner_shard=True)
        t_dp = dp(shard_rows(mesh8, binned), shard_rows(mesh8, vals),
                  fm, nb, na, is_cat=ic)
        assert int(t_ser.num_leaves) == int(t_dp.num_leaves) > 2
        assert np.asarray(t_ser.is_cat_node)[:int(t_ser.num_leaves) - 1].any()
        for k in ("split_feature", "threshold_bin", "left_child",
                  "right_child", "is_cat_node", "cat_rank"):
            np.testing.assert_array_equal(
                np.asarray(getattr(t_ser, k)), np.asarray(getattr(t_dp, k)),
                err_msg=k)
        np.testing.assert_array_equal(np.asarray(t_ser.leaf_of_row),
                                      np.asarray(t_dp.leaf_of_row))

    def test_efb_group_permutation_tiebreak(self):
        """Exact-gain ties must break toward the LOWEST FEATURE ID like
        serial's flat argmax, even when EFB group order permutes shard
        ownership (lowest-shard-index would pick the wrong duplicate):
        features 0 and 2 are identical columns, but group order is
        permuted so feature 2 lives on shard 0 and feature 0 on shard 1."""
        if len(jax.devices()) < 2:
            pytest.skip("needs 2 devices")
        import lightgbm_tpu.efb as efb_mod
        mesh2 = make_mesh((2,), ("data",))
        n, B, L = 2048, 16, 4
        rng = np.random.RandomState(1)
        fcol = rng.randint(0, B, n).astype(np.uint8)
        y = (fcol >= B // 2).astype(np.float32) \
            + 0.1 * rng.randn(n).astype(np.float32)
        g = (0.5 - y).astype(np.float32)
        vals = np.stack([g, np.ones(n, np.float32),
                         np.ones(n, np.float32)], axis=1)
        # 4 singleton groups, PERMUTED: group g holds feature perm[g]
        # (feature j is in group group_of[j]); features 0 and 2 identical
        group_of = np.array([3, 2, 0, 1], np.int32)
        grouped = np.zeros((n, 4), np.uint8)
        feat_data = {0: fcol, 2: fcol,
                     1: np.zeros(n, np.uint8), 3: np.zeros(n, np.uint8)}
        for j in range(4):
            grouped[:, group_of[j]] = feat_data[j]
        efb_dev = efb_mod.EFBDevice(
            group_of_feat=jnp.asarray(group_of),
            col_idx=jnp.asarray(np.tile(
                np.arange(B, dtype=np.int32)[None], (4, 1))),
            fix0=jnp.asarray(np.zeros(4, bool)),
            off_host=np.full(4, -1, np.int32),
            group_host=group_of, group_bins=B)
        p = SplitParams(min_data_in_leaf=5)
        nb = jnp.full(4, B, jnp.int32)
        na = jnp.full(4, -1, jnp.int32)
        fm = jnp.ones(4, bool)
        serial = make_grower(num_leaves=L, num_bins=B, params=p,
                             efb=efb_dev)
        t_ser = serial(jnp.asarray(grouped), jnp.asarray(vals), fm, nb, na)
        dp = make_dp_grower(mesh2, num_leaves=L, num_bins=B, params=p,
                            efb=efb_dev, owner_shard=True)
        t_dp = dp(shard_rows(mesh2, grouped), shard_rows(mesh2, vals),
                  fm, nb, na)
        assert int(t_ser.num_leaves) > 1
        assert int(np.asarray(t_ser.split_feature)[0]) == 0
        np.testing.assert_array_equal(np.asarray(t_ser.split_feature),
                                      np.asarray(t_dp.split_feature))

    def test_monotone_matches_serial(self, mesh8):
        # monotone 'basic' under owner sharding: the scan sees the owned
        # slice of the constraint vector, partitioning the global one
        rng = np.random.RandomState(3)
        n, f, B, L = 4096, 10, 16, 8
        binned = rng.randint(0, B, size=(n, f)).astype(np.uint8)
        y = (binned[:, 2].astype(np.float32) / B
             + 0.3 * rng.randn(n).astype(np.float32))
        g = (0.5 - y).astype(np.float32)
        vals = np.stack([g, np.ones(n, np.float32),
                         np.ones(n, np.float32)], axis=1)
        mono = np.zeros(f, np.int32)
        mono[2] = 1
        p = SplitParams(min_data_in_leaf=5)
        nb = jnp.full(f, B, jnp.int32)
        na = jnp.full(f, -1, jnp.int32)
        fm = jnp.ones(f, bool)
        serial = make_grower(num_leaves=L, num_bins=B, params=p, mono=mono)
        t_ser = serial(jnp.asarray(binned), jnp.asarray(vals), fm, nb, na)
        dp = make_dp_grower(mesh8, num_leaves=L, num_bins=B, params=p,
                            mono=mono, owner_shard=True)
        t_dp = dp(shard_rows(mesh8, binned), shard_rows(mesh8, vals),
                  fm, nb, na)
        assert int(t_ser.num_leaves) == int(t_dp.num_leaves) > 2
        np.testing.assert_array_equal(np.asarray(t_ser.split_feature),
                                      np.asarray(t_dp.split_feature))
        np.testing.assert_allclose(np.asarray(t_ser.leaf_value),
                                   np.asarray(t_dp.leaf_value),
                                   rtol=1e-4, atol=1e-5)


class TestVotingParallel:
    def test_quality_with_vote_compression(self, mesh8):
        binned, vals = _data(n=4096, f=8)
        F, B, L = binned.shape[1], 16, 8
        p = SplitParams(min_data_in_leaf=5)
        nb = jnp.full(F, B, jnp.int32)
        na = jnp.full(F, -1, jnp.int32)
        fm = jnp.ones(F, bool)
        vp = make_voting_grower(mesh8, num_leaves=L, num_bins=B, params=p,
                                top_k=2)
        t = vp(shard_rows(mesh8, binned), shard_rows(mesh8, vals), fm, nb, na)
        assert int(t.num_leaves) > 2
        # informative feature must still be found despite vote compression
        assert int(np.asarray(t.split_feature)[0]) == 2
        bc = np.bincount(np.asarray(t.leaf_of_row),
                         minlength=int(t.num_leaves))
        np.testing.assert_allclose(bc[:int(t.num_leaves)],
                                   np.asarray(t.leaf_count)[:int(t.num_leaves)])


class TestFeatureParallel:
    def test_matches_serial(self, mesh_feat):
        binned, vals = _data(n=2048, f=8)
        F, B, L = binned.shape[1], 16, 8
        p = SplitParams(min_data_in_leaf=5)
        nb = jnp.full(F, B, jnp.int32)
        na = jnp.full(F, -1, jnp.int32)
        fm = jnp.ones(F, bool)

        serial = make_grower(num_leaves=L, num_bins=B, params=p)
        t_ser = serial(jnp.asarray(binned), jnp.asarray(vals), fm, nb, na)

        fp = make_fp_grower(mesh_feat, num_features=F, num_leaves=L,
                            num_bins=B, params=p)
        t_fp = fp(jnp.asarray(binned), jnp.asarray(vals), fm, nb, na, na)

        assert int(t_ser.num_leaves) == int(t_fp.num_leaves) > 2
        for k in ("split_feature", "threshold_bin", "left_child", "right_child"):
            np.testing.assert_array_equal(np.asarray(getattr(t_ser, k)),
                                          np.asarray(getattr(t_fp, k)),
                                          err_msg=k)
        np.testing.assert_allclose(np.asarray(t_ser.leaf_value),
                                   np.asarray(t_fp.leaf_value),
                                   rtol=1e-4, atol=1e-5)
