"""Unit tests for histogram construction, split search and the tree grower."""

import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.ops.histogram import compute_histogram
from lightgbm_tpu.ops.split import SplitParams, find_best_split, leaf_output
from lightgbm_tpu.grower import make_grower


def _ref_hist(binned, vals, B):
    n, f = binned.shape
    ref = np.zeros((f, B, vals.shape[1]))
    for fi in range(f):
        for b in range(B):
            m = binned[:, fi] == b
            ref[fi, b] = vals[m].sum(axis=0)
    return ref


class TestHistogram:
    def test_matches_reference_loop(self):
        rng = np.random.RandomState(0)
        N, F, B = 2000, 5, 16
        binned = rng.randint(0, B, size=(N, F)).astype(np.uint8)
        g = rng.randn(N).astype(np.float32)
        vals = np.stack([g, np.abs(g), np.ones(N, np.float32)], axis=1)
        hist = np.array(compute_histogram(jnp.array(binned), jnp.array(vals), num_bins=B))
        ref = _ref_hist(binned, vals, B)
        np.testing.assert_allclose(hist, ref, rtol=1e-4, atol=1e-3)

    def test_masked_rows_excluded(self):
        rng = np.random.RandomState(1)
        N, F, B = 512, 3, 8
        binned = rng.randint(0, B, size=(N, F)).astype(np.uint8)
        vals = np.ones((N, 3), np.float32)
        mask = (rng.rand(N) < 0.5).astype(np.float32)
        hist = np.array(compute_histogram(
            jnp.array(binned), jnp.array(vals * mask[:, None]), num_bins=B))
        assert hist[0, :, 2].sum() == pytest.approx(mask.sum())

    def test_nonuniform_block(self):
        # N not divisible by block_rows exercises the padding path
        rng = np.random.RandomState(2)
        N, F, B = 1037, 4, 8
        binned = rng.randint(0, B, size=(N, F)).astype(np.uint8)
        vals = np.ones((N, 3), np.float32)
        hist = np.array(compute_histogram(jnp.array(binned), jnp.array(vals),
                                          num_bins=B, block_rows=256))
        assert hist[2, :, 2].sum() == pytest.approx(N)


class TestSplit:
    def _mk(self, binned, g, h, B):
        N, F = binned.shape
        vals = np.stack([g, h, np.ones(N, np.float32)], axis=1)
        # the scan takes its histogram channel-major, [3, F, B]
        hist = compute_histogram(jnp.array(binned), jnp.array(vals), num_bins=B,
                                 channel_major=True)
        total = jnp.asarray(vals.sum(axis=0), dtype=jnp.float32)
        return hist, total

    def test_finds_informative_feature(self):
        rng = np.random.RandomState(0)
        N, F, B = 4000, 6, 16
        binned = rng.randint(0, B, size=(N, F)).astype(np.uint8)
        y = (binned[:, 2] >= 8).astype(np.float32)
        g = (0.5 - y).astype(np.float32)
        h = np.ones(N, np.float32)
        hist, total = self._mk(binned, g, h, B)
        res = find_best_split(hist, total, jnp.full(F, B, jnp.int32),
                              jnp.full(F, -1, jnp.int32), jnp.ones(F, bool),
                              SplitParams(min_data_in_leaf=5))
        assert int(res.feature) == 2
        assert int(res.threshold) == 7  # left = bins <= 7
        assert float(res.gain) > 0

    def test_gain_matches_closed_form(self):
        # two bins, exact gain formula: GL^2/HL + GR^2/HR - G^2/H
        binned = np.array([[0], [0], [1], [1]], dtype=np.uint8)
        g = np.array([-1.0, -1.0, 1.0, 2.0], np.float32)
        h = np.ones(4, np.float32)
        hist, total = self._mk(binned, g, h, 2)
        p = SplitParams(min_data_in_leaf=1, min_sum_hessian_in_leaf=0.0)
        res = find_best_split(hist, total, jnp.full(1, 2, jnp.int32),
                              jnp.full(1, -1, jnp.int32), jnp.ones(1, bool), p)
        expect = (-2.0) ** 2 / 2 + 3.0 ** 2 / 2 - 1.0 ** 2 / 4
        assert float(res.gain) == pytest.approx(expect, rel=1e-5)
        assert float(res.left_output) == pytest.approx(1.0)   # -(-2)/2
        assert float(res.right_output) == pytest.approx(-1.5)  # -(3)/2

    def test_min_data_constraint(self):
        binned = np.array([[0], [1], [1], [1]], dtype=np.uint8)
        g = np.array([-5.0, 1.0, 1.0, 1.0], np.float32)
        h = np.ones(4, np.float32)
        hist, total = self._mk(binned, g, h, 2)
        p = SplitParams(min_data_in_leaf=2, min_sum_hessian_in_leaf=0.0)
        res = find_best_split(hist, total, jnp.full(1, 2, jnp.int32),
                              jnp.full(1, -1, jnp.int32), jnp.ones(1, bool), p)
        assert float(res.gain) == -np.inf  # only split leaves 1 row left

    def test_lambda_l2_shrinks_output(self):
        binned = np.array([[0], [0], [1], [1]], dtype=np.uint8)
        g = np.array([-1.0, -1.0, 1.0, 1.0], np.float32)
        h = np.ones(4, np.float32)
        hist, total = self._mk(binned, g, h, 2)
        p = SplitParams(min_data_in_leaf=1, min_sum_hessian_in_leaf=0.0, lambda_l2=2.0)
        res = find_best_split(hist, total, jnp.full(1, 2, jnp.int32),
                              jnp.full(1, -1, jnp.int32), jnp.ones(1, bool), p)
        assert float(res.left_output) == pytest.approx(2.0 / 4.0)  # -(-2)/(2+2)

    def test_missing_direction(self):
        # feature with NaN bin: put strong negative grads in the NaN bin;
        # best dir should send missing left with the negative group
        B = 4
        binned = np.concatenate([
            np.zeros(50, np.uint8), np.ones(50, np.uint8) * 1,
            np.ones(30, np.uint8) * 3,  # na bin
        ]).reshape(-1, 1)
        g = np.concatenate([-np.ones(50), np.ones(50), -np.ones(30)]).astype(np.float32)
        h = np.ones(130, np.float32)
        hist, total = self._mk(binned, g, h, B)
        p = SplitParams(min_data_in_leaf=1, min_sum_hessian_in_leaf=0.0)
        res = find_best_split(hist, total, jnp.full(1, 4, jnp.int32),
                              jnp.full(1, 3, jnp.int32), jnp.ones(1, bool), p)
        assert bool(res.default_left)
        assert int(res.threshold) == 0


class TestGrower:
    def test_grows_and_partitions(self):
        rng = np.random.RandomState(0)
        N, F, B, L = 5000, 6, 16, 8
        binned = rng.randint(0, B, size=(N, F)).astype(np.uint8)
        y = (binned[:, 2] >= 8).astype(np.float32) + 0.1 * rng.randn(N).astype(np.float32)
        g = (0.5 - y).astype(np.float32)
        vals = np.stack([g, np.ones(N, np.float32), np.ones(N, np.float32)], axis=1)
        grow = make_grower(num_leaves=L, num_bins=B, params=SplitParams(min_data_in_leaf=5))
        tree = grow(jnp.array(binned), jnp.array(vals), jnp.ones(F, bool),
                    jnp.full(F, B, jnp.int32), jnp.full(F, -1, jnp.int32))
        nl = int(tree.num_leaves)
        assert 2 <= nl <= L
        # leaf counts of active leaves sum to N
        assert float(np.array(tree.leaf_count)[:nl].sum()) == pytest.approx(N)
        # row partition agrees with leaf counts
        bc = np.bincount(np.array(tree.leaf_of_row), minlength=L)
        np.testing.assert_allclose(bc[:nl], np.array(tree.leaf_count)[:nl])
        # first split must use the informative feature
        assert int(np.array(tree.split_feature)[0]) == 2

    def test_partition_consistent_with_tree(self):
        """Rows' final leaves must equal a traversal of the built tree."""
        rng = np.random.RandomState(3)
        N, F, B, L = 2000, 5, 8, 6
        binned = rng.randint(0, B, size=(N, F)).astype(np.uint8)
        g = rng.randn(N).astype(np.float32)
        vals = np.stack([g, np.ones(N, np.float32), np.ones(N, np.float32)], axis=1)
        grow = make_grower(num_leaves=L, num_bins=B, params=SplitParams(min_data_in_leaf=10))
        tree = grow(jnp.array(binned), jnp.array(vals), jnp.ones(F, bool),
                    jnp.full(F, B, jnp.int32), jnp.full(F, -1, jnp.int32))
        nl = int(tree.num_leaves)
        sf = np.array(tree.split_feature)
        th = np.array(tree.threshold_bin)
        lc = np.array(tree.left_child)
        rc = np.array(tree.right_child)
        leaves = np.array(tree.leaf_of_row)
        if nl < 2:
            pytest.skip("no split found")
        for i in rng.choice(N, 200, replace=False):
            node = 0
            while node >= 0:
                node = lc[node] if binned[i, sf[node]] <= th[node] else rc[node]
            assert ~node == leaves[i]

    def test_max_depth(self):
        rng = np.random.RandomState(4)
        N, F, B, L = 3000, 6, 16, 16
        binned = rng.randint(0, B, size=(N, F)).astype(np.uint8)
        g = rng.randn(N).astype(np.float32)
        vals = np.stack([g, np.ones(N, np.float32), np.ones(N, np.float32)], axis=1)
        grow = make_grower(num_leaves=L, num_bins=B,
                           params=SplitParams(min_data_in_leaf=5), max_depth=2)
        tree = grow(jnp.array(binned), jnp.array(vals), jnp.ones(F, bool),
                    jnp.full(F, B, jnp.int32), jnp.full(F, -1, jnp.int32))
        assert int(tree.num_leaves) <= 4  # depth-2 tree has at most 4 leaves
        assert int(np.array(tree.leaf_depth)[:int(tree.num_leaves)].max()) <= 2




class TestPathSmooth:
    """path_smooth parity with the reference formula
    (feature_histogram.hpp:742-764): the smoothing weight uses the leaf's
    DATA COUNT, not its hessian sum — they differ for every
    non-unit-hessian objective — and max_delta_step clamps BEFORE the
    smoothing blend."""

    @staticmethod
    def _ref_output(g, h, l1, l2, mds, smooth, n, parent):
        t = np.sign(g) * max(abs(g) - l1, 0.0) if l1 > 0 else g
        ret = -t / (h + l2)
        if mds > 0 and abs(ret) > mds:
            ret = np.sign(ret) * mds
        if smooth > 0:
            ret = (ret * (n / smooth) / (n / smooth + 1)
                   + parent / (n / smooth + 1))
        return ret

    def test_leaf_output_formula_weighted(self):
        # hessian sum deliberately != data count (binary-like hessians)
        cases = [
            (3.7, 12.4, 0.0, 1.0, 0.0, 5.0, 80.0, -0.3),
            (-2.1, 4.9, 0.5, 0.1, 0.0, 2.0, 33.0, 0.7),
            (9.0, 1.5, 0.0, 0.0, 0.5, 10.0, 400.0, 0.1),  # clamp then smooth
            (-6.2, 2.2, 1.0, 2.0, 0.3, 1.0, 7.0, -1.4),
        ]
        for g, h, l1, l2, mds, smooth, n, parent in cases:
            p = SplitParams(lambda_l1=l1, lambda_l2=l2, max_delta_step=mds,
                            path_smooth=smooth)
            got = float(leaf_output(jnp.float32(g), jnp.float32(h), p,
                                    jnp.float32(parent), jnp.float32(n)))
            want = self._ref_output(g, h, l1, l2, mds, smooth, n, parent)
            np.testing.assert_allclose(got, want, rtol=1e-5)

    def test_grown_leaf_values_match_formula(self):
        # grow one 2-leaf tree with NON-UNIT hessians and check both leaf
        # values against the reference formula using per-leaf (g, h, count)
        # sums recomputed host-side
        rng = np.random.RandomState(5)
        N, B, smooth = 600, 16, 4.0
        binned = rng.randint(0, B, size=(N, 2)).astype(np.uint8)
        g = rng.randn(N).astype(np.float32)
        h = (0.05 + rng.rand(N) * 0.4).astype(np.float32)   # h != 1
        vals = jnp.asarray(np.stack([g, h, np.ones(N, np.float32)], axis=1))
        p = SplitParams(path_smooth=smooth, min_data_in_leaf=5)
        grow = make_grower(num_leaves=2, num_bins=B, params=p)
        tree = grow(jnp.asarray(binned), vals,
                    jnp.ones(2, bool), jnp.full(2, B, jnp.int32),
                    jnp.full(2, -1, jnp.int32))
        assert int(tree.num_leaves) == 2
        leaf_of_row = np.asarray(tree.leaf_of_row)
        root_parent = self._ref_output(g.sum(), h.sum(), 0, 0, 0, 0, N, 0)
        for leaf in (0, 1):
            m = leaf_of_row == leaf
            want = self._ref_output(g[m].sum(), h[m].sum(), 0.0, 0.0, 0.0,
                                    smooth, m.sum(), root_parent)
            np.testing.assert_allclose(float(tree.leaf_value[leaf]), want,
                                       rtol=2e-4)
            # the hessian-weight approximation would differ measurably here
            wrong = self._ref_output(g[m].sum(), h[m].sum(), 0.0, 0.0, 0.0,
                                     smooth, h[m].sum(), root_parent)
            assert abs(want - wrong) > 1e-3
