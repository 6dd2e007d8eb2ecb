"""Exactness + behaviour tests for the full query-answering pipeline.

The paper's invariant: every method returns the same exact kNN answers.
Hercules (all access paths and ablations) must match brute force.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import (BuildConfig, HerculesIndex, IndexConfig, SearchConfig,
                        brute_force_knn, exact_knn, pscan_knn)
from repro.data import make_query_workload, random_walks

jax.config.update("jax_platform_name", "cpu")


def _index(num=4000, n=128, tau=100, **search_kw):
    data = random_walks(jax.random.PRNGKey(11), num, n)
    search = SearchConfig(**{"k": 5, "l_max": 8, "chunk": 256,
                             "scan_block": 512, **search_kw})
    idx = HerculesIndex.build(
        data, IndexConfig(build=BuildConfig(leaf_capacity=tau), search=search))
    return data, idx


@pytest.fixture(scope="module")
def default_index():
    return _index()


def _assert_exact(res, data, queries, k):
    bf_d, _ = brute_force_knn(data, queries, k)
    np.testing.assert_allclose(np.asarray(res.dists), np.asarray(bf_d),
                               rtol=1e-3, atol=1e-3)


class TestExactness:
    @pytest.mark.parametrize("difficulty", ["1%", "2%", "5%", "10%", "ood"])
    def test_all_difficulties(self, default_index, difficulty):
        data, idx = default_index
        q = make_query_workload(jax.random.PRNGKey(5), data, 16, difficulty)
        _assert_exact(idx.knn(q), data, q, 5)

    @pytest.mark.parametrize("k", [1, 3, 10, 25])
    def test_k_sweep(self, default_index, k):
        data, idx = default_index
        q = make_query_workload(jax.random.PRNGKey(6), data, 8, "5%")
        _assert_exact(idx.knn(q, k=k), data, q, k)

    def test_result_ids_match_distances(self, default_index):
        data, idx = default_index
        q = make_query_workload(jax.random.PRNGKey(8), data, 8, "5%")
        res = idx.knn(q, k=3)
        got = np.asarray(data)[np.asarray(res.ids)]       # (Q, k, n)
        d = ((got - np.asarray(q)[:, None, :]) ** 2).sum(-1)
        np.testing.assert_allclose(d, np.asarray(res.dists), rtol=1e-3, atol=1e-3)

    def test_no_duplicate_results(self, default_index):
        data, idx = default_index
        q = make_query_workload(jax.random.PRNGKey(9), data, 8, "1%")
        res = idx.knn(q, k=10)
        ids = np.asarray(res.ids)
        for row in ids:
            assert len(set(row.tolist())) == len(row)

    def test_query_from_dataset_finds_itself(self, default_index):
        data, idx = default_index
        q = data[:8]
        res = idx.knn(q, k=1)
        np.testing.assert_allclose(np.asarray(res.dists), 0.0, atol=1e-4)
        np.testing.assert_array_equal(np.asarray(res.ids)[:, 0], np.arange(8))

    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 2**31 - 1))
    def test_exactness_property(self, seed):
        data, idx = _index(num=1500, n=64, tau=64)
        q = random_walks(jax.random.PRNGKey(seed % 2**31), 4, 64)
        _assert_exact(idx.knn(q, k=3), data, q, 3)


class TestAccessPaths:
    def test_forced_scan_exact(self, default_index):
        data, idx = default_index
        q = make_query_workload(jax.random.PRNGKey(10), data, 8, "10%")
        res = idx.knn(q, force_scan=True)
        assert (np.asarray(res.path) == 3).all()
        _assert_exact(res, data, q, 5)

    def test_nosax_exact(self, default_index):
        data, idx = default_index
        q = make_query_workload(jax.random.PRNGKey(12), data, 8, "5%")
        _assert_exact(idx.knn(q, use_sax=False), data, q, 5)

    def test_nothresh_exact(self, default_index):
        data, idx = default_index
        q = make_query_workload(jax.random.PRNGKey(13), data, 8, "5%")
        res = idx.knn(q, adaptive=False)
        assert (np.asarray(res.path) == 2).all()
        _assert_exact(res, data, q, 5)

    def test_thresholds_trigger_scan(self, default_index):
        """With EAPCA_TH=1.0 every query must take the scan path (ratio<1)."""
        data, idx = default_index
        q = make_query_workload(jax.random.PRNGKey(14), data, 4, "5%")
        res = idx.knn(q, eapca_th=1.01)
        assert (np.asarray(res.path) == 0).all()
        _assert_exact(res, data, q, 5)

    def test_pruning_reduces_access(self, default_index):
        """Easy queries must touch far less data than the scan (paper Fig 10)."""
        data, idx = default_index
        q = make_query_workload(jax.random.PRNGKey(15), data, 8, "1%")
        res = idx.knn(q, k=1)
        frac = np.asarray(res.accessed).mean() / data.shape[0]
        assert frac < 0.5, f"accessed fraction {frac:.2f}"

    def test_sax_prunes_more_than_eapca_alone(self, default_index):
        data, idx = default_index
        q = make_query_workload(jax.random.PRNGKey(16), data, 8, "2%")
        with_sax = idx.knn(q, k=1, adaptive=False)
        without = idx.knn(q, k=1, adaptive=False, use_sax=False)
        assert np.asarray(with_sax.accessed).mean() <= \
            np.asarray(without.accessed).mean() + 1e-6


class TestBaselines:
    def test_pscan_matches_brute_force(self, default_index):
        data, idx = default_index
        q = make_query_workload(jax.random.PRNGKey(17), data, 8, "5%")
        d, p = pscan_knn(data, q, k=5, block=512)
        bf_d, _ = brute_force_knn(data, q, 5)
        np.testing.assert_allclose(np.asarray(d), np.asarray(bf_d),
                                   rtol=1e-3, atol=1e-3)

    def test_pscan_ragged_tail(self):
        data = random_walks(jax.random.PRNGKey(18), 777, 64)
        q = data[:4]
        d, p = pscan_knn(data, q, k=1, block=256)
        np.testing.assert_allclose(np.asarray(d)[:, 0], 0.0, atol=1e-4)
        np.testing.assert_array_equal(np.asarray(p)[:, 0], np.arange(4))


class TestPersistence:
    def test_save_load_roundtrip(self, default_index, tmp_path):
        data, idx = default_index
        path = str(tmp_path / "hercules.npz")
        idx.save(path)
        idx2 = HerculesIndex.load(path)
        q = make_query_workload(jax.random.PRNGKey(19), data, 4, "5%")
        r1 = idx.knn(q, k=3)
        r2 = idx2.knn(q, k=3)
        np.testing.assert_allclose(np.asarray(r1.dists), np.asarray(r2.dists))
        np.testing.assert_array_equal(np.asarray(r1.ids), np.asarray(r2.ids))


class TestApproximate:
    def test_approx_never_better_than_exact(self, default_index):
        data, idx = default_index
        q = make_query_workload(jax.random.PRNGKey(20), data, 8, "5%")
        d_approx, ids = idx.knn_approx(q, k=5)
        bf_d, _ = brute_force_knn(data, q, 5)
        # tolerance matches the suite's exactness convention: the brute-force
        # oracle computes distances in matmul-identity form, whose fp32 noise
        # is relative to the distance magnitude
        bf = np.asarray(bf_d)
        assert (np.asarray(d_approx) >= bf - 1e-3 - 1e-3 * np.abs(bf)).all()

    def test_approx_recall_improves_with_lmax(self, default_index):
        data, idx = default_index
        q = make_query_workload(jax.random.PRNGKey(21), data, 8, "5%")
        _, bf_i = brute_force_knn(data, q, 5)

        def recall(l_max):
            _, ids = idx.knn_approx(q, k=5, l_max=l_max)
            return np.mean([len(set(np.asarray(ids)[i])
                                & set(np.asarray(bf_i)[i])) / 5
                            for i in range(8)])

        assert recall(16) >= recall(1) - 1e-9
        assert recall(16) > 0.5


class TestTopkRefine:
    """§Perf iteration 5: top-k candidate selection instead of full argsort."""

    def test_topk_mode_exact(self, default_index):
        data, idx = default_index
        q = make_query_workload(jax.random.PRNGKey(22), data, 8, "5%")
        res = idx.knn(q, refine_select="topk")
        _assert_exact(res, data, q, 5)

    def test_topk_budget_exhaustion_falls_back(self, default_index):
        """A 1-chunk budget forces the scan fallback; answers stay exact."""
        data, idx = default_index
        q = make_query_workload(jax.random.PRNGKey(23), data, 8, "ood")
        res = idx.knn(q, refine_select="topk", topk_budget_chunks=1,
                      adaptive=False)
        _assert_exact(res, data, q, 5)


class TestPaddedSlots:
    """``exact_knn(..., n_valid=)``: rows past the count skip the pipeline."""

    Q = 8

    @pytest.fixture(scope="class")
    def workload(self, default_index):
        data, idx = default_index
        # easy and out-of-distribution rows: both access paths run
        q = jnp.concatenate([
            make_query_workload(jax.random.PRNGKey(31), data, 4, "1%"),
            make_query_workload(jax.random.PRNGKey(32), data, 4, "ood")])
        full = exact_knn(idx.tree, idx.layout, q, idx.config.search,
                         idx.max_depth)
        return q, full

    @pytest.mark.parametrize("n_valid", [1, Q // 2, Q, None])
    def test_real_rows_bitwise_padded_rows_placeholder(self, default_index,
                                                       workload, n_valid):
        _, idx = default_index
        q, full = workload
        res = exact_knn(idx.tree, idx.layout, q, idx.config.search,
                        idx.max_depth,
                        None if n_valid is None else jnp.int32(n_valid))
        n = self.Q if n_valid is None else n_valid
        assert {int(p) for p in np.asarray(full.path)} >= {0, 2}
        for name, got, want in zip(res._fields, res, full):
            got, want = np.asarray(got), np.asarray(want)
            assert got.shape == want.shape and got.dtype == want.dtype, name
            np.testing.assert_array_equal(got[:n], want[:n], err_msg=name)
        pad = slice(n, None)
        assert np.all(np.isposinf(np.asarray(res.dists)[pad]))
        for name in ("positions", "ids", "path"):
            assert np.all(np.asarray(getattr(res, name))[pad] == -1), name
        for name in ("eapca_pr", "sax_pr", "accessed", "visited_leaves"):
            assert np.all(np.asarray(getattr(res, name))[pad] == 0), name
