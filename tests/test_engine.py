"""Unified QueryEngine surface: backend parity, plan cache, serving.

The api_redesign acceptance criteria live here:
  * the same workload through LocalBackend / ScanBackend / (single-device
    degenerate) ShardedBackend answers with bit-identical exact top-k
    distances;
  * a repeated same-bucket knn call is a plan-cache hit with zero new
    compiles (plans are AOT executables — a hit cannot retrace);
  * per-call overrides (k, l_max, thresholds, and any chunk/scan_block
    dividing the padded layout) no longer raise pad-multiple errors.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (BuildConfig, EngineConfig, HerculesIndex, IndexConfig,
                        LocalBackend, QueryEngine, ScanBackend, SearchBackend,
                        SearchConfig, ShardedBackend, brute_force_knn,
                        make_backend)
from repro.data import make_query_workload, random_walks
from repro.serve import (KnnAnswer, KnnFailure, KnnServeConfig,
                         KnnServeEngine, QueueFull)

jax.config.update("jax_platform_name", "cpu")

NUM, LEN, K = 2000, 64, 3
CFG = IndexConfig(build=BuildConfig(leaf_capacity=64),
                  search=SearchConfig(k=K, l_max=4, chunk=128, scan_block=256))


@pytest.fixture(scope="module")
def data():
    return random_walks(jax.random.PRNGKey(0), NUM, LEN)


@pytest.fixture(scope="module")
def queries(data):
    # mixed difficulty so both access paths (scan + pruned refinement) occur
    easy = make_query_workload(jax.random.PRNGKey(1), data, 4, "1%")
    hard = make_query_workload(jax.random.PRNGKey(2), data, 4, "ood")
    return jnp.concatenate([easy, hard])


@pytest.fixture(scope="module")
def local(data):
    return QueryEngine(LocalBackend(HerculesIndex.build(data, CFG)))


class TestBackendParity:
    def test_local_is_exact(self, data, queries, local):
        res = local.knn(queries)
        bf_d, _ = brute_force_knn(data, queries, K)
        np.testing.assert_allclose(np.asarray(res.dists), np.asarray(bf_d),
                                   rtol=1e-3, atol=1e-3)

    def test_scan_matches_local_bitwise(self, data, queries, local):
        scan = QueryEngine(ScanBackend(data, CFG.search))
        r_local = local.knn(queries)
        r_scan = scan.knn(queries)
        assert np.array_equal(np.asarray(r_local.dists),
                              np.asarray(r_scan.dists))
        assert np.array_equal(np.sort(np.asarray(r_local.ids), axis=1),
                              np.sort(np.asarray(r_scan.ids), axis=1))

    def test_sharded_single_device_matches_local_bitwise(
            self, data, queries, local):
        sharded = QueryEngine(
            make_backend("sharded", data, index_config=CFG, num_shards=1))
        r_local = local.knn(queries)
        r_shard = sharded.knn(queries)
        assert np.array_equal(np.asarray(r_local.dists),
                              np.asarray(r_shard.dists))
        assert np.array_equal(np.sort(np.asarray(r_local.ids), axis=1),
                              np.sort(np.asarray(r_shard.ids), axis=1))

    def test_scan_mxu_is_exact(self, data, queries):
        scan = QueryEngine(ScanBackend(data, CFG.search, mxu=True))
        bf_d, _ = brute_force_knn(data, queries, K)
        np.testing.assert_allclose(np.asarray(scan.knn(queries).dists),
                                   np.asarray(bf_d), rtol=1e-3, atol=1e-3)

    def test_backends_conform_to_protocol(self, data):
        for b in (LocalBackend(HerculesIndex.build(data, CFG)),
                  ScanBackend(data, CFG.search)):
            assert isinstance(b, SearchBackend)
            assert b.describe()["backend"] == b.name


class TestKernelModeParity:
    """Kernelization acceptance: for every ``kernel_mode``, every backend
    answers with bit-identical top-k distances and the same id sets.

    ``ref`` runs the jnp oracles; ``interpret`` routes the hot path through
    the Pallas kernel bodies (ScanBackend ED via ops.ed_matrix/ed_min,
    phase-3 LB_SAX pruning via ops.lb_sax) on the interpreter — the same
    code Mosaic compiles on TPU. ``kernel_mode`` is a per-call override, so
    these also prove a serving engine can flip modes without a rebuild.
    """

    MODES = ("ref", "interpret")

    @staticmethod
    def _assert_same(a, b):
        assert np.array_equal(np.asarray(a.dists), np.asarray(b.dists))
        assert np.array_equal(np.sort(np.asarray(a.ids), axis=1),
                              np.sort(np.asarray(b.ids), axis=1))

    def test_local_bitwise_across_modes(self, queries, local):
        base = local.knn(queries, kernel_mode="ref")
        for mode in self.MODES:
            self._assert_same(local.knn(queries, kernel_mode=mode), base)

    def test_scan_bitwise_across_modes_and_vs_local(self, data, queries,
                                                    local):
        scan = QueryEngine(ScanBackend(data, CFG.search))
        base = local.knn(queries, kernel_mode="ref")
        for mode in self.MODES:
            self._assert_same(scan.knn(queries, kernel_mode=mode), base)

    def test_scan_k1_fused_ed_min_bitwise(self, data, queries):
        # k=1 takes the fused ops.ed_min kernel path, not blocked ed_matrix
        scan = QueryEngine(ScanBackend(data, CFG.search))
        base = scan.knn(queries, k=1, kernel_mode="ref")
        got = scan.knn(queries, k=1, kernel_mode="interpret")
        assert np.array_equal(np.asarray(base.dists), np.asarray(got.dists))
        assert np.array_equal(np.asarray(base.ids), np.asarray(got.ids))

    def test_sharded_bitwise_across_modes(self, data, queries, local):
        sharded = QueryEngine(
            make_backend("sharded", data, index_config=CFG, num_shards=1))
        base = local.knn(queries, kernel_mode="ref")
        for mode in self.MODES:
            self._assert_same(sharded.knn(queries, kernel_mode=mode), base)

    def test_mode_is_a_plan_cache_key(self, data, queries):
        eng = QueryEngine(LocalBackend(HerculesIndex.build(data, CFG)))
        eng.knn(queries, kernel_mode="ref")
        eng.knn(queries, kernel_mode="interpret")
        eng.knn(queries, kernel_mode="ref")        # must hit, not recompile
        pc = eng.telemetry()["plan_cache"]
        assert (pc["misses"], pc["hits"]) == (2, 1)

    def test_invalid_mode_rejected(self, local):
        with pytest.raises(ValueError, match="kernel_mode"):
            local.knn(jnp.zeros((1, LEN)), kernel_mode="bogus")


class TestPlanCache:
    def test_repeat_call_hits_zero_compiles(self, data, queries):
        eng = QueryEngine(LocalBackend(HerculesIndex.build(data, CFG)))
        eng.knn(queries)
        t1 = eng.telemetry()["plan_cache"]
        assert (t1["misses"], t1["hits"], t1["compiles"]) == (1, 0, 1)
        r2 = eng.knn(queries)
        t2 = eng.telemetry()["plan_cache"]
        assert (t2["misses"], t2["hits"], t2["compiles"]) == (1, 1, 1)
        bf_d, _ = brute_force_knn(data, queries, K)
        np.testing.assert_allclose(np.asarray(r2.dists), np.asarray(bf_d),
                                   rtol=1e-3, atol=1e-3)

    def test_same_bucket_different_batch_size_hits(self, data, local):
        before = local.telemetry()["plan_cache"]
        q5 = make_query_workload(jax.random.PRNGKey(3), data, 5, "5%")
        q7 = make_query_workload(jax.random.PRNGKey(4), data, 7, "5%")
        r5 = local.knn(q5)          # bucket 8
        r7 = local.knn(q7)          # same bucket -> must not compile again
        after = local.telemetry()["plan_cache"]
        assert after["compiles"] <= before["compiles"] + 1
        assert r5.dists.shape == (5, K) and r7.dists.shape == (7, K)
        bf_d, _ = brute_force_knn(data, q7, K)
        np.testing.assert_allclose(np.asarray(r7.dists), np.asarray(bf_d),
                                   rtol=1e-3, atol=1e-3)

    def test_distinct_config_compiles_new_plan(self, data, queries):
        eng = QueryEngine(LocalBackend(HerculesIndex.build(data, CFG)))
        eng.knn(queries, k=1)
        eng.knn(queries, k=2)
        pc = eng.telemetry()["plan_cache"]
        assert pc["misses"] == 2 and pc["size"] == 2

    def test_lru_eviction(self, data, queries):
        eng = QueryEngine(LocalBackend(HerculesIndex.build(data, CFG)),
                          EngineConfig(plan_cache_size=1))
        eng.knn(queries, k=1)
        eng.knn(queries, k=2)
        pc = eng.telemetry()["plan_cache"]
        assert pc["size"] == 1 and pc["evictions"] == 1

    def test_explicit_buckets(self, data):
        eng = QueryEngine(LocalBackend(HerculesIndex.build(data, CFG)),
                          EngineConfig(bucket_sizes=(16,)))
        q3 = make_query_workload(jax.random.PRNGKey(5), data, 3, "5%")
        q9 = make_query_workload(jax.random.PRNGKey(6), data, 9, "5%")
        eng.knn(q3)
        eng.knn(q9)                 # both land in the single 16-wide bucket
        pc = eng.telemetry()["plan_cache"]
        assert (pc["misses"], pc["hits"]) == (1, 1)


class TestOverrides:
    def test_per_call_knobs_no_longer_raise(self, data, queries, local):
        res = local.knn(queries, k=5, l_max=2, use_sax=False, adaptive=False)
        bf_d, _ = brute_force_knn(data, queries, 5)
        np.testing.assert_allclose(np.asarray(res.dists), np.asarray(bf_d),
                                   rtol=1e-3, atol=1e-3)

    def test_divisor_chunk_override_accepted(self, data, queries, local):
        n_pad = local.backend.index.layout.lrd.shape[0]
        assert n_pad % 64 == 0
        res = local.knn(queries, chunk=64, scan_block=64)
        bf_d, _ = brute_force_knn(data, queries, K)
        np.testing.assert_allclose(np.asarray(res.dists), np.asarray(bf_d),
                                   rtol=1e-3, atol=1e-3)

    def test_non_divisor_override_rejected(self, data, local):
        n_pad = local.backend.index.layout.lrd.shape[0]
        bad = n_pad - 1             # never divides a padded size > 1
        with pytest.raises(ValueError, match="divide"):
            local.knn(jnp.zeros((1, LEN)), scan_block=bad)

    def test_index_knn_divisor_override(self, data, queries):
        # the old pad-multiple equality check rejected this valid override
        idx = HerculesIndex.build(data, CFG)
        res = idx.knn(queries, k=K, scan_block=128)
        bf_d, _ = brute_force_knn(data, queries, K)
        np.testing.assert_allclose(np.asarray(res.dists), np.asarray(bf_d),
                                   rtol=1e-3, atol=1e-3)


class TestTelemetry:
    def test_paths_and_pruning_accumulate(self, data, queries):
        eng = QueryEngine(LocalBackend(HerculesIndex.build(data, CFG)))
        eng.knn(queries)
        t = eng.telemetry()
        assert t["backend"] == "local"
        assert sum(t["paths"].values()) == queries.shape[0]
        assert 0.0 <= t["pruning"]["eapca_mean"] <= 1.0
        assert t["latency_s"]["total"] > 0
        assert t["queries"] == queries.shape[0]

    def test_describe_lists_cached_plans(self, data, queries):
        eng = QueryEngine(LocalBackend(HerculesIndex.build(data, CFG)))
        eng.knn(queries)
        d = eng.describe()
        assert d["backend"]["backend"] == "local"
        assert len(d["engine"]["cached_plans"]) == 1


class TestKnnServeEngine:
    def test_submit_poll_drain(self, data):
        eng = QueryEngine(LocalBackend(HerculesIndex.build(data, CFG)))
        serve = KnnServeEngine(eng, KnnServeConfig(batch_slots=4))
        workload = np.asarray(
            make_query_workload(jax.random.PRNGKey(7), data, 10, "5%"))
        rids = [serve.submit(q) for q in workload]
        assert serve.poll(rids[0]) is None and serve.pending() == 10
        answers = serve.drain()
        assert set(answers) == set(rids) and serve.pending() == 0
        got = np.stack([answers[r].dists for r in rids])
        bf_d, _ = brute_force_knn(data, jnp.asarray(workload), K)
        np.testing.assert_allclose(got, np.asarray(bf_d),
                                   rtol=1e-3, atol=1e-3)
        assert isinstance(answers[rids[0]], KnnAnswer)
        # drain claimed every answer: results are handed out exactly once
        assert serve.poll(rids[0]) is None
        assert serve.telemetry()["serving"]["unclaimed"] == 0
        # 3 waves, every wave padded to the slot pool -> exactly one plan
        tele = serve.telemetry()
        pc = tele["plan_cache"]
        assert (pc["misses"], pc["hits"]) == (1, 2)
        # slot padding must not pollute telemetry: 10 real queries only
        assert tele["queries"] == 10
        assert sum(tele["paths"].values()) == 10

    def test_step_serves_one_wave(self, data):
        eng = QueryEngine(LocalBackend(HerculesIndex.build(data, CFG)))
        serve = KnnServeEngine(eng, KnnServeConfig(batch_slots=4))
        for q in np.asarray(
                make_query_workload(jax.random.PRNGKey(8), data, 6, "5%")):
            serve.submit(q)
        assert serve.step() == 4 and serve.pending() == 2
        assert serve.step() == 2 and serve.pending() == 0
        assert serve.step() == 0

    def test_mixed_k_groups_into_sub_waves(self, data):
        # regression: interleaved k=1/k=2 traffic used to raise ValueError
        # and requeue the wave at the head — drain() then re-selected the
        # same incompatible wave forever (livelock). Mixed signatures must
        # instead serve as compatible sub-waves, in submission order.
        eng = QueryEngine(LocalBackend(HerculesIndex.build(data, CFG)))
        serve = KnnServeEngine(eng, KnnServeConfig(batch_slots=4))
        q = np.asarray(make_query_workload(
            jax.random.PRNGKey(9), data, 10, "5%"))
        ks = [1 if i % 2 == 0 else 2 for i in range(10)]
        rids = [serve.submit(qi, k=k) for qi, k in zip(q, ks)]
        # head is k=1: its sub-wave takes the 4 oldest k=1 requests only
        assert serve.step() == 4 and serve.pending() == 6
        answers = serve.drain()
        assert set(answers) == set(rids) and serve.pending() == 0
        for k in (1, 2):
            rows = [i for i, kk in enumerate(ks) if kk == k]
            got = np.stack([answers[rids[i]].dists for i in rows])
            assert got.shape == (len(rows), k)
            bf_d, _ = brute_force_knn(data, jnp.asarray(q[rows]), k)
            np.testing.assert_allclose(got, np.asarray(bf_d),
                                       rtol=1e-3, atol=1e-3)
        # 4 sub-waves: 4×k=1, then 4×k=2, then the k=1 and k=2 stragglers
        sv = serve.telemetry()["serving"]
        assert sv["failed"] == 0 and sv["waves"] == 4

    def test_poisoned_request_fails_alone(self, data):
        # regression: one invalid request used to poison its whole wave
        # (np.stack raised before any member was served). It must now
        # complete as a claimable KnnFailure while its wave-mates answer.
        eng = QueryEngine(LocalBackend(HerculesIndex.build(data, CFG)))
        serve = KnnServeEngine(eng, KnnServeConfig(batch_slots=4))
        good = np.asarray(make_query_workload(
            jax.random.PRNGKey(10), data, 3, "5%"))
        g0 = serve.submit(good[0])
        bad = serve.submit(np.zeros(LEN // 2, np.float32))  # wrong length
        g1 = serve.submit(good[1])
        g2 = serve.submit(good[2])
        answers = serve.drain()
        assert serve.pending() == 0
        assert isinstance(answers[bad], KnnFailure)
        assert "ValueError" in answers[bad].error
        got = np.stack([answers[r].dists for r in (g0, g1, g2)])
        bf_d, _ = brute_force_knn(data, jnp.asarray(good), K)
        np.testing.assert_allclose(got, np.asarray(bf_d),
                                   rtol=1e-3, atol=1e-3)
        assert serve.telemetry()["serving"]["failed"] == 1

    def test_admission_control_queue_full(self, data):
        eng = QueryEngine(LocalBackend(HerculesIndex.build(data, CFG)))
        serve = KnnServeEngine(
            eng, KnnServeConfig(batch_slots=2, max_queue=3))
        q = np.asarray(make_query_workload(
            jax.random.PRNGKey(11), data, 5, "5%"))
        for i in range(3):
            serve.submit(q[i])
        with pytest.raises(QueueFull):
            serve.submit(q[3])
        assert serve.telemetry()["serving"]["rejected"] == 1
        serve.step()                      # frees two slots
        serve.submit(q[3])                # backpressure retry succeeds
        serve.drain()
        assert serve.pending() == 0

    def test_difficulty_packing_serves_everything(self, data):
        eng = QueryEngine(LocalBackend(HerculesIndex.build(data, CFG)))
        serve = KnnServeEngine(
            eng, KnnServeConfig(batch_slots=4, pack="difficulty"))
        easy = np.asarray(make_query_workload(
            jax.random.PRNGKey(12), data, 5, "1%"))
        hard = np.asarray(make_query_workload(
            jax.random.PRNGKey(13), data, 5, "ood"))
        q = np.concatenate([easy, hard])
        order = [0, 5, 1, 6, 2, 7, 3, 8, 4, 9]   # interleave easy/hard
        rids = [serve.submit(q[i]) for i in order]
        answers = serve.drain()
        assert set(answers) == set(rids) and serve.pending() == 0
        got = np.stack([answers[r].dists for r in rids])
        bf_d, _ = brute_force_knn(data, jnp.asarray(q[order]), K)
        np.testing.assert_allclose(got, np.asarray(bf_d),
                                   rtol=1e-3, atol=1e-3)
        sv = serve.telemetry()["serving"]
        assert sv["pack"] == "difficulty" and sv["difficulty_scored"] == 10

    @pytest.mark.parametrize("k", [1, K])
    def test_partial_waves_skip_padded_slots(self, data, k):
        """Waves of every fill share one plan per k; the plan skips the
        padding slots, and every answer stays exact."""
        slots = 32
        eng = QueryEngine(LocalBackend(HerculesIndex.build(data, CFG)))
        serve = KnnServeEngine(eng, KnnServeConfig(batch_slots=slots, k=k))
        fills = range(1, slots + 1)
        workload = np.asarray(make_query_workload(
            jax.random.PRNGKey(14), data, sum(fills), "5%"))
        rids, start = [], 0
        for fill in fills:
            rids += [serve.submit(q) for q in workload[start:start + fill]]
            start += fill
            assert serve.step() == fill
        answers = serve.drain()
        t = serve.telemetry()
        pc = t.plan_cache
        assert (pc.misses, pc.compiles, pc.hits) == (1, 1, slots - 1)
        assert t.queries == len(workload)
        assert t.rows_skipped == sum(slots - f for f in fills)
        assert t["rows_skipped"] == t.rows_skipped
        bf_d, bf_i = brute_force_knn(data, jnp.asarray(workload), k)
        got_d = np.stack([answers[r].dists for r in rids])
        got_i = np.stack([answers[r].ids for r in rids])
        np.testing.assert_allclose(got_d, np.asarray(bf_d),
                                   rtol=1e-3, atol=1e-3)
        np.testing.assert_array_equal(got_i, np.asarray(bf_i))
