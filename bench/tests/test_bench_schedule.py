"""The arrival schedule and the query set are fixed by the seed, and every
seed gets the same sizes and arrivals in another order; a closed loop gets
one set of requests in another order."""
import collections
import hashlib

import jax
import numpy as np
import pytest

from bench import registry, schedule, synth

OPEN = registry.load_json("traffic", "easy-open")
HARD = registry.load_json("traffic", "hard-open")
BATCH = registry.load_json("traffic", "easy-batch")


@pytest.fixture(scope="module")
def data():
    return synth.collection(schedule.prng_key(3), 4096, 256)


def test_same_seed_same_requests(data):
    a = schedule.make_requests(OPEN, 11, 5.0, data)
    b = schedule.make_requests(OPEN, 11, 5.0, data)
    np.testing.assert_array_equal(a.due, b.due)
    assert a.hardness == b.hardness and a.k == b.k
    np.testing.assert_array_equal(a.queries, b.queries)


def test_seed_changes_order_not_sizes(data):
    a = schedule.make_requests(OPEN, 11, 5.0, data)
    b = schedule.make_requests(OPEN, 12, 5.0, data)
    assert len(a) == len(b) == round(OPEN["rate_per_s"] * 5.0)
    # the gaps, the one from the last arrival to the close among them
    gaps = [np.sort(np.diff(np.append(r.due, 5.0))) for r in (a, b)]
    np.testing.assert_allclose(gaps[0], gaps[1], rtol=1e-9)
    assert collections.Counter(a.hardness) == collections.Counter(b.hardness)
    assert collections.Counter(a.k) == collections.Counter(b.k)
    assert a.hardness != b.hardness or a.k != b.k
    assert not np.array_equal(a.queries, b.queries)


def test_seed_reorders_one_collection():
    # the index's shapes follow the set of series, so every seed gets the
    # same set, in its own order
    a, b = (np.asarray(synth.collection(schedule.prng_key(s), 2048, 64))
            for s in (11, 2**31 + 12))
    assert not np.array_equal(a, b)
    np.testing.assert_array_equal(a[np.lexsort(a.T[::-1])],
                                  b[np.lexsort(b.T[::-1])])


def test_equal_shares_and_window(data):
    reqs = schedule.make_requests(HARD, 5, 7.0, data)
    for values, field in ((HARD["hardness"], reqs.hardness),
                          (HARD["k"], reqs.k)):
        counts = collections.Counter(field)
        assert set(counts) == set(values)
        assert max(counts.values()) - min(counts.values()) <= 1
    assert reqs.due[0] == 0.0 and np.all(np.diff(reqs.due) > 0)
    assert reqs.due[-1] < 7.0


def test_warm_up_stream_differs(data):
    a = schedule.make_requests(OPEN, 11, 5.0, data, count=64)
    b = schedule.make_requests(OPEN, 11, 5.0, data, count=64, stream=1)
    assert not np.array_equal(a.queries, b.queries)


def test_closed_pool(data):
    loop = registry.load_module("loops", BATCH["loop"])
    count = loop.count(BATCH, 5.0)
    assert count == round(BATCH["requests_per_window_second"] * 5.0)
    reqs = schedule.make_requests(BATCH, 4, 5.0, data, count=count)
    assert reqs.due is None and len(reqs) == count


def test_queries_follow_hardness(data):
    reqs = schedule.make_requests(dict(OPEN, hardness=["1%", "ood"]), 8,
                                  5.0, data)
    host = np.asarray(data)
    for q, h in zip(reqs.queries[:12], reqs.hardness[:12]):
        nearest = np.min(np.sum((host - q) ** 2, axis=1))
        # 1% noise: about 0.01 * 256 = 2.56 from its source series
        assert (nearest < 10.0) == (h == "1%")


@pytest.mark.parametrize("seed", [5, 2**31 + 5, 2**32 + 5, 3 * 2**32 + 5])
def test_large_seeds_keep_their_bits(seed):
    keys = {int(np.asarray(jax.random.key_data(schedule.prng_key(s))).sum())
            for s in (5, seed)}
    assert len(keys) == (1 if seed == 5 else 2)
    with pytest.raises(ValueError):
        schedule.prng_key(-1)


def _longest_run(values) -> int:
    best = run = 1
    for prev, cur in zip(values, values[1:]):
        run = run + 1 if cur == prev else 1
        best = max(best, run)
    return best


@pytest.mark.parametrize("seeds", [(4, 5), (11, 2**33 + 7)])
def test_fixed_set_is_the_same_for_every_seed(data, seeds):
    # disk-easy-batch: every seed gets the same requests in the same order,
    # with equal hardness and k shares mixed as a workload file holds them,
    # not in runs that match a wave
    a, b = (schedule.make_requests(BATCH, s, 20.0, data, count=340)
            for s in seeds)
    assert len(a) == len(b) == 340 and a.due is None
    assert a.k == b.k and a.hardness == b.hardness
    np.testing.assert_array_equal(a.queries, b.queries)
    for values, field in ((BATCH["hardness"], a.hardness), (BATCH["k"], a.k)):
        counts = collections.Counter(field)
        assert set(counts) == set(values)
        assert max(counts.values()) - min(counts.values()) < len(values)
        assert _longest_run(field) < 16


def test_fixed_set_ignores_the_collection_order():
    # the set is drawn against the series in their own order, so the seed's
    # order of the collection does not change it; a query still perturbs a
    # series of the collection
    a, b = (schedule.make_requests(BATCH, 4, 20.0,
                                   synth.collection(schedule.prng_key(s),
                                                    4096, 256), count=16)
            for s in (1, 2))
    np.testing.assert_array_equal(a.queries, b.queries)
    host = np.asarray(synth.collection(schedule.prng_key(1), 4096, 256))
    for q in a.queries:
        assert np.min(np.sum((host - q) ** 2, axis=1)) < 0.1 * 256


GOLDEN_OPEN = {
    7: "135de5227c95c1d95b671b4a8df045735dcbde048b076155b8177850c54dede0",
    2**31 + 17:
        "5e24bbb17ee882c0b6dbd6118121fb1290e66241644f15ba8bf79d403f107214",
    5_000_000_123:
        "e61862afed8bdd0cdcfb9913131a0fb44734f1b6338a7aeec2cba9dc7de5b568",
}


@pytest.mark.parametrize("seed", sorted(GOLDEN_OPEN))
def test_hbm_easy_open_inputs_unchanged(seed):
    # hbm-easy-open's collection, timed requests and warm-up requests at
    # the test size, hashed as the benchmark first made them
    from bench.tests.tiny import tiny_cell

    cell = tiny_cell("hbm-easy-open")
    cfg, traffic = cell["config"], cell["traffic"]
    loop = registry.load_module("loops", traffic["loop"])
    data = synth.collection(schedule.prng_key(seed), cfg["num_series"],
                            cfg["series_len"])
    h = hashlib.sha256(np.asarray(data).tobytes())
    for count, stream in ((loop.count(traffic, 20.0), 0),
                          (loop.warm_count(traffic, 32), 1)):
        reqs = schedule.make_requests(traffic, seed, 20.0, data, count=count,
                                      stream=stream)
        h.update(repr((reqs.hardness, reqs.k)).encode())
        if reqs.due is not None:
            h.update(np.asarray(reqs.due, np.float64).tobytes())
        h.update(np.asarray(reqs.queries).tobytes())
    assert h.hexdigest() == GOLDEN_OPEN[seed]
