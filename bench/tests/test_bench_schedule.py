"""The arrival schedule and the query set are fixed by the seed, and every
seed gets the same sizes and arrivals in another order."""
import collections

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
    reqs = schedule.make_requests(dict(BATCH, pool=96), 4, 5.0, data)
    assert reqs.due is None and len(reqs) == 96


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
