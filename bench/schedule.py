"""Requests from a traffic file and a seed.

Every seed gets the same set of sizes and arrivals, in another order: the
hardness levels and the k values come in equal shares, and the open-loop
inter-arrival gaps are the same set of exponential quantiles, each list
shuffled by its own stream of the seed. The hardness and the k of a request
are drawn independently. What the seed changes beyond the order is which
collection series a query perturbs and its noise.

A closed loop fixes even that: its requests are one set in one order, the
same for every seed (``fixed_set``).
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

from bench import synth


def prng_key(seed: int) -> jax.Array:
    """A JAX key that keeps every bit of a non-negative seed of up to 64."""
    if seed < 0:
        raise ValueError(f"seed={seed}; expected a non-negative integer")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


@dataclasses.dataclass(frozen=True)
class Requests:
    """The requests of one run: ``due`` (seconds after the window opens;
    None for a closed loop), each one's hardness and k, and the queries."""
    hardness: tuple[str, ...]
    k: tuple[int, ...]
    due: np.ndarray | None
    queries: np.ndarray

    def __len__(self) -> int:
        return len(self.k)


def _equal_shares(values, n: int, rng: np.random.Generator) -> list:
    reps = -(-n // len(values))
    out = [v for v in values for _ in range(reps)][:n]
    # equal shares up to one request; the cut falls on a seed-drawn value
    rng.shuffle(out)
    return out


def poisson_gaps(rate: float, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` inter-arrival gaps of a Poisson process at ``rate``: the mid
    quantiles of the exponential distribution, scaled so that they add up
    to ``n / rate`` exactly, in a seed-drawn order."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    gaps *= (n / rate) / gaps.sum()
    return rng.permutation(gaps)


def arrivals(rate: float, seconds: float, rng: np.random.Generator):
    """Due times in [0, seconds) of an open loop at ``rate`` per second."""
    n = max(int(round(rate * seconds)), 1)
    gaps = poisson_gaps(rate, n, rng) * (seconds / (n / rate))
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def make_requests(traffic: dict, seed: int, seconds: float, data,
                  count: int | None = None, stream: int = 0) -> Requests:
    """The run's requests: open loops (``rate_per_s`` in the traffic) get
    due times over ``seconds``; closed loops get ``count`` requests, one
    set in one order for every seed (``fixed_set``). ``stream`` separates
    warm-up requests from timed ones under the same seed."""
    if "rate_per_s" not in traffic:
        return fixed_set(traffic, data.shape, count, stream)
    due = None
    if count is None:
        rng_due = np.random.default_rng([seed, stream, 3])
        due = arrivals(float(traffic["rate_per_s"]), seconds, rng_due)
        count = len(due)
    return _draw(traffic, seed, data, count, stream, due)


def _draw(traffic: dict, seed: int, data, count: int, stream: int,
          due: np.ndarray | None) -> Requests:
    """``count`` requests of the traffic's hardness and k mix, drawn from
    ``seed`` against the series of ``data``."""
    rng_order = np.random.default_rng([seed, stream, 1])
    rng_k = np.random.default_rng([seed, stream, 2])
    hardness = _equal_shares(list(traffic["hardness"]), count, rng_order)
    ks = _equal_shares([int(k) for k in traffic["k"]], count, rng_k)
    queries = np.zeros((count, data.shape[1]), np.float32)
    key = jax.random.fold_in(prng_key(seed), 1000 + stream)
    for i, level in enumerate(synth.HARDNESS):
        rows = [j for j, h in enumerate(hardness) if h == level]
        if rows:
            q = synth.noisy_queries(jax.random.fold_in(key, i), data,
                                    num=len(rows), hardness=level)
            queries[rows] = np.asarray(q)
    return Requests(hardness=tuple(hardness), k=tuple(ks), due=due,
                    queries=queries)


def fixed_set(traffic: dict, shape: tuple, count: int,
              stream: int = 0) -> Requests:
    """``count`` requests that are one set, in one order, for every seed.

    Which series a query perturbs decides how much of an out-of-core
    collection it streams, and which requests share a wave decides how
    much a wave streams for all of them (PERF.md), so a set or an order
    drawn per seed would change a closed loop's work from seed to seed.
    The set is drawn from ``synth.QUERY_SET_SEED`` against the
    collection's series in their own order (``synth.series_set``), with
    the hardness levels and k values in the order that seed draws, as a
    query workload file holds them. The run's seed orders the collection,
    and with it every id the answers carry."""
    return _draw(traffic, synth.QUERY_SET_SEED, synth.series_set(*shape),
                 count, stream, None)
