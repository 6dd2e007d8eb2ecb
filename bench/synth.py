"""The benchmark's own copy of the data and query generators.

Copied from the program's ``repro.data.synthetic`` (the paper's Synth
random walks and its query-hardness protocol) and ``chip_smoke.walks``, so
that a change to the program cannot change the yardstick.

* ``random_walks``: cumulative sums of i.i.d. N(0, 1) steps, z-normalised.
* ``collection``: (num, length) random walks made on the device a slab at a
  time in one jitted call, so the generator's temporaries stay one slab big.
  Every seed gets the same set of series, from ``COLLECTION_SEED``, in an
  order drawn from the seed: the index's tree takes its node and leaf
  counts from the set, and the program bakes them into its shapes, so a
  set that changed with the seed would compile the build and the plans
  again in every run.
* ``series_set``: that fixed set in its own order, for traffic whose
  queries have to be the same for every seed (``bench/schedule.py``).
* ``noisy_queries``: the hardness protocol. A noise level "p%" picks
  collection series at random and adds N(0, p/100) noise to each point;
  ``ood`` draws fresh random walks, which the collection does not hold.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HARDNESS = ("1%", "2%", "5%", "10%", "ood")
SLAB = 1 << 18
COLLECTION_SEED = 20221001        # the one set of series every seed reorders
QUERY_SET_SEED = 20221002         # a closed loop's one set of requests


def random_walks(key: jax.Array, num: int, length: int) -> jax.Array:
    steps = jax.random.normal(key, (num, length), dtype=jnp.float32)
    walks = jnp.cumsum(steps, axis=-1)
    mu = jnp.mean(walks, axis=-1, keepdims=True)
    sd = jnp.maximum(jnp.std(walks, axis=-1, keepdims=True), 1e-8)
    return (walks - mu) / sd


@functools.partial(jax.jit, static_argnames=("num", "length", "slab"))
def _fill(key: jax.Array, *, num: int, length: int, slab: int) -> jax.Array:
    def body(i, buf):
        blk = random_walks(jax.random.fold_in(key, i), slab, length)
        return jax.lax.dynamic_update_slice(buf, blk, (i * slab, 0))

    buf = jnp.zeros((num, length), jnp.float32)
    return jax.lax.fori_loop(0, num // slab, body, buf)


@functools.partial(jax.jit, static_argnames=("num", "length", "slab"))
def _fill_in_order(order_key: jax.Array, *, num: int, length: int,
                   slab: int) -> jax.Array:
    data = _fill(jax.random.PRNGKey(COLLECTION_SEED), num=num, length=length,
                 slab=slab)
    return data[jax.random.permutation(order_key, num)]


def _slab(num: int) -> int:
    slab = min(SLAB, num)
    if num % slab:
        raise ValueError(f"num={num} is not a multiple of the slab {slab}")
    return slab


def collection(key: jax.Array, num: int, length: int) -> jax.Array:
    """(num, length) float32 Synth collection, made on the device: the
    fixed set of ``num`` series, in the order ``key`` draws."""
    return _fill_in_order(key, num=num, length=length, slab=_slab(num))


def series_set(num: int, length: int) -> jax.Array:
    """The fixed set of ``num`` series that ``collection`` reorders, in
    its own order."""
    return _fill(jax.random.PRNGKey(COLLECTION_SEED), num=num, length=length,
                 slab=_slab(num))


@functools.partial(jax.jit, static_argnames=("num", "hardness"))
def noisy_queries(key: jax.Array, data: jax.Array, *, num: int,
                  hardness: str) -> jax.Array:
    """(num, n) queries of one hardness level against ``data`` (N, n)."""
    if hardness not in HARDNESS:
        raise ValueError(f"hardness {hardness!r} not in {HARDNESS}")
    n = data.shape[-1]
    if hardness == "ood":
        return random_walks(key, num, n)
    sigma2 = float(hardness.rstrip("%")) / 100.0
    k_sel, k_noise = jax.random.split(key)
    idx = jax.random.randint(k_sel, (num,), 0, data.shape[0])
    noise = jax.random.normal(k_noise, (num, n)) * jnp.sqrt(sigma2)
    return data[idx] + noise.astype(jnp.float32)
