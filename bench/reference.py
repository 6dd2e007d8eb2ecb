"""The plain reference, and the comparison that decides ``correct``.

The reference is exact k-nearest-neighbour search by brute force: the
squared Euclidean distance of every query to every collection series, in
the difference form ``sum((x - q)^2)`` in float32, and the k smallest. It
imports nothing of the program. It runs in blocks of queries and of
collection rows, so it fits beside what the run keeps on the device.

The comparison holds each answer to the configuration's guarantee, the
exact k nearest neighbours:

* ``missing``: requests of the window that never got an answer, or got a
  failure. An exact comparison: its limit is 0.
* ``dist_gap``: over every answer and rank j, the gap between the answer's
  j-th distance and the reference's j-th distance, relative to the
  reference's.
* ``id_gap``: over every answer and rank j, the gap between the true
  distance of the series the answer names at rank j and the reference's
  j-th distance, relative to the reference's. A wrong, repeated or
  out-of-range id reads high here even where the distances were copied
  right.

The control is the same reference computed from bfloat16 inputs, the next
precision below the float32 the configurations state (``precision`` below).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 128
ROW_BLOCK = 2048
# relative gaps are taken against max(reference distance, FLOOR), so an
# exact duplicate of a collection series (distance 0) cannot divide by 0
FLOOR = 1e-6


@functools.partial(jax.jit, static_argnames=("k", "dtype"))
def _knn_block(data: jax.Array, queries: jax.Array, *, k: int, dtype):
    """Exact top-k of a query block over the whole collection."""
    num, n = data.shape
    rows = data.reshape(num // ROW_BLOCK, ROW_BLOCK, n)
    qs = queries.astype(dtype).astype(jnp.float32)
    qn = queries.shape[0]

    def body(carry, xs):
        d_top, i_top = carry
        blk, base = xs
        blk = blk.astype(dtype).astype(jnp.float32)
        d = jnp.sum(jnp.square(blk[None, :, :] - qs[:, None, :]), axis=2)
        ids = base + jnp.arange(ROW_BLOCK, dtype=jnp.int32)
        dd = jnp.concatenate([d_top, d], axis=1)
        ii = jnp.concatenate([i_top, jnp.broadcast_to(ids, (qn, ROW_BLOCK))],
                             axis=1)
        neg, pos = jax.lax.top_k(-dd, k)
        return (-neg, jnp.take_along_axis(ii, pos, axis=1)), None

    init = (jnp.full((qn, k), jnp.inf, jnp.float32),
            jnp.full((qn, k), -1, jnp.int32))
    bases = jnp.arange(num // ROW_BLOCK, dtype=jnp.int32) * ROW_BLOCK
    (d, i), _ = jax.lax.scan(body, init, (rows, bases))
    return d, i


@jax.jit
def _pair_dists(data: jax.Array, queries: jax.Array, ids: jax.Array):
    """(Q, k) distance of each query to the series each id names; an id out
    of range reads +inf."""
    ok = (ids >= 0) & (ids < data.shape[0])
    rows = data[jnp.clip(ids, 0, data.shape[0] - 1)]        # (Q, k, n)
    d = jnp.sum(jnp.square(rows - queries[:, None, :]), axis=2)
    return jnp.where(ok, d, jnp.inf)


def knn(data: jax.Array, queries: np.ndarray, k: int,
        precision: str = "float32") -> tuple[np.ndarray, np.ndarray]:
    """Exact (distances, ids) of the k nearest series, ascending. With
    ``precision="bfloat16"`` the collection and the queries are rounded to
    bfloat16 first: the control."""
    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[precision]
    if data.shape[0] % ROW_BLOCK:
        raise ValueError(f"collection of {data.shape[0]} rows is not a "
                         f"multiple of {ROW_BLOCK}")
    out_d, out_i = [], []
    for lo in range(0, len(queries), QUERY_BLOCK):
        q = np.asarray(queries[lo:lo + QUERY_BLOCK], np.float32)
        real = len(q)
        if real < QUERY_BLOCK:   # one block shape, one compile
            q = np.concatenate([q, np.zeros((QUERY_BLOCK - real, q.shape[1]),
                                            np.float32)])
        d, i = _knn_block(data, jnp.asarray(q), k=k, dtype=dtype)
        out_d.append(np.asarray(d)[:real])
        out_i.append(np.asarray(i)[:real])
    if not out_d:
        return np.zeros((0, k), np.float32), np.zeros((0, k), np.int32)
    return np.concatenate(out_d), np.concatenate(out_i)


def true_dists(data: jax.Array, queries: np.ndarray,
               ids: np.ndarray) -> np.ndarray:
    out = []
    for lo in range(0, len(queries), QUERY_BLOCK):
        q = np.asarray(queries[lo:lo + QUERY_BLOCK], np.float32)
        i = np.asarray(ids[lo:lo + QUERY_BLOCK], np.int32)
        out.append(np.asarray(_pair_dists(data, jnp.asarray(q),
                                          jnp.asarray(i))))
    return np.concatenate(out) if out else np.zeros(ids.shape, np.float32)


def _rel_gap(got: np.ndarray, want: np.ndarray) -> float:
    if got.size == 0:
        return 0.0
    with np.errstate(invalid="ignore"):
        gap = np.abs(got.astype(np.float64) - want) / np.maximum(want, FLOOR)
    gap = np.where(np.isnan(gap), np.inf, gap)
    return float(gap.max())


def compare(data: jax.Array, queries: np.ndarray, ks, answers: list) -> dict:
    """The numbers that decide ``correct``. ``answers[i]`` is
    ``(dists, ids)`` for request i, or None where it never came or failed."""
    ks = np.asarray(ks)
    out = {"missing": sum(a is None for a in answers),
           "dist_gap": 0.0, "id_gap": 0.0}
    for k in sorted(set(ks.tolist())):
        rows = [i for i, kk in enumerate(ks) if kk == k and
                answers[i] is not None]
        if not rows:
            continue
        want_d, _ = knn(data, queries[rows], int(k))
        got_d = np.stack([np.asarray(answers[i][0], np.float32).reshape(-1)
                          for i in rows])
        got_i = np.stack([np.asarray(answers[i][1]).reshape(-1)
                          for i in rows])
        if got_d.shape != want_d.shape or got_i.shape != want_d.shape:
            out["dist_gap"] = out["id_gap"] = float("inf")
            continue
        named = true_dists(data, queries[rows], got_i)
        out["dist_gap"] = max(out["dist_gap"], _rel_gap(got_d, want_d))
        out["id_gap"] = max(out["id_gap"], _rel_gap(named, want_d))
    return out
