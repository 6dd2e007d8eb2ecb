"""Pallas TPU kernels for the exact-distance hot loop (paper's SIMD scans).

The paper's skip-sequential scan and refinement steps spend their cycles in
SIMD Euclidean-distance code (§3.4 "distance calculations in all steps are
performed using SIMD"). On TPU the same computation is a blocked matmul-
identity reduction on the MXU:

    ||q - s||^2 = ||q||^2 + ||s||^2 - 2 q.s

Two kernels:

* ``ed_matrix_kernel`` — (Q, n) x (N, n) -> (Q, N) squared distances, tiled
  (bq x bn x bk) with fp32 accumulation in the output block across the k-grid
  (the canonical Pallas matmul schedule). Norm contributions are accumulated
  per k-tile so no separate norm pass over HBM is needed.
* ``ed_min_kernel`` — fused 1-NN: per query block, a VMEM scratch accumulates
  the (bq, bn) partial distances over k-tiles, then folds a running
  (min distance, argmin) pair across series blocks. This is the paper's most
  common query (k=1) without materializing the (Q, N) matrix.

Tiling notes (VMEM/MXU): block shapes default to (128, 512, 128) — last-dim
multiples of 128 keep the MXU systolic dims aligned; f32 tiles of
128x512 + 128x128 + 512x128 ≈ 0.6 MB comfortably fit the ~16 MB VMEM
with double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BQ = 128
DEFAULT_BN = 512
DEFAULT_BK = 128


def _ed_matrix_kernel(q_ref, s_ref, out_ref):
    """Grid (iq, jn, kk); accumulate ||.||^2 identity terms per k-tile."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    q = q_ref[...].astype(jnp.float32)          # (bq, bk)
    s = s_ref[...].astype(jnp.float32)          # (bn, bk)
    qn = jnp.sum(q * q, axis=1)                 # (bq,)
    sn = jnp.sum(s * s, axis=1)                 # (bn,)
    dot = jax.lax.dot_general(q, s, (((1,), (1,)), ((), ())),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)
    out_ref[...] += qn[:, None] + sn[None, :] - 2.0 * dot


def _ed_min_kernel(q_ref, s_ref, dmin_ref, amin_ref, acc_ref, *, bn: int,
                   nk: int, valid_n: int):
    """Grid (iq, jn, kk). acc_ref: VMEM scratch (bq, bn) partial distances.

    ``valid_n``: logical series count — columns at or past it are padding
    and are masked to ``+inf`` before the fold, so ragged collections never
    need sentinel rows (which break down for adversarial input magnitudes).
    """
    jn = pl.program_id(1)
    kk = pl.program_id(2)

    @pl.when((jn == 0) & (kk == 0))
    def _init_out():
        # +inf, not a finite sentinel: real distances can land anywhere up
        # to and including inf, and the strict-< fold must still admit them
        # (all-inf collections then match the oracle's argmin of 0)
        dmin_ref[...] = jnp.full_like(dmin_ref, jnp.inf)
        amin_ref[...] = jnp.zeros_like(amin_ref)

    @pl.when(kk == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32)
    s = s_ref[...].astype(jnp.float32)
    qn = jnp.sum(q * q, axis=1)
    sn = jnp.sum(s * s, axis=1)
    dot = jax.lax.dot_general(q, s, (((1,), (1,)), ((), ())),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)
    acc_ref[...] += qn[:, None] + sn[None, :] - 2.0 * dot

    @pl.when(kk == nk - 1)
    def _fold():
        d = acc_ref[...]                                       # (bq, bn)
        cols = jn * bn + jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
        d = jnp.where(cols < valid_n, d, jnp.inf)
        local_min = jnp.min(d, axis=1)
        local_arg = jnp.argmin(d, axis=1).astype(jnp.int32) + jn * bn
        better = local_min < dmin_ref[...]
        dmin_ref[...] = jnp.where(better, local_min, dmin_ref[...])
        amin_ref[...] = jnp.where(better, local_arg, amin_ref[...])


@functools.partial(jax.jit, static_argnames=("bq", "bn", "bk", "interpret"))
def ed_matrix(queries: jax.Array, series: jax.Array,
              bq: int = DEFAULT_BQ, bn: int = DEFAULT_BN, bk: int = DEFAULT_BK,
              interpret: bool = False) -> jax.Array:
    """Blocked squared-ED matrix. Shapes must be multiples of the blocks
    (ops.py pads); returns (Q, N) float32."""
    qn, n = queries.shape
    sn = series.shape[0]
    grid = (qn // bq, sn // bn, n // bk)
    return pl.pallas_call(
        _ed_matrix_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((bq, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((qn, sn), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="ed_matrix",
    )(queries, series)


@functools.partial(jax.jit, static_argnames=("bq", "bn", "bk", "valid_n",
                                             "interpret"))
def ed_min(queries: jax.Array, series: jax.Array,
           bq: int = DEFAULT_BQ, bn: int = DEFAULT_BN, bk: int = DEFAULT_BK,
           valid_n: int | None = None,
           interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """Fused 1-NN scan: returns ((Q,) min squared ED, (Q,) argmin).

    ``valid_n``: logical (unpadded) series count; rows at or past it never
    win the min. Defaults to every row being live."""
    qn, n = queries.shape
    sn = series.shape[0]
    nk = n // bk
    grid = (qn // bq, sn // bn, nk)
    kernel = functools.partial(_ed_min_kernel, bn=bn, nk=nk,
                               valid_n=sn if valid_n is None else valid_n)
    dmin, amin = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
        ],
        out_specs=[
            pl.BlockSpec((bq,), lambda i, j, k: (i,)),
            pl.BlockSpec((bq,), lambda i, j, k: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((qn,), jnp.float32),
            jax.ShapeDtypeStruct((qn,), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((bq, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="ed_min",
    )(queries, series)
    return dmin, amin
