"""Pallas TPU kernel for LB_SAX (MINDIST) over packed iSAX codes.

The paper's phase 3 streams the in-memory LSDFile (uint8 iSAX codes, 16 bytes
per series vs 4*n bytes of raw data) and computes LB_SAX per series. On TPU
this is a bandwidth-bound VPU job; the only awkward part is the breakpoint
table lookup (codes -> cell [lo, hi] bounds). Gathers are not VPU-friendly, so
the lookup is a **select sweep over the alphabet**: the bound tables sit in
SMEM, and for every symbol ``a`` the block takes ``lo_table[a]`` /
``hi_table[a]`` where its code equals ``a``. That is exact (a select copies
the table value; no arithmetic touches it) and needs no reshape.

    lo = lo_table[code]        hi = hi_table[code]
    d  = max(lo - paa, paa - hi, 0)     lb = seg_len * sum_i d_i^2

Layout: the codes are transposed to (m, N) so the series axis lies on the
128-wide lanes and the m = 16 segments on the sublanes — the per-series sum
over segments is then a sublane reduction that lands lane-dense in the
(bq, bn) output row. The query PAA block is (bq, m, 1), so each query's
segment values broadcast across lanes.

The stored LSD stays (N, m); the wrapper transposes it on every call, one
extra pass over the codes. On a TPU v5e, for 32 queries against 2^20 codes,
that transpose took 0.63 ms against 4.8 ms for the kernel.

Tiling: codes block (m, bn) uint8, query block (bq, m, 1) f32, tables whole
(alphabet,) in SMEM. Output (bq, bn). m = 16 everywhere (paper's segment
count). The alphabet sweep runs once per (query block, code block), so a
larger ``bq`` repeats it less: on the same v5e reading, 32 queries took
14.3 ms at bq=8, 7.9 ms at bq=16 and 4.8 ms at bq=32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import summaries as S

DEFAULT_BQ = 32
DEFAULT_BN = 1024


def _lb_sax_kernel(qpaa_ref, codes_ref, lo_tab_ref, hi_tab_ref, out_ref,
                   *, seg_len: float, alphabet: int):
    c = codes_ref[...].astype(jnp.int32)             # (m, bn)

    unroll = 8 if alphabet % 8 == 0 else 1

    def lookup(step, bounds):
        lo, hi = bounds
        for u in range(unroll):
            a = step * unroll + u
            hit = c == a
            lo = jnp.where(hit, lo_tab_ref[a], lo)
            hi = jnp.where(hit, hi_tab_ref[a], hi)
        return lo, hi

    zeros = jnp.zeros(c.shape, jnp.float32)
    lo, hi = jax.lax.fori_loop(0, alphabet // unroll, lookup, (zeros, zeros))
    for r in range(out_ref.shape[0]):                # bq queries, unrolled
        q = qpaa_ref[r]                              # (m, 1)
        d = jnp.maximum(jnp.maximum(lo - q, q - hi), 0.0)
        out_ref[pl.ds(r, 1), :] = seg_len * jnp.sum(d * d, axis=0,
                                                    keepdims=True)


def _bound_tables(alphabet: int) -> tuple[jax.Array, jax.Array]:
    """Per-symbol cell bound tables (lo_table, hi_table), each (alphabet,)."""
    big = 3.0e38
    bps = S.sax_breakpoints(alphabet)                # (A-1,)
    lo = jnp.concatenate([jnp.asarray([-big], jnp.float32), bps])
    hi = jnp.concatenate([bps, jnp.asarray([big], jnp.float32)])
    return lo, hi


@functools.partial(jax.jit,
                   static_argnames=("series_len", "alphabet", "bq", "bn",
                                    "interpret"))
def lb_sax_matrix(q_paa: jax.Array, codes: jax.Array, series_len: int,
                  alphabet: int = S.SAX_ALPHABET,
                  bq: int = DEFAULT_BQ, bn: int = DEFAULT_BN,
                  interpret: bool = False) -> jax.Array:
    """(Q, m) PAA x (N, m) uint8 codes -> (Q, N) squared LB_SAX."""
    qn, m = q_paa.shape
    sn = codes.shape[0]
    grid = (qn // bq, sn // bn)
    lo_tab, hi_tab = _bound_tables(alphabet)
    kernel = functools.partial(_lb_sax_kernel, seg_len=series_len / m,
                               alphabet=alphabet)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, m, 1), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((m, bn), lambda i, j: (0, j)),
            smem,
            smem,
        ],
        out_specs=pl.BlockSpec((bq, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((qn, sn), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="lb_sax",
    )(q_paa.astype(jnp.float32)[:, :, None], codes.T, lo_tab, hi_tab)
