"""Unified query engine — one search surface over every backend.

The paper's system answers exact kNN through one carefully scheduled
pipeline; this repo grew three incompatible entry points around it
(``HerculesIndex.knn``, the distributed ``StackedIndex``, the PSCAN
baseline). This module is the serving layer that unifies them:

* :class:`SearchBackend` — the protocol every answering path conforms to:
  ``knn(queries, k=None, **overrides) -> KnnResult`` plus ``stats()`` /
  ``describe()``. Three adapters ship here:

  - :class:`LocalBackend`   — in-process :class:`HerculesIndex` (the paper).
  - :class:`ShardedBackend` — the distributed ``StackedIndex`` under a mesh
    (per-shard exact top-k + all-gather merge).
  - :class:`ScanBackend`    — the dense blocked scan (PSCAN). Its default
    *parity* arithmetic uses the same difference-form squared-ED as the
    index's refinement/leaf paths, so answers are **bit-identical** across
    backends; ``mxu=True`` switches to the matmul-identity form (the
    high-arithmetic-intensity MXU path, equal up to fp32 rounding).

* :class:`QueryEngine` — a serving session over one backend that

  (a) buckets arbitrary query-batch shapes to a small set of padded sizes
      and keeps an LRU **compiled-plan cache** keyed by (static
      SearchConfig, bucket shape): plans are AOT-lowered and compiled
      (``jit(...).lower(...).compile()``), so a cache hit *cannot* retrace —
      the executable takes only device arrays;
  (b) separates build-time statics (the layout's padded row count) from
      per-call knobs: any ``chunk``/``scan_block`` dividing the padded size
      is a legal override (``validate_runtime_config``), and ``k``/``l_max``/
      threshold/ablation knobs are always legal;
  (c) exposes engine-level telemetry — plan-cache hits/misses/evictions,
      compile and execute latency, access-path counts and pruning ratios —
      as a plain dict (:meth:`QueryEngine.telemetry`).

Everything above this layer (serving loop, benchmarks, examples, CLIs)
talks to backends only through the engine.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import logging
import time
from typing import Any, Callable, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import summaries as S
from repro.core.index import HerculesIndex, IndexConfig
from repro.core.probes import HostSyncs, span
from repro.core.search import (INF, KnnResult, SearchConfig, _merge_topk,
                               exact_knn, pscan_knn, validate_runtime_config,
                               wave_knn)
from repro.kernels import ops as kops
from repro.kernels.compat import resolve_kernel_mode

logger = logging.getLogger(__name__)


@runtime_checkable
class SearchBackend(Protocol):
    """What the engine (and anything else) may assume about an answering path."""

    name: str

    def resolve(self, k: int | None = None,
                overrides: dict[str, Any] | None = None) -> SearchConfig: ...

    def make_plan(self, cfg: SearchConfig,
                  q_struct: jax.ShapeDtypeStruct
                  ) -> Callable[[jax.Array], KnnResult]: ...

    def make_wave_plan(self, cfg: SearchConfig,
                       q_struct: jax.ShapeDtypeStruct
                       ) -> Callable[[jax.Array], KnnResult]: ...

    def knn(self, queries: jax.Array, k: int | None = None,
            **overrides: Any) -> KnnResult: ...

    def stats(self) -> dict: ...

    def describe(self) -> dict: ...


class BackendBase:
    """Shared resolve/describe plumbing; subclasses supply the compute."""

    name = "backend"

    @property
    def series_len(self) -> int | None:
        """Collection series length, when known (engine input validation)."""
        return None

    @property
    def base_config(self) -> SearchConfig:
        raise NotImplementedError

    def _validate(self, cfg: SearchConfig) -> None:
        pass

    def resolve(self, k: int | None = None,
                overrides: dict[str, Any] | None = None) -> SearchConfig:
        cfg = self.base_config
        upd = dict(overrides or {})
        if k is not None:
            upd["k"] = k
        if upd:
            cfg = dataclasses.replace(cfg, **upd)
        self._validate(cfg)
        return cfg

    def make_plan(self, cfg, q_struct):
        raise NotImplementedError

    def make_wave_plan(self, cfg, q_struct):
        """Plan for a *wave* — a batch of queries answered with fused
        scheduling (shared descent/BSF/fetches). The default falls back to
        the regular plan: dense scans and the sharded all-gather are
        already batch-fused, so for them the wave path IS the batch path.
        Backends with per-query work to share override this."""
        return self.make_plan(cfg, q_struct)

    def knn(self, queries: jax.Array, k: int | None = None,
            **overrides: Any) -> KnnResult:
        """Direct (non-engine) call; still jit-cached, but may retrace on
        new shapes. Serving code should go through :class:`QueryEngine`."""
        cfg = self.resolve(k, overrides)
        return self._bind(cfg)(jnp.asarray(queries))

    def _bind(self, cfg: SearchConfig) -> Callable[[jax.Array], KnnResult]:
        raise NotImplementedError

    @staticmethod
    def _fill_result(dists, positions, ids, *, path: int = -1,
                     accessed=None) -> KnnResult:
        """KnnResult from the (dists, positions, ids) a backend computes,
        with the per-query telemetry fields it does not track filled by one
        convention: path ``-1`` = unknown, pruning ratios 0, ``accessed``
        0 / a scalar broadcast / a per-query vector."""
        qn = dists.shape[0]
        zeros_f = jnp.zeros((qn,), jnp.float32)
        zeros_i = jnp.zeros((qn,), jnp.int32)
        if accessed is None:
            accessed = zeros_i
        elif jnp.ndim(accessed) == 0:
            accessed = jnp.full((qn,), accessed, jnp.int32)
        return KnnResult(
            dists=dists, positions=positions, ids=ids,
            path=jnp.full((qn,), path, jnp.int32),
            eapca_pr=zeros_f, sax_pr=zeros_f,
            accessed=accessed, visited_leaves=zeros_i)

    def stats(self) -> dict:
        return {}

    def describe(self) -> dict:
        return {"backend": self.name,
                "config": dataclasses.asdict(self.base_config)}


# ---------------------------------------------------------------------------
# Local backend — the paper's single-node Hercules index
# ---------------------------------------------------------------------------

class LocalBackend(BackendBase):
    """In-process :class:`HerculesIndex` (tree + LRD/LSD layout)."""

    name = "local"

    def __init__(self, index: HerculesIndex):
        self.index = index
        # read from the device once here, so telemetry() never waits on it
        self._stats = index.stats()

    @property
    def series_len(self) -> int:
        return self.index.layout.series_len

    @property
    def base_config(self) -> SearchConfig:
        return self.index.config.search

    def _validate(self, cfg: SearchConfig) -> None:
        validate_runtime_config(cfg, self.index.layout.lrd.shape[0])

    def _bind(self, cfg):
        idx = self.index
        return lambda q: exact_knn(idx.tree, idx.layout, q, cfg, idx.max_depth)

    def make_plan(self, cfg, q_struct):
        """One program for every fill of the bucket: the count of real rows
        is a traced scalar, and the padded slots skip the pipeline."""
        idx = self.index
        compiled = exact_knn.lower(
            idx.tree, idx.layout, q_struct, cfg, idx.max_depth,
            jax.ShapeDtypeStruct((), jnp.int32)).compile()

        def run(q, valid_rows=None):
            n = q.shape[0] if valid_rows is None else valid_rows
            return compiled(idx.tree, idx.layout, q, np.int32(n))

        run.valid_aware = run.skips_padding = True
        return run

    def make_wave_plan(self, cfg, q_struct):
        idx = self.index
        compiled = wave_knn.lower(
            idx.tree, idx.layout, q_struct, cfg, idx.max_depth).compile()
        return lambda q: compiled(idx.tree, idx.layout, q)

    def estimate_difficulty(self, queries: jax.Array) -> np.ndarray:
        from repro.core.search import _wave_leaf_lbs
        return _difficulty_from_leaf_lbs(
            _wave_leaf_lbs(jnp.asarray(queries), self.index.layout))

    def stats(self) -> dict:
        return dict(self._stats)

    def describe(self) -> dict:
        d = super().describe()
        d["num_series"] = self.index.layout.num_series
        d["series_len"] = self.index.layout.series_len
        return d


# ---------------------------------------------------------------------------
# Scan backend — PSCAN as a first-class backend
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("k", "block"))
def dense_scan_knn(data: jax.Array, queries: jax.Array, k: int = 1,
                   block: int = 4096):
    """Blocked exact scan in *difference form* (``sum((s - q)^2)`` per row —
    the same arithmetic as the index's leaf/refinement paths, hence
    bit-identical answers). ``data`` may be unpadded. Returns (Q,k) dists
    and positions."""
    num, n = data.shape
    n_pad = -(-num // block) * block
    if n_pad != num:
        data = jnp.concatenate(
            [data, jnp.zeros((n_pad - num, n), data.dtype)], axis=0)
    blocks3 = data.reshape(n_pad // block, block, n)

    def one(q):
        d0 = jnp.full((k,), INF)
        p0 = jnp.full((k,), -1, jnp.int32)

        def body(carry, blk):
            d_top, p_top, base = carry
            d = jnp.sum(jnp.square(blk - q[None, :]), axis=1)
            pos = base + jnp.arange(block, dtype=jnp.int32)
            d = jnp.where(pos < num, d, INF)
            d_top, p_top = _merge_topk(d_top, p_top, d, pos, k)
            return (d_top, p_top, base + block), None

        (d_top, p_top, _), _ = jax.lax.scan(body, (d0, p0, jnp.int32(0)), blocks3)
        return d_top, p_top

    return jax.lax.map(one, queries)


@functools.partial(jax.jit, static_argnames=("k", "block", "mode"))
def kernel_scan_knn(data: jax.Array, queries: jax.Array, k: int = 1,
                    block: int = 4096, mode: str = "pallas"):
    """Blocked exact scan through the Pallas ED kernels (``kernels/ops``).

    Candidate *selection* runs on the kernels — the fused :func:`ops.ed_min`
    1-NN scan for ``k == 1`` (the paper's dominant query), blocked
    :func:`ops.ed_matrix` + per-block top-k otherwise. The *reported*
    distances for selected rows are always recomputed in difference form
    (``sum((s - q)^2)``) — the same arithmetic as every other backend path —
    and for ``k > 1`` the cross-block running top-k merges those exact
    values through the shared :func:`_merge_topk`, so kernel arithmetic
    influences at most the within-block candidate choice. Answers match
    :func:`dense_scan_knn` bit-for-bit unless the matmul-identity fp32
    error exceeds the distance gap at a top-k boundary (the ``scan-mxu``
    caveat; asserted exactly on the parity workloads). Returns (Q, k)
    dists and positions.
    """
    num, n = data.shape
    qn = queries.shape[0]

    def exact_d(p):
        """Difference-form distances for selected positions (-1/pad -> inf)."""
        rows = data[jnp.clip(p, 0, num - 1)]                     # (Q, k, n)
        d = jnp.sum(jnp.square(rows - queries[:, None, :]), axis=-1)
        return jnp.where((p >= 0) & (p < num), d, INF)

    if k == 1:
        # valid_n masking in the kernel guarantees a real row wins the min
        _, amin = kops.ed_min(queries, data, mode=mode)
        p_top = amin[:, None].astype(jnp.int32)                  # (Q, 1)
        return exact_d(p_top), p_top

    n_pad = -(-num // block) * block
    padded = data if n_pad == num else jnp.concatenate(
        [data, jnp.zeros((n_pad - num, n), data.dtype)], axis=0)
    blocks3 = padded.reshape(n_pad // block, block, n)
    merge = jax.vmap(functools.partial(_merge_topk, k=k))

    def body(carry, blk):
        d_top, p_top, base = carry
        d = kops.ed_matrix(queries, blk, mode=mode)              # (Q, block)
        pos = base + jnp.arange(block, dtype=jnp.int32)
        d = jnp.where((pos < num)[None, :], d, INF)
        _, idx = jax.lax.top_k(-d, k)                            # (Q, k)
        cand = jnp.where(jnp.take_along_axis(d, idx, axis=1) < INF,
                         pos[idx], -1)
        d_top, p_top = merge(d_top, p_top, exact_d(cand), cand)
        return (d_top, p_top, base + block), None

    d0 = jnp.full((qn, k), INF)
    p0 = jnp.full((qn, k), -1, jnp.int32)
    (d_top, p_top, _), _ = jax.lax.scan(body, (d0, p0, jnp.int32(0)), blocks3)
    return d_top, p_top


class ScanBackend(BackendBase):
    """Dense blocked scan over the raw collection (the PSCAN baseline).

    Arithmetic selection, in priority order:

    * ``cfg.kernel_mode`` *explicitly* ``pallas``/``interpret`` (or ``auto``
      resolving to Pallas with ``mxu=False``): the scan runs on the ED
      kernels via :func:`kernel_scan_knn` — reported distances are
      recomputed in difference form, so answers match the reference path.
    * ``mxu=True``: matmul-identity distances on the MXU via XLA
      (:func:`pscan_knn`; equal up to fp32 rounding). Wins over the
      implicit ``auto`` resolution, never over an explicit Pallas request.
    * otherwise: difference-form :func:`dense_scan_knn`, bit-identical to
      :class:`LocalBackend`.
    """

    name = "scan"

    def __init__(self, data: jax.Array, config: SearchConfig | None = None,
                 mxu: bool = False):
        self.data = jnp.asarray(data)
        self._config = dataclasses.replace(
            config or SearchConfig(), force_scan=True)
        self.mxu = mxu

    @property
    def series_len(self) -> int:
        return int(self.data.shape[1])

    @property
    def base_config(self) -> SearchConfig:
        return self._config

    def _validate(self, cfg: SearchConfig) -> None:
        if cfg.scan_block <= 0:
            raise ValueError("scan_block must be positive")

    def _result(self, d, p) -> KnnResult:
        # identity layout (pos == id); path 3 = forced scan, everything read
        return self._fill_result(d, p, p, path=3, accessed=self.data.shape[0])

    def _fn_args(self, cfg):
        """(jitted fn, static args after (data, queries)) for this config.

        ``mxu=True`` is an explicit arithmetic choice, so it wins over the
        implicit ``kernel_mode="auto"`` resolution; an *explicit* Pallas
        mode (``pallas``/``interpret``) wins over ``mxu``.
        """
        mode = resolve_kernel_mode(cfg.kernel_mode)
        if mode != "ref" and not (self.mxu and cfg.kernel_mode == "auto"):
            return kernel_scan_knn, (cfg.k, cfg.scan_block, mode)
        return (pscan_knn if self.mxu else dense_scan_knn), \
            (cfg.k, cfg.scan_block)

    def _bind(self, cfg):
        fn, args = self._fn_args(cfg)
        return lambda q: self._result(*fn(self.data, q, *args))

    def make_plan(self, cfg, q_struct):
        fn, args = self._fn_args(cfg)
        compiled = fn.lower(self.data, q_struct, *args).compile()
        return lambda q: self._result(*compiled(self.data, q))

    def stats(self) -> dict:
        return {"num_series": int(self.data.shape[0]),
                "series_len": int(self.data.shape[1])}

    def describe(self) -> dict:
        d = super().describe()
        d.update(self.stats(), mxu=self.mxu)
        return d


# ---------------------------------------------------------------------------
# Out-of-core backends — serving a memory-mapped on-disk index under a budget
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("k", "block", "mode"))
def _ooc_scan_block(rows: jax.Array, queries: jax.Array, base: jax.Array,
                    *, k: int, block: int, mode: str):
    """Top-k of one streamed row block through the in-memory scan hot path;
    positions shifted to global layout coordinates."""
    if mode == "ref":
        d, p = dense_scan_knn(rows, queries, k=k, block=block)
    else:
        d, p = kernel_scan_knn(rows, queries, k=k, block=block, mode=mode)
    return d, jnp.where(p >= 0, p + base, -1)


@functools.partial(jax.jit, static_argnames=("k",))
def _ooc_merge(d0, p0, d1, p1, *, k: int):
    merge = jax.vmap(lambda a, b, c, e: _merge_topk(a, b, c, e, k))
    return merge(d0, p0, d1, p1)


@functools.partial(jax.jit, static_argnames=("k",))
def _ooc_refine_block(rows: jax.Array, pos: jax.Array, queries: jax.Array,
                      d0, p0, *, k: int):
    """Merge exact difference-form distances of one padded row block into
    each query's running top-k. ``pos`` (rows,) int32 is each row's global
    layout position, −1 on pad rows (masked to INF), so one block may hold
    several extents back to back.

    The distances are mapped one query at a time (a (rows, n) temporary,
    not (Q, rows, n)); the merge is vmapped: a ``top_k`` inside the map's
    loop body takes the TPU compiler ~25 s per block shape, vmapped ~1 s."""
    d = jax.lax.map(lambda q: jnp.sum(jnp.square(rows - q[None, :]), axis=1),
                    queries)
    d = jnp.where((pos >= 0)[None, :], d, INF)
    return jax.vmap(lambda d_top, p_top, d_new: _merge_topk(
        d_top, p_top, d_new, pos, k))(d0, p0, d)


# -- the raw stream's per-wave selection programs ---------------------------
#
# Each is one jitted program per query shape (and, for the per-piece filter,
# per padded piece shape), where the eager ops they replace compiled one
# small program each, per shape and per device, and an eager ``lax.map`` or
# ``fori_loop`` over a closure lowered again on every call.

@jax.jit
def _leaf_lbs_of(q, endpoints, synopsis, seg_lens, dead):
    """(Q, L) squared LB_EAPCA of every query to every leaf synopsis, INF
    at the ``dead`` (empty) leaves."""
    from repro.core.lower_bounds import lb_eapca_node
    from repro.core.search import _query_seg_stats

    qp, qp2 = S.prefix_sums(q)

    def one(args):
        p_row, p2_row = args
        qm, qs = _query_seg_stats(p_row, p2_row, endpoints)
        return lb_eapca_node(qm, qs, synopsis, seg_lens)

    return jnp.where(dead[None, :], INF, jax.lax.map(one, (qp, qp2)))


@functools.partial(jax.jit, static_argnames=("max_depth", "l_max"))
def _seed_leaves(tree, leaf_rank, q, lbs, *, max_depth: int, l_max: int):
    """Phase 1's leaves: each query's home leaf rank and its ``l_max`` best
    leaf ranks by LB_EAPCA."""
    from repro.core.tree import route_to_leaf

    home = leaf_rank[route_to_leaf(tree, q, max_depth)]
    return home, jax.lax.top_k(-lbs, l_max)[1]


@jax.jit
def _leaf_candidates(lbs, bsf, slack):
    """Phase 2's leaf-level test: which leaves some query cannot prune
    against its ``bsf``, and how many leaves each query keeps."""
    cand = lbs * slack < bsf[:, None]
    return jnp.any(cand, axis=0), jnp.sum(cand, axis=1)


@functools.partial(jax.jit, static_argnames=("series_len", "mode"))
def _sax_filter_block(q_paa, codes, valid, lbs, ranks, d, shared, slack,
                      alive_counts, *, series_len: int, mode: str):
    """Phase 3's filter of one padded piece: the rows some query cannot
    prune by LB_SAX (raised to its leaf's LB_EAPCA) against the smaller of
    its running kth distance and ``shared``, and each query's running count
    of rows it keeps. Rows beyond ``valid`` are pad and never kept."""
    lb_row = jnp.maximum(kops.lb_sax(q_paa, codes, series_len, mode=mode),
                         lbs[:, ranks])
    bsf = jnp.minimum(d[:, -1], shared)
    live = ((lb_row * slack < bsf[:, None])
            & (jnp.arange(codes.shape[0]) < valid)[None, :])
    return (alive_counts + jnp.sum(live, axis=1, dtype=jnp.int32),
            jnp.any(live, axis=0))


# -- codec-aware streaming (format v3 encoded leaves) -----------------------
#
# With a lossy codec the streamed bytes are approximations, so decoded
# distances can only *select* candidates, never answer. Per block we turn
# each decoded distance d̂ into a sound interval around the true distance
# using the per-row reconstruction bound e embedded at encode time
# (||s - ŝ|| <= e, storage/codecs.py):
#
#     sqrt(d_true) ∈ [sqrt(d̂) - e, sqrt(d̂) + e]
#
# and carry two running sets per query: the k smallest *upper* bounds
# (a conservative BSF — the kth UB provably upper-bounds the true kth
# distance) and the _CAND smallest *lower* bounds (the candidate pool).
# After the stream, candidates are re-checked against the full-precision
# float32 rows with the exact difference-form arithmetic — bit-identical
# distances to LocalBackend — and a guard certifies completeness: every
# dropped/pruned row had LB >= the kth UB, so it cannot beat the top-k.
# Guard failure (bounds too loose for this batch) falls back to the raw
# float32 stream — counted in ``codec_fallbacks``, never wrong.

_CAND_MARGIN = 32   # candidate pool size = k + margin (see _codec_cand)

# slack absorbing the float32 evaluation error of the decoded distances
# themselves (identity-form matmul): additive in the *squared* domain,
# scaled by the norms entering the dot product. The stored per-row ``e``
# only covers reconstruction error, not arithmetic.
_BOUND_REL = 1e-5
_BOUND_ABS = 1e-6


def _codec_cand(k: int, num: int) -> int:
    return min(num, k + _CAND_MARGIN)


def _merge_topc(d0, p0, d1, p1, c: int):
    """Per-query: merge (value, position) pairs, keep the ``c`` smallest.
    Unlike ``_merge_topk`` there is no duplicate suppression — codec
    streams visit each position exactly once."""
    d = jnp.concatenate([d0, d1])
    pos = jnp.concatenate([p0, p1])
    neg, idx = jax.lax.top_k(-d, c)
    return -neg, pos[idx]


@functools.partial(jax.jit, static_argnames=("codec", "series_len", "k",
                                             "cand", "mode"))
def _codec_bounds_block(enc, queries, base, valid, ub_d, ub_p, lb_d, lb_p, *,
                        codec, series_len: int, k: int, cand: int, mode: str):
    """Fold one encoded row block into the UB/LB carries (see above).

    ``enc`` is (B, W) uint8; rows at or past ``valid`` are padding. For the
    bf16 codec on a kernel mode the decode is fused into the ED kernel
    (``kops.decode_bf16_ed_matrix``): the payload is bitcast to bfloat16 and
    upcast per tile in VMEM, so decoded float32 rows never touch HBM.
    """
    num = enc.shape[0]
    qn2 = jnp.sum(queries * queries, axis=1)
    if getattr(codec, "name", None) == "bf16" and mode != "ref":
        payload, err = codec.split(enc)
        d_dec = kops.decode_bf16_ed_matrix(queries, payload, mode=mode)
        half = jax.lax.bitcast_convert_type(
            jnp.reshape(payload, (num, series_len, 2)), jnp.bfloat16)
        sn2 = jnp.sum(jnp.square(half.astype(jnp.float32)), axis=1)
    else:
        rows, err = codec.decode(enc, series_len)
        sn2 = jnp.sum(rows * rows, axis=1)
        d_dec = (qn2[:, None] + sn2[None, :]
                 - 2.0 * jnp.dot(queries, rows.T,
                                 precision=jax.lax.Precision.HIGHEST))
    # additive slack in the squared domain, then sound sqrt-scale interval
    delta = _BOUND_REL * (qn2[:, None] + sn2[None, :]) + _BOUND_ABS
    r_lo = jnp.sqrt(jnp.maximum(d_dec - delta, 0.0))
    r_hi = jnp.sqrt(jnp.maximum(d_dec, 0.0) + delta)
    lb = jnp.square(jnp.maximum(r_lo - err[None, :], 0.0))
    ub = jnp.square(r_hi + err[None, :])
    live = jnp.arange(num) < valid
    pos = jnp.where(live, base + jnp.arange(num, dtype=jnp.int32), -1)
    lb = jnp.where(live[None, :], lb, INF)
    ub = jnp.where(live[None, :], ub, INF)
    pos_b = jnp.broadcast_to(pos, lb.shape)
    ub_d, ub_p = jax.vmap(
        lambda a, b, c, e: _merge_topc(a, b, c, e, k))(ub_d, ub_p, ub, pos_b)
    lb_d, lb_p = jax.vmap(
        lambda a, b, c, e: _merge_topc(a, b, c, e, cand))(lb_d, lb_p, lb,
                                                          pos_b)
    return ub_d, ub_p, lb_d, lb_p


@functools.partial(jax.jit, static_argnames=("k",))
def _codec_exact_topk(rows, p, queries, *, k: int):
    """Exact top-k over the gathered candidate rows: (Q, C, n) float32 rows
    at positions ``p`` (−1 = padding), same difference-form arithmetic as
    ``_ooc_refine_block`` — distances bit-identical to LocalBackend's."""
    d = jnp.sum(jnp.square(rows - queries[:, None, :]), axis=-1)
    d = jnp.where(p >= 0, d, INF)
    neg, idx = jax.lax.top_k(-d, k)
    return -neg, jnp.take_along_axis(p, idx, axis=1)


def _difficulty_from_leaf_lbs(lbs) -> np.ndarray:
    """Per-query cost score in [0, 1] from the leaf-bound landscape: the
    fraction of alive leaves whose LB_EAPCA is within 2x of the query's
    best bound. A flat landscape (many near-best leaves) predicts weak
    pruning — the query will touch many leaves and serve expensive; a
    spiky one prunes well and serves cheap. This is the difficulty signal
    the serve loop's ``pack="difficulty"`` wave packing keys on."""
    lbs = np.asarray(lbs)
    finite = np.isfinite(lbs)
    n_alive = np.maximum(finite.sum(axis=1), 1)
    best = np.where(finite, lbs, np.inf).min(axis=1)
    near = finite & (lbs <= 2.0 * best[:, None] + 1e-12)
    return near.sum(axis=1).astype(np.float32) / n_alive


def _block_positions(extents, pad_to: int) -> np.ndarray:
    """(pad_to,) int32 layout positions of ``extents`` read back to back
    into one block, −1 on the pad rows after them."""
    pos = np.full((pad_to,), -1, np.int32)
    at = 0
    for start, count in extents:
        pos[at:at + count] = np.arange(start, start + count, dtype=np.int32)
        at += count
    return pos


def _pack_extents(extents, cap: int) -> list[list[tuple[int, int]]]:
    """Greedy blocks of consecutive ``(start, count)`` extents, in the
    order given: a block closes when the next extent would take it past
    ``cap`` rows (no extent is longer than ``cap``)."""
    blocks: list[list[tuple[int, int]]] = []
    rows = 0
    for start, count in extents:
        if not blocks or rows + count > cap:
            blocks.append([])
            rows = 0
        blocks[-1].append((start, count))
        rows += count
    return blocks


def _alive_runs(alive: np.ndarray, base: int) -> list[tuple[int, int]]:
    """Contiguous True runs of a row-survival mask as absolute
    (start, count) pairs — the sub-extents the SAX filter could not prune."""
    idx = np.flatnonzero(alive)
    if idx.size == 0:
        return []
    breaks = np.flatnonzero(np.diff(idx) > 1)
    starts = np.concatenate([[0], breaks + 1])
    ends = np.concatenate([breaks, [idx.size - 1]])
    return [(base + int(idx[s]), int(idx[e] - idx[s] + 1))
            for s, e in zip(starts, ends)]


class _OutOfCoreBase(BackendBase):
    """Shared plumbing for backends that stream a :class:`SavedIndex`
    (``repro.storage.open_index``): memory-mapped LRD rows move host→device
    in blocks bounded by ``memory_budget_mb``; only small state (tree, leaf
    tables, permutation) is resident."""

    def __init__(self, saved, config: SearchConfig | None = None,
                 memory_budget_mb: float = 64.0):
        if memory_budget_mb <= 0:
            raise ValueError("memory_budget_mb must be positive")
        self.saved = saved
        self.memory_budget_mb = float(memory_budget_mb)
        self._config = config or saved.config.search
        self._perm = jnp.asarray(saved.small["perm"])
        self._syncs = HostSyncs()
        self._t = {"calls": 0, "blocks": 0, "extents": 0, "rows_streamed": 0,
                   "bytes_streamed": 0, "sax_rows_read": 0,
                   "read_seconds": 0.0, "read_wait_seconds": 0.0,
                   "overlap_blocks": 0,
                   # wave-fused serving: fetches shared across wave members
                   "wave_calls": 0, "wave_rows_shared": 0,
                   "runs_deduped": 0, "runs_skipped_bsf": 0,
                   # codec streaming (format v3): candidate rows re-checked
                   # against float32 truth, and whole-batch fallbacks when
                   # the bounds guard could not certify completeness
                   "codec_refine_rows": 0, "codec_fallbacks": 0}

    def _lrd(self) -> np.ndarray:
        """The LRD memmap, failing loudly if the SavedIndex was closed
        (e.g. the store compacted underneath a stale backend)."""
        return self.saved._mapped("lrd")

    def _lsd(self) -> np.ndarray:
        return self.saved._mapped("lsd")

    def _enc(self) -> np.ndarray:
        return self.saved._mapped("enc")

    def _active_codec(self, cfg: SearchConfig):
        """The codec instance this call streams under, or ``None`` for the
        raw float32 path. ``cfg.codec="auto"`` follows the opened index;
        ``"raw"`` forces the float32 stream (always available); any other
        name must match what the index was encoded with."""
        from repro.storage.codecs import get_codec

        name = getattr(cfg, "codec", "auto")
        saved_codec = getattr(self.saved, "codec", "raw")
        if name == "auto":
            name = saved_codec
        if name == "raw":
            return None
        if name != saved_codec:
            raise ValueError(
                f"codec={name!r} but the index at {self.saved.path!r} was "
                f"encoded with {saved_codec!r}; reopen after "
                f"compact(codec={name!r}) or use codec='auto'|'raw'")
        return get_codec(name)

    @property
    def series_len(self) -> int:
        return self.saved.series_len

    @property
    def base_config(self) -> SearchConfig:
        return self._config

    @classmethod
    def budget_stream_rows(cls, memory_budget_mb: float,
                           series_len: int) -> int:
        """Rows per streamed block/piece under ``memory_budget_mb``: half
        the budget's rows, because the stream keeps two blocks in flight
        (one being consumed, one being read/transferred) at peak. The one
        budget→rows code path — backends, the store, and the CLI all
        derive from here, so the arithmetic cannot drift."""
        budget_rows = int(memory_budget_mb * (1 << 20)) // (4 * series_len)
        return max(budget_rows // 2, 1)

    def stream_rows(self) -> int:
        """Cap on rows per streamed block (see :meth:`budget_stream_rows`)."""
        return self.budget_stream_rows(self.memory_budget_mb,
                                       self.saved.series_len)

    def _reap_reader(self, reader) -> None:
        """Close a chunk reader and fold its stats into the backend's."""
        from repro.data.pipeline import READ_STAT_KEYS

        reader.close()
        for key in READ_STAT_KEYS:
            self._t[key] += reader.stats[key]

    def _ids_of(self, p: jax.Array) -> jax.Array:
        safe = jnp.clip(p, 0, self._perm.shape[0] - 1)
        return jnp.where(p >= 0, self._perm[safe], -1)

    def _count(self, rows: int, row_bytes: int | None = None,
               extents: int = 1) -> None:
        """Account one streamed block of ``extents`` extents: ``row_bytes``
        defaults to the raw float32 width; codec streams pass their encoded
        width so ``bytes_streamed`` reflects the real disk traffic."""
        self._t["blocks"] += 1
        self._t["extents"] += extents
        self._t["rows_streamed"] += rows
        self._t["bytes_streamed"] += rows * (
            4 * self.saved.series_len if row_bytes is None else row_bytes)

    def make_plan(self, cfg, q_struct):
        # Streaming plans are Python loops over jitted block kernels; the
        # jit cache (keyed on block shapes, which the budget fixes) plays
        # the role of the AOT executable here.
        return self._bind(cfg)

    def stats(self) -> dict:
        return {"num_series": self.saved.num_series,
                "series_len": self.saved.series_len,
                "memory_budget_mb": self.memory_budget_mb,
                "codec": getattr(self.saved, "codec", "raw"),
                "host_syncs": self._syncs.count,
                **self._t}

    def _codec_finalize(self, q, cfg: SearchConfig, ub_d, ub_p, lb_d, lb_p,
                        valid_rows: int | None = None):
        """Certify + exact-re-check the codec carries (see the module-level
        codec notes). Returns ``(d, p, fallback_queries)``: exact top-k
        distances/positions, and how many queries the guard could NOT
        certify (0 = the returned answer is complete and exact).
        ``valid_rows`` limits the certification to the leading real queries
        of a padded batch — bucket-padding rows are sliced away by the
        caller, so their (often uncertifiable, e.g. all-zero) guard status
        must not force a fallback."""
        k = cfg.k
        theta = ub_d[:, k - 1]
        # every row not carried in the LB pool had LB >= the pool's largest
        # kept LB; if that is >= theta (>= the true kth distance), dropped
        # and pruned rows can at most tie the kth answer
        certified = self._syncs.read(lb_d[:, -1] >= theta)
        if valid_rows is not None:
            certified = certified[:valid_rows]
        bad = int(certified.size - int(certified.sum()))
        if bad:
            return None, None, bad
        cand_p = self._syncs.read(lb_p)
        safe = np.clip(cand_p, 0, max(self.saved.n_pad - 1, 0))
        # np.take = copy-guaranteed gather of the candidate rows (never a
        # view of the mapped file, so the device transfer cannot alias it)
        rows = jnp.asarray(np.take(self._lrd(), safe, axis=0))
        self._t["codec_refine_rows"] += int(cand_p.size)
        self._t["bytes_streamed"] += int(cand_p.size) * 4 * self.saved.series_len
        d, p = _codec_exact_topk(rows, jnp.asarray(cand_p), q, k=k)
        return d, p, 0

    def describe(self) -> dict:
        d = super().describe()
        d.update(self.stats(), path=self.saved.path)
        return d


class OutOfCoreScanBackend(_OutOfCoreBase):
    """Exact kNN over an on-disk collection via a streamed blocked scan.

    The memory-mapped LRD file is read in row blocks sized to half of
    ``memory_budget_mb`` — the stream keeps two blocks in flight (one
    computing, one being read/transferred), so the *budget* covers peak
    residency, not one block. ``cfg.prefetch`` picks the scheduler:
    ``"sync"`` double-buffers only the host→device copy (the memmap read
    blocks the consumer), ``"thread"`` adds the reader thread + two-slot
    host buffer so the disk read overlaps compute as well — answers are
    bit-identical either way, and ``stats()`` exposes
    ``read_wait_seconds``/``overlap_blocks`` to compare the two. A base
    ``scan_block`` too large for the budget's streamed blocks is
    auto-shrunk (logged) at construction, so small budgets behave the same
    from every entry point. Each block runs the *same* in-memory scan hot
    path (:func:`kernel_scan_knn` when the kernel mode resolves to Pallas,
    else the difference-form :func:`dense_scan_knn`) and running top-k
    merges through the shared :func:`_merge_topk` in file order. Distances
    are bit-identical to :class:`ScanBackend`; ``ids`` are exact original
    ids via the stored permutation and match the in-memory scan except when
    distinct rows *tie exactly* at the top-k boundary (the streamed scan
    visits rows in LRD order, the in-memory scan in original order, so ties
    break differently). ``positions`` are layout (LRD) positions.
    """

    name = "ooc-scan"

    def __init__(self, saved, config: SearchConfig | None = None,
                 memory_budget_mb: float = 64.0):
        super().__init__(saved, config, memory_budget_mb)
        self._config = dataclasses.replace(self._config, force_scan=True)
        # auto-fit: a base scan_block that cannot fit one streamed block is
        # shrunk to the budget's block size, so every entry point (store,
        # CLI, direct construction) behaves identically on small budgets.
        # Explicit per-call scan_block overrides still fail validation.
        rows = self.stream_rows()
        if rows < self._config.scan_block:
            logger.warning(
                "ooc-scan: scan_block=%d exceeds the %g MiB budget's "
                "%d-row streamed blocks; auto-shrinking scan_block to %d",
                self._config.scan_block, self.memory_budget_mb, rows, rows)
            self._config = dataclasses.replace(self._config, scan_block=rows)

    def _validate(self, cfg: SearchConfig) -> None:
        if cfg.scan_block <= 0:
            raise ValueError("scan_block must be positive")
        if self.stream_rows() < cfg.scan_block:
            raise ValueError(
                f"memory_budget_mb={self.memory_budget_mb} streams "
                f"{self.stream_rows()} rows per block (two blocks in "
                f"flight) — less than one scan_block={cfg.scan_block}; "
                f"lower scan_block or raise the budget")

    def _block_rows(self, cfg: SearchConfig) -> int:
        return (self.stream_rows() // cfg.scan_block) * cfg.scan_block

    def _bind(self, cfg):
        mode = resolve_kernel_mode(cfg.kernel_mode)
        codec = self._active_codec(cfg)
        if codec is not None:
            def run(q, valid_rows=None):
                return self._stream_codec_knn(jnp.asarray(q), cfg, mode,
                                              codec, valid_rows=valid_rows)
            run.valid_aware = True
            return run
        return lambda q: self._stream_knn(jnp.asarray(q), cfg, mode)

    def _stream_knn(self, q: jax.Array, cfg: SearchConfig,
                    mode: str) -> KnnResult:
        from repro.data.pipeline import ArrayChunkSource, iter_device_chunks

        num = self.saved.num_series
        R = self._block_rows(cfg)
        qn = q.shape[0]
        d = jnp.full((qn, cfg.k), INF)
        p = jnp.full((qn, cfg.k), -1, jnp.int32)
        blocks = ArrayChunkSource(self._lrd()[:num], R)
        for start, rows in iter_device_chunks(blocks, prefetch=cfg.prefetch,
                                              telemetry=self._t):
            d_b, p_b = _ooc_scan_block(rows, q, jnp.int32(start), k=cfg.k,
                                       block=cfg.scan_block, mode=mode)
            d, p = _ooc_merge(d, p, d_b, p_b, k=cfg.k)
            self._count(rows.shape[0])
        self._t["calls"] += 1
        return self._fill_result(d, p, self._ids_of(p), path=3, accessed=num)

    def _stream_codec_knn(self, q: jax.Array, cfg: SearchConfig, mode: str,
                          codec, valid_rows: int | None = None) -> KnnResult:
        """Streamed scan over the *encoded* sidecar: decoded distances feed
        the UB/LB carries, then candidates are re-checked against float32
        rows (see the module-level codec notes). Bit-identical distances to
        the raw stream; falls back to it when the guard cannot certify."""
        from repro.data.pipeline import ArrayChunkSource, iter_device_chunks

        num = self.saved.num_series
        n = self.saved.series_len
        W = codec.row_bytes(n)
        R = self.stream_rows()
        qn = q.shape[0]
        k = cfg.k
        cand = _codec_cand(k, num)
        ub_d = jnp.full((qn, k), INF)
        ub_p = jnp.full((qn, k), -1, jnp.int32)
        lb_d = jnp.full((qn, cand), INF)
        lb_p = jnp.full((qn, cand), -1, jnp.int32)
        blocks = ArrayChunkSource(self._enc()[:num], R, dtype=np.uint8)
        for start, enc in iter_device_chunks(blocks, prefetch=cfg.prefetch,
                                             telemetry=self._t):
            ub_d, ub_p, lb_d, lb_p = _codec_bounds_block(
                enc, q, jnp.int32(start), jnp.int32(enc.shape[0]),
                ub_d, ub_p, lb_d, lb_p,
                codec=codec, series_len=n, k=k, cand=cand, mode=mode)
            self._count(enc.shape[0], row_bytes=W)
        d, p, bad = self._codec_finalize(q, cfg, ub_d, ub_p, lb_d, lb_p,
                                         valid_rows=valid_rows)
        if bad:
            self._t["codec_fallbacks"] += bad
            return self._stream_knn(q, cfg, mode)
        self._t["calls"] += 1
        return self._fill_result(d, p, self._ids_of(p), path=3, accessed=num)

    def make_wave_plan(self, cfg, q_struct):
        """The streamed scan already reads each block exactly once for the
        whole batch, so the wave path is the batch path — plus telemetry
        attributing the sharing: every streamed row serves all wave
        members but is fetched once. Codec streams share identically (the
        encoded block feeds the whole wave's bound carries)."""
        mode = resolve_kernel_mode(cfg.kernel_mode)
        codec = self._active_codec(cfg)

        def run(q, valid_rows=None):
            q = jnp.asarray(q)
            before = self._t["rows_streamed"]
            if codec is not None:
                res = self._stream_codec_knn(q, cfg, mode, codec,
                                             valid_rows=valid_rows)
            else:
                res = self._stream_knn(q, cfg, mode)
            self._t["wave_calls"] += 1
            self._t["wave_rows_shared"] += ((self._t["rows_streamed"] - before)
                                            * max(int(q.shape[0]) - 1, 0))
            return res

        run.valid_aware = True
        return run


class OutOfCoreLocalBackend(_OutOfCoreBase):
    """Index-pruned out-of-core answering (the paper's reason to build the
    tree at all: touch only the leaves — and series — the bounds cannot
    exclude).

    Resident state is the tree plus the per-leaf pruning tables; raw series
    stay on disk. Per batch: (1) route every query to its home leaf and seed
    BSF_k from those leaf extents; (2) one vectorized LB_EAPCA pass over all
    leaf synopses; (3) for the leaves some query cannot prune, stream the
    **LSD sidecar** (m bytes/series — tiny next to the n-float rows) and
    apply the per-series LB_SAX filter, then fetch only the surviving rows
    as contiguous LRD runs (leaf in-order == file order) cut into
    budget-bounded pieces, refining with exact difference-form distances —
    the paper's phase-3 LSDFile stream, restored for the out-of-core path.
    ``use_sax=False`` falls back to leaf-granularity pruning. Exact by the
    paper's no-false-dismissal argument: a leaf (or series) is skipped only
    if ``lb * (1 - lb_slack)`` ≥ the running BSF_k, which upper-bounds the
    final kth distance.
    """

    name = "ooc-local"

    def __init__(self, saved, config: SearchConfig | None = None,
                 memory_budget_mb: float = 64.0):
        super().__init__(saved, config, memory_budget_mb)
        s = saved.small
        self._leaf_start = s["leaf_start"]
        self._leaf_count = s["leaf_count"]
        self._leaf_rank = jnp.asarray(s["leaf_rank"])
        self._leaf_endpoints = jnp.asarray(s["leaf_endpoints"])
        self._leaf_synopsis = jnp.asarray(s["leaf_synopsis"])
        self._leaf_seg_lens = jnp.asarray(s["leaf_seg_lens"])
        self._srank = np.asarray(s["series_leaf_rank"])

    def _validate(self, cfg: SearchConfig) -> None:
        if self.stream_rows() < self.saved.max_leaf:
            raise ValueError(
                f"memory_budget_mb={self.memory_budget_mb} streams "
                f"{self.stream_rows()} rows per block — less than one leaf "
                f"extent (max_leaf={self.saved.max_leaf}); raise the budget "
                f"or rebuild with a smaller leaf_capacity")

    def _bind(self, cfg):
        codec = self._active_codec(cfg)
        if codec is not None:
            def run(q, valid_rows=None):
                return self._stream_codec_knn(jnp.asarray(q), cfg, codec,
                                              valid_rows=valid_rows)
            run.valid_aware = True
            return run
        return lambda q: self._stream_knn(jnp.asarray(q), cfg)

    def _pad_bucket(self, count: int, cap: int) -> int:
        """Pad a block to a small set of shapes (powers of two between
        max_leaf and the streaming cap) so refine kernels compile O(log)
        times while small blocks don't pay a full-budget zero-fill/copy."""
        b = max(self.saved.max_leaf, 1)
        while b < count:
            b <<= 1
        return min(max(b, 1), max(cap, count))

    def _leaf_lbs(self, q: jax.Array) -> jax.Array:
        """(Q, L) squared LB_EAPCA of every query to every leaf synopsis."""
        return _leaf_lbs_of(q, self._leaf_endpoints, self._leaf_synopsis,
                            self._leaf_seg_lens,
                            np.asarray(self._leaf_count) <= 0)

    def _seed_candidates(self, q: jax.Array, cfg: SearchConfig):
        """Phase 1's inputs (Alg. 11): the (Q, L) LB_EAPCA of every query
        to every leaf, and on the host each query's home leaf rank and its
        ``l_max`` best leaf ranks by that bound."""
        lbs = self._leaf_lbs(q)                              # (Q, L)
        l_max = min(cfg.l_max, self.saved.num_leaves)
        home_ranks, best = _seed_leaves(
            self.saved.tree, self._leaf_rank, q, lbs,
            max_depth=self.saved.max_depth, l_max=l_max)
        return lbs, self._syncs.read(home_ranks), self._syncs.read(best)

    def _needed_leaves(self, lbs: jax.Array, bsf: jax.Array, slack,
                       seeded: list[int]):
        """Phase 2 (Alg. 12): the leaves some query cannot prune against
        its ``bsf``, less the ``seeded`` ones already read, and each query's
        leaf-level pruning ratio."""
        any_q, per_q = _leaf_candidates(lbs, bsf, slack)
        needed = np.array(self._syncs.read(any_q))
        needed[seeded] = False
        n_alive = max(int((np.asarray(self._leaf_count) > 0).sum()), 1)
        eapca_pr = 1.0 - self._syncs.read(per_q).astype(np.float32) / n_alive
        return needed, eapca_pr

    def _stream_knn(self, q: jax.Array, cfg: SearchConfig,
                    exchange=None) -> KnnResult:
        """``exchange``, where given, is called once at the end of phase 1
        with the phase-1 top-k ``(d, p)`` and returns a ``(Q,)`` bound on
        each query's final kth distance (``dist-ooc``'s best-so-far shared
        across shards); phases 2-3 then prune against the smaller of that
        bound and the running kth distance. The bound must be the kth
        distance of k real rows, so pruning stays no-false-dismissal."""
        from repro.data.pipeline import make_chunk_reader

        k = cfg.k
        qn = q.shape[0]
        n = self.saved.series_len
        R = self.stream_rows()
        rows_before = self._t["rows_streamed"]
        d = jnp.full((qn, k), INF)
        p = jnp.full((qn, k), -1, jnp.int32)

        # every raw-row fetch of this call (seeded leaves, then alive runs)
        # flows through one reader, and each fetch is one block of up to R
        # rows packed from many extents: one read, one stage and one refine
        # dispatch a block. Blocks are submitted ahead of consumption, so
        # with prefetch="thread" the next block's page faults land in a
        # slot buffer while the current one refines
        lrd_reader = make_chunk_reader(self._lrd(), R, n,
                                       prefetch=cfg.prefetch)
        lsd_reader = None

        def refine_all(d, p, blocks):
            """Refine blocks of (start, cnt) extents, each read back to back
            into one slot — all submitted before the first is consumed, the
            reader's lookahead window. Rows keep their visit order, and the
            merge keeps the carry first, so ties resolve as extent by
            extent."""
            sized = [(b, sum(c for _, c in b)) for b in blocks]
            for block, rows_in in sized:
                lrd_reader.submit_extents(block,
                                          self._pad_bucket(rows_in, R))
            for block, rows_in in sized:
                rows = lrd_reader.stage(lrd_reader.get())
                pos = _block_positions(block, rows.shape[0])
                with span("repro.ooc.refine", rows=rows_in,
                          extents=len(block)):
                    d, p = _ooc_refine_block(rows, pos, q, d, p, k=k)
                self._count(rows_in, extents=len(block))
            return d, p

        try:
            # -- phase 1 (Alg. 11): seed BSF from each query's home leaf plus
            # its l_max best leaves by LB_EAPCA — same visit set as the
            # in-memory pipeline, so the bound entering phase 2 is comparably
            # tight.
            with span("repro.ooc.seed"):
                lbs, home_ranks, best = self._seed_candidates(q, cfg)
                seeded = sorted(set(int(r) for r in home_ranks if r >= 0)
                                | set(int(r) for r in best.ravel()))
                seeds = [(int(self._leaf_start[r]), int(self._leaf_count[r]))
                         for r in seeded if int(self._leaf_count[r]) > 0]
                seed_rows = sum(cnt for _, cnt in seeds)
                d, p = refine_all(d, p, _pack_extents(seeds, R))
            # the bound shared across shards (none: INF, which leaves each
            # query's running kth distance as its bound)
            shared = jnp.asarray(np.full((qn,), np.inf, np.float32)
                                 if exchange is None else exchange(d, p))

            # -- phase 2: leaf-level pruning over resident synopses ----------
            with span("repro.ooc.select") as sel:
                slack = jnp.float32(1.0 - cfg.lb_slack)
                needed, eapca_pr = self._needed_leaves(
                    lbs, d[:, k - 1] if exchange is None
                    else jnp.minimum(d[:, k - 1], shared), slack, seeded)
                pieces = self._runs(needed, R)
                sel.set_metadata(runs=len(pieces))

            # -- phase 3: stream the LSD sidecar over non-prunable leaves,
            # keep only series the per-row LB_SAX filter cannot exclude, and
            # fetch those as contiguous LRD runs (the paper's LSDFile pass:
            # m bytes of codes buy skipping n floats of raw series) ---------
            use_sax = bool(cfg.use_sax)
            # seeded-leaf rows were read and refined for every query — they
            # count as alive, or sax_pr would overstate pruning (rows the
            # phase-3 filter never saw are not rows it pruned)
            alive_counts = jnp.full((qn,), seed_rows, jnp.int32)
            if not use_sax:
                d, p = refine_all(d, p, _pack_extents(pieces, R))
            else:
                m_sax = int(self._lsd().shape[1])
                q_paa = S.paa(q, m_sax)
                kmode = resolve_kernel_mode(cfg.kernel_mode)
                lsd_reader = make_chunk_reader(self._lsd(), R, m_sax,
                                               np.uint8,
                                               prefetch=cfg.prefetch)
                # the sidecar stream is submitted up front: piece j+1's
                # codes (m bytes/series) read while piece j filters/refines
                for start, cnt in pieces:
                    lsd_reader.submit(start, cnt, self._pad_bucket(cnt, R))
                for start, cnt in pieces:
                    with span("repro.ooc.filter", rows=cnt):
                        # codes padded to the same bucketed shapes as the
                        # row fetches, so the LB kernel compiles O(log)
                        # times, not once per piece length; pad columns are
                        # masked out of `live` below
                        pad_to = self._pad_bucket(cnt, R)
                        codes = lsd_reader.stage(lsd_reader.get())
                        ranks = np.zeros((pad_to,), np.int32)
                        ranks[:cnt] = self._srank[start:start + cnt]
                        self._t["sax_rows_read"] += cnt
                        alive_counts, live = _sax_filter_block(
                            q_paa, codes, jnp.int32(cnt), lbs, ranks, d,
                            shared, slack, alive_counts, series_len=n,
                            mode=kmode)
                        alive = self._syncs.read(live)[:cnt]
                    with span("repro.ooc.select") as sel:
                        runs = _alive_runs(alive, start)
                        sel.set_metadata(runs=len(runs))
                    # a piece's runs fit one block (a piece is at most R
                    # rows), and blocks never cross pieces: the next
                    # piece's filter reads the top-k after this refine
                    d, p = refine_all(d, p, _pack_extents(runs, R))
            self._t["calls"] += 1
        finally:
            self._reap_reader(lrd_reader)
            if lsd_reader is not None:
                self._reap_reader(lsd_reader)

        res = self._fill_result(
            d, p, self._ids_of(p), path=2,
            accessed=self._t["rows_streamed"] - rows_before)
        sax_pr = (1.0 - alive_counts.astype(jnp.float32)
                  / max(self.saved.num_series, 1)
                  if use_sax else jnp.zeros((qn,), jnp.float32))
        return res._replace(
            eapca_pr=jnp.asarray(eapca_pr, jnp.float32),
            sax_pr=sax_pr,
            visited_leaves=jnp.full((qn,), len(seeded) + int(needed.sum()),
                                    jnp.int32))

    def _stream_codec_knn(self, q: jax.Array, cfg: SearchConfig,
                          codec, valid_rows: int | None = None) -> KnnResult:
        """Index-pruned streaming over the *encoded* sidecar (format v3):
        the `_stream_knn` phase structure with the exact running top-k
        replaced by the sound UB/LB carries over decoded distances (see the
        module-level codec notes). The kth *upper* bound plays the BSF role
        in the leaf-level and per-series filters — it provably upper-bounds
        the true kth distance, so pruning stays no-false-dismissal — and the
        candidate pool is re-checked against full-precision float32 rows at
        the end: distances bit-identical to the raw stream, with a
        whole-batch fallback to it when the guard cannot certify."""
        from repro.data.pipeline import make_chunk_reader

        k = cfg.k
        qn = q.shape[0]
        n = self.saved.series_len
        num = self.saved.num_series
        max_leaf = self.saved.max_leaf
        W = codec.row_bytes(n)
        R = self.stream_rows()
        kmode = resolve_kernel_mode(cfg.kernel_mode)
        rows_before = self._t["rows_streamed"]
        cand = _codec_cand(k, num)
        ub_d = jnp.full((qn, k), INF)
        ub_p = jnp.full((qn, k), -1, jnp.int32)
        lb_d = jnp.full((qn, cand), INF)
        lb_p = jnp.full((qn, cand), -1, jnp.int32)

        # every encoded fetch flows through one reader, same submit-ahead
        # lookahead discipline as the raw path's lrd_reader
        enc_reader = make_chunk_reader(self._enc(), R, W, np.uint8,
                                       prefetch=cfg.prefetch)
        lsd_reader = None

        def bounds_all(ub_d, ub_p, lb_d, lb_p, extents):
            """Fold (start, cnt, pad_to) encoded extents into the carries —
            all submitted before the first is consumed."""
            for start, cnt, pad_to in extents:
                enc_reader.submit(start, cnt, pad_to)
            for start, cnt, _ in extents:
                enc = enc_reader.stage(enc_reader.get())
                with span("repro.ooc.refine", rows=cnt):
                    ub_d, ub_p, lb_d, lb_p = _codec_bounds_block(
                        enc, q, jnp.int32(start), jnp.int32(cnt),
                        ub_d, ub_p, lb_d, lb_p, codec=codec, series_len=n,
                        k=k, cand=cand, mode=kmode)
                self._count(cnt, row_bytes=W)
            return ub_d, ub_p, lb_d, lb_p

        try:
            # -- phase 1: seed the conservative BSF (kth upper bound) from
            # each query's home leaf plus its l_max best leaves ------------
            with span("repro.ooc.seed"):
                lbs, home_ranks, best = self._seed_candidates(q, cfg)
                seeded = sorted(set(int(r) for r in home_ranks if r >= 0)
                                | set(int(r) for r in best.ravel()))
                seeds = [(int(self._leaf_start[r]), int(self._leaf_count[r]),
                          max_leaf) for r in seeded
                         if int(self._leaf_count[r]) > 0]
                seed_rows = sum(cnt for _, cnt, _ in seeds)
                ub_d, ub_p, lb_d, lb_p = bounds_all(ub_d, ub_p, lb_d, lb_p,
                                                    seeds)

            # -- phase 2: leaf-level pruning against the kth upper bound ---
            with span("repro.ooc.select") as sel:
                slack = jnp.float32(1.0 - cfg.lb_slack)
                needed, eapca_pr = self._needed_leaves(lbs, ub_d[:, k - 1],
                                                       slack, seeded)
                pieces = self._runs(needed, R)
                sel.set_metadata(runs=len(pieces))

            # -- phase 3: LSD sidecar filter, then encoded alive runs ------
            use_sax = bool(cfg.use_sax)
            alive_counts = jnp.full((qn,), seed_rows, jnp.int32)
            if not use_sax:
                ub_d, ub_p, lb_d, lb_p = bounds_all(
                    ub_d, ub_p, lb_d, lb_p,
                    [(s, c, self._pad_bucket(c, R)) for s, c in pieces])
            else:
                m_sax = int(self._lsd().shape[1])
                q_paa = S.paa(q, m_sax)
                lsd_reader = make_chunk_reader(self._lsd(), R, m_sax,
                                               np.uint8,
                                               prefetch=cfg.prefetch)
                for start, cnt in pieces:
                    lsd_reader.submit(start, cnt, self._pad_bucket(cnt, R))
                for start, cnt in pieces:
                    with span("repro.ooc.filter", rows=cnt):
                        pad_to = self._pad_bucket(cnt, R)
                        codes = lsd_reader.stage(lsd_reader.get())
                        ranks = np.zeros((pad_to,), np.int32)
                        ranks[:cnt] = self._srank[start:start + cnt]
                        self._t["sax_rows_read"] += cnt
                        lb_row = jnp.maximum(
                            kops.lb_sax(q_paa, codes, n, mode=kmode),
                            lbs[:, ranks])                    # (Q, pad_to)
                        bsf = ub_d[:, k - 1]
                        live = ((lb_row * slack < bsf[:, None])
                                & (jnp.arange(pad_to) < cnt)[None, :])
                        alive_counts = alive_counts + jnp.sum(
                            live, axis=1, dtype=jnp.int32)
                        alive = self._syncs.read(
                            jnp.any(live, axis=0))[:cnt]
                    with span("repro.ooc.select") as sel:
                        runs = _alive_runs(alive, start)
                        sel.set_metadata(runs=len(runs))
                    ub_d, ub_p, lb_d, lb_p = bounds_all(
                        ub_d, ub_p, lb_d, lb_p,
                        [(s0, c0, self._pad_bucket(c0, R))
                         for s0, c0 in runs])
        finally:
            self._reap_reader(enc_reader)
            if lsd_reader is not None:
                self._reap_reader(lsd_reader)

        d, p, bad = self._codec_finalize(q, cfg, ub_d, ub_p, lb_d, lb_p,
                                         valid_rows=valid_rows)
        if bad:
            self._t["codec_fallbacks"] += bad
            return self._stream_knn(q, cfg)
        self._t["calls"] += 1
        res = self._fill_result(
            d, p, self._ids_of(p), path=2,
            accessed=self._t["rows_streamed"] - rows_before)
        sax_pr = (1.0 - alive_counts.astype(jnp.float32)
                  / max(self.saved.num_series, 1)
                  if use_sax else jnp.zeros((qn,), jnp.float32))
        return res._replace(
            eapca_pr=jnp.asarray(eapca_pr, jnp.float32),
            sax_pr=sax_pr,
            visited_leaves=jnp.full((qn,), len(seeded) + int(needed.sum()),
                                    jnp.int32))

    def make_wave_plan(self, cfg, q_struct):
        codec = self._active_codec(cfg)
        if codec is not None:
            # Codec streams fold whole blocks into batched bound carries, so
            # the wave already shares every encoded fetch across members;
            # the raw path's per-run demand scheduling (and its BSF-based
            # run skipping) doesn't apply to the carry formulation.
            def run(q, valid_rows=None):
                res = self._stream_codec_knn(jnp.asarray(q), cfg, codec,
                                             valid_rows=valid_rows)
                self._t["wave_calls"] += 1
                return res

            run.valid_aware = True
            return run
        return lambda q: self._stream_wave_knn(jnp.asarray(q), cfg)

    def estimate_difficulty(self, queries: jax.Array) -> np.ndarray:
        return _difficulty_from_leaf_lbs(
            self._leaf_lbs(jnp.asarray(queries)))

    def _stream_wave_knn(self, q: jax.Array, cfg: SearchConfig) -> KnnResult:
        """Wave-fused out-of-core answering: the `_stream_knn` pipeline with
        the wave's disk schedule made explicit (the ROADMAP's "carefully
        schedule costly operations" applied *across* queries).

        Where `_stream_knn` walks leaf runs in file order, this merges every
        member's alive-run list, counts each run's **demand** (how many
        members still need it), fetches each run exactly once in descending
        demand order, and refines all members per fetched block through the
        shared BSF matrix — so a popular leaf is read once for the whole
        wave and its rows tighten every member's bound before the less
        popular runs are even submitted. Submissions flow through
        :func:`repro.data.pipeline.iter_scheduled_chunks`, whose
        ``still_needed`` re-check runs against the *current* BSF matrix
        right before each submit: a run whose last interested member was
        satisfied by an earlier block is dropped without touching the disk
        (``runs_skipped_bsf``). Exactness: a member is counted out of a
        run's demand only when the run's per-member lower bound (min over
        its rows) cannot beat that member's BSF_k — the same
        no-false-dismissal test as the per-query path — so answers stay
        bit-identical to per-query serving. Telemetry: ``runs_deduped``
        (fetches avoided vs independent queries) and ``wave_rows_shared``
        (rows that served >1 member per single fetch).
        """
        from repro.data.pipeline import (iter_scheduled_chunks,
                                         make_chunk_reader)

        k = cfg.k
        qn = q.shape[0]
        n = self.saved.series_len
        max_leaf = self.saved.max_leaf
        R = self.stream_rows()
        rows_before = self._t["rows_streamed"]
        slack_f = 1.0 - cfg.lb_slack
        d = jnp.full((qn, k), INF)
        p = jnp.full((qn, k), -1, jnp.int32)

        lrd_reader = make_chunk_reader(self._lrd(), R, n,
                                       prefetch=cfg.prefetch)
        lsd_reader = None
        counts = np.asarray(self._leaf_count)
        starts_np = np.asarray(self._leaf_start)
        try:
            # -- phase 1: per-member seed sets, fetched once for the union.
            # Demand = how many members asked for the leaf; popular leaves
            # go first so the shared BSF matrix tightens fastest.
            with span("repro.ooc.seed"):
                lbs, home_ranks, best_np = self._seed_candidates(q, cfg)
                demand: collections.Counter = collections.Counter()
                for w in range(qn):
                    member = ({int(home_ranks[w])}
                              | {int(r) for r in best_np[w]})
                    for r in member:
                        if r >= 0 and counts[r] > 0:
                            demand[r] += 1
                seeded = sorted(demand)
                self._t["runs_deduped"] += sum(demand[r] - 1 for r in seeded)
                self._t["wave_rows_shared"] += sum(
                    int(counts[r]) * (demand[r] - 1) for r in seeded)
                seed_rows = sum(int(counts[r]) for r in seeded)
                order = sorted(seeded, key=lambda r: (-demand[r], r))
                extents = [(int(starts_np[r]), int(counts[r]), max_leaf)
                           for r in order]
                for start, cnt, pad_to in extents:
                    lrd_reader.submit(start, cnt, pad_to)
                for start, cnt, _ in extents:
                    rows = lrd_reader.stage(lrd_reader.get())
                    with span("repro.ooc.refine", rows=cnt):
                        d, p = _ooc_refine_block(
                            rows, _block_positions([(start, cnt)],
                                                   rows.shape[0]),
                            q, d, p, k=k)
                    self._count(cnt)

            # -- phase 2: leaf-level pruning, per member -----------------
            with span("repro.ooc.select") as sel:
                slack = jnp.float32(slack_f)
                bsf = d[:, k - 1]
                needed, eapca_pr = self._needed_leaves(lbs, bsf, slack,
                                                       seeded)
                pieces = self._runs(needed, R)
                sel.set_metadata(runs=len(pieces))

            # -- phase 3: build the merged alive-run list with a per-member
            # lower bound per run (min over the run's rows/leaves), instead
            # of refining file-order as the per-query path does -----------
            use_sax = bool(cfg.use_sax)
            alive_counts = jnp.full((qn,), seed_rows, jnp.int32)
            runs: list[tuple[int, int, np.ndarray]] = []
            if not use_sax:
                lbs_np = self._syncs.read(lbs)
                for start, cnt in pieces:
                    ranks = np.unique(self._srank[start:start + cnt])
                    runs.append((start, cnt, lbs_np[:, ranks].min(axis=1)))
            elif pieces:
                m_sax = int(self._lsd().shape[1])
                q_paa = S.paa(q, m_sax)
                kmode = resolve_kernel_mode(cfg.kernel_mode)
                lsd_reader = make_chunk_reader(self._lsd(), R, m_sax,
                                               np.uint8,
                                               prefetch=cfg.prefetch)
                for start, cnt in pieces:
                    lsd_reader.submit(start, cnt, self._pad_bucket(cnt, R))
                for start, cnt in pieces:
                    with span("repro.ooc.filter", rows=cnt):
                        pad_to = self._pad_bucket(cnt, R)
                        codes = lsd_reader.stage(lsd_reader.get())
                        ranks = np.zeros((pad_to,), np.int32)
                        ranks[:cnt] = self._srank[start:start + cnt]
                        self._t["sax_rows_read"] += cnt
                        lb_row = jnp.maximum(
                            kops.lb_sax(q_paa, codes, n, mode=kmode),
                            lbs[:, ranks])                   # (W, pad_to)
                        live = ((lb_row * slack < bsf[:, None])
                                & (jnp.arange(pad_to) < cnt)[None, :])
                        alive_counts = alive_counts + jnp.sum(
                            live, axis=1, dtype=jnp.int32)
                        alive = self._syncs.read(
                            jnp.any(live, axis=0))[:cnt]
                        lb_np = self._syncs.read(lb_row)
                    with span("repro.ooc.select") as sel:
                        alive_runs = _alive_runs(alive, start)
                        sel.set_metadata(runs=len(alive_runs))
                        for s0, c0 in alive_runs:
                            lo = s0 - start
                            runs.append((s0, c0,
                                         lb_np[:, lo:lo + c0].min(axis=1)))

            # -- phase 4: fetch each run once, most-demanded first, with a
            # late BSF re-check per submit ---------------------------------
            bsf_host = {"kth": self._syncs.read(d[:, k - 1])}

            def run_demand(run_lb: np.ndarray) -> int:
                return int((run_lb * slack_f < bsf_host["kth"]).sum())

            runs.sort(key=lambda r: (-run_demand(r[2]), r[0]))

            def still_needed(tag) -> bool:
                _, c0, run_lb = tag
                dm = run_demand(run_lb)
                if dm == 0:
                    self._t["runs_skipped_bsf"] += 1
                    return False
                self._t["runs_deduped"] += dm - 1
                self._t["wave_rows_shared"] += c0 * (dm - 1)
                return True

            reqs = [((s0, c0, run_lb), s0, c0, self._pad_bucket(c0, R))
                    for s0, c0, run_lb in runs]
            for (s0, c0, _), rows in iter_scheduled_chunks(
                    lrd_reader, reqs, still_needed=still_needed):
                with span("repro.ooc.refine", rows=c0):
                    d, p = _ooc_refine_block(
                        rows, _block_positions([(s0, c0)], rows.shape[0]),
                        q, d, p, k=k)
                self._count(c0)
                bsf_host["kth"] = self._syncs.read(d[:, k - 1])
            self._t["calls"] += 1
            self._t["wave_calls"] += 1
        finally:
            self._reap_reader(lrd_reader)
            if lsd_reader is not None:
                self._reap_reader(lsd_reader)

        res = self._fill_result(
            d, p, self._ids_of(p), path=2,
            accessed=self._t["rows_streamed"] - rows_before)
        sax_pr = (1.0 - alive_counts.astype(jnp.float32)
                  / max(self.saved.num_series, 1)
                  if use_sax else jnp.zeros((qn,), jnp.float32))
        return res._replace(
            eapca_pr=jnp.asarray(eapca_pr, jnp.float32),
            sax_pr=sax_pr,
            visited_leaves=jnp.full((qn,), len(seeded) + int(needed.sum()),
                                    jnp.int32))

    def _runs(self, needed: np.ndarray, max_rows: int):
        """Merge needed leaves' extents into contiguous row intervals (leaf
        in-order == file order), then cut into ≤ max_rows pieces."""
        starts = np.asarray(self._leaf_start)
        counts = np.asarray(self._leaf_count)
        intervals: list[list[int]] = []
        for r in np.flatnonzero(needed):
            lo, hi = int(starts[r]), int(starts[r] + counts[r])
            if hi <= lo:
                continue
            if intervals and intervals[-1][1] == lo:
                intervals[-1][1] = hi
            else:
                intervals.append([lo, hi])
        pieces = []
        for lo, hi in intervals:
            for s in range(lo, hi, max_rows):
                pieces.append((s, min(max_rows, hi - s)))
        return pieces


# ---------------------------------------------------------------------------
# Sharded backend — the distributed StackedIndex under a mesh
# ---------------------------------------------------------------------------

class ShardedBackend(BackendBase):
    """Series-sharded Hercules (``StackedIndex``): per-shard exact top-k,
    all-gather, global merge. With one shard on one device this degenerates
    to the local pipeline (same arithmetic, same answers).

    ``positions`` in results are -1 (layout positions are per-shard; global
    ``ids`` are exact) and the per-query pruning telemetry is zeroed —
    cross-shard aggregation of those counters is future work.
    """

    name = "sharded"

    def __init__(self, stacked):
        self.stacked = stacked
        self.mesh = stacked.mesh        # one shard per device, by build
        self._programs: dict[tuple, Callable] = {}

    @property
    def plan_signature(self) -> tuple:
        """Identity of everything the compiled program bakes in besides
        ``cfg``: the mesh topology and the sharded index's shape. Part of
        every plan-cache key (here and in ``QueryEngine``) so plans can
        never be reused across a different mesh or a reopened index —
        the PR 9 dist-ooc convention, now enforced by the
        plan-key-completeness lint."""
        st = self.stacked
        return (self.name, st.num_shards,
                tuple((a, int(s)) for a, s in self.mesh.shape.items()),
                st.max_depth, st.layout.num_series, st.layout.series_len)

    @property
    def series_len(self) -> int:
        return self.stacked.layout.series_len

    @property
    def base_config(self) -> SearchConfig:
        return self.stacked.config.search

    def _validate(self, cfg: SearchConfig) -> None:
        validate_runtime_config(cfg, self.stacked.layout.lrd.shape[-2])

    def _run_for(self, cfg: SearchConfig):
        key = (cfg, self.plan_signature)
        if key not in self._programs:
            from repro.distributed.search import make_distributed_search
            self._programs[key] = make_distributed_search(
                self.mesh, cfg, self.stacked.max_depth,
                self.stacked.tree, self.stacked.layout)
        return self._programs[key]

    def _offsets(self):
        return self.stacked.shard_offsets.reshape(self.stacked.num_shards, 1)

    def _result(self, d, gid) -> KnnResult:
        return self._fill_result(d, jnp.full_like(gid, -1), gid)

    def _bind(self, cfg):
        run = self._run_for(cfg)
        st = self.stacked
        return lambda q: self._result(
            *run(st.tree, st.layout, self._offsets(), q))

    def make_plan(self, cfg, q_struct):
        run = self._run_for(cfg)
        st = self.stacked
        offsets = self._offsets()
        compiled = run.lower(st.tree, st.layout, offsets, q_struct).compile()
        return lambda q: self._result(
            *compiled(st.tree, st.layout, offsets, q))

    def stats(self) -> dict:
        st = self.stacked
        return {"num_shards": st.num_shards,
                "num_series": st.num_shards * st.layout.num_series,
                "series_len": st.layout.series_len}

    def describe(self) -> dict:
        d = super().describe()
        d.update(self.stats(), mesh={a: int(s) for a, s in self.mesh.shape.items()})
        return d


# ---------------------------------------------------------------------------
# The engine: bucketed batching + compiled-plan LRU + telemetry
# ---------------------------------------------------------------------------

class _TelemetrySection:
    """Dict-compatibility shim for the telemetry dataclasses: the historical
    ``telemetry()["plan_cache"]["hits"]`` access style keeps working (keys
    are deprecated aliases of the fields), while attribute access —
    ``telemetry().plan_cache.hits`` — is the API. ``None``-valued optional
    sections behave like absent dict keys (``"ooc" not in telemetry()``)."""

    _ALIASES: dict = {}

    def keys(self):
        return tuple(f.name for f in dataclasses.fields(self)
                     if getattr(self, f.name) is not None)

    def values(self):
        return tuple(getattr(self, k) for k in self.keys())

    def items(self):
        return tuple((k, getattr(self, k)) for k in self.keys())

    def _resolve(self, key):
        key = self._ALIASES.get(key, key)
        if key not in (f.name for f in dataclasses.fields(self)):
            raise KeyError(key)
        return key

    def __getitem__(self, key):
        key = self._resolve(key)
        value = getattr(self, key)
        if value is None:
            raise KeyError(key)
        return value

    def __setitem__(self, key, value):
        object.__setattr__(self, self._resolve(key), value)

    def get(self, key, default=None):
        try:
            return self[key]
        except KeyError:
            return default

    def __contains__(self, key):
        return self.get(key) is not None

    def __iter__(self):
        return iter(self.keys())


@dataclasses.dataclass
class PlanCacheTelemetry(_TelemetrySection):
    hits: int = 0
    misses: int = 0
    evictions: int = 0
    size: int = 0
    capacity: int = 0
    compiles: int = 0
    compile_s: float = 0.0
    invalidations: int = 0


@dataclasses.dataclass
class LatencyTelemetry(_TelemetrySection):
    total: float = 0.0


@dataclasses.dataclass
class PathsTelemetry(_TelemetrySection):
    scan_eapca: int = 0
    scan_sax: int = 0
    pruned: int = 0
    forced_scan: int = 0
    unknown: int = 0


@dataclasses.dataclass
class PruningTelemetry(_TelemetrySection):
    eapca_mean: float = 0.0
    sax_mean: float = 0.0


@dataclasses.dataclass
class OocTelemetry(_TelemetrySection):
    """Streaming counters of the out-of-core backends (absent — ``None``
    section — for fully-resident backends). ``blocks`` counts fetches and
    ``extents`` the leaf extents and alive runs they held (the raw stream
    packs many into one block, so ``extents / blocks`` is its packing
    ratio). ``bytes_streamed`` counts the bytes actually fetched (encoded
    width under a codec, plus the float32 re-check rows), the honest
    bandwidth number the codec benchmarks key on;
    ``codec_refine_rows``/``codec_fallbacks`` account the exactness
    machinery of format-v3 encoded streams."""
    calls: int = 0
    blocks: int = 0
    extents: int = 0
    rows_streamed: int = 0
    bytes_streamed: int = 0
    sax_rows_read: int = 0
    read_seconds: float = 0.0
    read_wait_seconds: float = 0.0
    overlap_blocks: int = 0
    wave_calls: int = 0
    wave_rows_shared: int = 0
    runs_deduped: int = 0
    runs_skipped_bsf: int = 0
    codec_refine_rows: int = 0
    codec_fallbacks: int = 0
    host_syncs: int = 0


@dataclasses.dataclass
class ServingTelemetry(_TelemetrySection):
    """The slot-based front end's counters
    (:class:`repro.serve.engine.KnnServeEngine`). ``queue_wait_s`` sums,
    over the ``dequeued`` requests whose wave was taken, the time from
    their submit to the start of that wave."""
    pending: int = 0
    served: int = 0
    unclaimed: int = 0
    batch_slots: int = 0
    waves: int = 0
    wave_mode: bool = False
    pack: str = ""
    max_queue: int | None = None
    rejected: int = 0
    failed: int = 0
    difficulty_scored: int = 0
    difficulty_mean: float = 0.0
    queue_wait_s: float = 0.0
    dequeued: int = 0


@dataclasses.dataclass
class DistTelemetry(_TelemetrySection):
    """Per-shard accounting of the distributed out-of-core backend
    (``dist-ooc``; absent for single-host backends). List fields are
    indexed by shard. ``imbalance`` is the max/min per-shard
    ``rows_streamed`` ratio of the traffic actually served;
    ``plan_imbalance`` is the same ratio over the shard *plan*'s row
    counts, and ``balance_warning`` mirrors the
    ``repro.storage.partition`` guardrail (plan ratio above
    ``BALANCE_WARN_RATIO``). ``row_range`` is each shard's assigned
    ``[lo, hi)`` file-row range and ``rows_touched`` the absolute extremes
    its readers actually touched (``None`` until the first read) — the
    residency-confinement proof: touched ⊆ assigned, always. Where the
    shards share one bound (the raw per-query stream over more than one
    shard), ``phase1_rows`` counts the rows each shard read in phase 1 and
    ``exchange_wait_seconds`` the time each waited at the exchange for the
    slowest shard; ``merge_seconds`` sums both collective merges (the
    exchange's and the final one) over all calls."""
    shards: int = 0
    rows_streamed: list = dataclasses.field(default_factory=list)
    read_wait_seconds: list = dataclasses.field(default_factory=list)
    bytes_streamed: list = dataclasses.field(default_factory=list)
    imbalance: float = 1.0
    plan_rows: list = dataclasses.field(default_factory=list)
    plan_imbalance: float = 1.0
    balance_warning: bool = False
    row_range: list = dataclasses.field(default_factory=list)
    rows_touched: list = dataclasses.field(default_factory=list)
    phase1_rows: list = dataclasses.field(default_factory=list)
    exchange_wait_seconds: list = dataclasses.field(default_factory=list)
    merge_seconds: float = 0.0


@dataclasses.dataclass
class Telemetry(_TelemetrySection):
    """The one serving-telemetry shape (see ``repro.api`` for the key →
    field mapping table). Sections are dataclasses; ``ooc`` is ``None``
    unless the backend streams from disk, ``serving`` is filled by
    :class:`repro.serve.engine.KnnServeEngine`. ``host_syncs`` counts the
    blocking device-to-host reads and waits of the layers it covers: the
    engine's, the backend's, and the front end's where ``serving`` is
    filled. ``rows_skipped`` counts the padding rows of a batch that the
    plan skipped (the local plan runs no pipeline for them)."""
    backend: str = ""
    calls: int = 0
    queries: int = 0
    rows_skipped: int = 0
    wave_calls: int = 0
    host_syncs: int = 0
    plan_cache: PlanCacheTelemetry = dataclasses.field(
        default_factory=PlanCacheTelemetry)
    latency: LatencyTelemetry = dataclasses.field(
        default_factory=LatencyTelemetry)
    paths: PathsTelemetry = dataclasses.field(default_factory=PathsTelemetry)
    pruning: PruningTelemetry = dataclasses.field(
        default_factory=PruningTelemetry)
    ooc: OocTelemetry | None = None
    dist: DistTelemetry | None = None
    serving: ServingTelemetry | None = None

    _ALIASES = {"latency_s": "latency"}


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    plan_cache_size: int = 32
    # explicit batch buckets (ascending); empty -> next power of two
    bucket_sizes: tuple[int, ...] = ()
    # pull per-query path/pruning stats to host after each call
    collect_result_stats: bool = True


class QueryEngine:
    """A serving session over one :class:`SearchBackend`.

    Every call pads the query batch up to a bucket size and dispatches a
    cached AOT-compiled plan for (SearchConfig, bucket). Repeated serving
    calls with the same statics therefore never retrace or recompile —
    ``telemetry()["plan_cache"]`` proves it.
    """

    def __init__(self, backend: SearchBackend,
                 config: EngineConfig | None = None):
        self.backend = backend
        self.config = config or EngineConfig()
        self._plans: collections.OrderedDict = collections.OrderedDict()
        self._syncs = HostSyncs()
        self._t = {
            "calls": 0, "queries": 0, "rows_skipped": 0, "wave_calls": 0,
            "hits": 0, "misses": 0, "evictions": 0,
            "invalidations": 0,
            "compile_s": 0.0, "exec_s": 0.0,
            "paths": np.zeros(4, np.int64), "path_unknown": 0,
            "eapca_pr_sum": 0.0, "sax_pr_sum": 0.0, "stat_queries": 0,
        }

    def invalidate(self) -> None:
        """Drop every cached compiled plan. Called when the data a plan was
        compiled against changes underneath the backend — e.g. the store
        handle (``repro.storage.store.Hercules``) appended or compacted —
        so a stale executable can never serve the mutated collection."""
        self._plans.clear()
        self._t["invalidations"] += 1

    # -- batching -----------------------------------------------------------

    def _bucket(self, qn: int) -> int:
        for b in sorted(self.config.bucket_sizes):
            if qn <= b:
                return b
        # larger than every configured bucket (or none configured):
        # next power of two keeps the distinct-shape count logarithmic
        return max(1, 1 << (qn - 1).bit_length())

    # -- the one call that matters ------------------------------------------

    def knn(self, queries: jax.Array, k: int | None = None,
            valid_rows: int | None = None, wave: bool = False,
            **overrides: Any) -> KnnResult:
        """``valid_rows``: when the caller already padded the batch (e.g. a
        slot-based server filling its wave), the number of leading real
        queries — results are sliced and telemetry counted on those only.

        ``wave=True`` answers the batch through the backend's wave-fused
        plan (shared descent / BSF matrix / once-per-wave disk fetches);
        answers are bit-identical to ``wave=False``, which maps the
        per-query pipeline over the batch. Backends without per-query work
        to share (dense scans, sharded) fall back to the regular plan."""
        q = jnp.asarray(queries)
        if q.ndim == 1:
            q = q[None, :]
        n = getattr(self.backend, "series_len", None)
        if n and q.shape[1] != n:
            raise ValueError(f"query length {q.shape[1]} != collection "
                             f"series length {n}")
        cfg = self.backend.resolve(k, overrides)
        qn = q.shape[0] if valid_rows is None else valid_rows
        if not 0 < qn <= q.shape[0]:
            raise ValueError(f"valid_rows={valid_rows} out of range for "
                             f"batch of {q.shape[0]}")
        bucket = self._bucket(q.shape[0])
        if bucket != q.shape[0]:
            q = jnp.concatenate(
                [q, jnp.zeros((bucket - q.shape[0], q.shape[1]), q.dtype)],
                axis=0)

        # plan_signature folds backend identity the SearchConfig cannot see
        # into the key — e.g. dist-ooc's mesh shape: a plan compiled for one
        # mesh must never serve another
        key = (cfg, bucket, q.shape[1], q.dtype.name, wave,
               getattr(self.backend, "plan_signature", None))
        plan = self._plans.get(key)
        with span("repro.engine.plan", hit=int(plan is not None)):
            if plan is None:
                t0 = time.perf_counter()
                maker = (self.backend.make_wave_plan if wave
                         else self.backend.make_plan)
                plan = maker(cfg, jax.ShapeDtypeStruct(q.shape, q.dtype))
                self._t["compile_s"] += time.perf_counter() - t0
                self._t["misses"] += 1
                self._plans[key] = plan
                while len(self._plans) > self.config.plan_cache_size:
                    self._plans.popitem(last=False)
                    self._t["evictions"] += 1
            else:
                self._t["hits"] += 1
                self._plans.move_to_end(key)

        with span("repro.engine.run"):
            t0 = time.perf_counter()
            if getattr(plan, "valid_aware", False):
                # the local plan skips the padding rows (sliced away below);
                # codec plans certify per-query completeness, and those rows
                # must not trip the certify guard
                res = plan(q, valid_rows=qn)
            else:
                res = plan(q)
            self._syncs.wait(res.dists)
            self._t["exec_s"] += time.perf_counter() - t0
        self._t["calls"] += 1
        self._t["queries"] += qn
        if getattr(plan, "skips_padding", False):
            self._t["rows_skipped"] += bucket - qn
        if wave:
            self._t["wave_calls"] += 1

        if bucket != qn:
            with span("repro.engine.cut"):
                res = KnnResult(*[a[:qn] for a in res])
        if self.config.collect_result_stats:
            self._record(res)
        return res

    def estimate_difficulty(self, queries) -> np.ndarray | None:
        """Cheap per-query cost scores in [0, 1] (higher = likely slower),
        from the backend's resident pruning tables — the signal behind
        difficulty-aware wave packing. ``None`` when the backend has no
        leaf-bound landscape to score against (dense scans cost the same
        for every query)."""
        fn = getattr(self.backend, "estimate_difficulty", None)
        if fn is None:
            return None
        return fn(jnp.asarray(queries))

    def _record(self, res: KnnResult) -> None:
        with span("repro.engine.stats"):
            path = self._syncs.read(res.path)
            known = path >= 0
            self._t["paths"] += np.bincount(path[known], minlength=4)[:4]
            self._t["path_unknown"] += int((~known).sum())
            if known.any():
                self._t["eapca_pr_sum"] += float(
                    self._syncs.read(res.eapca_pr)[known].sum())
                self._t["sax_pr_sum"] += float(
                    self._syncs.read(res.sax_pr)[known].sum())
                self._t["stat_queries"] += int(known.sum())

    # -- introspection ------------------------------------------------------

    def telemetry(self) -> Telemetry:
        t = self._t
        n_stat = max(t["stat_queries"], 1)
        bstats = self.backend.stats()
        ooc = None
        if "rows_streamed" in bstats:
            ooc = OocTelemetry(**{f.name: bstats[f.name]
                                  for f in dataclasses.fields(OocTelemetry)
                                  if f.name in bstats})
        dist = None
        if "dist" in bstats:
            dsec = bstats["dist"]
            dist = DistTelemetry(**{f.name: dsec[f.name]
                                    for f in dataclasses.fields(DistTelemetry)
                                    if f.name in dsec})
        return Telemetry(
            backend=self.backend.name,
            calls=t["calls"],
            queries=t["queries"],
            rows_skipped=t["rows_skipped"],
            wave_calls=t["wave_calls"],
            host_syncs=self._syncs.count + bstats.get("host_syncs", 0),
            plan_cache=PlanCacheTelemetry(
                hits=t["hits"], misses=t["misses"],
                evictions=t["evictions"], size=len(self._plans),
                capacity=self.config.plan_cache_size,
                compiles=t["misses"], compile_s=t["compile_s"],
                invalidations=t["invalidations"]),
            latency=LatencyTelemetry(total=t["exec_s"]),
            paths=PathsTelemetry(
                scan_eapca=int(t["paths"][0]),
                scan_sax=int(t["paths"][1]),
                pruned=int(t["paths"][2]),
                forced_scan=int(t["paths"][3]),
                unknown=t["path_unknown"]),
            pruning=PruningTelemetry(
                eapca_mean=t["eapca_pr_sum"] / n_stat,
                sax_mean=t["sax_pr_sum"] / n_stat),
            ooc=ooc, dist=dist)

    def stats(self) -> dict:
        return self.backend.stats()

    def describe(self) -> dict:
        return {
            "engine": {
                "plan_cache_size": self.config.plan_cache_size,
                "bucket_sizes": list(self.config.bucket_sizes) or "pow2",
                "cached_plans": [
                    {"k": key[0].k, "bucket": key[1], "series_len": key[2]}
                    for key in self._plans],
            },
            "backend": self.backend.describe(),
        }


# ---------------------------------------------------------------------------
# Name-based construction (benchmarks/run.py --backend, serve_knn CLI)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BackendSpec:
    """One registered backend name: which construction paths serve it
    (``"memory"`` = :func:`make_backend` over an in-RAM collection,
    ``"disk"`` = :func:`make_disk_backend` over a saved index) and a
    one-line description for CLIs/docs."""
    name: str
    kinds: tuple[str, ...]
    description: str


#: The one registry of servable backend names. Every name-based entry point
#: (``make_backend``, ``make_disk_backend``, ``Hercules.engine``, the serve
#: CLI, benchmarks) resolves through here via :func:`resolve_backend_name`,
#: so the valid-name set and the error message cannot drift between them.
BACKENDS: dict[str, BackendSpec] = {s.name: s for s in (
    BackendSpec("local", ("memory", "disk"),
                "Hercules index in RAM: tree routing + EAPCA/SAX pruning "
                "+ exact refine"),
    BackendSpec("scan", ("memory", "disk"),
                "exact dense scan of the full collection"),
    BackendSpec("scan-mxu", ("memory",),
                "dense scan through the Pallas ED kernel (MXU matmul form)"),
    BackendSpec("sharded", ("memory",),
                "series-sharded index under a device mesh"),
    BackendSpec("ooc-scan", ("disk",),
                "streamed blocked scan of the on-disk collection under a "
                "memory budget"),
    BackendSpec("ooc-local", ("disk",),
                "index-pruned out-of-core answering (stream only "
                "unprunable leaves/series)"),
    BackendSpec("dist-ooc", ("disk",),
                "sharded out-of-core serving: each mesh device streams its "
                "own leaf-run row range, top-k merged collectively"),
)}


def backend_names(kind: str | None = None) -> tuple[str, ...]:
    """Registered backend names, registration order; ``kind`` filters to
    one construction path (``"memory"`` or ``"disk"``)."""
    return tuple(n for n, s in BACKENDS.items()
                 if kind is None or kind in s.kinds)


def resolve_backend_name(name: str, *, kind: str) -> BackendSpec:
    """The single place backend-name strings are validated. Returns the
    :class:`BackendSpec` or raises the one canonical error message."""
    spec = BACKENDS.get(name)
    if spec is not None and kind in spec.kinds:
        return spec
    raise ValueError(f"unknown {kind} backend {name!r}; expected one of "
                     f"{backend_names(kind)}")


# deprecated aliases of the registry's two views — prefer
# ``backend_names("memory")`` / ``backend_names("disk")``
BACKEND_NAMES = backend_names("memory")


def make_backend(name: str, data: jax.Array, *,
                 index_config: IndexConfig | None = None,
                 search: SearchConfig | None = None,
                 num_shards: int | None = None,
                 mesh=None) -> SearchBackend:
    """Build a backend over ``data`` by name (see :data:`BACKENDS`).

    ``local``/``sharded`` construct the Hercules index (or stacked indexes);
    ``scan``/``scan-mxu`` serve the raw collection directly.
    """
    resolve_backend_name(name, kind="memory")
    if name == "local":
        cfg = index_config or IndexConfig(search=search or SearchConfig())
        return LocalBackend(HerculesIndex.build(data, cfg))
    if name in ("scan", "scan-mxu"):
        scfg = search or (index_config.search if index_config else SearchConfig())
        return ScanBackend(data, scfg, mxu=name == "scan-mxu")
    if name == "sharded":
        from repro.distributed.search import build_distributed_index
        cfg = index_config or IndexConfig(search=search or SearchConfig())
        shards = num_shards or (int(mesh.devices.size) if mesh is not None
                                else len(jax.devices()))
        return ShardedBackend(
            build_distributed_index(data, shards, cfg, mesh=mesh))
    raise AssertionError(f"registered backend {name!r} not constructed")


DISK_BACKEND_NAMES = backend_names("disk")   # deprecated alias


def make_disk_backend(name: str, store, *,
                      search: SearchConfig | None = None,
                      memory_budget_mb: float = 64.0,
                      verify: bool = True,
                      prefetch: str | None = None,
                      shards: int | None = None,
                      mesh=None) -> SearchBackend:
    """Serve a saved index by backend name.

    ``store`` is an index-directory path, an already-open ``SavedIndex``,
    or a ``Hercules`` store handle (backends then resolve their data
    through the handle's current base index). ``local``/``scan``
    materialize the saved arrays into the ordinary in-memory backends
    (bit-identical to the ones built from the original data);
    ``ooc-scan``/``ooc-local`` keep the raw series memory-mapped and
    stream them under ``memory_budget_mb``. ``prefetch`` overrides
    ``SearchConfig.prefetch`` for the streamed backends (``"thread"`` =
    async reader thread + two-slot host buffer; answers bit-identical to
    ``"sync"``). ``dist-ooc`` serves the index from every device of a
    mesh at once — ``shards`` (default: device count) or an explicit
    ``mesh`` picks the layout; each shard streams only its own leaf-run
    row range and ``memory_budget_mb`` applies per shard.

    .. deprecated:: store API
        For directory paths prefer ``repro.api.Hercules.open(path)
        .engine(name)``, which additionally caches engines and invalidates
        compiled plans across ``append``/``compact``; this remains the
        low-level constructor the store delegates to.
    """
    from repro.storage import open_index

    resolve_backend_name(name, kind="disk")
    if isinstance(store, str):
        saved = open_index(store, verify=verify)
    else:
        # a Hercules handle exposes .saved; a SavedIndex is used directly
        saved = getattr(store, "saved", store)
        if saved is None:
            raise ValueError(
                f"{store!r} has no base index to serve — append rows and "
                f"compact() first")
    if prefetch is not None:
        search = dataclasses.replace(search or saved.config.search,
                                     prefetch=prefetch)
    if name == "local":
        idx = saved.to_index()
        if search is not None:
            idx.config = dataclasses.replace(idx.config, search=search)
        return LocalBackend(idx)
    if name == "scan":
        return ScanBackend(jnp.asarray(saved.original_data()),
                           search or saved.config.search)
    if name == "ooc-scan":
        return OutOfCoreScanBackend(saved, search,
                                    memory_budget_mb=memory_budget_mb)
    if name == "ooc-local":
        return OutOfCoreLocalBackend(saved, search,
                                     memory_budget_mb=memory_budget_mb)
    if name == "dist-ooc":
        # lazy import: core must not depend on repro.distributed at import
        from repro.distributed.ooc import DistOutOfCoreBackend

        return DistOutOfCoreBackend(saved, search,
                                    memory_budget_mb=memory_budget_mb,
                                    shards=shards, mesh=mesh)
    raise AssertionError(f"registered backend {name!r} not constructed")
