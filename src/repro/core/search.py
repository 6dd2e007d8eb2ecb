"""Exact kNN query answering (paper §3.4, Algorithms 10–14), TPU-native.

Phase map (DESIGN.md §2):

  1. *Approximate search* (Alg. 11): route the query to its home leaf, rank
     all leaves by LB_EAPCA (the vectorized fixpoint of the paper's priority
     queue) and visit the best ``l_max``; exact distances over those leaf
     extents seed the best-so-far BSF_k.
  2. *Candidate leaves* (Alg. 12): vectorized LB_EAPCA test over every leaf;
     pruning ratio ``eapca_pr``.
  3. *Candidate series* (Alg. 13): LB_SAX over the LSD sidecar, masked to
     candidate leaves; pruning ratio ``sax_pr``.
  4. *Exact refinement* (Alg. 14): candidates sorted by LB ascending are
     processed in fixed-size chunks inside ``lax.while_loop``; the loop exits
     when the chunk's smallest LB exceeds BSF_k — the same no-false-dismissal
     argument as the paper, with a static shape budget.

Adaptive access-path selection (Alg. 10 lines 10/15): when ``eapca_pr`` <
EAPCA_TH or ``sax_pr`` < SAX_TH, fall back to the *dense scan* — a blocked
streaming pass over the leaf-ordered LRD array (the skip-sequential-scan
analogue; on the MXU this is the high-arithmetic-intensity path). Queries run
through ``lax.map`` so the ``lax.cond`` branches stay real branches (the
paper's "queries run asynchronously"; parallelism lives *inside* a query).

Everything here is exact: all paths return the true k nearest neighbors.

Device scopes: in `exact_knn` and `wave_knn`, phase 1 runs under
``jax.named_scope("seed")``, phases 2-3 under ``candidates``, and phase 4
under ``refine`` or ``scan`` (the two branches of `_finish_one`). The names
ride in each operation's metadata (``op_name``) into the profiler's trace,
so device time can be put down to a phase; they change nothing else in the
compiled program.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import lower_bounds as LB
from repro.core import summaries as S
from repro.core.layout import HerculesLayout
from repro.core.tree import HerculesTree, route_to_leaf
from repro.kernels import ops as kops
from repro.kernels.compat import KERNEL_MODES, resolve_kernel_mode


@dataclasses.dataclass(frozen=True)
class SearchConfig:
    """Static query-answering settings (paper §4.2 Parameterization)."""
    k: int = 1
    l_max: int = 80              # approximate-phase leaf visits (paper: 80)
    eapca_th: float = 0.25       # paper: 0.25
    sax_th: float = 0.50         # paper: 0.50
    chunk: int = 1024            # phase-4 refinement chunk (static budget)
    scan_block: int = 4096       # dense-scan block
    use_sax: bool = True         # False -> NoSAX ablation (EAPCA-only LBs)
    adaptive: bool = True        # False -> NoThresh ablation (always prune path)
    force_scan: bool = False     # True -> PSCAN baseline behaviour
    lb_slack: float = 1e-5       # fp32 guard: treat lb*(1-slack) as the bound
    unroll_visits: bool = False  # unroll the phase-1 leaf-visit loop (dry-run
                                 # probes: XLA counts scan bodies once)
    refine_select: str = "argsort"   # 'argsort' (full sort) | 'topk'
    topk_budget_chunks: int = 32     # candidate budget C = chunks * chunk
    kernel_mode: str = "auto"    # Pallas dispatch: auto | pallas | interpret
                                 # | ref (kernels/compat.py owns the policy)
    prefetch: str = "sync"       # out-of-core disk reads: sync | thread
                                 # (reader thread + two-slot host buffer;
                                 # data/pipeline.py owns the readers).
                                 # Answers are bit-identical across modes.
    codec: str = "auto"          # out-of-core leaf codec: auto | raw | bf16
                                 # | sax-residual (storage/codecs.py owns
                                 # the registry; "auto" follows the opened
                                 # index). Answers are bit-identical under
                                 # every codec — lossy codecs only shrink
                                 # the streamed bytes.

    def __post_init__(self):
        # every field is validated here (herculint config-plumbing): a bad
        # value must raise at construction, not as an XLA shape error three
        # layers into a traced kernel
        for field, lo in (("k", 1), ("l_max", 1), ("chunk", 1),
                          ("scan_block", 1), ("topk_budget_chunks", 1)):
            val = getattr(self, field)
            if not isinstance(val, int) or isinstance(val, bool) or val < lo:
                raise ValueError(f"{field}={val!r}; expected an int >= {lo}")
        import math
        for field in ("eapca_th", "sax_th"):
            # pruning ratios live in [0, 1], but >1 is a legitimate knob
            # (always below threshold -> always scan, the PSCAN-ish probe)
            val = getattr(self, field)
            if not (math.isfinite(float(val)) and float(val) >= 0.0):
                raise ValueError(f"{field}={val!r}; expected a finite "
                                 "pruning threshold >= 0")
        if not 0.0 <= float(self.lb_slack) < 1.0:
            raise ValueError(f"lb_slack={self.lb_slack!r}; expected a "
                             "relative guard in [0, 1)")
        for field in ("use_sax", "adaptive", "force_scan", "unroll_visits"):
            if not isinstance(getattr(self, field), bool):
                raise ValueError(f"{field}={getattr(self, field)!r}; "
                                 "expected a bool")
        if self.refine_select not in ("argsort", "topk"):
            raise ValueError(f"refine_select={self.refine_select!r}; "
                             "expected 'argsort' or 'topk'")
        if self.kernel_mode not in KERNEL_MODES:
            raise ValueError(f"kernel_mode={self.kernel_mode!r}; expected "
                             f"one of {KERNEL_MODES}")
        from repro.data.pipeline import PREFETCH_MODES
        if self.prefetch not in PREFETCH_MODES:
            raise ValueError(f"prefetch={self.prefetch!r}; expected one of "
                             f"{PREFETCH_MODES}")
        from repro.storage.codecs import CODEC_CHOICES
        if self.codec not in CODEC_CHOICES:
            raise ValueError(f"codec={self.codec!r}; expected one of "
                             f"{CODEC_CHOICES}")

    def pad_multiple(self) -> int:
        import math
        return math.lcm(self.chunk, self.scan_block)


def validate_runtime_config(cfg: SearchConfig, n_pad: int) -> None:
    """Check per-call settings against a layout padded to ``n_pad`` rows.

    The only thing a built layout bakes in is its padded row count; any
    ``chunk``/``scan_block`` that *divides* ``n_pad`` is servable without a
    rebuild (blocked reshapes and chunked slices stay exact — no ragged
    tail). Every other SearchConfig field is a free per-call knob. This
    replaces the older, stricter pad-multiple equality test, which rejected
    valid combinations like halving ``chunk`` on an already-padded layout.
    """
    for field in ("chunk", "scan_block"):
        val = getattr(cfg, field)
        if val <= 0 or n_pad % val:
            raise ValueError(
                f"{field}={val} does not divide the padded collection size "
                f"{n_pad}; pick a divisor of {n_pad} or rebuild the index "
                f"with the target SearchConfig")


class KnnResult(NamedTuple):
    dists: jax.Array       # (Q, k) squared ED, ascending
    positions: jax.Array   # (Q, k) layout (LRD) positions
    ids: jax.Array         # (Q, k) original series ids
    path: jax.Array        # (Q,) 0=scan(eapca) 1=scan(sax) 2=pruned 3=forced
    eapca_pr: jax.Array    # (Q,) leaf-level pruning ratio
    sax_pr: jax.Array      # (Q,) series-level pruning ratio
    accessed: jax.Array    # (Q,) exact-distance computations performed
    visited_leaves: jax.Array  # (Q,)


INF = jnp.float32(jnp.inf)


def _merge_topk(d0, p0, d1, p1, k: int):
    """Merge (d1, p1) candidates into the running top-k (d0, p0).

    The paper's Results array is a *set* of series; a position already present
    in the running top-k must not enter twice (phase 1 may visit a leaf that
    refinement later re-reads). New candidates are distinct among themselves
    by construction (leaf extents / argsort chunks / scan blocks), so checking
    against the carry is sufficient.
    """
    dup = jnp.any(p1[None, :] == p0[:, None], axis=0)
    d1 = jnp.where(dup, INF, d1)
    d = jnp.concatenate([d0, d1])
    p = jnp.concatenate([p0, p1])
    neg, idx = jax.lax.top_k(-d, k)
    return -neg, p[idx]


def _query_seg_stats(qp, qp2, endpoints):
    """Query stats under many segmentations. qp/qp2 (n+1,), endpoints (L, M)."""
    starts = jnp.concatenate(
        [jnp.zeros((endpoints.shape[0], 1), endpoints.dtype), endpoints[:, :-1]],
        axis=1)
    lens = jnp.maximum((endpoints - starts).astype(jnp.float32), 1.0)
    s1 = qp[endpoints] - qp[starts]
    s2 = qp2[endpoints] - qp2[starts]
    mean = s1 / lens
    var = jnp.maximum(s2 / lens - jnp.square(mean), 0.0)
    empty = (endpoints - starts) <= 0
    return (jnp.where(empty, 0.0, mean), jnp.where(empty, 0.0, jnp.sqrt(var)))


def _leaf_lbs(q, layout: HerculesLayout):
    """(L,) squared LB_EAPCA of the query to every leaf (+inf for empty/pad)."""
    qp, qp2 = S.prefix_sums(q[None])
    qp, qp2 = qp[0], qp2[0]
    qm, qs = _query_seg_stats(qp, qp2, layout.leaf_endpoints)
    lb = LB.lb_eapca_node(qm, qs, layout.leaf_synopsis, layout.leaf_seg_lens)
    # empty/padded leaf slots carry count 0 (works under distributed stacking
    # where the padded leaf count varies per shard)
    dead = layout.leaf_count <= 0
    return jnp.where(dead, INF, lb)


def _leaf_block_ed(q, layout: HerculesLayout, rank, *, max_leaf: int):
    """Exact squared ED of q to every series of leaf ``rank`` (masked block)."""
    start = layout.leaf_start[rank]
    cnt = layout.leaf_count[rank]
    block = jax.lax.dynamic_slice(
        layout.lrd, (start, 0), (max_leaf, layout.lrd.shape[1]))
    d = jnp.sum(jnp.square(block - q[None, :]), axis=1)
    pos = start + jnp.arange(max_leaf, dtype=jnp.int32)
    d = jnp.where(jnp.arange(max_leaf) < cnt, d, INF)
    return d, pos


# ---------------------------------------------------------------------------
# Dense scan path (the PSCAN / skip-sequential analogue)
# ---------------------------------------------------------------------------

def _scan_path(q, layout: HerculesLayout, d0, p0, cfg: SearchConfig):
    """Blocked streaming exact scan over the leaf-ordered LRD array."""
    n_pad = layout.lrd.shape[0]
    blocks = n_pad // cfg.scan_block
    lrd3 = layout.lrd.reshape(blocks, cfg.scan_block, layout.lrd.shape[1])

    def body(carry, blk):
        d_top, p_top, base = carry
        d = jnp.sum(jnp.square(blk - q[None, :]), axis=1)
        pos = base + jnp.arange(cfg.scan_block, dtype=jnp.int32)
        d = jnp.where(pos < layout.num_series, d, INF)
        d_top, p_top = _merge_topk(d_top, p_top, d, pos, cfg.k)
        return (d_top, p_top, base + cfg.scan_block), None

    (d_top, p_top, _), _ = jax.lax.scan(body, (d0, p0, jnp.int32(0)), lrd3)
    return d_top, p_top, jnp.int32(layout.num_series)


# ---------------------------------------------------------------------------
# Pruned refinement path (phases 3-4)
# ---------------------------------------------------------------------------

def _refine_path(q, layout: HerculesLayout, cand_lb, d0, p0, cfg: SearchConfig):
    """Chunked exact refinement of candidates ordered by lower bound.

    ``cand_lb``: (N_pad,) lower bound per layout position, +inf for pruned.
    Exits when the next chunk's best LB can no longer improve BSF_k.

    Candidate ordering (EXPERIMENTS.md §Perf iteration 5): ``argsort`` fully
    sorts all N_pad bounds; ``topk`` selects only the C = budget smallest
    (lax.top_k returns them sorted) — cheaper when C << N. Exactness under
    ``topk``: the caller falls back to the dense scan if the budget is
    exhausted while the BSF could still improve (returned ``exhausted``).
    """
    n_pad = cand_lb.shape[0]
    if cfg.refine_select == "topk":
        c_budget = min(n_pad, cfg.topk_budget_chunks * cfg.chunk)
        neg_lb, order = jax.lax.top_k(-cand_lb, c_budget)
        sorted_lb = -neg_lb
        order = order.astype(jnp.int32)
        n_chunks = c_budget // cfg.chunk
    else:
        order = jnp.argsort(cand_lb).astype(jnp.int32)
        sorted_lb = cand_lb[order]
        n_chunks = n_pad // cfg.chunk
    slack = jnp.float32(1.0 - cfg.lb_slack)

    def cond(state):
        c, d_top, p_top, acc = state
        bsf = d_top[cfg.k - 1]
        head = sorted_lb[c * cfg.chunk]
        return (c < n_chunks) & (head * slack < bsf)

    def body(state):
        c, d_top, p_top, acc = state
        bsf = d_top[cfg.k - 1]
        idx = jax.lax.dynamic_slice(order, (c * cfg.chunk,), (cfg.chunk,))
        lbs = jax.lax.dynamic_slice(sorted_lb, (c * cfg.chunk,), (cfg.chunk,))
        rows = layout.lrd[idx]                       # (chunk, n) gather
        d = jnp.sum(jnp.square(rows - q[None, :]), axis=1)
        live = lbs * slack < bsf                     # Alg. 14 line 4 re-check
        d = jnp.where(live, d, INF)
        d_top, p_top = _merge_topk(d_top, p_top, d, idx, cfg.k)
        return (c + 1, d_top, p_top, acc + jnp.sum(live.astype(jnp.int32)))

    c, d_top, p_top, acc = jax.lax.while_loop(
        cond, body, (jnp.int32(0), d0, p0, jnp.int32(0)))
    # budget exhausted while the tail could still improve? (topk mode only)
    exhausted = (c >= n_chunks) & (sorted_lb[-1] * slack < d_top[cfg.k - 1])
    return d_top, p_top, acc, exhausted


# ---------------------------------------------------------------------------
# Full per-query pipeline
# ---------------------------------------------------------------------------

def _query_one(q, tree: HerculesTree, layout: HerculesLayout,
               cfg: SearchConfig, max_depth: int):
    n = layout.series_len
    L = layout.leaf_start.shape[0]
    l_max = min(cfg.l_max, layout.num_leaves)
    slack = jnp.float32(1.0 - cfg.lb_slack)

    # ---- Phase 1: approximate search (Alg. 11) ----------------------------
    with jax.named_scope("seed"):
        leaf_lb = _leaf_lbs(q, layout)                   # (L,)
        home = layout.leaf_rank[route_to_leaf(tree, q[None], max_depth)[0]]
        _, best_ranks = jax.lax.top_k(-leaf_lb, l_max)
        visit = jnp.concatenate([home[None].astype(jnp.int32),
                                 best_ranks.astype(jnp.int32)])

        d_top = jnp.full((cfg.k,), INF)
        p_top = jnp.full((cfg.k,), -1, jnp.int32)

        def visit_body(carry, rank):
            d_top, p_top, acc = carry
            d, pos = _leaf_block_ed(q, layout, rank, max_leaf=layout.max_leaf)
            d_top, p_top = _merge_topk(d_top, p_top, d, pos, cfg.k)
            return (d_top, p_top, acc + layout.leaf_count[rank]), None

        if cfg.unroll_visits:
            carry = (d_top, p_top, jnp.int32(0))
            for i in range(l_max + 1):
                carry, _ = visit_body(carry, visit[i])
            d_top, p_top, accessed = carry
        else:
            (d_top, p_top, accessed), _ = jax.lax.scan(
                visit_body, (d_top, p_top, jnp.int32(0)), visit)
        bsf = d_top[cfg.k - 1]

    # ---- Phases 2-3: candidate leaves (Alg. 12), then series (Alg. 13) -----
    with jax.named_scope("candidates"):
        cand_leaf = leaf_lb * slack < bsf                # (L,)
        n_cand_leaves = jnp.sum(cand_leaf.astype(jnp.int32))
        n_alive = jnp.maximum(
            jnp.sum((layout.leaf_count > 0).astype(jnp.int32)), 1)
        eapca_pr = 1.0 - (n_cand_leaves.astype(jnp.float32)
                          / n_alive.astype(jnp.float32))

        leaf_mask_pad = jnp.concatenate([cand_leaf, jnp.zeros((1,), bool)])
        series_in_cand = leaf_mask_pad[layout.series_leaf_rank]  # (N_pad,)

        q_paa = S.paa(q[None], layout.lsd.shape[1])[0]
        kmode = resolve_kernel_mode(cfg.kernel_mode)
        if kmode == "ref":
            lb_s = LB.lb_sax(q_paa, layout.lsd, n)       # (N_pad,)
        else:
            # the paper's phase-3 LSDFile stream: the Pallas LB_SAX (MINDIST)
            # kernel over the whole uint8 sidecar. LB values gate pruning only
            # (with lb_slack guarding fp32 rounding), so exact answers are
            # preserved for any kernel arithmetic. The single query row is
            # padded to the kernel's 8-row minimum tile — on TPU that is free
            # (the VPU/MXU processes >= 8 sublanes per op regardless), and it
            # keeps LB memory at (N_pad,) per in-flight query instead of
            # materializing a (Q, N_pad) matrix outside the lax.map.
            lb_s = kops.lb_sax(q_paa[None, :], layout.lsd, n, mode=kmode)[0]
        leaf_lb_pad = jnp.concatenate([leaf_lb, jnp.full((1,), INF)])
        lb_leaf_series = leaf_lb_pad[layout.series_leaf_rank]

        if cfg.use_sax:
            cand_lb = jnp.where(series_in_cand,
                                jnp.maximum(lb_s, lb_leaf_series), INF)
        else:
            cand_lb = jnp.where(series_in_cand, lb_leaf_series, INF)
        n_cand = jnp.sum((cand_lb * slack < bsf).astype(jnp.int32))
        sax_pr = 1.0 - n_cand.astype(jnp.float32) / layout.num_series

    # ---- Adaptive access-path selection (Alg. 10) ---------------------------
    d_f, p_f, path, acc_f = _finish_one(
        q, layout, cfg, d_top, p_top, accessed, cand_lb, eapca_pr, sax_pr)

    return (d_f, p_f, path, eapca_pr, sax_pr, acc_f,
            jnp.int32(l_max + 1))


def _finish_one(q, layout: HerculesLayout, cfg: SearchConfig,
                d_top, p_top, accessed, cand_lb, eapca_pr, sax_pr):
    """Adaptive access-path selection (Alg. 10) + exact refinement for ONE
    query — the shared tail of the per-query (`_query_one`) and wave-fused
    (`wave_knn`) pipelines. Returns (dists, positions, path, accessed)."""

    @jax.named_scope("scan")
    def do_scan(_):
        d, p, acc = _scan_path(q, layout, d_top, p_top, cfg)
        return d, p, accessed + acc

    @jax.named_scope("refine")
    def do_refine(_):
        d, p, acc, exhausted = _refine_path(q, layout, cand_lb, d_top, p_top, cfg)
        if cfg.refine_select == "topk":
            # exactness fallback: finish with a dense scan when the candidate
            # budget ran out before the bound crossed BSF_k
            return jax.lax.cond(
                exhausted,
                lambda _: (lambda r: (r[0], r[1], acc + accessed + r[2]))(
                    _scan_path(q, layout, d, p, cfg)),
                lambda _: (d, p, accessed + acc), None)
        return d, p, accessed + acc

    if cfg.force_scan:
        d_f, p_f, acc_f = do_scan(None)
        path = jnp.int32(3)
    elif not cfg.adaptive:
        d_f, p_f, acc_f = do_refine(None)
        path = jnp.int32(2)
    else:
        use_scan = (eapca_pr < cfg.eapca_th) | (
            jnp.asarray(cfg.use_sax) & (sax_pr < cfg.sax_th))
        d_f, p_f, acc_f = jax.lax.cond(use_scan, do_scan, do_refine, None)
        path = jnp.where(eapca_pr < cfg.eapca_th, 0,
                         jnp.where(sax_pr < cfg.sax_th, 1, 2)).astype(jnp.int32)
    return d_f, p_f, path, acc_f


@functools.partial(jax.jit, static_argnames=("cfg", "max_depth"))
def exact_knn(tree: HerculesTree, layout: HerculesLayout, queries: jax.Array,
              cfg: SearchConfig, max_depth: int,
              n_valid: jax.Array | None = None) -> KnnResult:
    """Exact kNN for a workload of queries (Q, n). See module docstring.

    ``n_valid``: the count of leading real rows in a padded batch, a traced
    int32 scalar (one program serves every fill). Each slot from ``n_valid``
    on skips the per-query pipeline and holds a placeholder: dists ``+inf``,
    positions, ids and path ``-1``, pruning ratios and counts 0. The real
    rows run the same ``_query_one``. ``None`` means every row is real."""

    def one(q):
        return _query_one(q, tree, layout, cfg, max_depth)

    def skipped(q):
        return (jnp.full((cfg.k,), INF), jnp.full((cfg.k,), -1, jnp.int32),
                jnp.int32(-1), jnp.float32(0), jnp.float32(0), jnp.int32(0),
                jnp.int32(0))

    def slot(args):
        i, q = args
        return jax.lax.cond(i < n_valid, one, skipped, q)

    if n_valid is None:
        out = jax.lax.map(one, queries)
    else:
        slots = jnp.arange(queries.shape[0], dtype=jnp.int32)
        out = jax.lax.map(slot, (slots, queries))
    d, p, path, e_pr, s_pr, acc, vis = out
    safe_p = jnp.clip(p, 0, layout.perm.shape[0] - 1)
    ids = jnp.where(p >= 0, layout.perm[safe_p], -1)
    return KnnResult(dists=d, positions=p, ids=ids, path=path,
                     eapca_pr=e_pr, sax_pr=s_pr, accessed=acc,
                     visited_leaves=vis)


# ---------------------------------------------------------------------------
# Wave-fused multi-query search (ROADMAP "Multi-query wave search")
# ---------------------------------------------------------------------------

def _wave_leaf_lbs(queries, layout: HerculesLayout):
    """(W, L) squared LB_EAPCA of every wave member to every leaf.

    The batched form of `_leaf_lbs`: per-row prefix sums and segment stats
    are arithmetic-identical to the single-query path, so the bounds (and
    hence every pruning decision derived from them) match bit for bit.
    """
    qp, qp2 = S.prefix_sums(queries)

    def one(args):
        qp_r, qp2_r = args
        qm, qs = _query_seg_stats(qp_r, qp2_r, layout.leaf_endpoints)
        return LB.lb_eapca_node(qm, qs, layout.leaf_synopsis,
                                layout.leaf_seg_lens)

    lb = jax.lax.map(one, (qp, qp2))
    dead = layout.leaf_count <= 0
    return jnp.where(dead[None, :], INF, lb)


@functools.partial(jax.jit, static_argnames=("cfg", "max_depth"))
def wave_knn(tree: HerculesTree, layout: HerculesLayout, queries: jax.Array,
             cfg: SearchConfig, max_depth: int) -> KnnResult:
    """Exact kNN for a *wave* of queries with fused scheduling.

    Where `exact_knn` maps `_query_one` over the workload (each query runs
    its own leaf-visit scan and its own LB_SAX kernel call), this fuses the
    per-query work that is identical in structure across the wave:

      * ONE tree descent for all members (`route_to_leaf` is batched);
      * the phase-1 visit loop runs level by level over the whole wave —
        one (W, max_leaf) gather of LRD rows per level instead of W
        per-leaf dynamic slices (layout geometry guarantees every leaf
        extent [start, start + max_leaf) stays inside the padded array, so
        the gather reads exactly the rows the per-query slice reads);
      * a shared per-wave BSF matrix (W, k) carried through the visit scan;
      * ONE LB_SAX kernel launch over the (W, m) PAA matrix for phase 3,
        instead of W single-row launches padded to the kernel's 8-row tile.

    Per member the merge sequence (home leaf, then the l_max best leaves in
    rank order) and all distance arithmetic are the same as `_query_one`,
    so answers are bit-identical to the per-query path. Phase 4 stays a
    per-member `lax.map` over the shared `_finish_one` tail — access-path
    selection is a real branch per member, exactly as in `exact_knn`.

    Memory note: phase 3 materializes the (W, N_pad) LB matrix (the
    per-query path keeps it at (N_pad,)); that is the wave's footprint cost
    and why serving waves are bounded by `batch_slots`. `unroll_visits` is
    a dry-run probe knob and is ignored here (the wave path always scans).
    """
    W = queries.shape[0]
    n = layout.series_len
    l_max = min(cfg.l_max, layout.num_leaves)
    slack = jnp.float32(1.0 - cfg.lb_slack)
    n_pad_rows = layout.lrd.shape[0]

    # ---- Phase 1: approximate search, wave-fused (Alg. 11) ----------------
    with jax.named_scope("seed"):
        leaf_lb = _wave_leaf_lbs(queries, layout)            # (W, L)
        home = layout.leaf_rank[route_to_leaf(tree, queries, max_depth)]
        _, best = jax.lax.top_k(-leaf_lb, l_max)             # (W, l_max)
        visit = jnp.concatenate([home[:, None].astype(jnp.int32),
                                 best.astype(jnp.int32)], axis=1)

        d_top = jnp.full((W, cfg.k), INF)    # the shared per-wave BSF matrix
        p_top = jnp.full((W, cfg.k), -1, jnp.int32)
        offs = jnp.arange(layout.max_leaf, dtype=jnp.int32)
        merge = jax.vmap(functools.partial(_merge_topk, k=cfg.k))

        def level_body(carry, ranks):        # ranks: (W,) — one visit level
            d_top, p_top, acc = carry
            starts = layout.leaf_start[ranks]
            cnts = layout.leaf_count[ranks]
            pos = starts[:, None] + offs[None, :]            # (W, max_leaf)
            rows = layout.lrd[jnp.clip(pos, 0, n_pad_rows - 1)]  # one gather
            d = jnp.sum(jnp.square(rows - queries[:, None, :]), axis=2)
            d = jnp.where(offs[None, :] < cnts[:, None], d, INF)
            d_top, p_top = merge(d_top, p_top, d, pos)
            return (d_top, p_top, acc + cnts), None

        (d_top, p_top, accessed), _ = jax.lax.scan(
            level_body, (d_top, p_top, jnp.zeros((W,), jnp.int32)), visit.T)
        bsf = d_top[:, cfg.k - 1]

    # ---- Phases 2-3: candidate leaves (Alg. 12), then series (Alg. 13),
    # whole wave at once, one LB_SAX kernel launch -----------------------
    with jax.named_scope("candidates"):
        cand_leaf = leaf_lb * slack < bsf[:, None]           # (W, L)
        n_cand_leaves = jnp.sum(cand_leaf.astype(jnp.int32), axis=1)
        n_alive = jnp.maximum(
            jnp.sum((layout.leaf_count > 0).astype(jnp.int32)), 1)
        eapca_pr = (1.0 - n_cand_leaves.astype(jnp.float32)
                    / n_alive.astype(jnp.float32))

        leaf_mask_pad = jnp.concatenate(
            [cand_leaf, jnp.zeros((W, 1), bool)], axis=1)
        series_in_cand = leaf_mask_pad[:, layout.series_leaf_rank]  # (W, N_pad)

        q_paa = S.paa(queries, layout.lsd.shape[1])          # (W, m)
        kmode = resolve_kernel_mode(cfg.kernel_mode)
        if kmode == "ref":
            lb_s = jax.lax.map(lambda qp: LB.lb_sax(qp, layout.lsd, n), q_paa)
        else:
            lb_s = kops.lb_sax(q_paa, layout.lsd, n, mode=kmode)  # (W, N_pad)
        leaf_lb_pad = jnp.concatenate([leaf_lb, jnp.full((W, 1), INF)],
                                      axis=1)
        lb_leaf_series = leaf_lb_pad[:, layout.series_leaf_rank]

        if cfg.use_sax:
            cand_lb = jnp.where(series_in_cand,
                                jnp.maximum(lb_s, lb_leaf_series), INF)
        else:
            cand_lb = jnp.where(series_in_cand, lb_leaf_series, INF)
        n_cand = jnp.sum((cand_lb * slack < bsf[:, None]).astype(jnp.int32),
                         axis=1)
        sax_pr = 1.0 - n_cand.astype(jnp.float32) / layout.num_series

    # ---- Phase 4: per-member adaptive refinement (Alg. 10/14) -------------
    def one(args):
        q, d0, p0, acc, clb, e_pr, s_pr = args
        return _finish_one(q, layout, cfg, d0, p0, acc, clb, e_pr, s_pr)

    d_f, p_f, path, acc_f = jax.lax.map(
        one, (queries, d_top, p_top, accessed, cand_lb, eapca_pr, sax_pr))
    safe_p = jnp.clip(p_f, 0, layout.perm.shape[0] - 1)
    ids = jnp.where(p_f >= 0, layout.perm[safe_p], -1)
    return KnnResult(dists=d_f, positions=p_f, ids=ids, path=path,
                     eapca_pr=eapca_pr, sax_pr=sax_pr, accessed=acc_f,
                     visited_leaves=jnp.full((W,), l_max + 1, jnp.int32))


# ---------------------------------------------------------------------------
# Approximate search (paper §5 future work: approximate answering — here the
# phase-1 prefix of the exact pipeline, with recall measured in benchmarks)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg", "max_depth"))
def approx_knn(tree: HerculesTree, layout: HerculesLayout, queries: jax.Array,
               cfg: SearchConfig, max_depth: int):
    """Phase-1-only kNN: visit the home leaf + the l_max best leaves by
    LB_EAPCA and return the best-so-far — the paper's Approx-kNN (Alg. 11)
    as a standalone answering mode. Returns (dists, ids)."""

    def one(q):
        leaf_lb = _leaf_lbs(q, layout)
        home = layout.leaf_rank[route_to_leaf(tree, q[None], max_depth)[0]]
        l_max = min(cfg.l_max, layout.num_leaves)
        _, best = jax.lax.top_k(-leaf_lb, l_max)
        visit = jnp.concatenate([home[None].astype(jnp.int32),
                                 best.astype(jnp.int32)])
        d_top = jnp.full((cfg.k,), INF)
        p_top = jnp.full((cfg.k,), -1, jnp.int32)

        def body(carry, rank):
            d_top, p_top = carry
            d, pos = _leaf_block_ed(q, layout, rank, max_leaf=layout.max_leaf)
            return _merge_topk(d_top, p_top, d, pos, cfg.k), None

        (d_top, p_top), _ = jax.lax.scan(body, (d_top, p_top), visit)
        return d_top, p_top

    d, p = jax.lax.map(one, queries)
    safe = jnp.clip(p, 0, layout.perm.shape[0] - 1)
    ids = jnp.where(p >= 0, layout.perm[safe], -1)
    return d, ids


# ---------------------------------------------------------------------------
# Standalone baselines
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("k", "block"))
def pscan_knn(data: jax.Array, queries: jax.Array, k: int = 1,
              block: int = 4096) -> tuple[jax.Array, jax.Array]:
    """PSCAN baseline (paper §4.1): optimized parallel scan.

    Batched across all queries (the double-buffer analogue is XLA streaming);
    blocked matmul-identity distances on the MXU. Returns (Q,k) dists + ids.
    ``data`` may be unpadded; handles the ragged tail by masking.
    """
    qn = queries.shape[0]
    num = data.shape[0]
    n_pad = -(-num // block) * block
    if n_pad != num:
        data = jnp.concatenate(
            [data, jnp.zeros((n_pad - num, data.shape[1]), data.dtype)], axis=0)
    blocks = data.reshape(n_pad // block, block, data.shape[1])
    q_norm = jnp.sum(jnp.square(queries), axis=1)

    d0 = jnp.full((qn, k), INF)
    p0 = jnp.full((qn, k), -1, jnp.int32)

    def body(carry, xs):
        d_top, p_top, base = carry
        blk = xs
        s_norm = jnp.sum(jnp.square(blk), axis=1)
        dot = jnp.dot(queries, blk.T, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)
        d = jnp.maximum(q_norm[:, None] + s_norm[None, :] - 2.0 * dot, 0.0)
        pos = base + jnp.arange(block, dtype=jnp.int32)
        d = jnp.where((pos < num)[None, :], d, INF)
        dd = jnp.concatenate([d_top, d], axis=1)
        pp = jnp.concatenate([p_top, jnp.broadcast_to(pos, (qn, block))], axis=1)
        neg, idx = jax.lax.top_k(-dd, k)
        return (-neg, jnp.take_along_axis(pp, idx, axis=1), base + block), None

    (d_top, p_top, _), _ = jax.lax.scan(body, (d0, p0, jnp.int32(0)), blocks)
    return d_top, p_top


def brute_force_knn(data: jax.Array, queries: jax.Array, k: int = 1):
    """Reference oracle: full ED matrix + top_k (tests only)."""
    d = LB.squared_ed_matrix(queries, data)
    neg, idx = jax.lax.top_k(-d, k)
    return -neg, idx
