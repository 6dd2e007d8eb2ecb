"""Distributed out-of-core serving: one on-disk index, a mesh of readers.

``DistOutOfCoreBackend`` (registry name ``dist-ooc``) serves a single
committed base generation from every device of a mesh at once. The shard
plan (``repro.storage.partition``) cuts the file into contiguous leaf-run
row ranges balanced by row count; each shard then

* memory-maps **only its own** LRD/LSD/enc row range — the per-shard
  :class:`_ShardRows` views translate shard-local row slices to absolute
  file rows, *refuse* anything outside the shard's range, and record the
  absolute rows actually touched (``stats()["dist"]["rows_touched"]``), so
  tests can assert residency confinement instead of trusting it;
* descends the shared resident tree (routing tables are small and
  replicated; only raw rows are sharded) and streams its local leaf runs
  through its own :class:`repro.data.pipeline.AsyncChunkReader` — the
  codec-certified encoded stream and the wave-fused dedup'd run schedule
  both come along for free, because each shard is a full
  :class:`~repro.core.engine.OutOfCoreLocalBackend` over its range view;
* shares one best-so-far across shards (the paper's parallel query
  answering prunes every worker against one BSF): on the raw per-query
  stream each shard runs phase 1 (its home leaf and its ``l_max`` best
  leaves), the shards' phase-1 top-k go through the collective merge once,
  and every shard prunes phases 2-3 against the global kth distance as
  well as its own running one (``_BoundExchange``). The codec and wave
  streams keep per-shard bounds;
* merges per-shard top-k triplets **in difference form** through the same
  ``shard_map`` + ``all_gather`` collective idiom as
  ``repro.distributed.search``.

Exactness / bit-identity argument: the shared bound is the kth distance
of k real rows, so it bounds every query's final kth distance from above,
and a row whose lower bound reaches it cannot be an answer. Each shard's
answer is therefore the exact top-k of its row range, with the same
difference-form squared-ED arithmetic as every other backend, and shards
partition the file into *ascending contiguous* ranges. ``jax.lax.top_k``
breaks ties toward the lower index, so the shard-major concatenation the
collective merge sorts resolves equal distances toward the lower file
position — exactly the tie-break the
single-host fold (:func:`repro.core.search._merge_topk` in file order)
produces. Hence distances, positions, and ids match ``LocalBackend`` /
``ooc-local`` bit for bit for every shard count, codec, and
``kernel_mode``; only the telemetry differs.

Placement: each shard's stream is staged and refined under
``jax.default_device(shard_device)``, so on a real (or
``--xla_force_host_platform_device_count``-forced) mesh the blocks land on
the device that owns the shard before the collective merge runs.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.analysis.sanitize import lockdep_task
from repro.core.engine import OutOfCoreLocalBackend, _OutOfCoreBase
from repro.core.probes import span
from repro.core.search import SearchConfig
from repro.storage.partition import ShardPlan, shard_plan

MESH_AXIS = "shards"


class _ShardRows:
    """Row-range view of one mapped base file, in shard-local coordinates.

    The chunk readers only ever take contiguous row slices
    (``rows[start:start+count]``); this proxy translates them to absolute
    file rows, raises on anything outside ``[row_lo, row_hi)``, and records
    the absolute extremes touched into ``audit`` (a shared two-element
    ``[lo, hi)`` list) — the residency-confinement proof the telemetry
    exposes. ``take`` provides the copy-guaranteed gather
    ``_codec_finalize`` needs (advanced indexing on a memmap always
    copies).
    """

    def __init__(self, base, row_lo: int, row_hi: int, audit: list):
        self._base = base
        self._lo = int(row_lo)
        self._hi = int(row_hi)
        self._audit = audit

    @property
    def shape(self) -> tuple:
        return (self._hi - self._lo,) + tuple(self._base.shape[1:])

    @property
    def dtype(self):
        return self._base.dtype

    def __len__(self) -> int:
        return self._hi - self._lo

    def _record(self, a: int, b: int) -> None:
        if b > a:
            self._audit[0] = min(self._audit[0], a)
            self._audit[1] = max(self._audit[1], b)

    def _absolute(self, start: int, stop: int) -> tuple[int, int]:
        rows = self._hi - self._lo
        if not 0 <= start <= stop <= rows:
            raise IndexError(
                f"rows [{start}, {stop}) escape the shard's range view "
                f"(local rows [0, {rows}) = file rows "
                f"[{self._lo}, {self._hi}))")
        a, b = self._lo + start, self._lo + stop
        self._record(a, b)
        return a, b

    def __getitem__(self, idx):
        if not isinstance(idx, slice):
            raise TypeError(
                f"_ShardRows supports contiguous row slices, got {idx!r}")
        start, stop, step = idx.indices(self._hi - self._lo)
        if step != 1:
            raise IndexError(f"_ShardRows slices must be contiguous "
                             f"(step={step})")
        a, b = self._absolute(start, stop)
        return self._base[a:b]

    def take(self, indices, axis: int = 0, out=None, mode: str = "raise"):
        """Copy-guaranteed gather of shard-local rows (np.take dispatches
        here) — advanced indexing on the underlying map always copies, so
        the result can cross to device without aliasing the file."""
        if axis != 0 or out is not None or mode != "raise":
            raise ValueError(
                f"_ShardRows.take supports axis=0/out=None/mode='raise'; "
                f"got axis={axis}, out={out!r}, mode={mode!r}")
        idx = np.asarray(indices, np.int64)
        rows = self._hi - self._lo
        if idx.size:
            lo, hi = int(idx.min()), int(idx.max())
            if lo < 0 or hi >= rows:
                raise IndexError(
                    f"take indices [{lo}, {hi}] escape the shard's "
                    f"{rows}-row range view")
            self._record(self._lo + lo, self._lo + hi + 1)
        return self._base[idx + self._lo]


@dataclasses.dataclass
class _ShardView:
    """A ``SavedIndex``-shaped window onto one shard of an opened index.

    Leaf tables are sliced to the shard's leaf run and re-based to
    shard-local rows/ranks; the tree stays the shared resident one (node ->
    leaf-rank lookups map out-of-shard leaves to -1, so routing a query to
    a home leaf another shard owns simply contributes no seed here). The
    big files surface as :class:`_ShardRows` range views, which is what
    makes "this reader cannot leave its shard" a structural property
    rather than a convention.
    """
    path: str
    manifest: dict
    config: object
    max_depth: int
    tree: object
    small: dict
    codec: str
    series_len: int
    max_leaf: int
    num_leaves: int
    num_series: int
    row_lo: int
    row_hi: int
    _parent: object = dataclasses.field(repr=False, default=None)
    _audit: dict = dataclasses.field(default_factory=dict)

    @classmethod
    def of(cls, saved, plan: ShardPlan, shard: int) -> "_ShardView":
        leaf_lo, leaf_hi = plan.leaf_range(shard)
        row_lo, row_hi = plan.row_range(shard)
        s = saved.small
        lr = np.asarray(s["leaf_rank"])
        local_rank = np.where((lr >= leaf_lo) & (lr < leaf_hi),
                              lr - leaf_lo, -1).astype(lr.dtype)
        small = {
            "perm": np.asarray(s["perm"])[row_lo:row_hi],
            "leaf_rank": local_rank,
            "leaf_start": np.asarray(s["leaf_start"])[leaf_lo:leaf_hi]
            - row_lo,
            "leaf_count": np.asarray(s["leaf_count"])[leaf_lo:leaf_hi],
            "leaf_synopsis": np.asarray(s["leaf_synopsis"])[leaf_lo:leaf_hi],
            "leaf_endpoints": np.asarray(s["leaf_endpoints"])[leaf_lo:leaf_hi],
            "leaf_seg_lens": np.asarray(s["leaf_seg_lens"])[leaf_lo:leaf_hi],
            "series_leaf_rank": np.asarray(s["series_leaf_rank"])
            [row_lo:row_hi] - leaf_lo,
        }
        return cls(
            path=saved.path, manifest=saved.manifest, config=saved.config,
            max_depth=saved.max_depth, tree=saved.tree, small=small,
            codec=getattr(saved, "codec", "raw"),
            series_len=saved.series_len,
            # max_leaf stays global so every shard pads fetches to the same
            # bucket shapes (one compiled refine kernel set for the mesh)
            max_leaf=saved.max_leaf,
            num_leaves=leaf_hi - leaf_lo, num_series=row_hi - row_lo,
            row_lo=row_lo, row_hi=row_hi, _parent=saved)

    @property
    def n_pad(self) -> int:
        return self.row_hi - self.row_lo

    def _mapped(self, name: str) -> _ShardRows:
        audit = self._audit.setdefault(name, [self.row_hi, self.row_lo])
        return _ShardRows(self._parent._mapped(name), self.row_lo,
                          self.row_hi, audit)

    def rows_touched(self) -> tuple[int, int] | None:
        """Absolute ``[lo, hi)`` file rows this shard's readers touched so
        far, across lrd/lsd/enc; ``None`` before the first read."""
        lo = min((a[0] for a in self._audit.values()), default=self.row_hi)
        hi = max((a[1] for a in self._audit.values()), default=self.row_lo)
        if hi <= lo:
            return None
        return lo, hi


def _make_collective_merge(mesh):
    """The jitted shard_map program that merges stacked per-shard top-k
    triplets ``(D, Q, k)`` into the global ``(Q, k)`` answer — the same
    all_gather + stable top_k idiom as ``make_distributed_search``, so
    equal distances resolve toward the lower shard (= lower file
    position)."""
    axes = tuple(mesh.axis_names)
    spec = P(axes)
    repl = P()

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=(repl, repl, repl),
        check_vma=False)
    def merge(d_s, p_s, i_s):
        # local block (1, Q, k): drop the shard dim, gather the mesh's
        qn, k = d_s.shape[1], d_s.shape[2]
        all_d = jax.lax.all_gather(d_s[0], axes, axis=0, tiled=False)
        all_p = jax.lax.all_gather(p_s[0], axes, axis=0, tiled=False)
        all_i = jax.lax.all_gather(i_s[0], axes, axis=0, tiled=False)
        # all_gather over multiple axes stacks per axis: flatten to (D, Q, k)
        dd = jnp.moveaxis(all_d.reshape(-1, qn, k), 0, 1).reshape(qn, -1)
        pp = jnp.moveaxis(all_p.reshape(-1, qn, k), 0, 1).reshape(qn, -1)
        ii = jnp.moveaxis(all_i.reshape(-1, qn, k), 0, 1).reshape(qn, -1)
        neg, idx = jax.lax.top_k(-dd, k)
        return (-neg, jnp.take_along_axis(pp, idx, axis=1),
                jnp.take_along_axis(ii, idx, axis=1))

    return jax.jit(merge)


def _shard_results(futures) -> list:
    """The shards' results in order. Where shards failed, raises the first
    failure that is not a broken exchange, i.e. the cause and not a shard
    that was waiting for it."""
    failed = [e for e in (f.exception() for f in futures) if e is not None]
    if failed:
        raise next((e for e in failed
                    if not isinstance(e, threading.BrokenBarrierError)),
                   failed[0])
    return [f.result() for f in futures]


class _BoundExchange:
    """One wave's exchange of phase-1 answers between the shard threads.

    Each shard hands in its phase-1 top-k and waits at a barrier; the last
    to arrive runs ``merge`` (``{shard: (d, p)}`` -> the ``(Q,)`` global
    kth distance, on the host, so each shard can use it on its own
    device), and every shard leaves with that bound. ``waits`` holds the
    seconds each shard waited for the last to arrive. A shard that fails
    before the exchange aborts the barrier, so the others raise instead of
    waiting for it."""

    def __init__(self, shards: int, merge):
        self._merge = merge
        self._parts: dict = {}
        self._last_arrival = 0.0
        self._barrier = threading.Barrier(shards, action=self._merge_all)
        self.bound: np.ndarray | None = None
        self.waits: dict = {}

    def __call__(self, shard: int, d, p) -> np.ndarray:
        """Hand in ``shard``'s phase-1 ``(d, p)``; returns the bound."""
        self._parts[shard] = (d, p)
        arrived = time.perf_counter()
        self._barrier.wait()
        self.waits[shard] = self._last_arrival - arrived
        return self.bound

    def _merge_all(self) -> None:
        self._last_arrival = time.perf_counter()
        with span("repro.dist.exchange"):
            self.bound = self._merge(self._parts)

    def abort(self) -> None:
        self._barrier.abort()


class DistOutOfCoreBackend(_OutOfCoreBase):
    """Sharded out-of-core serving over one saved index (see module docs).

    ``shards`` defaults to the device count; the mesh must have exactly one
    device per shard (force host devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` to test meshes
    on one machine). ``memory_budget_mb`` is **per shard** — each reader
    keeps its own two blocks in flight.
    """

    name = "dist-ooc"

    def __init__(self, saved, config: SearchConfig | None = None,
                 memory_budget_mb: float = 64.0, *,
                 shards: int | None = None, mesh=None):
        super().__init__(saved, config, memory_budget_mb)
        if mesh is None:
            n = int(shards) if shards else len(jax.devices())
            if n < 1:
                raise ValueError(f"shards={shards}; expected >= 1")
            if n > len(jax.devices()):
                raise ValueError(
                    f"dist-ooc needs one device per shard: {n} shards > "
                    f"{len(jax.devices())} devices. Force host devices with "
                    f"XLA_FLAGS=--xla_force_host_platform_device_count={n} "
                    f"(before jax import) or lower --shards")
            mesh = jax.make_mesh((n,), (MESH_AXIS,),
                                 axis_types=(AxisType.Explicit,))
        self.mesh = mesh
        devices = np.asarray(mesh.devices).reshape(-1)
        self.num_shards = int(devices.size)
        if shards is not None and int(shards) != self.num_shards:
            raise ValueError(f"shards={shards} but the mesh has "
                             f"{self.num_shards} devices")
        self._devices = list(devices)
        self.plan = shard_plan(saved, self.num_shards)
        self._views = [_ShardView.of(saved, self.plan, i)
                       for i in range(self.num_shards)]
        # each shard's resident tables live on the device that serves it
        self._subs = []
        for view, dev in zip(self._views, self._devices):
            with jax.default_device(dev):
                self._subs.append(OutOfCoreLocalBackend(
                    view, self._config, memory_budget_mb))
        self._merge = _make_collective_merge(mesh)
        # per-shard exchange accounting (DistTelemetry): rows read in phase
        # 1, seconds waited at the exchange for the slowest shard, and the
        # seconds of both merges
        self._phase1_rows = [0] * self.num_shards
        self._exchange_wait = [0.0] * self.num_shards
        self._merge_s = 0.0
        # folded into the engine's plan-cache key: a plan compiled for one
        # mesh must not serve another (different collective program and
        # different shard placement)
        self.plan_signature = (
            self.name, self.num_shards,
            tuple((str(a), int(mesh.shape[a])) for a in mesh.axis_names))

    # -- plans ---------------------------------------------------------------

    def _validate(self, cfg: SearchConfig) -> None:
        for sub in self._subs:
            sub._validate(cfg)

    def _bind(self, cfg):
        return self._fan_plan(cfg, wave=False)

    def make_wave_plan(self, cfg, q_struct):
        return self._fan_plan(cfg, wave=True, q_struct=q_struct)

    def _fan_plan(self, cfg, wave: bool, q_struct=None):
        subs = [(i, sub) for i, sub in enumerate(self._subs)
                if self._views[i].num_series > 0]
        # the raw per-query stream shares one bound across shards; the
        # codec and wave streams prune each shard against its own
        shared = (not wave and len(subs) > 1
                  and self._active_codec(cfg) is None)
        if shared:
            plans = [(i, functools.partial(sub._stream_knn, cfg=cfg))
                     for i, sub in subs]
        else:
            plans = [(i, (sub.make_wave_plan(cfg, q_struct) if wave
                          else sub._bind(cfg)))
                     for i, sub in subs]
        valid_aware = any(getattr(p, "valid_aware", False) for _, p in plans)

        def run(q, valid_rows=None):
            return self._fan_out(jnp.asarray(q), cfg, plans, valid_rows,
                                 shared)

        run.valid_aware = valid_aware
        return run

    def estimate_difficulty(self, queries: jax.Array) -> np.ndarray | None:
        scores = [sub.estimate_difficulty(queries)
                  for i, sub in enumerate(self._subs)
                  if self._views[i].num_leaves > 0]
        if not scores:
            return None
        return np.max(np.stack([np.asarray(s) for s in scores]), axis=0)

    # -- the fan-out / collective-merge call ---------------------------------

    def _run_shard(self, shard: int, plan, q, valid_rows, exchange=None):
        """One shard's stream, pinned to its mesh device: blocks stage to
        (and the refine kernels run on) the device that owns the shard.
        With an ``exchange`` the stream stops after phase 1 to hand in its
        answers, and prunes phases 2-3 against the bound it gets back."""
        with jax.default_device(self._devices[shard]), \
                contextlib.ExitStack() as phase:
            if exchange is None:
                phase.enter_context(span("repro.dist.shard", shard=shard,
                                         phase="all"))
                res = (plan(q, valid_rows=valid_rows)
                       if getattr(plan, "valid_aware", False) else plan(q))
            else:
                sub = self._subs[shard]
                rows0 = sub._t["rows_streamed"]

                def hand_over(d, p):
                    phase.close()              # phase 1 ends here
                    self._phase1_rows[shard] += (sub._t["rows_streamed"]
                                                 - rows0)
                    bound = exchange(shard, d, p)
                    phase.enter_context(span("repro.dist.shard", shard=shard,
                                             phase="2-3"))
                    return bound

                phase.enter_context(span("repro.dist.shard", shard=shard,
                                         phase="1"))
                try:
                    res = plan(q, exchange=hand_over)
                except BaseException:
                    exchange.abort()
                    raise
            jax.block_until_ready(res.dists)
        return res

    def _merge_parts(self, parts: dict, qn: int, k: int):
        """Merge ``{shard: (d, shard-local p[, ids])}`` top-k parts through
        the collective merge; absent shards contribute nothing."""
        t0 = time.perf_counter()
        empty_d = np.full((qn, k), np.float32(np.inf))
        empty_i = np.full((qn, k), -1, np.int32)
        d_parts, p_parts, i_parts = [], [], []
        for s in range(self.num_shards):
            part = parts.get(s)
            if part is None:
                d_parts.append(empty_d)
                p_parts.append(empty_i)
                i_parts.append(empty_i)
                continue
            row_lo = self._views[s].row_lo
            p_local = np.asarray(part[1])
            p_global = np.where(p_local >= 0, p_local + row_lo,
                                -1).astype(p_local.dtype)
            d_parts.append(np.asarray(part[0]))
            p_parts.append(p_global)
            i_parts.append(np.asarray(part[2]) if len(part) > 2
                           else p_global)
        # each shard's top-k goes straight to its own device for the merge
        spread = NamedSharding(self.mesh, P(tuple(self.mesh.axis_names)))
        out = self._merge(*(jax.device_put(np.stack(parts), spread)
                            for parts in (d_parts, p_parts, i_parts)))
        jax.block_until_ready(out)
        self._merge_s += time.perf_counter() - t0
        return out

    def _fan_out(self, q, cfg: SearchConfig, plans, valid_rows,
                 shared: bool = False):
        k = cfg.k
        qn = q.shape[0]
        if len(plans) > 1:
            # one worker per shard: reads and refines overlap across the
            # mesh (each shard already overlaps read with compute via its
            # own reader; this overlaps the shards with each other).
            # Under REPRO_SANITIZE=1 lockdep asserts each work item enters
            # and leaves lock-free — pool threads are recycled, so a
            # carried lock would deadlock a later, unrelated item.
            exchange = None
            if shared:
                def kth(parts):
                    md, _, _ = self._merge_parts(parts, qn, k)
                    return np.asarray(md)[:, k - 1]

                exchange = _BoundExchange(len(plans), kth)
            run = lockdep_task(
                lambda ip: self._run_shard(ip[0], ip[1], q, valid_rows,
                                           exchange),
                name="dist-ooc-shard")
            with ThreadPoolExecutor(max_workers=len(plans),
                                    thread_name_prefix="repro-dist-shard"
                                    ) as pool:
                results = _shard_results([pool.submit(run, ip)
                                          for ip in plans])
            if exchange is not None:
                for shard, wait in exchange.waits.items():
                    self._exchange_wait[shard] += wait
        else:
            results = [self._run_shard(i, p, q, valid_rows)
                       for i, p in plans]

        with span("repro.dist.merge"):
            md, mp, mi = self._merge_parts(
                {i: (res.dists, res.positions, res.ids)
                 for (i, _), res in zip(plans, results)}, qn, k)
        self._t["calls"] += 1

        # per-query telemetry: exact counters sum; pruning ratios recombine
        # from per-shard fractions weighted by what each shard could prune
        accessed = jnp.zeros((qn,), jnp.int32)
        visited = jnp.zeros((qn,), jnp.int32)
        alive_rows = jnp.zeros((qn,), jnp.float32)
        alive_leaves = jnp.zeros((qn,), jnp.float32)
        tot_rows = tot_leaves = 0
        for (i, _), res in zip(plans, results):
            v = self._views[i]
            accessed = accessed + res.accessed
            visited = visited + res.visited_leaves
            alive_rows = alive_rows + (1.0 - res.sax_pr) * v.num_series
            alive_leaves = alive_leaves + (1.0 - res.eapca_pr) * v.num_leaves
            tot_rows += v.num_series
            tot_leaves += v.num_leaves
        res = self._fill_result(md, mp, mi, path=2, accessed=accessed)
        return res._replace(
            eapca_pr=1.0 - alive_leaves / max(tot_leaves, 1),
            sax_pr=1.0 - alive_rows / max(tot_rows, 1),
            visited_leaves=visited)

    # -- introspection -------------------------------------------------------

    @staticmethod
    def _ratio(values) -> float:
        """max/min over per-shard counts, JSON-safe: empty shards count as
        one row so a starved mesh reads as a huge finite ratio, not inf."""
        vals = [int(v) for v in values]
        if not vals or max(vals) == 0:
            return 1.0
        return max(vals) / max(min(vals), 1)

    def stats(self) -> dict:
        agg = dict(self._t)
        for sub in self._subs:
            for key, val in sub._t.items():
                agg[key] = agg.get(key, 0) + val
        agg["calls"] = self._t["calls"]  # one dist call, not one per shard
        agg["host_syncs"] = sum(sub._syncs.count for sub in self._subs)
        per = lambda key: [sub._t[key] for sub in self._subs]  # noqa: E731
        streamed = per("rows_streamed")
        return {
            "num_series": self.saved.num_series,
            "series_len": self.saved.series_len,
            "memory_budget_mb": self.memory_budget_mb,
            "codec": getattr(self.saved, "codec", "raw"),
            **agg,
            "dist": {
                "shards": self.num_shards,
                "rows_streamed": streamed,
                "read_wait_seconds": per("read_wait_seconds"),
                "bytes_streamed": per("bytes_streamed"),
                "imbalance": self._ratio(streamed),
                "plan_rows": list(self.plan.shard_rows),
                "plan_imbalance": self._ratio(self.plan.shard_rows),
                "balance_warning": not self.plan.balanced,
                "row_range": [list(self.plan.row_range(s))
                              for s in range(self.num_shards)],
                "rows_touched": [list(t) if (t := v.rows_touched()) else None
                                 for v in self._views],
                "phase1_rows": list(self._phase1_rows),
                "exchange_wait_seconds": list(self._exchange_wait),
                "merge_seconds": self._merge_s,
            },
        }

    def describe(self) -> dict:
        d = super().describe()
        d["mesh"] = {str(a): int(self.mesh.shape[a])
                     for a in self.mesh.axis_names}
        return d
