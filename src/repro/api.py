"""``repro.api`` — the one import for the whole index lifecycle.

The central object is the :class:`Hercules` store: one handle that owns an
index directory from creation through incremental ingest, compaction, and
query serving::

    from repro import api

    # create -> append -> compact -> query, one handle, context-managed
    with api.Hercules.create("idx/", api.IndexConfig(), data=chunks_a) as hx:
        hx.append(chunks_b)            # journal segment; atomic commit
        res = hx.query(queries, k=5)   # exact: base index + journal merge
        hx.compact()                   # fold the journal into the base —
                                       # bit-identical to a from-scratch
                                       # build over A concat B
        engine = hx.engine("ooc-local", memory_budget_mb=64)
        engine.knn(queries)            # compiled-plan-cached serving
        engine.telemetry()["plan_cache"]   # hits/misses/invalidations

    hx = api.Hercules.open("idx/", mode="a")   # reopen later; "r" = serve only

Appends land in checksummed journal segments (the manifest republish is the
single atomic commit point — a crash before it leaves only orphans the next
writable open sweeps away); ``compact`` replays base + journal rows through
the chunked-build primitives into a new file generation, so append+compact
answers bit-identically to building once over the concatenated collection
on every backend (``tests/test_store.py``).

Purely in-memory serving (no directory on disk) still goes through
:func:`make_backend` + :class:`QueryEngine`; ``local`` | ``scan`` |
``scan-mxu`` | ``sharded`` all answer exactly and interchangeably, and
:class:`KnnServeEngine` adds slot-based submit/poll/drain serving. All
servable names live in the one :data:`BACKENDS` registry
(``backend_names("memory")`` / ``backend_names("disk")`` are its two
construction-path views; the ``BACKEND_NAMES`` / ``DISK_BACKEND_NAMES``
tuples remain as deprecated aliases).

**Compressed leaves (format v3).** ``Hercules.create(..., codec="bf16")``
(or ``compact(codec=...)`` to migrate) stores an encoded sidecar next to
the float32 rows; the out-of-core backends stream the encoded bytes and
re-check candidates against full precision, so answers stay bit-identical
while the stream shrinks to the codec's ratio. The :class:`Codec` protocol
plus :func:`register_codec` / :func:`list_codecs` make the codec set
pluggable; ``SearchConfig.codec`` (``"auto"`` follows the index) selects
per call and flows through plan-cache keys like every other config field.

**Distributed serving (dist-ooc).** ``hx.engine("dist-ooc", shards=8)``
serves one on-disk index from every device of a mesh at once: the manifest
records a shard *plan* (contiguous leaf-run row ranges balanced by rows —
:class:`ShardPlan` / :func:`shard_plan`, derivable on open for old
indexes), each device memory-maps and streams **only its own** row range,
and per-shard top-k merges through a ``shard_map`` collective whose stable
``top_k`` reproduces the single-host tie order — answers stay bit-identical
to ``local`` for every shard count, codec, and ``kernel_mode``. Telemetry
gains a per-shard ``dist`` section (see README "Distributed serving" for
the ``XLA_FLAGS=--xla_force_host_platform_device_count`` recipe).

**Telemetry.** ``QueryEngine.telemetry()`` returns the :class:`Telemetry`
dataclass-of-sections (one shape for serving counters, plan-cache, paths,
pruning, and — for disk backends — streaming/codec counters). The old
dict keys keep working as deprecated aliases:

======================================  ===================================
old dict access                         Telemetry field
======================================  ===================================
``t["backend"] / ["calls"] /``          same-named top-level fields
``["queries"] / ["rows_skipped"] /``    (``host_syncs``: blocking device→
``["wave_calls"] / ["host_syncs"]``     host reads and waits;
                                        ``rows_skipped``: padding rows a
                                        plan skipped, so the skip share is
                                        ``rows_skipped / (queries +
                                        rows_skipped)``)
``t["plan_cache"]["hits" | ...]``       ``t.plan_cache.hits`` ...
``t["latency_s"]["total"]``             ``t.latency.total`` (seconds in
                                        the plans, waits included)
``t["paths"]["scan_eapca" | ...]``      ``t.paths.scan_eapca`` ...
``t["pruning"]["eapca_mean" | ...]``    ``t.pruning.eapca_mean`` ...
``t["ooc"]["rows_streamed" | ...]``     ``t.ooc.rows_streamed`` ... (the
                                        section is ``None`` — key absent —
                                        for in-memory backends; it now also
                                        carries ``bytes_streamed`` and the
                                        ``codec_refine_rows`` /
                                        ``codec_fallbacks`` counters, and
                                        the stream's ``host_syncs``;
                                        ``blocks``: fetches, ``extents``:
                                        the leaf extents and alive runs
                                        they held, so ``extents /
                                        blocks`` is the packing ratio)
``t["dist"]["rows_streamed" | ...]``    ``t.dist.rows_streamed`` ...
                                        (per-shard lists; ``None`` — key
                                        absent — except under ``dist-ooc``)
``t["serving"]["waves" | ...]``         ``t.serving.waves`` ... (filled
                                        by KnnServeEngine; ``queue_wait_s``
                                        / ``dequeued``: submit to wave
                                        start, summed over the dequeued)
======================================  ===================================

Deprecated entry points (kept working; each docstring names its successor):

======================================  ===================================
old surface                             store-API successor
======================================  ===================================
``HerculesIndex.build(data, cfg)``      ``Hercules.create(path, cfg,
                                        data=data)`` (in-memory: unchanged)
``HerculesIndex.build_streaming(src)``  ``Hercules.create(path, cfg,
                                        data=src)``
``build_index_streaming(src, cfg)``     ``Hercules.create(...)`` +
                                        ``.index()``
``build_index_to_disk(src, path)``      ``Hercules.create(path, cfg,
                                        data=src)``
``save_index(index, path)``             ``Hercules.from_index(path, index)``
``load_index(path)``                    ``Hercules.open(path).index()``
``open_index(path)``                    ``Hercules.open(path)`` (``.saved``
                                        is the SavedIndex)
``make_disk_backend(name, path)``       ``Hercules.open(path).engine(name)``
======================================  ===================================

See README.md for the full tour.
"""
from repro.core.engine import (  # noqa: F401
    BACKEND_NAMES, BACKENDS, DISK_BACKEND_NAMES, BackendSpec, DistTelemetry,
    EngineConfig, LatencyTelemetry, LocalBackend, OocTelemetry,
    OutOfCoreLocalBackend, OutOfCoreScanBackend, PathsTelemetry,
    PlanCacheTelemetry, PruningTelemetry, QueryEngine, ScanBackend,
    SearchBackend, ServingTelemetry, ShardedBackend, Telemetry,
    backend_names, dense_scan_knn,
    kernel_scan_knn, make_backend, make_disk_backend, resolve_backend_name,
)
from repro.kernels.compat import KERNEL_MODES, resolve_kernel_mode  # noqa: F401
from repro.core.index import HerculesIndex, IndexConfig  # noqa: F401
from repro.core.search import (  # noqa: F401
    KnnResult, SearchConfig, brute_force_knn, pscan_knn, wave_knn,
)
from repro.core.tree import BuildConfig, build_tree_chunked  # noqa: F401
from repro.data.pipeline import (  # noqa: F401
    ArrayChunkSource, AsyncChunkReader, ChunkSource, NpyChunkSource,
    PREFETCH_MODES, SyncChunkReader, iter_device_chunks, iter_host_chunks,
    iter_scheduled_chunks,
    make_chunk_reader,
)
from repro.serve.engine import (  # noqa: F401
    KnnAnswer, KnnFailure, KnnServeConfig, KnnServeEngine, QueueFull,
)
from repro.storage import (  # noqa: F401
    BALANCE_WARN_RATIO, CODEC_CHOICES, Codec, FORMAT_VERSION, Hercules,
    IndexFormatError, SavedIndex, ShardPlan, build_index_streaming,
    build_index_to_disk, get_codec, list_codecs, load_index, open_index,
    partition_plan, register_codec, save_index, shard_plan,
)
