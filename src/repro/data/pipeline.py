"""Double-buffered host->device data pipeline (the paper's DBuffer, §3.3).

The paper overlaps disk reads with tree inserts via a two-slot buffer and a
coordinator thread. The JAX analogue overlaps host batch generation with
device compute: while the device works on batch t, the host prepares and
transfers batch t+1 (``jax.device_put`` is async). State is (seed, step) so
a restarted worker regenerates exactly the same stream (the fault-tolerance
contract used by launch/train.py).

This module also owns the **chunk sources** feeding the out-of-core build
(``core/tree.py::build_tree_chunked`` and ``repro/storage``): a
:class:`ChunkSource` carves one series collection into fixed-size row chunks
with stable boundaries, re-iterable any number of times (the chunked build
makes two passes per round). :class:`ArrayChunkSource` wraps an in-memory
array; :class:`NpyChunkSource` memory-maps a ``.npy`` file so a chunk's rows
are only read from disk when sliced. :func:`iter_device_chunks` streams any
source through the two-slot buffer: chunk i+1's (async) host→device transfer
is issued before chunk i is handed to the consumer, so the copy overlaps the
consumer's compute.

Disk reads themselves are scheduled by the **chunk readers**
(:func:`make_chunk_reader`). The synchronous double-buffer above only
overlaps the host→device *copy*; the memmap *read* — where an out-of-core
collection actually pays its page faults — still blocks the consumer. With
``prefetch="thread"`` an :class:`AsyncChunkReader` (the paper's DBuffer
coordinator thread; ParIS+'s read/insert overlap) fills a bounded set of
reusable host slot buffers from a daemon thread, so read, host→device copy,
and device compute all overlap. Extents are served strictly in submission
order (deterministic — answers stay bit-identical to ``prefetch="sync"``),
reader-side exceptions re-raise at the consumer's ``get()``, and ``close()``
joins the thread. ``prefetch="sync"`` (:class:`SyncChunkReader`) keeps the
legacy inline reads behind the same surface and times them, so the two
modes are directly comparable via ``read_wait_seconds``/``overlap_blocks``.
Both readers' ``get()`` runs inside a ``repro.ooc.read`` span (argument
``rows``) and ``stage()`` inside ``repro.ooc.stage``, in the profiler's trace.
"""
from __future__ import annotations

import collections
import queue
import threading
import time
from typing import Callable, Iterator, Protocol, runtime_checkable

import jax
import numpy as np

from repro.analysis import sanitize

PREFETCH_MODES = ("sync", "thread")

_span = jax.profiler.TraceAnnotation


class DoubleBufferedLoader:
    """Prefetching loader over a deterministic batch function.

    ``make_batch(step) -> pytree of np/jnp arrays`` must be pure in ``step``.
    """

    def __init__(self, make_batch: Callable[[int], dict], start_step: int = 0,
                 device=None):
        self._make = make_batch
        self._step = start_step
        self._device = device or jax.devices()[0]
        self._next = self._stage(self._step)

    def _stage(self, step: int):
        host = self._make(step)
        # async transfer: returns immediately, compute overlaps the copy
        return jax.tree.map(lambda x: jax.device_put(x, self._device), host)

    def __iter__(self) -> Iterator:
        return self

    def __next__(self):
        batch = self._next
        self._step += 1
        self._next = self._stage(self._step)   # prefetch t+1 while t runs
        return batch

    @property
    def state(self) -> int:
        """Checkpointable pipeline state: the next step index."""
        return self._step


# ---------------------------------------------------------------------------
# Chunk sources (out-of-core ingest)
# ---------------------------------------------------------------------------

@runtime_checkable
class ChunkSource(Protocol):
    """A series collection carved into fixed-size row chunks.

    Chunk boundaries are a pure function of (num_series, chunk_size), so
    repeated iterations see identical chunks — the contract the two-pass
    chunked build rounds rely on. ``chunk(i)`` returns host rows
    ``[i * chunk_size, min((i + 1) * chunk_size, num_series))`` as float32.
    """

    num_series: int
    series_len: int
    chunk_size: int

    @property
    def num_chunks(self) -> int: ...

    def chunk(self, i: int) -> np.ndarray: ...


class _ChunkedBase:
    """Shared chunk arithmetic over a row-sliceable backing store."""

    def __init__(self, rows, chunk_size: int, dtype=np.float32):
        if chunk_size <= 0:
            raise ValueError("chunk_size must be positive")
        self._rows = rows
        self.num_series = int(rows.shape[0])
        self.series_len = int(rows.shape[1])
        self.chunk_size = int(chunk_size)
        # row element type: float32 raw series by default; codec-encoded
        # sources (format v3 ``enc.npy``) stream uint8 rows instead
        self.dtype = np.dtype(dtype)

    @property
    def num_chunks(self) -> int:
        return -(-self.num_series // self.chunk_size)

    def chunk(self, i: int) -> np.ndarray:
        if not 0 <= i < self.num_chunks:
            raise IndexError(f"chunk {i} out of range ({self.num_chunks})")
        lo = i * self.chunk_size
        hi = min(lo + self.chunk_size, self.num_series)
        return np.asarray(self._rows[lo:hi], dtype=self.dtype)


class ArrayChunkSource(_ChunkedBase):
    """Chunk view over an in-memory (N, n) array — tests and the
    chunked-vs-one-shot equality harness."""

    def __init__(self, data, chunk_size: int, dtype=np.float32):
        super().__init__(np.asarray(data), chunk_size, dtype)


class NpyChunkSource(_ChunkedBase):
    """Chunk view over an on-disk ``.npy`` file via ``np.load(mmap_mode="r")``
    — rows hit RAM only when a chunk is sliced, so the build's host
    footprint is one chunk, not the collection."""

    def __init__(self, path: str, chunk_size: int):
        mm = np.load(path, mmap_mode="r")
        if mm.ndim != 2:
            raise ValueError(f"{path}: expected a 2-D series collection, "
                             f"got shape {mm.shape}")
        super().__init__(mm, chunk_size)
        self.path = path


def iter_chunks(source: ChunkSource) -> Iterator[tuple[int, np.ndarray]]:
    """Yield (row_start, host_chunk) over the whole source."""
    for i in range(source.num_chunks):
        yield i * source.chunk_size, source.chunk(i)


# ---------------------------------------------------------------------------
# Chunk readers (disk-aware scheduling: the paper's DBuffer coordinator)
# ---------------------------------------------------------------------------

READ_STAT_KEYS = ("read_seconds", "read_wait_seconds", "overlap_blocks")


def _tally(telemetry: dict | None, stats: dict) -> None:
    """Accumulate a reader's read-timing stats into a shared telemetry dict
    (in place; ``blocks`` is deliberately excluded — consumers count their
    own blocks and must not double-count the reader's)."""
    if telemetry is None:
        return
    for key in READ_STAT_KEYS:
        telemetry[key] = telemetry.get(key, 0) + stats[key]


def _checked_extents(extents, pad_to: int | None, capacity: int):
    """``extents`` as a tuple of int ``(start, count)`` pairs and the
    resolved ``pad_to``, or ValueError: every count positive and
    ``sum(counts) <= pad_to <= capacity`` (``pad_to`` defaults to the
    total). The one bound both readers enforce, so a consumer cannot work
    under the default sync mode yet break under ``"thread"``."""
    extents = tuple((int(s), int(c)) for s, c in extents)
    if not extents:
        raise ValueError("no extents to read")
    for _, count in extents:
        if count <= 0:
            raise ValueError(f"count must be positive, got {count}")
    total = sum(c for _, c in extents)
    pad_to = total if pad_to is None else int(pad_to)
    if not total <= pad_to <= capacity:
        raise ValueError(f"pad_to={pad_to} outside [count={total}, "
                         f"slot capacity={capacity}]")
    return extents, pad_to


def _fill_extents(buf: np.ndarray, rows, extents, pad_to: int) -> None:
    """Copy ``extents`` of ``rows`` into ``buf`` back to back, in the order
    given, and zero the rest of ``buf[:pad_to]``."""
    at = 0
    for start, count in extents:
        buf[at:at + count] = rows[start:start + count]
        at += count
    if pad_to > at:
        buf[at:pad_to] = 0


class SyncChunkReader:
    """Inline reads behind the reader surface (``prefetch="sync"``).

    ``get()`` performs the read it was submitted, into a fresh array (data
    rows copied out of the store, pad rows zeroed) — byte-identical values
    to the legacy per-piece fetch, with no buffer reuse, so the returned
    array is the caller's to keep. Because the copy faults the backing
    store's pages inside the timed region, ``read_wait_seconds`` counts
    the real synchronous disk wait — exactly what the threaded mode hides;
    ``overlap_blocks`` stays 0. Submission bounds match the threaded
    reader's slot capacity, keeping the two surfaces interchangeable.
    """

    def __init__(self, rows, capacity_rows: int, width: int,
                 dtype=np.float32, *, slots: int = 2):
        self._rows = rows
        self._capacity = max(int(capacity_rows), 1)
        self._width = int(width)
        self._dtype = np.dtype(dtype)
        self._reqs: collections.deque = collections.deque()
        self.stats = {"blocks": 0, "read_seconds": 0.0,
                      "read_wait_seconds": 0.0, "overlap_blocks": 0}
        self._closed = False

    def submit(self, start: int, count: int, pad_to: int | None = None):
        self.submit_extents([(start, count)], pad_to)

    def submit_extents(self, extents, pad_to: int | None = None):
        """Enqueue one block read from several ``(start, count)`` extents,
        copied back to back in the order given and zero-padded to
        ``pad_to`` rows."""
        if self._closed:
            raise RuntimeError("reader is closed")
        extents, pad_to = _checked_extents(extents, pad_to, self._capacity)
        self._reqs.append((extents, pad_to))

    def get(self) -> np.ndarray:
        if self._closed:
            raise RuntimeError("reader is closed")
        if not self._reqs:
            raise RuntimeError("get() without a pending submit()")
        extents, pad_to = self._reqs.popleft()
        with _span("repro.ooc.read", rows=sum(c for _, c in extents)):
            t0 = time.perf_counter()
            out = np.empty((pad_to, self._width), self._dtype)
            _fill_extents(out, self._rows, extents, pad_to)
            dt = time.perf_counter() - t0
        self.stats["read_seconds"] += dt
        self.stats["read_wait_seconds"] += dt
        self.stats["blocks"] += 1
        return out

    def stage(self, view: np.ndarray, device=None, *,
              block: bool = True) -> jax.Array:
        """Host→device transfer of a fetched block. Sync blocks are fresh
        arrays the transfer machinery keeps alive, so the async
        ``device_put`` needs no completion barrier (``block`` is accepted
        for surface parity with the threaded reader and ignored).

        ``device=None`` defers to jax's current default device — NOT a
        hardcoded ``jax.devices()[0]`` — so a consumer running under a
        ``jax.default_device(...)`` context (each dist-ooc shard pins its
        stream to its own mesh device that way) gets its blocks on the
        right device, same as the threaded reader's ``_staged_copy``."""
        del block
        with _span("repro.ooc.stage"):
            if device is None:
                # herculint: ok[alias-transfer] -- sync get() returns a fresh buffer per call; nothing refills it, so a zero-copy alias is harmless
                return jax.device_put(view)
            # herculint: ok[alias-transfer] -- sync get() returns a fresh buffer per call; nothing refills it, so a zero-copy alias is harmless
            return jax.device_put(view, device)

    def close(self) -> None:
        self._closed = True
        self._reqs.clear()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def _staged_copy(view: np.ndarray, device=None) -> jax.Array:
    """A device array guaranteed (once ready) to own memory independent of
    ``view`` — ``jnp.array(copy=True)``, unlike ``device_put``, may never
    zero-copy alias the host buffer."""
    import jax.numpy as jnp

    if device is None:
        return jnp.array(view, copy=True)
    with jax.default_device(device):
        return jnp.array(view, copy=True)


class AsyncChunkReader:
    """Daemon reader thread + bounded reusable host slots (DBuffer, §3.3).

    ``rows`` is any row-sliceable store (an ``np.memmap``, an ndarray, the
    store's concat views). ``submit(start, count, pad_to)`` enqueues one
    extent, ``submit_extents(extents, pad_to)`` one slot fill from several,
    back to back; ``get()`` serves fills **strictly in submission order** as
    views into one of ``slots`` reusable ``(capacity_rows, width)`` arrays.
    Each view is valid only until the next ``get()`` or ``close()`` — move
    it off-slot (``stage``) before requesting the next fill. Rows beyond
    the extents up to ``pad_to`` are zero-filled, matching the legacy
    zero-padded fetch byte for byte. A reader-side exception re-raises at
    the ``get()`` for the failing fill and ends the stream. ``close()``
    is idempotent, unblocks the thread wherever it waits, and joins it.
    """

    THREAD_NAME = "repro-chunk-reader"

    def __init__(self, rows, capacity_rows: int, width: int,
                 dtype=np.float32, *, slots: int = 2):
        if slots < 2:
            raise ValueError("need at least two slots (one computing, one "
                             "filling)")
        self._rows = rows
        self._slots = [np.empty((max(int(capacity_rows), 1), int(width)),
                                np.dtype(dtype)) for _ in range(slots)]
        self._requests: queue.SimpleQueue = queue.SimpleQueue()
        self._free: queue.SimpleQueue = queue.SimpleQueue()
        for i in range(slots):
            self._free.put(i)
        self._ready: queue.SimpleQueue = queue.SimpleQueue()
        self._held: int | None = None
        self._pending = 0
        self._stop = threading.Event()
        self._closed = False
        self._exc: BaseException | None = None
        self.stats = {"blocks": 0, "read_seconds": 0.0,
                      "read_wait_seconds": 0.0, "overlap_blocks": 0}
        # REPRO_SANITIZE=1: (slot_id, host snapshot, device array) per
        # stage(); verified against the poisoned slot at recycle time
        self._sanitize = sanitize.sanitize_enabled()
        self._staged_tracks: list[tuple[int, np.ndarray, jax.Array]] = []
        # The consumer surface (submit/get/stage) is single-owner by
        # contract: slot views and self.stats are driven by exactly one
        # thread, with the reader thread on the other side of the queues.
        # Binds to the first consuming thread, not the constructor —
        # building on main and consuming in a pool worker is legal.
        # close() is exempt: __del__ may run it from any thread.
        self._consumer = sanitize.ThreadAffinity(type(self).__name__)
        self._thread = threading.Thread(target=self._run,
                                        name=self.THREAD_NAME, daemon=True)
        self._thread.start()

    # -- reader thread -------------------------------------------------------

    def _fill(self, buf: np.ndarray, extents, pad_to: int) -> None:
        _fill_extents(buf, self._rows, extents, pad_to)

    def _run(self) -> None:
        while True:
            req = self._requests.get()
            if req is None or self._stop.is_set():
                break
            sid = self._free.get()
            if sid is None or self._stop.is_set():
                break
            extents, pad_to = req
            t0 = time.perf_counter()
            try:
                self._fill(self._slots[sid], extents, pad_to)
            except BaseException as e:          # propagate to the consumer
                self._ready.put((None, 0, 0.0, e))
                break
            # the read duration rides the ready tuple: the worker must not
            # touch self.stats (consumer-owned; herculint lock-discipline)
            self._ready.put((sid, pad_to, time.perf_counter() - t0, None))

    # -- consumer side -------------------------------------------------------

    def _check_alive(self) -> None:
        if self._closed:
            raise RuntimeError("reader is closed")
        if self._exc is not None:
            raise RuntimeError("reader stream already failed") from self._exc

    def submit(self, start: int, count: int, pad_to: int | None = None):
        self.submit_extents([(start, count)], pad_to)

    def submit_extents(self, extents, pad_to: int | None = None):
        """Enqueue one slot fill from several ``(start, count)`` extents,
        copied back to back in the order given and zero-padded to
        ``pad_to`` rows; served in submission order like ``submit``."""
        self._consumer.check("submit")
        self._check_alive()
        extents, pad_to = _checked_extents(extents, pad_to,
                                           self._slots[0].shape[0])
        self._pending += 1
        self._requests.put((extents, pad_to))

    def get(self) -> np.ndarray:
        self._consumer.check("get")
        self._check_alive()
        if self._pending <= 0:
            raise RuntimeError("get() without a pending submit()")
        self._pending -= 1
        if self._held is not None:              # recycle the previous view
            self._recycle(self._held)
            self._held = None
        overlapped = not self._ready.empty()    # read finished before asked
        with _span("repro.ooc.read") as sp:
            t0 = time.perf_counter()
            sid, n_rows, read_s, exc = self._ready.get()
            self.stats["read_wait_seconds"] += time.perf_counter() - t0
            sp.set_metadata(rows=n_rows)
        if exc is not None:
            # the reader thread has exited: latch the failure so later
            # get()/submit() fail loudly instead of blocking forever
            self._exc = exc
            raise exc
        self.stats["read_seconds"] += read_s
        self.stats["overlap_blocks"] += int(overlapped)
        self.stats["blocks"] += 1
        self._held = sid
        return self._slots[sid][:n_rows]

    def _recycle(self, sid: int) -> None:
        """Hand a slot back to the reader thread. Under REPRO_SANITIZE=1
        the slot is poisoned *first*, then every staged copy taken from it
        is re-checked against its snapshot — a zero-copy alias shows the
        canary and raises before the reader can overwrite live data."""
        if self._sanitize:
            sanitize.poison(self._slots[sid])
            self._verify_staged(sid)
        self._free.put(sid)

    def _verify_staged(self, sid: int) -> None:
        keep = []
        for slot_id, snap, dev in self._staged_tracks:
            if slot_id != sid:
                keep.append((slot_id, snap, dev))
        tracked = [t for t in self._staged_tracks if t[0] == sid]
        self._staged_tracks = keep              # drop before any raise
        for slot_id, snap, dev in tracked:
            sanitize.verify_staged(dev, snap, slot_id=slot_id)

    def stage(self, view: np.ndarray, device=None, *,
              block: bool = True) -> jax.Array:
        """Host→device transfer of a slot view, blocked to completion so the
        slot can be recycled at the next ``get()`` while async device
        compute on the staged copy proceeds. ``copy=True`` is load-bearing:
        a plain ``device_put`` may zero-copy *alias* an aligned numpy
        buffer on CPU jax, and an aliased slot would be overwritten by the
        reader thread mid-computation.

        ``block=False`` defers the completion barrier to the caller, who
        **must** ``jax.block_until_ready`` the result before the next
        ``get()`` (which recycles the slot the copy reads from) — the
        double-buffer loop uses this to overlap the copy with consumer
        compute."""
        self._consumer.check("stage")
        with _span("repro.ooc.stage"):
            dev = _staged_copy(view, device)
            if block:
                jax.block_until_ready(dev)
        if self._sanitize and self._held is not None:
            self._staged_tracks.append(
                (self._held, sanitize.snapshot(view), dev))
        return dev

    def close(self) -> None:
        """Idempotent: stops and joins the reader thread (sentinels unblock
        it from whichever queue it waits on), invalidating every view."""
        if self._closed:
            return
        self._closed = True
        self._stop.set()
        self._requests.put(None)
        self._free.put(None)
        self._thread.join(timeout=30.0)
        if self._thread.is_alive():             # pragma: no cover
            raise RuntimeError("chunk reader thread failed to join")
        self._held = None
        if self._sanitize:
            # final sweep: poison every slot (the thread is joined, nothing
            # refills them) and verify any still-tracked staged copies
            for slot in self._slots:
                sanitize.poison(slot)
            tracked, self._staged_tracks = self._staged_tracks, []
            for slot_id, snap, dev in tracked:
                sanitize.verify_staged(dev, snap, slot_id=slot_id)

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:                       # pragma: no cover
            pass


def make_chunk_reader(rows, capacity_rows: int, width: int,
                      dtype=np.float32, *, prefetch: str = "sync",
                      slots: int = 2):
    """Reader over a row-sliceable store: ``"thread"`` → daemon-thread
    :class:`AsyncChunkReader`, ``"sync"`` → inline :class:`SyncChunkReader`
    (same surface, same bytes, so consumers have one code path)."""
    if prefetch not in PREFETCH_MODES:
        raise ValueError(f"prefetch={prefetch!r}; expected one of "
                         f"{PREFETCH_MODES}")
    cls = AsyncChunkReader if prefetch == "thread" else SyncChunkReader
    return cls(rows, capacity_rows, width, dtype, slots=slots)


def iter_scheduled_chunks(reader, requests, still_needed=None,
                          lookahead: int = 2, device=None
                          ) -> Iterator[tuple[object, jax.Array]]:
    """Demand-scheduled fetches over one shared chunk reader (the wave
    path's multi-consumer submissions).

    ``requests`` is an ordered iterable of ``(tag, start, count, pad_to)``
    — typically leaf runs sorted by how many consumers still need them.
    Each surviving request is fetched **once** and yielded as
    ``(tag, staged_device_rows)``; the tag tells the caller which run (and
    therefore which consumers) the block belongs to.

    ``still_needed(tag) -> bool`` is consulted immediately before each
    ``submit()`` — as late as possible — so a run whose every interested
    consumer has since been satisfied (e.g. all wave members' best-so-far
    bounds tightened past the run's lower bound while earlier blocks
    refined) is dropped without ever touching the disk. ``lookahead``
    bounds the number of in-flight submissions: large enough that reads
    overlap the consumer's compute (the reader's slot pair), small enough
    that the drop decision still sees a recent bound.
    """
    if lookahead < 1:
        raise ValueError(f"lookahead={lookahead}; expected >= 1")
    pending: collections.deque = collections.deque()
    it = iter(requests)

    def pump() -> None:
        while len(pending) < lookahead:
            for tag, start, count, pad_to in it:
                if still_needed is None or still_needed(tag):
                    reader.submit(start, count, pad_to)
                    pending.append(tag)
                    break
            else:
                return

    pump()
    while pending:
        tag = pending.popleft()
        rows = reader.stage(reader.get(), device)
        pump()                       # refill the window before the consumer
        yield tag, rows              # computes, so the next read overlaps


class _SourceRows:
    """Row-sliceable adapter over a protocol-only :class:`ChunkSource`
    (slices must align to the source's chunk boundaries — the whole-source
    iterators request exactly its chunks)."""

    def __init__(self, source: ChunkSource):
        self._source = source

    def __getitem__(self, sl: slice) -> np.ndarray:
        i, rem = divmod(sl.start, self._source.chunk_size)
        if rem:
            raise ValueError(f"row {sl.start} is not a chunk boundary of "
                             f"chunk_size={self._source.chunk_size}")
        return self._source.chunk(i)[:sl.stop - sl.start]


def _source_rows(source: ChunkSource):
    """The cheapest row-sliceable view of a source: its backing store when
    it has one (memmap reads land straight in the slot buffer), else the
    chunk-aligned adapter."""
    rows = getattr(source, "_rows", None)
    return _SourceRows(source) if rows is None else rows


def _whole_source_reader(source: ChunkSource, prefetch: str):
    """A reader with every chunk of ``source`` submitted, in order."""
    reader = make_chunk_reader(_source_rows(source), source.chunk_size,
                               source.series_len,
                               getattr(source, "dtype", np.float32),
                               prefetch=prefetch)
    num = source.num_series
    for i in range(source.num_chunks):
        lo = i * source.chunk_size
        reader.submit(lo, min(source.chunk_size, num - lo))
    return reader


def iter_host_chunks(source: ChunkSource, prefetch: str = "sync",
                     telemetry: dict | None = None
                     ) -> Iterator[tuple[int, np.ndarray]]:
    """Yield (row_start, host_chunk) over the whole source through a chunk
    reader. With ``prefetch="thread"`` the yielded chunk is a reusable slot
    view, valid only until the next iteration — consume (copy/scatter) it
    before advancing. Reader stats accumulate into ``telemetry``."""
    if prefetch == "sync" and telemetry is None:
        yield from iter_chunks(source)
        return
    reader = _whole_source_reader(source, prefetch)
    try:
        for i in range(source.num_chunks):
            yield i * source.chunk_size, reader.get()
    finally:
        reader.close()
        _tally(telemetry, reader.stats)


def iter_device_chunks(source: ChunkSource, device=None,
                       prefetch: str = "sync",
                       telemetry: dict | None = None
                       ) -> Iterator[tuple[int, jax.Array]]:
    """Yield (row_start, device_chunk) with two-slot prefetch (DBuffer).

    ``prefetch="sync"``: chunk i+1's async ``device_put`` is issued before
    chunk i is yielded, overlapping its copy with the consumer's compute on
    chunk i — but the memmap *read* of chunk i+1 still blocks here.
    ``prefetch="thread"``: an :class:`AsyncChunkReader` reads ahead into
    reusable host slots, so read, copy, and compute all overlap; each
    staged transfer is blocked to completion before its slot is recycled,
    which is what keeps the yielded device chunks immutable (and answers
    bit-identical to the sync path). Reader/read stats accumulate into
    ``telemetry`` (``read_wait_seconds``, ``overlap_blocks``, ...).

    Codec note: sources whose ``dtype`` is uint8 (format v3 encoded rows)
    stream encoded bytes through the very same machinery; the consumer
    decodes *after* the yield, i.e. after the disk wait — so the reader's
    prefetch of block i+1 overlaps block i's decode+refine compute.
    """
    device = device or jax.devices()[0]
    n = source.num_chunks
    if n == 0:
        return
    if prefetch not in PREFETCH_MODES:
        raise ValueError(f"prefetch={prefetch!r}; expected one of "
                         f"{PREFETCH_MODES}")
    reader = _whole_source_reader(source, prefetch)
    # both modes read through the reader: a sync get() copies the extent out
    # of the backing store (faulting its pages) inside the timed read, so
    # read_wait_seconds measures real disk wait — a raw memmap slice would
    # defer the page faults into device_put and under-report it as ~0
    try:
        if prefetch == "sync":
            # fresh per-chunk buffers: SyncChunkReader.stage is an async
            # device_put, so the transfer for chunk i+1 stays in flight
            # while the consumer computes on chunk i (the legacy
            # copy/compute overlap; nothing mutates the buffer)
            staged = reader.stage(reader.get(), device)
            for i in range(n):
                cur = staged
                if i + 1 < n:
                    staged = reader.stage(reader.get(), device)
                yield i * source.chunk_size, cur
        else:
            # block=False: the barrier is the block_until_ready(cur) below,
            # which always runs before the get() that recycles cur's slot
            staged = reader.stage(reader.get(), device, block=False)
            for i in range(n):
                cur = staged
                # copy committed -> the slot backing `cur` may be recycled
                # by the get() below while async compute on `cur` proceeds
                jax.block_until_ready(cur)
                if i + 1 < n:
                    staged = reader.stage(reader.get(), device, block=False)
                yield i * source.chunk_size, cur
    finally:
        reader.close()
        _tally(telemetry, reader.stats)
