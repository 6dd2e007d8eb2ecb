"""Disk-aware scheduling (async chunk reader + two-slot host buffer).

Covers the PR's acceptance contract:
* the chunk readers — deterministic submission-order serving, zero-padded
  reusable slots, exception propagation, idempotent close that joins the
  daemon thread (no leaks — also asserted globally by the conftest
  fixture);
* ``prefetch="thread"`` answers bit-identical to ``prefetch="sync"`` on
  ooc-scan and ooc-local, including under randomly jittered read timings;
* adversarial budgets — the minimum viable budget, budgets whose
  ``stream_rows`` divides neither ``scan_block`` nor ``max_leaf`` — stay
  bit-identical to the in-memory backends;
* the ``sax_pr`` fix — seeded-leaf rows count as alive, pinned against
  rows actually streamed;
* one budget→``stream_rows`` code path shared by backends and the CLI;
* ``scan_block`` auto-shrink behaves identically from every entry point.
"""
from __future__ import annotations

import dataclasses
import threading
import time

import jax
import numpy as np
import pytest

from repro.core.engine import (LocalBackend, OutOfCoreLocalBackend,
                               OutOfCoreScanBackend, ScanBackend,
                               _OutOfCoreBase, make_disk_backend)
from repro.core.index import HerculesIndex, IndexConfig
from repro.core.search import SearchConfig
from repro.core.tree import BuildConfig
from repro.data.pipeline import (ArrayChunkSource, AsyncChunkReader,
                                 PREFETCH_MODES, SyncChunkReader,
                                 iter_device_chunks, iter_host_chunks,
                                 make_chunk_reader)
from repro.data.synthetic import make_query_workload, random_walks
from repro.storage import open_index, save_index

NUM, LEN = 2048, 64
CFG = IndexConfig(
    build=BuildConfig(leaf_capacity=64),
    search=SearchConfig(k=3, l_max=4, chunk=256, scan_block=256))
ROW_BYTES = 4 * LEN


def budget_mb_for_stream_rows(stream_rows: int) -> float:
    """The budget that makes ``budget_stream_rows`` == ``stream_rows``."""
    return 2 * stream_rows * ROW_BYTES / (1 << 20)


@pytest.fixture(scope="module")
def data():
    return random_walks(jax.random.PRNGKey(7), NUM, LEN)


@pytest.fixture(scope="module")
def queries(data):
    return make_query_workload(jax.random.PRNGKey(8), data, 4, "5%")


@pytest.fixture(scope="module")
def index(data):
    return HerculesIndex.build(data, CFG)


@pytest.fixture(scope="module")
def saved_dir(index, tmp_path_factory):
    path = str(tmp_path_factory.mktemp("prefetch") / "idx")
    save_index(index, path)
    return path


def _same(a, b):
    assert np.array_equal(np.asarray(a.dists), np.asarray(b.dists))
    assert np.array_equal(np.asarray(a.ids), np.asarray(b.ids))


def _no_reader_threads():
    return not [t for t in threading.enumerate()
                if t.name == AsyncChunkReader.THREAD_NAME and t.is_alive()]


# ---------------------------------------------------------------------------
# Reader unit behaviour
# ---------------------------------------------------------------------------

class TestChunkReader:
    ROWS = np.arange(100 * 8, dtype=np.float32).reshape(100, 8)

    @pytest.mark.parametrize("mode", PREFETCH_MODES)
    def test_submission_order_and_padding(self, mode):
        with make_chunk_reader(self.ROWS, 32, 8, prefetch=mode) as r:
            r.submit(10, 5, 16)
            r.submit(90, 10)
            r.submit(0, 32)
            a = r.get()
            assert a.shape == (16, 8)
            assert np.array_equal(a[:5], self.ROWS[10:15])
            assert not a[5:].any()          # zero-filled pad, every request
            b = r.get()
            # a slot view is valid until the *next* get(): copy to compare
            assert np.array_equal(np.array(b), self.ROWS[90:100])
            c = r.get()
            assert np.array_equal(np.array(c), self.ROWS[0:32])
            assert r.stats["blocks"] == 3
        assert _no_reader_threads()

    def test_thread_reuses_bounded_slots(self):
        r = make_chunk_reader(self.ROWS, 16, 8, prefetch="thread")
        bases = set()
        for i in range(8):
            r.submit(i * 10, 10)
        for _ in range(8):
            view = r.get()
            bases.add(view.base.ctypes.data)
        r.close()
        assert len(bases) == 2              # two reusable slot arrays

    def test_exception_propagates_to_get(self):
        class Exploding:
            def __getitem__(self, sl):
                raise OSError("bad sector")

        r = make_chunk_reader(Exploding(), 8, 4, prefetch="thread")
        r.submit(0, 4)
        r.submit(4, 4)
        with pytest.raises(OSError, match="bad sector"):
            r.get()
        # the failure is latched: a later get()/submit() must fail loudly
        # instead of blocking forever on the dead reader thread
        with pytest.raises(RuntimeError, match="already failed"):
            r.get()
        with pytest.raises(RuntimeError, match="already failed"):
            r.submit(8, 4)
        r.close()
        assert _no_reader_threads()

    def test_close_is_idempotent_and_joins(self):
        r = make_chunk_reader(self.ROWS, 16, 8, prefetch="thread")
        for i in range(16):                 # more requests than slots
            r.submit(i, 1)
        r.get()
        r.close()
        r.close()
        assert _no_reader_threads()
        with pytest.raises(RuntimeError, match="closed"):
            r.get()
        with pytest.raises(RuntimeError, match="closed"):
            r.submit(0, 1)

    @pytest.mark.parametrize("mode", PREFETCH_MODES)
    def test_get_without_submit_raises(self, mode):
        with make_chunk_reader(self.ROWS, 8, 8, prefetch=mode) as r:
            with pytest.raises(RuntimeError, match="without a pending"):
                r.get()

    @pytest.mark.parametrize("mode", PREFETCH_MODES)
    def test_submit_validation(self, mode):
        # both modes enforce the same bounds, so a consumer cannot work
        # under sync and break only when prefetch flips to thread
        with make_chunk_reader(self.ROWS, 8, 8, prefetch=mode) as r:
            with pytest.raises(ValueError, match="positive"):
                r.submit(0, 0)
            with pytest.raises(ValueError, match="pad_to"):
                r.submit(0, 4, 100)         # beyond slot capacity
            with pytest.raises(ValueError, match="pad_to"):
                r.submit(0, 4, 2)           # pad below count

    def test_unknown_mode_rejected(self):
        with pytest.raises(ValueError, match="prefetch"):
            make_chunk_reader(self.ROWS, 8, 8, prefetch="bogus")

    def test_stage_is_independent_of_slot(self):
        """The staged device copy must not alias the reusable slot (a plain
        device_put may zero-copy an aligned numpy buffer)."""
        r = make_chunk_reader(self.ROWS, 16, 8, prefetch="thread")
        r.submit(0, 16)
        view = r.get()
        dev = r.stage(view)
        view[:] = -1.0                      # simulate the reader refilling
        assert np.array_equal(np.asarray(dev), self.ROWS[:16])
        r.close()


class TestMultiExtentSubmit:
    """One slot filled from several extents: the raw stream's packed
    block."""
    ROWS = np.arange(100 * 8, dtype=np.float32).reshape(100, 8) + 1.0
    EXTENTS = [(40, 3), (5, 4), (90, 10), (12, 1)]

    def _want(self, extents, pad_to):
        want = np.zeros((pad_to, 8), np.float32)
        at = 0
        for start, count in extents:
            want[at:at + count] = self.ROWS[start:start + count]
            at += count
        return want

    @pytest.mark.parametrize("mode", PREFETCH_MODES)
    def test_back_to_back_in_order_with_zero_pad(self, mode):
        with make_chunk_reader(self.ROWS, 32, 8, prefetch=mode) as r:
            r.submit_extents(self.EXTENTS, 32)
            r.submit_extents([(7, 18)])       # pad_to defaults to the total
            got = np.array(r.get())
            assert got.shape == (32, 8)
            assert np.array_equal(got[:3], self.ROWS[40:43])
            assert np.array_equal(got[3:7], self.ROWS[5:9])
            assert np.array_equal(got[7:17], self.ROWS[90:100])
            assert np.array_equal(got[17], self.ROWS[12])
            assert not got[18:].any()       # zero-filled pad
            assert np.array_equal(np.array(r.get()), self.ROWS[7:25])
            assert r.stats["blocks"] == 2
        assert _no_reader_threads()

    @pytest.mark.parametrize("mode", PREFETCH_MODES)
    def test_one_extent_is_submit(self, mode):
        with make_chunk_reader(self.ROWS, 32, 8, prefetch=mode) as r:
            r.submit(10, 5, 16)
            r.submit_extents([(10, 5)], 16)
            a = np.array(r.get())
            assert np.array_equal(a, np.array(r.get()))
            assert np.array_equal(a, self._want([(10, 5)], 16))

    @pytest.mark.parametrize("mode", PREFETCH_MODES)
    def test_multi_extent_validation(self, mode):
        with make_chunk_reader(self.ROWS, 16, 8, prefetch=mode) as r:
            with pytest.raises(ValueError, match="pad_to"):
                r.submit_extents([(0, 6), (10, 6)], 8)   # rows above pad_to
            with pytest.raises(ValueError, match="pad_to"):
                r.submit_extents([(0, 6), (10, 6)], 32)  # above capacity
            with pytest.raises(ValueError, match="pad_to"):
                r.submit_extents([(0, 10), (20, 10)])    # total > capacity
            with pytest.raises(ValueError, match="positive"):
                r.submit_extents([(0, 4), (9, 0)], 8)
            with pytest.raises(ValueError, match="no extents"):
                r.submit_extents([], 8)

    def test_sync_and_thread_fill_identical_slots(self):
        blocks = [(self.EXTENTS, 32), ([(0, 32)], 32), ([(60, 2)], 8),
                  ([(3, 1), (70, 20)], 24), (self.EXTENTS[::-1], 18)]
        got = {}
        for mode in PREFETCH_MODES:
            with make_chunk_reader(self.ROWS, 32, 8, prefetch=mode) as r:
                for extents, pad_to in blocks:
                    r.submit_extents(extents, pad_to)
                got[mode] = [np.array(r.get()) for _ in blocks]
        for a, b, (extents, pad_to) in zip(got["sync"], got["thread"],
                                           blocks):
            assert np.array_equal(a, b)
            assert np.array_equal(a, self._want(extents, pad_to))

    def test_staged_block_is_independent_of_refilled_slot(self):
        """A staged packed block must not alias its slot: the reader
        refills both slots with the next blocks while it is in use."""
        r = make_chunk_reader(self.ROWS, 32, 8, prefetch="thread")
        r.submit_extents(self.EXTENTS, 32)
        r.submit_extents([(0, 32)])
        r.submit_extents([(50, 16), (20, 16)])
        first = r.stage(r.get())
        r.stage(r.get())
        r.get()                             # refills the first block's slot
        assert np.array_equal(np.asarray(first), self._want(self.EXTENTS, 32))
        r.close()


class TestScheduledChunks:
    """The wave path's demand-scheduled fetch loop (iter_scheduled_chunks)."""
    ROWS = np.arange(100 * 8, dtype=np.float32).reshape(100, 8)

    def _reqs(self):
        return [("a", 0, 10, 16), ("b", 20, 10, 16), ("c", 40, 10, 16),
                ("d", 60, 10, 16)]

    @pytest.mark.parametrize("mode", PREFETCH_MODES)
    def test_fetches_in_request_order(self, mode):
        from repro.data.pipeline import iter_scheduled_chunks
        with make_chunk_reader(self.ROWS, 32, 8, prefetch=mode) as r:
            got = list(iter_scheduled_chunks(r, self._reqs()))
        assert [t for t, _ in got] == ["a", "b", "c", "d"]
        for (tag, rows), (_, start, cnt, pad) in zip(got, self._reqs()):
            assert rows.shape == (pad, 8)
            assert np.array_equal(np.asarray(rows)[:cnt],
                                  self.ROWS[start:start + cnt])

    def test_still_needed_checked_at_submit_time(self):
        """A request whose consumers were satisfied while earlier blocks
        were in flight is dropped without a disk read; the drop decision
        runs per-request, as late as the lookahead window allows."""
        from repro.data.pipeline import iter_scheduled_chunks
        dead = set()
        checked = []

        def still_needed(tag):
            checked.append(tag)
            return tag not in dead

        with make_chunk_reader(self.ROWS, 32, 8, prefetch="sync") as r:
            out = []
            for tag, rows in iter_scheduled_chunks(
                    r, self._reqs(), still_needed=still_needed, lookahead=1):
                out.append(tag)
                if tag == "a":
                    dead.add("c")   # bound tightened: run c no longer needed
        assert out == ["a", "b", "d"]
        assert checked == ["a", "b", "c", "d"]

    def test_lookahead_validation(self):
        from repro.data.pipeline import iter_scheduled_chunks
        with make_chunk_reader(self.ROWS, 32, 8, prefetch="sync") as r:
            with pytest.raises(ValueError, match="lookahead"):
                list(iter_scheduled_chunks(r, self._reqs(), lookahead=0))


class TestChunkIterators:
    @pytest.mark.parametrize("chunk_size", [7, 64, 100, 1000])
    def test_device_chunks_thread_matches_sync(self, chunk_size):
        rows = np.random.default_rng(0).standard_normal(
            (100, 8)).astype(np.float32)
        src = ArrayChunkSource(rows, chunk_size)
        sync = [(s, np.asarray(c)) for s, c in iter_device_chunks(src)]
        tel: dict = {}
        thr = [(s, np.asarray(c)) for s, c in
               iter_device_chunks(src, prefetch="thread", telemetry=tel)]
        assert len(sync) == len(thr) == src.num_chunks
        for (s0, c0), (s1, c1) in zip(sync, thr):
            assert s0 == s1
            assert np.array_equal(c0, c1)
        assert tel["read_wait_seconds"] >= 0
        assert _no_reader_threads()

    def test_host_chunks_thread_matches_sync(self):
        rows = np.random.default_rng(1).standard_normal(
            (50, 4)).astype(np.float32)
        src = ArrayChunkSource(rows, 12)
        sync = [(s, c.copy()) for s, c in iter_host_chunks(src)]
        thr = [(s, np.array(c)) for s, c in
               iter_host_chunks(src, prefetch="thread")]
        for (s0, c0), (s1, c1) in zip(sync, thr):
            assert s0 == s1
            assert np.array_equal(c0, c1)

    def test_consumer_break_joins_reader(self):
        src = ArrayChunkSource(np.zeros((100, 4), np.float32), 10)
        for _ in iter_device_chunks(src, prefetch="thread"):
            break                           # generator close -> finally
        assert _no_reader_threads()


# ---------------------------------------------------------------------------
# Backend parity: thread == sync == in-memory, adversarial budgets
# ---------------------------------------------------------------------------

class TestPrefetchParity:
    BUDGET_MB = 0.125                       # collection (0.5 MiB) = 4x this

    def _ooc_scan(self, saved, mode, budget=None, **kw):
        cfg = dataclasses.replace(CFG.search, prefetch=mode, **kw)
        return OutOfCoreScanBackend(saved, cfg,
                                    memory_budget_mb=budget or self.BUDGET_MB)

    def _ooc_local(self, saved, mode, budget=None, **kw):
        cfg = dataclasses.replace(CFG.search, prefetch=mode, **kw)
        return OutOfCoreLocalBackend(saved, cfg,
                                     memory_budget_mb=budget or self.BUDGET_MB)

    def test_scan_thread_matches_sync_and_memory(self, data, saved_dir,
                                                 queries):
        mem = ScanBackend(data, CFG.search).knn(queries)
        with open_index(saved_dir) as saved:
            r_sync = self._ooc_scan(saved, "sync").knn(queries)
            thr = self._ooc_scan(saved, "thread")
            r_thr = thr.knn(queries)
            _same(mem, r_sync)
            _same(r_sync, r_thr)
            st = thr.stats()
            assert st["read_wait_seconds"] >= 0
            assert 0 <= st["overlap_blocks"] <= st["blocks"]

    def test_local_thread_matches_sync_and_memory(self, index, saved_dir,
                                                  queries):
        mem = LocalBackend(index).knn(queries, k=1)
        with open_index(saved_dir) as saved:
            r_sync = self._ooc_local(saved, "sync").knn(queries, k=1)
            r_thr = self._ooc_local(saved, "thread").knn(queries, k=1)
            _same(mem, r_sync)
            _same(r_sync, r_thr)

    def test_parity_under_random_read_timings(self, data, saved_dir, queries,
                                              monkeypatch):
        """Jitter every threaded read by a random delay: answers must not
        depend on when the reader thread lands its fills."""
        rng = np.random.default_rng(1234)
        orig = AsyncChunkReader._fill

        def jittered(self, buf, extents, pad_to):
            time.sleep(float(rng.uniform(0.0, 0.002)))
            orig(self, buf, extents, pad_to)

        monkeypatch.setattr(AsyncChunkReader, "_fill", jittered)
        mem_scan = ScanBackend(data, CFG.search).knn(queries)
        with open_index(saved_dir) as saved:
            r_scan = self._ooc_scan(saved, "thread").knn(queries)
            _same(mem_scan, r_scan)
            r_sync = self._ooc_local(saved, "sync").knn(queries, k=2)
            r_thr = self._ooc_local(saved, "thread").knn(queries, k=2)
            _same(r_sync, r_thr)
        assert _no_reader_threads()

    @pytest.mark.parametrize("mode", PREFETCH_MODES)
    def test_minimum_viable_budget(self, data, index, saved_dir, queries,
                                   mode):
        """The smallest budget each backend accepts still answers
        bit-identically to the in-memory backends."""
        with open_index(saved_dir) as saved:
            # ooc-local floor: one max_leaf extent per streamed piece
            budget = budget_mb_for_stream_rows(saved.max_leaf)
            ooc = self._ooc_local(saved, mode, budget=budget)
            assert ooc.stream_rows() == saved.max_leaf
            _same(LocalBackend(index).knn(queries, k=1),
                  ooc.knn(queries, k=1))
            # ooc-scan floor: one scan_block per streamed block
            block = CFG.search.scan_block
            ooc = self._ooc_scan(saved, mode,
                                 budget=budget_mb_for_stream_rows(block))
            assert ooc.stream_rows() == block == ooc.base_config.scan_block
            _same(ScanBackend(data, CFG.search).knn(queries),
                  ooc.knn(queries))

    @pytest.mark.parametrize("mode", PREFETCH_MODES)
    def test_non_divisible_budgets(self, data, index, saved_dir, queries,
                                   mode):
        """stream_rows that divide neither scan_block nor max_leaf: ragged
        final pieces everywhere, still bit-identical."""
        with open_index(saved_dir) as saved:
            stream = 3 * saved.max_leaf // 2 + 1    # not a max_leaf multiple
            ooc = self._ooc_local(saved, mode,
                                  budget=budget_mb_for_stream_rows(stream))
            assert ooc.stream_rows() % saved.max_leaf != 0
            _same(LocalBackend(index).knn(queries, k=3),
                  ooc.knn(queries, k=3))

            stream = CFG.search.scan_block + 77     # not a scan_block multiple
            ooc = self._ooc_scan(saved, mode,
                                 budget=budget_mb_for_stream_rows(stream))
            assert ooc.stream_rows() % ooc.base_config.scan_block != 0
            _same(ScanBackend(data, CFG.search).knn(queries), ooc.knn(queries))


# ---------------------------------------------------------------------------
# Bugfix sweep: sax_pr accounting, shared budget arithmetic, auto-shrink
# ---------------------------------------------------------------------------

class TestSaxPrAccounting:
    def test_seeded_rows_counted_as_alive(self, saved_dir, queries):
        """With every leaf seeded in phase 1 there are no phase-3 pieces;
        the old accounting reported sax_pr == 1 (everything 'pruned') even
        though every row was read and refined. Seeded rows count as alive,
        so full coverage now reads as zero pruning."""
        with open_index(saved_dir) as saved:
            cfg = dataclasses.replace(CFG.search, l_max=saved.num_leaves)
            ooc = OutOfCoreLocalBackend(saved, cfg, memory_budget_mb=4.0)
            res = ooc.knn(queries, k=1)
            assert np.allclose(np.asarray(res.sax_pr), 0.0)

    def test_alive_rows_bounded_by_rows_streamed(self, saved_dir, data):
        """Per query, (1 - sax_pr) * N is the number of rows read-and-
        refined on its behalf; the rows actually streamed in the call are
        a superset (runs are unions over the batch plus contiguity fill)."""
        q = make_query_workload(jax.random.PRNGKey(5), data, 1, "5%")
        with open_index(saved_dir) as saved:
            ooc = OutOfCoreLocalBackend(saved, CFG.search,
                                        memory_budget_mb=0.125)
            res = ooc.knn(q, k=1)
            sax_pr = float(np.asarray(res.sax_pr)[0])
            alive = (1.0 - sax_pr) * saved.num_series
            accessed = int(np.asarray(res.accessed)[0])
            assert 0.0 < sax_pr < 1.0
            assert 0 < alive <= accessed + 1e-6
            assert accessed == ooc.stats()["rows_streamed"]


class TestBudgetArithmetic:
    def test_one_code_path_for_stream_rows(self, saved_dir):
        """The classmethod the CLI uses and the instance method the
        backends validate with must be the same arithmetic."""
        with open_index(saved_dir) as saved:
            for budget in (0.125, 0.5, 1.0, 64.0):
                expect = _OutOfCoreBase.budget_stream_rows(budget, LEN)
                scan = OutOfCoreScanBackend(saved, CFG.search,
                                            memory_budget_mb=budget)
                loc = OutOfCoreLocalBackend(saved, CFG.search,
                                            memory_budget_mb=budget)
                assert scan.stream_rows() == loc.stream_rows() == expect

    def test_stats_expose_read_telemetry(self, saved_dir, queries):
        with open_index(saved_dir) as saved:
            ooc = OutOfCoreScanBackend(saved, CFG.search,
                                       memory_budget_mb=0.125)
            ooc.knn(queries)
            st = ooc.stats()
            for key in ("read_seconds", "read_wait_seconds",
                        "overlap_blocks"):
                assert key in st


class TestScanBlockAutoShrink:
    def test_construction_shrinks_and_logs(self, data, saved_dir, queries,
                                           caplog):
        import logging

        mem = ScanBackend(data, CFG.search).knn(queries)
        with open_index(saved_dir) as saved:
            with caplog.at_level(logging.WARNING, "repro.core.engine"):
                ooc = OutOfCoreScanBackend(saved, CFG.search,
                                           memory_budget_mb=0.06)
            assert ooc.base_config.scan_block == ooc.stream_rows()
            assert any("auto-shrinking" in r.message for r in caplog.records)
            _same(mem, ooc.knn(queries))

    def test_entry_points_agree(self, saved_dir, queries):
        """Direct construction and make_disk_backend (the store/CLI path)
        shrink identically and answer identically."""
        with open_index(saved_dir) as saved:
            direct = OutOfCoreScanBackend(saved, CFG.search,
                                          memory_budget_mb=0.06)
            via_factory = make_disk_backend("ooc-scan", saved_dir,
                                            memory_budget_mb=0.06)
            assert (direct.base_config.scan_block
                    == via_factory.base_config.scan_block)
            _same(direct.knn(queries), via_factory.knn(queries))

    def test_explicit_override_still_rejected(self, saved_dir, queries):
        with open_index(saved_dir) as saved:
            ooc = OutOfCoreScanBackend(saved, CFG.search,
                                       memory_budget_mb=0.06)
            with pytest.raises(ValueError, match="memory_budget_mb"):
                ooc.knn(queries, scan_block=4096)


# ---------------------------------------------------------------------------
# Build-path prefetch: chunked builds stay bit-identical across modes
# ---------------------------------------------------------------------------

class TestBuildPrefetch:
    def test_streaming_build_thread_matches_sync(self, data):
        from repro.storage import build_index_streaming

        src = ArrayChunkSource(np.asarray(data), 300)   # ragged chunks
        a = build_index_streaming(src, CFG, prefetch="sync")
        b = build_index_streaming(src, CFG, prefetch="thread")
        for name in a.tree._fields:
            assert np.array_equal(np.asarray(getattr(a.tree, name)),
                                  np.asarray(getattr(b.tree, name))), name
        for name in ("lrd", "lsd", "perm", "leaf_start", "leaf_count"):
            assert np.array_equal(np.asarray(getattr(a.layout, name)),
                                  np.asarray(getattr(b.layout, name))), name
        assert _no_reader_threads()
