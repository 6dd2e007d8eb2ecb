"""The Hercules store facade (repro/storage/store.py): the whole index
lifecycle through one handle.

Covers the PR's acceptance contract:
* append+compact ≡ from-scratch — ``Hercules.open(path, "a").append(B)``
  then ``compact()`` on an index built from A answers bit-identically to a
  from-scratch build over A∥B on ``local``, ``scan``, ``ooc-scan``, and
  ``ooc-local`` (and the tree/layout arrays themselves are bit-identical);
* exact journal-merge queries — with rows pending compaction, ``query``
  still answers bit-identically to the difference-form scan over the whole
  collection;
* crash safety — a kill between journal-segment write and manifest commit
  (or between compaction commit and cleanup) leaves orphans a writable
  reopen sweeps, never a corrupted store; version-1 directories still open;
* random chunkings — appending the collection in arbitrary pieces and
  compacting equals the one-shot build (hypothesis property);
* deterministic resource release — ``close()``/context managers actually
  drop the LRD/LSD memmaps;
* plan-cache invalidation — append/compact invalidate every engine the
  store handed out.
"""
from __future__ import annotations

import dataclasses
import json
import os

import jax
import numpy as np
import pytest

from repro.core.engine import LocalBackend, ScanBackend, make_disk_backend
from repro.core.index import HerculesIndex, IndexConfig
from repro.core.search import SearchConfig
from repro.core.tree import BuildConfig
from repro.data.pipeline import ArrayChunkSource
from repro.data.synthetic import make_query_workload, random_walks
from repro.storage import (Hercules, IndexFormatError, load_index,
                           open_index, save_index)
from repro.storage.format import (FORMAT_VERSION, JOURNAL_DIR,
                                  MANIFEST_FILE)

from tests._hypothesis_compat import given, settings, st

NUM_A, NUM_B, LEN = 2048, 1024, 64
CFG = IndexConfig(
    build=BuildConfig(leaf_capacity=64),
    search=SearchConfig(k=3, l_max=4, chunk=256, scan_block=512))
BUDGET_MB = 0.25   # collection is several x the ooc streaming budget


@pytest.fixture(scope="module")
def data_a():
    return np.asarray(random_walks(jax.random.PRNGKey(0), NUM_A, LEN))


@pytest.fixture(scope="module")
def data_b():
    return np.asarray(random_walks(jax.random.PRNGKey(5), NUM_B, LEN))


@pytest.fixture(scope="module")
def data_ab(data_a, data_b):
    return np.concatenate([data_a, data_b])


@pytest.fixture(scope="module")
def queries(data_ab):
    return np.asarray(make_query_workload(
        jax.random.PRNGKey(1), data_ab, 5, "5%"))


@pytest.fixture(scope="module")
def scratch_index(data_ab):
    """From-scratch one-shot build over A∥B — the acceptance oracle."""
    return HerculesIndex.build(data_ab, CFG)


@pytest.fixture(scope="module")
def compacted_dir(data_a, data_b, tmp_path_factory):
    """create(A) → reopen → append(B) → compact, in distinct handles (the
    reopen makes this the cross-handle path the acceptance criterion names)."""
    path = str(tmp_path_factory.mktemp("store") / "idx")
    with Hercules.create(path, CFG, data=data_a, chunk_size=700):
        pass
    with Hercules.open(path, "a") as hx:
        hx.append(data_b, chunk_size=500)
        hx.compact(chunk_size=900)
    return path


def _same(a, b, positions=True):
    assert np.array_equal(np.asarray(a.dists), np.asarray(b.dists))
    assert np.array_equal(np.asarray(a.ids), np.asarray(b.ids))
    if positions:
        assert np.array_equal(np.asarray(a.positions), np.asarray(b.positions))


class TestAppendCompactParity:
    """Acceptance oracle: append+compact ≡ from-scratch build over A∥B."""

    def test_tree_and_layout_bit_identical(self, compacted_dir, scratch_index):
        with Hercules.open(compacted_dir) as hx:
            loaded = hx.index()
        for name in scratch_index.tree._fields:
            assert np.array_equal(
                np.asarray(getattr(scratch_index.tree, name)),
                np.asarray(getattr(loaded.tree, name))), name
        for f in dataclasses.fields(scratch_index.layout):
            a = getattr(scratch_index.layout, f.name)
            b = getattr(loaded.layout, f.name)
            if isinstance(a, int):
                assert a == b, f.name
            else:
                assert np.array_equal(np.asarray(a), np.asarray(b)), f.name

    @pytest.mark.parametrize("backend", ["local", "scan", "ooc-scan",
                                         "ooc-local"])
    def test_backend_parity(self, compacted_dir, scratch_index, data_ab,
                            queries, backend):
        if backend == "local":
            mem = LocalBackend(scratch_index)
        else:
            mem = ScanBackend(data_ab, CFG.search)
        with Hercules.open(compacted_dir) as hx:
            res = hx.engine(backend, memory_budget_mb=BUDGET_MB).knn(
                queries, k=3)
            ref = mem.knn(queries, k=3)
            _same(res, ref, positions=backend in ("local",))

    def test_query_routes_through_engine(self, compacted_dir, scratch_index,
                                         queries):
        with Hercules.open(compacted_dir) as hx:
            _same(hx.query(queries, k=3),
                  LocalBackend(scratch_index).knn(queries, k=3))

    def test_multi_append_equals_single(self, data_a, data_b, data_ab,
                                        tmp_path):
        """Two appends in different chunkings compact to the same bytes."""
        path = str(tmp_path / "idx")
        with Hercules.create(path, CFG, data=data_a) as hx:
            hx.append(data_b[:300], chunk_size=128)
            hx.append(data_b[300:], chunk_size=999)
            assert len(hx.journal["segments"]) == 2
            hx.compact()
            oneshot = HerculesIndex.build(data_ab, CFG)
            assert np.array_equal(np.asarray(oneshot.layout.lrd),
                                  np.asarray(hx.saved._mapped("lrd")))


class TestJournalQueries:
    """Exactness with rows pending compaction (no rebuild needed)."""

    def test_journal_merge_matches_scan(self, data_a, data_b, data_ab,
                                        queries, tmp_path):
        path = str(tmp_path / "idx")
        with Hercules.create(path, CFG, data=data_a) as hx:
            hx.append(data_b)
            res = hx.query(queries, k=3)
            ref = ScanBackend(data_ab, CFG.search).knn(queries, k=3)
            assert np.array_equal(np.asarray(res.dists), np.asarray(ref.dists))
            assert np.array_equal(np.asarray(res.ids), np.asarray(ref.ids))
            # journal rows have no layout position yet
            journal_hits = np.asarray(res.ids) >= NUM_A
            assert journal_hits.any()
            assert (np.asarray(res.positions)[journal_hits] == -1).all()

    def test_empty_store_journal_only(self, data_ab, queries, tmp_path):
        path = str(tmp_path / "idx")
        with Hercules.create(path, CFG) as hx:
            assert hx.saved is None and hx.num_series == 0
            with pytest.raises(IndexFormatError, match="empty"):
                hx.query(queries, k=3)
            hx.append(data_ab[:NUM_A])
            hx.append(data_ab[NUM_A:])
            res = hx.query(queries, k=3)
            ref = ScanBackend(data_ab, CFG.search).knn(queries, k=3)
            assert np.array_equal(np.asarray(res.dists), np.asarray(ref.dists))
            assert np.array_equal(np.asarray(res.ids), np.asarray(ref.ids))
            # engine() needs a base; query() does not
            with pytest.raises(IndexFormatError, match="base"):
                hx.engine("local")
            hx.compact()
            res2 = hx.engine("local").knn(queries, k=3)
            assert np.array_equal(np.asarray(res2.dists),
                                  np.asarray(ref.dists))

    def test_index_refuses_pending_rows(self, data_a, data_b, tmp_path):
        path = str(tmp_path / "idx")
        with Hercules.create(path, CFG, data=data_a) as hx:
            hx.append(data_b)
            with pytest.raises(IndexFormatError, match="pending"):
                hx.index()


class TestAppendValidation:
    def test_mode_r_rejects_mutation(self, compacted_dir, data_b):
        with Hercules.open(compacted_dir) as hx:
            with pytest.raises(IndexFormatError, match="read-only"):
                hx.append(data_b)
            with pytest.raises(IndexFormatError, match="read-only"):
                hx.compact()

    def test_series_len_mismatch(self, data_a, tmp_path):
        path = str(tmp_path / "idx")
        with Hercules.create(path, CFG, data=data_a) as hx:
            with pytest.raises(ValueError, match="series length"):
                hx.append(np.zeros((4, LEN * 2), np.float32))

    def test_empty_append(self, data_a, tmp_path):
        path = str(tmp_path / "idx")
        with Hercules.create(path, CFG, data=data_a) as hx:
            with pytest.raises(ValueError, match="at least one row"):
                hx.append(np.zeros((0, LEN), np.float32))

    def test_create_refuses_existing(self, compacted_dir, data_a):
        with pytest.raises(IndexFormatError, match="already"):
            Hercules.create(compacted_dir, CFG, data=data_a)

    def test_compact_without_journal_is_noop(self, data_a, tmp_path):
        path = str(tmp_path / "idx")
        with Hercules.create(path, CFG, data=data_a) as hx:
            gen = hx.generation
            hx.compact()
            assert hx.generation == gen


class TestCrashSafety:
    def _store(self, data_a, tmp_path) -> str:
        path = str(tmp_path / "idx")
        Hercules.create(path, CFG, data=data_a).close()
        return path

    def test_segment_without_commit_is_swept(self, data_a, data_b, tmp_path,
                                             queries):
        """Kill between journal-segment write and manifest commit: the
        segment files exist but the manifest never named them — reopen
        recovers cleanly and serves the committed state."""
        path = self._store(data_a, tmp_path)
        os.makedirs(os.path.join(path, JOURNAL_DIR), exist_ok=True)
        np.save(os.path.join(path, JOURNAL_DIR, "seg-00000.lrd.npy"), data_b)
        np.save(os.path.join(path, JOURNAL_DIR, "seg-00000.lsd.npy"),
                np.zeros((NUM_B, 16), np.uint8))
        with Hercules.open(path, "a") as hx:
            assert sorted(hx.recovered) == [
                f"{JOURNAL_DIR}/seg-00000.lrd.npy",
                f"{JOURNAL_DIR}/seg-00000.lsd.npy"]
            assert hx.pending_rows == 0
            assert hx.num_series == NUM_A
            hx.query(queries, k=1)      # serves the committed state
            # the swept name is reusable: append lands a fresh segment 0
            seg = hx.append(data_b)
            assert seg["name"] == "seg-00000"
            assert hx.pending_rows == NUM_B

    def test_readonly_open_does_not_sweep(self, data_a, tmp_path):
        path = self._store(data_a, tmp_path)
        orphan = os.path.join(path, JOURNAL_DIR, "seg-00000.lrd.npy")
        os.makedirs(os.path.dirname(orphan), exist_ok=True)
        np.save(orphan, np.zeros((2, LEN), np.float32))
        with Hercules.open(path) as hx:
            assert hx.recovered == []
        assert os.path.exists(orphan)

    def test_interrupted_compaction_cleanup(self, data_a, data_b, tmp_path):
        """Kill after the compaction's manifest commit but before the old
        generation + journal were deleted: reopen sweeps the leftovers."""
        path = self._store(data_a, tmp_path)
        with Hercules.open(path, "a") as hx:
            hx.append(data_b)
            hx.compact()
            assert hx.generation == 1
        # resurrect plausible pre-compact leftovers
        np.save(os.path.join(path, "lrd.npy"), np.zeros((4, LEN), np.float32))
        os.makedirs(os.path.join(path, JOURNAL_DIR), exist_ok=True)
        np.save(os.path.join(path, JOURNAL_DIR, "seg-00000.lrd.npy"), data_b)
        with Hercules.open(path, "a") as hx:
            assert "lrd.npy" in hx.recovered
            assert f"{JOURNAL_DIR}/seg-00000.lrd.npy" in hx.recovered
            assert hx.num_series == NUM_A + NUM_B

    def test_journal_segment_corruption_detected(self, data_a, data_b,
                                                 tmp_path):
        path = self._store(data_a, tmp_path)
        with Hercules.open(path, "a") as hx:
            hx.append(data_b)
        seg = os.path.join(path, JOURNAL_DIR, "seg-00000.lrd.npy")
        size = os.path.getsize(seg)
        with open(seg, "r+b") as f:
            f.seek(size // 2)
            byte = f.read(1)
            f.seek(size // 2)
            f.write(bytes([byte[0] ^ 0xFF]))
        with pytest.raises(IndexFormatError, match="checksum|corrupted"):
            Hercules.open(path, "a")

    def test_v1_directory_still_opens(self, data_a, tmp_path, queries):
        """A pre-journal (version 1) manifest opens, serves, and migrates
        to the current format version on its first append."""
        path = str(tmp_path / "idx")
        save_index(HerculesIndex.build(data_a, CFG), path)
        mf = os.path.join(path, MANIFEST_FILE)
        manifest = json.load(open(mf))
        for key in ("journal", "generation"):
            manifest.pop(key, None)
        manifest["version"] = 1
        json.dump(manifest, open(mf, "w"))
        assert load_index(path).layout.num_series == NUM_A
        with Hercules.open(path, "a") as hx:
            assert hx.generation == 0 and hx.pending_rows == 0
            hx.query(queries, k=1)
            hx.append(data_a[:16])
        assert json.load(open(mf))["version"] == FORMAT_VERSION


class TestResourceRelease:
    def test_saved_index_close_releases_memmaps(self, compacted_dir):
        saved = open_index(compacted_dir)
        mm = saved.lrd._mmap
        saved.close()
        assert saved.closed and saved.lrd is None and saved.lsd is None
        assert mm.closed
        saved.close()                    # idempotent
        with pytest.raises(IndexFormatError, match="closed"):
            saved.original_data()

    def test_saved_index_context_manager(self, compacted_dir):
        with open_index(compacted_dir) as saved:
            assert saved.num_series == NUM_A + NUM_B
        assert saved.closed

    def test_store_close_is_loud_for_stale_backends(self, compacted_dir,
                                                    queries):
        hx = Hercules.open(compacted_dir)
        backend = make_disk_backend("ooc-scan", hx,
                                    memory_budget_mb=BUDGET_MB)
        hx.close()
        with pytest.raises(IndexFormatError, match="closed"):
            backend.knn(queries, k=1)
        with pytest.raises(IndexFormatError, match="closed"):
            hx.query(queries, k=1)

    def test_compact_closes_previous_generation(self, data_a, data_b,
                                                tmp_path):
        path = str(tmp_path / "idx")
        with Hercules.create(path, CFG, data=data_a) as hx:
            old = hx.saved
            hx.append(data_b)
            hx.compact()
            assert old.closed and not hx.saved.closed


class TestPlanInvalidation:
    def test_append_and_compact_invalidate_engines(self, data_a, data_b,
                                                   queries, tmp_path):
        path = str(tmp_path / "idx")
        with Hercules.create(path, CFG, data=data_a) as hx:
            eng = hx.engine("local")
            eng.knn(queries, k=1)
            assert eng.telemetry()["plan_cache"]["size"] == 1
            v0 = hx.data_version

            hx.append(data_b)
            assert hx.data_version == v0 + 1
            tele = eng.telemetry()["plan_cache"]
            assert tele["invalidations"] == 1 and tele["size"] == 0
            # the store hands out a *fresh* engine after the mutation
            assert hx.engine("local") is not eng

            eng2 = hx.engine("local")
            hx.compact()
            assert eng2.telemetry()["plan_cache"]["invalidations"] == 1
            # post-compact engine serves the appended rows
            res = hx.engine("local").knn(queries, k=3)
            ref = LocalBackend(HerculesIndex.build(
                np.concatenate([data_a, data_b]), CFG)).knn(queries, k=3)
            assert np.array_equal(np.asarray(res.dists), np.asarray(ref.dists))

    def test_engine_cache_reuse(self, compacted_dir):
        with Hercules.open(compacted_dir) as hx:
            assert hx.engine("local") is hx.engine("local")
            assert hx.engine("local") is not hx.engine("scan")

    def test_make_disk_backend_accepts_handle_and_saved(self, compacted_dir,
                                                        queries):
        with Hercules.open(compacted_dir) as hx:
            via_handle = make_disk_backend("local", hx)
            via_saved = make_disk_backend("local", hx.saved)
            via_path = make_disk_backend("local", compacted_dir)
            r1 = via_handle.knn(queries, k=1)
            _same(via_saved.knn(queries, k=1), r1)
            _same(via_path.knn(queries, k=1), r1)


class TestOocSaxStreaming:
    """Satellite: streamed LSD phase-3 pruning for ooc-local."""

    def test_sax_filter_cuts_reads_and_stays_exact(self, compacted_dir,
                                                   scratch_index, queries):
        with Hercules.open(compacted_dir) as hx:
            with_sax = hx.engine("ooc-local", memory_budget_mb=BUDGET_MB)
            res = with_sax.knn(queries, k=3)
            ref = LocalBackend(scratch_index).knn(queries, k=3)
            assert np.array_equal(np.asarray(res.dists), np.asarray(ref.dists))
            assert np.array_equal(np.asarray(res.ids), np.asarray(ref.ids))
            st_sax = with_sax.backend.stats()
            assert st_sax["sax_rows_read"] > 0
            assert np.all(np.asarray(res.sax_pr) >= 0)

            no_sax = hx.engine(
                "ooc-local",
                search=dataclasses.replace(CFG.search, use_sax=False),
                memory_budget_mb=BUDGET_MB)
            res2 = no_sax.knn(queries, k=3)
            assert np.array_equal(np.asarray(res2.dists),
                                  np.asarray(ref.dists))
            st_no = no_sax.backend.stats()
            assert st_no["sax_rows_read"] == 0
            # the per-series filter must fetch no more rows than
            # leaf-granularity pruning alone
            assert st_sax["rows_streamed"] <= st_no["rows_streamed"]


    @pytest.mark.parametrize("k", [1, 3])
    def test_repeated_wave_lowers_nothing(self, compacted_dir, queries, k):
        """Every program of the streamed search is cached by shape: a wave
        served again lowers nothing (phase 1's LB_EAPCA map and tree
        descent, phase 2's leaf test and phase 3's per-piece filter once
        lowered again on every call or compiled one program per op)."""
        lowered = []

        def on(event, secs, **_):
            if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
                lowered.append(event)

        with Hercules.open(compacted_dir) as hx:
            eng = hx.engine("ooc-local", memory_budget_mb=BUDGET_MB)
            first = eng.knn(queries, k=k)
            jax.monitoring.register_event_duration_secs_listener(on)
            try:
                again = eng.knn(queries, k=k)
            finally:
                jax.monitoring.unregister_event_duration_listener(on)
            _same(first, again)
            assert eng.backend.stats()["sax_rows_read"] > 0
            assert lowered == []

class TestPackedStream:
    """ooc-local's raw stream reads many leaf extents or alive runs into one
    budget-sized block: one read, one stage and one refine a block."""

    @pytest.fixture(scope="class")
    def hard_queries(self, data_ab):
        # 10% noise: k = 1 and k = 10 both leave pieces with several
        # alive runs
        return np.asarray(make_query_workload(
            jax.random.PRNGKey(1), data_ab, 8, "10%"))

    @pytest.mark.parametrize("prefetch", ["sync", "thread"])
    @pytest.mark.parametrize("k", [1, 10])
    def test_packed_blocks_answer_as_extents(self, compacted_dir,
                                             scratch_index, data_ab,
                                             hard_queries, monkeypatch, k,
                                             prefetch):
        import repro.core.engine as eng_mod

        seeds, pieces = [], []
        alive_runs = eng_mod._alive_runs
        seed_candidates = eng_mod.OutOfCoreLocalBackend._seed_candidates

        def spy_runs(alive, base):
            pieces.append(alive_runs(alive, base))
            return pieces[-1]

        def spy_seed(self, q, cfg):
            seeds.append(seed_candidates(self, q, cfg))
            return seeds[-1]

        monkeypatch.setattr(eng_mod, "_alive_runs", spy_runs)
        monkeypatch.setattr(eng_mod.OutOfCoreLocalBackend,
                            "_seed_candidates", spy_seed)
        with Hercules.open(compacted_dir) as hx:
            # stream_rows() = 512: eight full leaves a block
            eng = hx.engine("ooc-local", memory_budget_mb=BUDGET_MB,
                            prefetch=prefetch)
            backend = eng.backend
            R = backend.stream_rows()
            counts = np.asarray(backend._leaf_count)
            st0 = backend.stats()
            res = eng.knn(hard_queries, k=k)
            st1 = backend.stats()
            # the parent schedule, one fetch an extent: same answers and
            # rows, as many fetches as the packed blocks held extents
            with monkeypatch.context() as m:
                m.setattr(eng_mod, "_pack_extents",
                          lambda extents, cap: [[e] for e in extents])
                one = eng.knn(hard_queries, k=k)
            st2 = backend.stats()

        packed = {key: st1[key] - st0[key]
                  for key in ("blocks", "extents", "rows_streamed")}
        single = {key: st2[key] - st1[key]
                  for key in ("blocks", "extents", "rows_streamed")}
        _same(res, LocalBackend(scratch_index).knn(hard_queries, k=k))
        _same(res, ScanBackend(data_ab, CFG.search).knn(hard_queries, k=k),
              positions=False)
        for f in ("dists", "positions", "ids", "path", "eapca_pr", "sax_pr",
                  "visited_leaves", "accessed"):
            assert np.array_equal(np.asarray(getattr(res, f)),
                                  np.asarray(getattr(one, f))), f

        _, home, best = seeds[0]
        seeded = (set(int(r) for r in home if r >= 0)
                  | set(int(r) for r in best.ravel()))
        seed_rows = sum(int(counts[r]) for r in seeded)
        n_seeds = sum(int(counts[r] > 0) for r in seeded)
        alive = pieces[:len(pieces) // 2]          # the packed call's
        alive_rows = sum(c for runs in alive for _, c in runs)
        assert seed_rows > R                       # several phase-1 blocks
        assert max(len(runs) for runs in alive) > 1
        assert packed["rows_streamed"] == seed_rows + alive_rows
        assert packed["rows_streamed"] == single["rows_streamed"]
        assert packed["extents"] == n_seeds + sum(len(r) for r in alive)
        assert packed["extents"] == single["blocks"] == single["extents"]
        assert packed["blocks"] <= (-(-seed_rows // R)
                                    + sum(1 for runs in alive if runs))
        assert packed["extents"] / packed["blocks"] > 1


class TestRandomChunkings:
    @settings(max_examples=5, deadline=None)
    @given(st.data())
    def test_append_any_chunking_equals_oneshot(self, tmp_path_factory, data):
        """Property: appending the collection in arbitrary pieces (random
        split points, random per-append chunk sizes) and compacting equals
        the one-shot build bit-for-bit."""
        num, n = 384, 32
        cfg = IndexConfig(
            build=BuildConfig(leaf_capacity=48),
            search=SearchConfig(k=1, l_max=2, chunk=64, scan_block=64))
        rows = np.asarray(random_walks(jax.random.PRNGKey(7), num, n))
        n_cuts = data.draw(st.integers(0, 3), label="n_cuts")
        cuts = sorted(data.draw(
            st.lists(st.integers(1, num - 1), min_size=n_cuts,
                     max_size=n_cuts, unique=True), label="cuts"))
        pieces = np.split(rows, cuts)
        first_chunk = data.draw(st.integers(32, 512), label="first_chunk")

        path = str(tmp_path_factory.mktemp("prop") / "idx")
        with Hercules.create(path, cfg,
                             data=ArrayChunkSource(pieces[0], first_chunk)) \
                as hx:
            for piece in pieces[1:]:
                hx.append(piece, chunk_size=data.draw(
                    st.integers(16, 512), label="chunk"))
            hx.compact(chunk_size=data.draw(st.integers(32, 512),
                                            label="compact_chunk"))
            oneshot = HerculesIndex.build(rows, cfg)
            for name in oneshot.tree._fields:
                assert np.array_equal(
                    np.asarray(getattr(oneshot.tree, name)),
                    np.asarray(getattr(hx.saved.tree, name))), name
            assert np.array_equal(np.asarray(oneshot.layout.lrd),
                                  np.asarray(hx.saved._mapped("lrd")))
            assert np.array_equal(np.asarray(oneshot.layout.lsd),
                                  np.asarray(hx.saved._mapped("lsd")))
