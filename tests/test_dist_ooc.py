"""Sharded out-of-core serving (dist-ooc): shard plans, per-shard range
views, and bit-identical parity with the single-host backends.

Layout mirrors the environment the backend runs in:

* plan/view/unit tests and single-shard parity run everywhere (1 CPU
  device — conftest keeps the real device world);
* the full mesh matrix (shards {1,2,4,8} x codecs x prefetch x wave x
  journal, tie determinism, residency confinement) runs **in-process**
  when 8+ devices are visible — the CI `distributed` job forces them via
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8``;
* a lean subprocess leg covers multi-shard on a plain 1-device machine
  (marked slow, skipped when the in-process matrix already ran).
"""
import os
import subprocess
import sys
import textwrap
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro import api
from repro.distributed.ooc import DistOutOfCoreBackend, _ShardRows
from repro.storage.partition import (BALANCE_WARN_RATIO, ShardPlan,
                                     partition_plan, partition_section,
                                     shard_plan)

jax.config.update("jax_platform_name", "cpu")

NUM_DEVICES = len(jax.devices())
MESH_IN_PROCESS = NUM_DEVICES >= 8


def _assert_same(ref, res, *, positions: bool = True):
    assert np.array_equal(np.asarray(ref.dists), np.asarray(res.dists))
    assert np.array_equal(np.asarray(ref.ids), np.asarray(res.ids))
    if positions:
        assert np.array_equal(np.asarray(ref.positions),
                              np.asarray(res.positions))


@pytest.fixture(scope="module")
def rng():
    """Module-local generator shadowing the session ``rng``: this file's
    module-scoped stores must not consume draws from the shared stream
    (later test modules' data would shift with this file's edits)."""
    return np.random.default_rng(9219)


# ---------------------------------------------------------------------------
# shard plans (storage/partition.py)
# ---------------------------------------------------------------------------

class TestPartitionPlan:
    def _uniform(self, leaves: int, rows_per_leaf: int):
        counts = np.full(leaves, rows_per_leaf, np.int64)
        starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
        return starts, counts

    @pytest.mark.parametrize("shards", [1, 2, 4, 8])
    def test_balanced_cut(self, shards):
        starts, counts = self._uniform(16, 100)
        plan = partition_plan(starts, counts, shards)
        assert plan.num_shards == shards
        assert plan.leaf_bounds[0] == 0 and plan.leaf_bounds[-1] == 16
        assert plan.row_bounds[0] == 0 and plan.row_bounds[-1] == 1600
        assert sum(plan.shard_rows) == 1600
        assert plan.balanced and plan.imbalance == 1.0
        # contiguity: shard i's rows are exactly [row_bounds[i], [i+1])
        for s in range(shards):
            lo, hi = plan.row_range(s)
            llo, lhi = plan.leaf_range(s)
            assert lo == starts[llo]
            assert hi == (starts[lhi] if lhi < 16 else 1600)

    def test_every_shard_gets_a_leaf_under_skew(self):
        # one huge head leaf: quantile cuts would all land after it; the
        # clamp still hands every trailing shard at least one leaf
        counts = np.array([10_000, 5, 5, 5], np.int64)
        starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            plan = partition_plan(starts, counts, 4)
        assert all(plan.leaf_bounds[i] < plan.leaf_bounds[i + 1]
                   for i in range(4))

    def test_skewed_tree_warns_and_flags(self):
        counts = np.array([10_000, 5, 5, 5], np.int64)
        starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
        with pytest.warns(RuntimeWarning, match="imbalanced"):
            plan = partition_plan(starts, counts, 2)
        assert not plan.balanced
        assert plan.imbalance > BALANCE_WARN_RATIO
        # warn=False (what partition_section uses at commit time) is silent
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            partition_plan(starts, counts, 2, warn=False)

    def test_more_shards_than_leaves(self):
        starts, counts = self._uniform(3, 50)
        with pytest.warns(RuntimeWarning):
            plan = partition_plan(starts, counts, 8)
        assert plan.imbalance == float("inf")
        assert sum(plan.shard_rows) == 150
        # trailing shards are empty, never negative
        assert all(r >= 0 for r in plan.shard_rows)

    def test_section_roundtrip_matches_direct_plan(self):
        starts, counts = self._uniform(10, 37)
        section = partition_section(starts, counts)
        assert section["balanced_by"] == "rows"
        for n_str, entry in section["plans"].items():
            n = int(n_str)
            assert ShardPlan.from_manifest(n, entry) == partition_plan(
                starts, counts, n, warn=False)

    def test_validation(self):
        with pytest.raises(ValueError):
            ShardPlan(num_shards=2, leaf_bounds=(0, 1), row_bounds=(0, 5, 9))
        with pytest.raises(ValueError):
            ShardPlan(num_shards=2, leaf_bounds=(0, 2, 1),
                      row_bounds=(0, 5, 9))
        with pytest.raises(ValueError):
            partition_plan([0], [5], 0)


# ---------------------------------------------------------------------------
# per-shard range views
# ---------------------------------------------------------------------------

class TestShardRows:
    def _rows(self, lo=10, hi=20):
        base = np.arange(100, dtype=np.float32).reshape(50, 2)
        audit = [hi, lo]
        return _ShardRows(base, lo, hi, audit), base, audit

    def test_slice_translates_and_audits(self):
        view, base, audit = self._rows()
        np.testing.assert_array_equal(view[2:5], base[12:15])
        assert view.shape == (10, 2) and len(view) == 10
        assert audit == [12, 15]
        np.testing.assert_array_equal(view[0:10], base[10:20])
        assert audit == [10, 20]

    def test_escape_raises(self):
        view, _, _ = self._rows()
        with pytest.raises(IndexError, match="escape"):
            view.take(np.array([11]))
        with pytest.raises(IndexError, match="contiguous"):
            view[0:10:2]
        with pytest.raises(TypeError):
            view[3]

    def test_take_copies_and_stays_local(self):
        view, base, audit = self._rows()
        out = view.take(np.array([0, 9, 3]))
        np.testing.assert_array_equal(out, base[[10, 19, 13]])
        out[0, 0] = -1.0           # a copy: the base must not see this
        assert base[10, 0] != -1.0
        assert audit == [10, 20]


# ---------------------------------------------------------------------------
# serving fixtures
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def dist_store(tmp_path_factory, rng):
    data = rng.standard_normal((500, 48)).astype(np.float32)
    path = str(tmp_path_factory.mktemp("dist") / "idx")
    with api.Hercules.create(path, api.IndexConfig(), data=data) as hx:
        yield hx, data


@pytest.fixture(scope="module")
def dup_store(tmp_path_factory, rng):
    """Rows duplicated 5x: every distance appears five times, so any top-k
    is wall-to-wall ties. Duplicates share iSAX/EAPCA summaries, so they
    land in one leaf at adjacent file positions — the tie order every
    exact path must reproduce."""
    base = rng.standard_normal((80, 32)).astype(np.float32)
    data = np.repeat(base, 5, axis=0)
    path = str(tmp_path_factory.mktemp("dist_dup") / "idx")
    with api.Hercules.create(path, api.IndexConfig(), data=data) as hx:
        yield hx, base, data


class TestDistOocSingleShard:
    def test_registry_and_api_exports(self):
        assert "dist-ooc" in api.BACKENDS
        assert "dist-ooc" in api.backend_names("disk")
        assert "dist-ooc" not in api.backend_names("memory")
        assert api.DistTelemetry is not None
        assert api.ShardPlan is ShardPlan

    def test_unknown_backend_error_lists_registry(self, dist_store):
        hx, _ = dist_store
        with pytest.raises(ValueError, match="dist-ooc"):
            hx.engine("no-such-backend")
        with pytest.raises(ValueError, match="ooc-local"):
            api.make_disk_backend("no-such-backend", hx)

    def test_budget_keys_streaming_backends_only(self, dist_store):
        hx, _ = dist_store
        assert hx.engine("local", memory_budget_mb=32.0) is \
            hx.engine("local", memory_budget_mb=64.0)
        assert hx.engine("dist-ooc", shards=1, memory_budget_mb=4.0) is not \
            hx.engine("dist-ooc", shards=1, memory_budget_mb=8.0)

    @pytest.mark.parametrize("prefetch", ["sync", "thread"])
    @pytest.mark.parametrize("wave", [False, True])
    def test_parity_one_shard(self, dist_store, rng, prefetch, wave):
        hx, data = dist_store
        q = rng.standard_normal((6, 48)).astype(np.float32)
        ref = hx.engine("local").knn(q, k=5, wave=wave)
        eng = hx.engine("dist-ooc", shards=1, memory_budget_mb=8,
                        prefetch=prefetch)
        _assert_same(ref, eng.knn(q, k=5, wave=wave))

    def test_telemetry_dist_section(self, dist_store, rng):
        hx, data = dist_store
        q = rng.standard_normal((4, 48)).astype(np.float32)
        eng = hx.engine("dist-ooc", shards=1, memory_budget_mb=8)
        eng.knn(q, k=3)
        t = eng.telemetry()
        assert "dist" in t and "ooc" in t
        d = t.dist
        assert d.shards == 1
        assert len(d.rows_streamed) == 1 and d.rows_streamed[0] > 0
        assert len(d.read_wait_seconds) == 1
        assert d.imbalance == 1.0 and d.plan_imbalance == 1.0
        assert not d.balance_warning
        (lo, hi), (tlo, thi) = d.row_range[0], d.rows_touched[0]
        assert lo <= tlo and thi <= hi
        # streamed counters also aggregate into the regular ooc section
        assert t.ooc.rows_streamed == d.rows_streamed[0]

    def test_journal_rows_merge(self, dist_store, rng, tmp_path):
        hx, data = dist_store
        # a fresh store: the module fixture must stay journal-free
        q = rng.standard_normal((4, 48)).astype(np.float32)
        extra = rng.standard_normal((30, 48)).astype(np.float32)
        extra[:4] = q  # each query's 1-NN is a journal row (distance 0)
        path = str(tmp_path / "idx")
        with api.Hercules.create(path, api.IndexConfig(),
                                 data=data) as hx2:
            hx2.append(extra)
            ref = hx2.query(q, k=5, backend="local")
            res = hx2.query(q, k=5, backend="dist-ooc", shards=1,
                            memory_budget_mb=8)
            _assert_same(ref, res)
            # journal ids continue past the base collection
            assert np.asarray(ref.ids).max() >= data.shape[0]

    def test_plan_signature_in_cache_key(self, dist_store):
        hx, _ = dist_store
        be = hx.engine("dist-ooc", shards=1).backend
        assert be.plan_signature[0] == "dist-ooc"
        assert be.plan_signature[1] == 1
        # single-host streaming backends carry no signature: their plans
        # cache under the plain (cfg, bucket, ...) key as before
        assert getattr(hx.engine("ooc-local").backend,
                       "plan_signature", None) is None

    def test_shards_beyond_devices_error_names_recipe(self, dist_store):
        hx, _ = dist_store
        with pytest.raises(ValueError,
                           match="xla_force_host_platform_device_count"):
            DistOutOfCoreBackend(hx.saved, shards=NUM_DEVICES + 1)

    def test_codec_mesh_parity_one_shard(self, rng, tmp_path):
        data = rng.standard_normal((400, 32)).astype(np.float32)
        q = rng.standard_normal((3, 32)).astype(np.float32)
        path = str(tmp_path / "idx")
        with api.Hercules.create(path, api.IndexConfig(), data=data,
                                 codec="bf16") as hx:
            ref = hx.engine("local").knn(q, k=4)
            for wave in (False, True):
                res = hx.engine("dist-ooc", shards=1,
                                memory_budget_mb=8).knn(q, k=4, wave=wave)
                _assert_same(ref, res)


class TestShardPlanOnSavedIndex:
    def test_manifest_records_and_derivation_agrees(self, dist_store):
        hx, _ = dist_store
        saved = hx.saved
        section = saved.manifest.get("partition")
        assert section is not None
        assert set(section["plans"]) == {"2", "4", "8"}
        for n in (2, 3, 4, 8):   # 3 is not recorded: derived on demand
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", RuntimeWarning)
                recorded = shard_plan(saved, n)
                derived = partition_plan(saved.small["leaf_start"],
                                         saved.small["leaf_count"], n,
                                         warn=False)
            assert recorded == derived

    def test_old_manifest_without_section_derives(self, dist_store, rng):
        hx, _ = dist_store
        saved = hx.saved
        stripped = {k: v for k, v in saved.manifest.items()
                    if k != "partition"}
        import dataclasses as dc
        old = dc.replace(saved, manifest=stripped)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            assert shard_plan(old, 4) == shard_plan(saved, 4)


# ---------------------------------------------------------------------------
# top-k tie determinism (satellite: duplicated rows across shards)
# ---------------------------------------------------------------------------

class TestTieDeterminism:
    def test_duplicated_rows_same_ids_as_local(self, dup_store, rng):
        hx, base, data = dup_store
        # query at a tiny offset from real rows: the 5 duplicates of the
        # nearest row are exact distance ties filling the whole top-5
        q = (base[:4] + 1e-3 * rng.standard_normal((4, 32))
             ).astype(np.float32)
        ref = hx.engine("local").knn(q, k=10)
        res = hx.engine("dist-ooc", shards=1, memory_budget_mb=8).knn(
            q, k=10)
        # the ties are real: duplicate groups produce repeated distances
        dref = np.asarray(ref.dists)
        assert any((dref[i, :-1] == dref[i, 1:]).any()
                   for i in range(dref.shape[0]))
        _assert_same(ref, res)

    @settings(max_examples=10, deadline=None)
    @given(row=st.integers(min_value=0, max_value=79),
           scale=st.floats(min_value=1e-4, max_value=1e-2))
    def test_property_tie_merge_matches_local(self, dup_store, row, scale):
        hx, base, data = dup_store
        q = (base[row:row + 1] + np.float32(scale)).astype(np.float32)
        ref = hx.engine("local").knn(q, k=10)
        res = hx.engine("dist-ooc", shards=1, memory_budget_mb=8).knn(
            q, k=10)
        _assert_same(ref, res)


# ---------------------------------------------------------------------------
# the full mesh matrix — in-process when the CI distributed job forces
# 8 host devices, else via one lean subprocess leg
# ---------------------------------------------------------------------------

_MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import warnings; warnings.simplefilter("ignore", RuntimeWarning)
    import tempfile
    import numpy as np
    from repro import api

    rng = np.random.default_rng(7)
    data = rng.standard_normal((600, 32)).astype(np.float32)
    extra = rng.standard_normal((40, 32)).astype(np.float32)
    q = rng.standard_normal((4, 32)).astype(np.float32)
    base = rng.standard_normal((60, 32)).astype(np.float32)
    dup = np.repeat(base, 5, axis=0)
    qt = (base[:3] + 1e-3).astype(np.float32)

    def same(a, b):
        assert np.array_equal(np.asarray(a.dists), np.asarray(b.dists))
        assert np.array_equal(np.asarray(a.positions),
                              np.asarray(b.positions))
        assert np.array_equal(np.asarray(a.ids), np.asarray(b.ids))

    with tempfile.TemporaryDirectory() as d:
        for codec in ("raw", "bf16"):
            with api.Hercules.create(d + "/i-" + codec, api.IndexConfig(),
                                     data=data, codec=codec) as hx:
                hx.append(extra)        # journal rows merge on every path
                ref = hx.query(q, k=5, backend="local")
                for shards in (2, 4, 8):
                    for prefetch in ("sync", "thread"):
                        for wave in (False, True):
                            res = hx.query(q, k=5, backend="dist-ooc",
                                           shards=shards, memory_budget_mb=8,
                                           prefetch=prefetch, wave=wave)
                            same(ref, res)
                    # residency confinement, telemetry-asserted (same
                    # cached engine the query loop above served through)
                    t = hx.engine("dist-ooc", shards=shards,
                                  memory_budget_mb=8).telemetry()
                    ds = t.dist
                    assert ds.shards == shards
                    for (lo, hi), touched in zip(ds.row_range,
                                                 ds.rows_touched):
                        if touched is not None:
                            assert lo <= touched[0] and touched[1] <= hi
                    assert sum(ds.rows_streamed) > 0
        # tie determinism across shard counts (duplicated rows)
        with api.Hercules.create(d + "/dup", api.IndexConfig(),
                                 data=dup) as hx:
            ref = hx.engine("local").knn(qt, k=10)
            dd = np.asarray(ref.dists)
            assert any((dd[i, :-1] == dd[i, 1:]).any()
                       for i in range(dd.shape[0]))
            for shards in (1, 2, 4, 8):
                same(ref, hx.engine("dist-ooc", shards=shards,
                                    memory_budget_mb=8).knn(qt, k=10))
    print("DIST_OOC_MESH_OK")
""")


@pytest.mark.skipif(not MESH_IN_PROCESS,
                    reason="needs 8 devices (CI distributed job forces "
                           "them); 1-device machines run the subprocess leg")
class TestDistOocMeshInProcess:
    @pytest.fixture(scope="class")
    def mesh_store(self, tmp_path_factory, rng):
        data = rng.standard_normal((600, 32)).astype(np.float32)
        extra = rng.standard_normal((40, 32)).astype(np.float32)
        path = str(tmp_path_factory.mktemp("mesh") / "idx")
        with api.Hercules.create(path, api.IndexConfig(), data=data) as hx:
            hx.append(extra)
            yield hx

    @pytest.mark.parametrize("shards", [1, 2, 4, 8])
    @pytest.mark.parametrize("prefetch", ["sync", "thread"])
    @pytest.mark.parametrize("wave", [False, True])
    def test_parity_with_journal(self, mesh_store, rng, shards, prefetch,
                                 wave):
        q = rng.standard_normal((4, 32)).astype(np.float32)
        ref = mesh_store.query(q, k=5, backend="local")
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            res = mesh_store.query(q, k=5, backend="dist-ooc", shards=shards,
                                   memory_budget_mb=8, prefetch=prefetch,
                                   wave=wave)
        _assert_same(ref, res)

    @pytest.mark.parametrize("codec", ["raw", "bf16"])
    @pytest.mark.parametrize("shards", [2, 8])
    def test_codec_parity(self, rng, tmp_path, codec, shards):
        data = rng.standard_normal((500, 32)).astype(np.float32)
        q = rng.standard_normal((3, 32)).astype(np.float32)
        with api.Hercules.create(str(tmp_path / "i"), api.IndexConfig(),
                                 data=data, codec=codec) as hx:
            ref = hx.engine("local").knn(q, k=4)
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", RuntimeWarning)
                eng = hx.engine("dist-ooc", shards=shards,
                                memory_budget_mb=8)
                _assert_same(ref, eng.knn(q, k=4))
                ds = eng.telemetry().dist
            for (lo, hi), touched in zip(ds.row_range, ds.rows_touched):
                if touched is not None:
                    assert lo <= touched[0] and touched[1] <= hi

    @pytest.mark.parametrize("shards", [2, 4, 8])
    def test_tie_determinism_across_shards(self, rng, tmp_path, shards):
        base = rng.standard_normal((60, 32)).astype(np.float32)
        data = np.repeat(base, 5, axis=0)
        qt = (base[:3] + 1e-3).astype(np.float32)
        with api.Hercules.create(str(tmp_path / "dup"), api.IndexConfig(),
                                 data=data) as hx:
            ref = hx.engine("local").knn(qt, k=10)
            dd = np.asarray(ref.dists)
            assert any((dd[i, :-1] == dd[i, 1:]).any()
                       for i in range(dd.shape[0]))
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", RuntimeWarning)
                res = hx.engine("dist-ooc", shards=shards,
                                memory_budget_mb=8).knn(qt, k=10)
            _assert_same(ref, res)


@pytest.mark.slow
@pytest.mark.skipif(MESH_IN_PROCESS,
                    reason="8 devices visible: the in-process matrix "
                           "already covers the mesh")
def test_dist_ooc_mesh_subprocess():
    res = subprocess.run([sys.executable, "-c", _MESH_SCRIPT],
                         cwd="/root/repo", capture_output=True, text=True,
                         timeout=600)
    assert "DIST_OOC_MESH_OK" in res.stdout, res.stderr[-3000:]


# the lean always-on leg: 2 forced host devices, sanitizers armed — every
# machine exercises a real multi-device mesh even where the 8-device
# matrix above is skipped
_MESH2_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    os.environ["REPRO_SANITIZE"] = "1"
    import sys; sys.path.insert(0, "src")
    import warnings; warnings.simplefilter("ignore", RuntimeWarning)
    import tempfile
    import numpy as np
    from repro import api

    rng = np.random.default_rng(11)
    data = rng.standard_normal((400, 32)).astype(np.float32)
    extra = rng.standard_normal((24, 32)).astype(np.float32)
    q = rng.standard_normal((3, 32)).astype(np.float32)
    base = rng.standard_normal((50, 32)).astype(np.float32)
    dup = np.repeat(base, 4, axis=0)
    qt = (base[:2] + 1e-3).astype(np.float32)

    def same(a, b):
        assert np.array_equal(np.asarray(a.dists), np.asarray(b.dists))
        assert np.array_equal(np.asarray(a.positions),
                              np.asarray(b.positions))
        assert np.array_equal(np.asarray(a.ids), np.asarray(b.ids))

    with tempfile.TemporaryDirectory() as d:
        with api.Hercules.create(d + "/i", api.IndexConfig(),
                                 data=data) as hx:
            hx.append(extra)        # journal rows merge on every path
            ref = hx.query(q, k=5, backend="local")
            for prefetch in ("sync", "thread"):
                for wave in (False, True):
                    res = hx.query(q, k=5, backend="dist-ooc", shards=2,
                                   memory_budget_mb=8, prefetch=prefetch,
                                   wave=wave)
                    same(ref, res)
        # tie determinism on the 2-device mesh (duplicated rows)
        with api.Hercules.create(d + "/dup", api.IndexConfig(),
                                 data=dup) as hx:
            ref = hx.engine("local").knn(qt, k=8)
            dd = np.asarray(ref.dists)
            assert any((dd[i, :-1] == dd[i, 1:]).any()
                       for i in range(dd.shape[0]))
            same(ref, hx.engine("dist-ooc", shards=2,
                                memory_budget_mb=8).knn(qt, k=8))
    print("DIST_OOC_MESH2_OK")
""")


def test_dist_ooc_two_device_subprocess():
    res = subprocess.run([sys.executable, "-c", _MESH2_SCRIPT],
                         cwd="/root/repo", capture_output=True, text=True,
                         timeout=600)
    assert "DIST_OOC_MESH2_OK" in res.stdout, res.stderr[-3000:]


# ---------------------------------------------------------------------------
# one best-so-far shared across shards: 4 forced host devices
# ---------------------------------------------------------------------------

_SHARED_BSF_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys; sys.path.insert(0, "src")
    import warnings; warnings.simplefilter("ignore", RuntimeWarning)
    import tempfile
    import jax
    import numpy as np
    from repro import api
    from repro.core import engine as engine_mod
    from repro.core.engine import OutOfCoreLocalBackend

    rng = np.random.default_rng(16)
    n_series, length = 1 << 13, 64
    data = np.cumsum(rng.standard_normal((n_series, length)), axis=1
                     ).astype(np.float32)
    q = (data[rng.integers(0, n_series, 16)]
         + 0.5 * rng.standard_normal((16, length))).astype(np.float32)
    # few seeded leaves, so phases 2-3 have work to prune
    over = {"l_max": 2}

    def brute(k):
        d = ((data[None, :, :] - q[:, None, :]) ** 2).sum(-1)
        order = np.argsort(d, axis=1, kind="stable")[:, :k]
        return np.take_along_axis(d, order, axis=1)

    # spy: the bound each call of phase 2 prunes against, by backend
    seen = {}
    needed = OutOfCoreLocalBackend._needed_leaves

    # ... and the fetches it made before phase 2, by backend
    fetched = {}

    def spy(self, lbs, bsf, slack, seeded):
        seen[id(self)] = np.asarray(bsf)
        fetched[id(self)] = self._t["blocks"]
        return needed(self, lbs, bsf, slack, seeded)

    OutOfCoreLocalBackend._needed_leaves = spy

    # spy: the leaves each call of phase 1 seeds, by backend
    seeded = {}
    seed_candidates = OutOfCoreLocalBackend._seed_candidates

    def seed_spy(self, q, cfg):
        out = seed_candidates(self, q, cfg)
        _, home, best = out
        leaves = ({int(r) for r in home if r >= 0}
                  | {int(r) for r in best.ravel()})
        counts = np.asarray(self._leaf_count)
        seeded[id(self)] = sum(int(counts[r] > 0) for r in leaves)
        return out

    OutOfCoreLocalBackend._seed_candidates = seed_spy
    pack = engine_mod._pack_extents

    def per_shard(be, t0, key):
        return [s._t[key] - t[key] for s, t in zip(be._subs, t0)]

    def alone(be, sub, dev, cfg):
        # one shard's stream as the fan-out ran it before the exchange:
        # returns its phase-1 top-k distances, rows and fetches
        got = {}

        def keep(d, p):
            got["d"] = np.asarray(d)
            return np.full(d.shape[0], np.inf, np.float32)  # no bound

        t0 = dict(sub._t)
        with jax.default_device(dev):
            sub._stream_knn(jax.numpy.asarray(q), cfg, exchange=keep)
        return (got["d"], sub._t["rows_streamed"] - t0["rows_streamed"],
                sub._t["blocks"] - t0["blocks"])

    cfg_build = api.IndexConfig(build=api.BuildConfig(leaf_capacity=64))
    with tempfile.TemporaryDirectory() as d:
        with api.Hercules.create(d + "/i", cfg_build, data=data) as hx:
            for k in (1, 10):
                ref = hx.engine("local").knn(q, k=k, **over)
                want = brute(k)
                for prefetch in ("sync", "thread"):
                    eng = hx.engine("dist-ooc", shards=4, memory_budget_mb=1,
                                    prefetch=prefetch)
                    be = eng.backend
                    t0 = [dict(s._t) for s in be._subs]
                    seen.clear()
                    res = eng.knn(q, k=k, **over)
                    # exact: bit-identical to local, and brute force's
                    # distances
                    for a, b in (("dists", ref.dists), ("ids", ref.ids),
                                 ("positions", ref.positions)):
                        assert np.array_equal(np.asarray(getattr(res, a)),
                                              np.asarray(b)), (k, a)
                    np.testing.assert_allclose(np.asarray(res.dists), want,
                                               rtol=1e-4, atol=1e-3)
                    rows = sum(s._t["rows_streamed"] - t["rows_streamed"]
                               for s, t in zip(be._subs, t0))
                    fetches = sum(s._t["blocks"] - t["blocks"]
                                  for s, t in zip(be._subs, t0))
                    shared = [seen[id(s)] for s in be._subs]
                    # each shard's phase 1 packs its seeded leaves into
                    # fewer fetches than it has leaves, and the whole call
                    # into fewer than its extents; one fetch an extent (the
                    # schedule before packing) reads the same rows a shard
                    # and answers the same
                    blocks = per_shard(be, t0, "blocks")
                    extents = per_shard(be, t0, "extents")
                    phase1 = [fetched[id(s)] - t["blocks"]
                              for s, t in zip(be._subs, t0)]
                    shard_rows = per_shard(be, t0, "rows_streamed")
                    t1 = [dict(s._t) for s in be._subs]
                    engine_mod._pack_extents = lambda ext, cap: [
                        [e] for e in ext]
                    try:
                        one = eng.knn(q, k=k, **over)
                    finally:
                        engine_mod._pack_extents = pack
                    for a in ("dists", "ids", "positions"):
                        assert np.array_equal(np.asarray(getattr(res, a)),
                                              np.asarray(getattr(one, a)))
                    assert per_shard(be, t1, "rows_streamed") == shard_rows
                    assert per_shard(be, t1, "blocks") == extents
                    assert per_shard(be, t1, "extents") == extents
                    for s, b1, b, e in zip(be._subs, phase1, blocks,
                                           extents):
                        assert b1 < seeded[id(s)], (k, b1, seeded[id(s)])
                        assert b < e, (k, b, e)
                    print("PACKED", k, prefetch, phase1, blocks, extents,
                          [seeded[id(s)] for s in be._subs])
                    t = eng.telemetry().dist
                    assert len(t.phase1_rows) == 4 and min(t.phase1_rows) > 0
                    assert len(t.exchange_wait_seconds) == 4
                    assert min(t.exchange_wait_seconds) >= 0.0
                    assert t.merge_seconds > 0.0

                    cfg = be.resolve(k, over)
                    parts = [alone(be, s, dev, cfg)
                             for s, dev in zip(be._subs, be._devices)]
                    # the bound is the merged kth distance of every
                    # shard's phase-1 answers, on every shard
                    merged = np.sort(np.concatenate([p[0] for p in parts],
                                                    axis=1), axis=1)[:, k - 1]
                    for b in shared:
                        assert np.array_equal(b, merged), (k, b, merged)
                    # ... and no larger than ooc-local's after phase 1: the
                    # shards' seed sets hold ooc-local's
                    local = hx.engine("ooc-local", memory_budget_mb=4,
                                      prefetch=prefetch).backend
                    own = alone(local, local, jax.devices()[0],
                                local.resolve(k, over))[0][:, k - 1]
                    assert (merged <= own).all(), (merged, own)
                    assert (merged < own).any() or k == 1
                    # the shared bound reads no more than the shards alone
                    rows_alone = sum(p[1] for p in parts)
                    fetches_alone = sum(p[2] for p in parts)
                    assert rows <= rows_alone, (k, rows, rows_alone)
                    assert fetches <= fetches_alone, (k, fetches,
                                                      fetches_alone)
                    print("SHARED", k, prefetch, rows, rows_alone, fetches,
                          fetches_alone)

            # a shard that fails before the exchange fails the call with
            # its own error; the others do not wait for it
            eng = hx.engine("dist-ooc", shards=4, memory_budget_mb=1)
            sub = eng.backend._subs[2]

            def broken(q, cfg):
                raise RuntimeError("shard 2 broke")

            sub._seed_candidates = broken
            try:
                eng.knn(q, k=1, **over)
            except RuntimeError as e:
                assert "shard 2 broke" in str(e), e
            else:
                raise AssertionError("the broken shard went unnoticed")
            del sub._seed_candidates
            res = eng.knn(q, k=1, **over)
            assert np.array_equal(np.asarray(res.dists),
                                  np.asarray(hx.engine("local").knn(
                                      q, k=1, **over).dists))

            # the exchange under frequent thread switches: every wave
            # exact
            eng = hx.engine("dist-ooc", shards=4, memory_budget_mb=1,
                            prefetch="thread")
            ref = hx.engine("local").knn(q, k=10, **over)
            switch = sys.getswitchinterval()
            sys.setswitchinterval(1e-6)
            try:
                for _ in range(8):
                    res = eng.knn(q, k=10, **over)
                    assert np.array_equal(np.asarray(res.dists),
                                          np.asarray(ref.dists))
                    assert np.array_equal(np.asarray(res.ids),
                                          np.asarray(ref.ids))
            finally:
                sys.setswitchinterval(switch)
            t = eng.telemetry().dist
            assert len(t.exchange_wait_seconds) == 4
            assert all(r > 0 for r in t.phase1_rows)
    print("DIST_SHARED_BSF_OK")
""")


def test_dist_ooc_shared_bound_four_devices():
    res = subprocess.run([sys.executable, "-c", _SHARED_BSF_SCRIPT],
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))),
                         capture_output=True, text=True, timeout=600)
    assert "DIST_SHARED_BSF_OK" in res.stdout, (res.stdout[-3000:],
                                               res.stderr[-3000:])
