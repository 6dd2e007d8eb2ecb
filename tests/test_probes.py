"""The serving path's probes: phase scopes in the lowered plans, host syncs
counted per wave, queue wait stamped on submit, and telemetry that reads
nothing from the device."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (BuildConfig, HerculesIndex, IndexConfig,
                        LocalBackend, QueryEngine, SearchConfig)
from repro.core import index as index_mod
from repro.core.search import exact_knn, wave_knn
from repro.data import make_query_workload, random_walks
from repro.serve import KnnServeConfig, KnnServeEngine

NUM, LEN, K, SLOTS = 2000, 64, 3, 4
CFG = IndexConfig(build=BuildConfig(leaf_capacity=64),
                  search=SearchConfig(k=K, l_max=4, chunk=128, scan_block=256))
SCOPES = ("seed", "candidates", "refine", "scan")

# blocking device-to-host reads and waits per served wave (PERF.md):
# the engine's wait for the plan, its three reads of path and pruning
# ratios, and the front end's three reads of dists, ids and path
ENGINE_SYNCS, SERVE_SYNCS = 4, 3


@pytest.fixture(scope="module")
def data():
    return random_walks(jax.random.PRNGKey(0), NUM, LEN)


@pytest.fixture(scope="module")
def index(data):
    return HerculesIndex.build(data, CFG)


@pytest.fixture(scope="module")
def queries(data):
    return np.asarray(make_query_workload(jax.random.PRNGKey(1), data, 10,
                                          "5%"))


@pytest.mark.parametrize("plan, n_valid", [
    (exact_knn, False), (wave_knn, False), (exact_knn, True)],
    ids=["exact_knn", "wave_knn", "exact_knn-n_valid"])
def test_lowered_plan_carries_phase_scopes(index, plan, n_valid):
    q = jax.ShapeDtypeStruct((SLOTS, LEN), jnp.float32)
    # the engine's local plan: the padded slots' skip is a lax.cond
    extra = (jax.ShapeDtypeStruct((), jnp.int32),) if n_valid else ()
    lowered = plan.lower(index.tree, index.layout, q, CFG.search,
                         index.max_depth, *extra)
    text = lowered.as_text(debug_info=True)
    # the compiled program's op metadata is what the profiler's trace reads
    op_names = re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())
    for scope in SCOPES:
        assert re.search(rf'[/"]{scope}/', text), scope
        assert any(f"/{scope}/" in name for name in op_names), scope


@pytest.mark.parametrize("n_requests", [SLOTS, 3 * SLOTS, SLOTS + 1])
def test_host_syncs_per_wave(index, queries, n_requests):
    engine = QueryEngine(LocalBackend(index))
    serve = KnnServeEngine(engine, KnnServeConfig(batch_slots=SLOTS))
    serve.submit(queries[0])
    serve.drain()                          # compile outside the count
    before = serve.telemetry()
    for q in queries[:n_requests]:
        serve.submit(q)
    serve.drain()
    after = serve.telemetry()
    waves = after.serving.waves - before.serving.waves
    assert waves == -(-n_requests // SLOTS)
    assert after.calls - before.calls == waves
    assert (after.host_syncs - before.host_syncs
            == (ENGINE_SYNCS + SERVE_SYNCS) * waves)
    # the engine's own telemetry covers its layer and the backend's only
    assert engine.telemetry().host_syncs == ENGINE_SYNCS * after.calls


def test_queue_wait_with_injected_clock(index, queries):
    now = [0.0]
    serve = KnnServeEngine(QueryEngine(LocalBackend(index)),
                           KnnServeConfig(batch_slots=2),
                           clock=lambda: now[0])
    for t in (1.0, 2.0, 3.0):
        now[0] = t
        serve.submit(queries[int(t)])
    now[0] = 5.0
    assert serve.step() == 2               # waited 4 s and 3 s
    sv = serve.telemetry().serving
    assert (sv.queue_wait_s, sv.dequeued) == (7.0, 2)
    now[0] = 10.0
    assert serve.step() == 1               # waited 7 s
    sv = serve.telemetry().serving
    assert (sv.queue_wait_s, sv.dequeued) == (14.0, 3)
    assert serve.telemetry()["serving"]["queue_wait_s"] == 14.0


def test_telemetry_reads_nothing_from_the_device(index, queries,
                                                 monkeypatch):
    engine = QueryEngine(LocalBackend(index))
    serve = KnnServeEngine(engine, KnnServeConfig(batch_slots=SLOTS))
    serve.submit(queries[0])
    serve.drain()

    def no_device_reads(tree):
        raise AssertionError("telemetry() walked the tree on the device")

    monkeypatch.setattr(index_mod, "tree_stats", no_device_reads)
    t = serve.telemetry()
    assert t.backend == "local" and t.calls == 1
    assert engine.stats()["num_leaves"] > 0
    # the engine time is one total; per-call means are the reader's to take
    assert t.latency.keys() == ("total",) and t.latency.total > 0
