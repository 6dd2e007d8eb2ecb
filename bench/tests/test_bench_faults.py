"""A whole run at a test size with the check for a chip skipped: sound, it
comes out correct; with the timed path broken underneath, not correct.

The faults a serving cell can have: a step that leaves its state unchanged
(serves nothing), half of each wave answered with the other half's
answers, and one answer altered where the engine produces it. The cells
run on one chip, so there is no exchange between chips to leave out.
"""
import time

import jax
import numpy as np
import pytest

from bench import harness
from bench.loops import open_poisson
from bench.tests.tiny import tiny_cell
from repro.api import KnnResult, KnnServeEngine, QueryEngine


def run(cell, fault=None, monkeypatch=None):
    if fault is not None:
        warm_all = harness.warm_all

        def warm_then_break(*a, **kw):
            warm_all(*a, **kw)
            fault(monkeypatch)          # break the path the window drives

        monkeypatch.setattr(harness, "warm_all", warm_then_break)
    return harness.run(cell, 2**31 + 17, 1.0, False,
                       t_start=time.perf_counter(), devices=jax.devices())


def stuck_step(mp):
    mp.setattr(KnnServeEngine, "step", lambda self: 0)


def half_wave(mp):
    knn = QueryEngine.knn

    def copy_first_half(self, q, **kw):
        res = knn(self, q, **kw)
        n = res.dists.shape[0]
        half = n // 2
        src = np.arange(n)
        src[half:] = np.arange(n - half) % max(half, 1)
        return KnnResult(*[a[src] for a in res])

    mp.setattr(QueryEngine, "knn", copy_first_half)


def altered_answer(mp):
    knn = QueryEngine.knn

    def alter(self, q, **kw):
        res = knn(self, q, **kw)
        return res._replace(ids=res.ids.at[0, 0].add(1))

    mp.setattr(QueryEngine, "knn", alter)


@pytest.mark.parametrize("name", ["hbm-easy-open", "disk-easy-batch"])
def test_sound_run_is_correct(name):
    res = run(tiny_cell(name))
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) >= {"setup_s"}
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", [stuck_step, half_wave, altered_answer])
@pytest.mark.parametrize("name", ["hbm-easy-open", "disk-easy-batch"])
def test_broken_path_is_not_correct(name, fault, monkeypatch):
    monkeypatch.setattr(open_poisson, "GRACE_S", 1.0)
    res = run(tiny_cell(name), fault, monkeypatch)
    assert not res["correct"], res["checks"]
