"""The control: the plain reference computed from bfloat16 inputs, put in
the program's place, has to come out not correct under the cells' limits,
while the float32 reference itself reads no gap at all."""
import numpy as np
import pytest

import bench.control
from bench import harness, reference, registry, schedule, synth
from bench.tests.tiny import tiny_cell

CELLS = [w["name"] for w in registry.benchmark()["workloads"]]


@pytest.fixture(scope="module")
def data():
    return synth.collection(schedule.prng_key(21), 8192, 256)


def answers_of(data, queries, ks, precision):
    out = [None] * len(ks)
    for k in sorted(set(ks)):
        rows = [i for i, kk in enumerate(ks) if kk == k]
        d, i = reference.knn(data, queries[rows], k, precision=precision)
        for r, dd, ii in zip(rows, d, i):
            out[r] = (dd, ii)
    return out


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_every_cell(data, name):
    cell = registry.cell(name)
    limits = cell["config"]["limits"]
    reqs = schedule.make_requests(cell["traffic"], 4, 2.0, data, count=48)
    ks = list(reqs.k)
    sound = reference.compare(data, reqs.queries, ks,
                              answers_of(data, reqs.queries, ks, "float32"))
    assert sound == {"missing": 0, "dist_gap": 0.0, "id_gap": 0.0}
    control = reference.compare(data, reqs.queries, ks,
                                answers_of(data, reqs.queries, ks,
                                           "bfloat16"))
    assert control["missing"] == 0
    assert any(control[c] > limits[c] for c in harness.CHECKS), control


def test_control_tool_at_test_size():
    cell = tiny_cell("hbm-easy-open")
    checks = bench.control.control_checks(cell, 7, 1.0)
    limits = cell["config"]["limits"]
    assert not all(checks[c] <= limits[c] for c in harness.CHECKS)


def test_wrong_ids_and_missing_answers_read_high(data):
    reqs = schedule.make_requests({"hardness": ["1%"], "k": [10]}, 9, 1.0,
                                  data, count=8)
    ks = list(reqs.k)
    good = answers_of(data, reqs.queries, ks, "float32")
    d, i = good[0]
    swapped = list(good)
    swapped[0] = (d, np.roll(i, 1))                  # ids out of order
    assert reference.compare(data, reqs.queries, ks, swapped)["id_gap"] > 0.01
    repeated = list(good)
    repeated[1] = (good[1][0], np.full(10, good[1][1][0]))
    assert reference.compare(data, reqs.queries, ks,
                             repeated)["id_gap"] > 0.01
    bad = list(good)
    bad[2] = (good[2][0], np.full(10, -1))            # out of range
    assert reference.compare(data, reqs.queries, ks, bad)["id_gap"] == \
        float("inf")
    lost = list(good)
    lost[3] = None
    assert reference.compare(data, reqs.queries, ks, lost)["missing"] == 1
