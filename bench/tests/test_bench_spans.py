"""The reduction of the program's spans and phase scopes: innermost idle
attribution, scope self times, the benchmark's own reduction left as it
was, and the span nesting a recorded serving run shows."""
import contextlib

import jax
import numpy as np
import pytest

from bench import registry, schedule, spans, synth, trace
from bench.serving import Server
from bench.tests import test_bench_trace
from bench.tests.test_bench_trace import Ev, Line, Plane, Profile
from bench.tests.tiny import tiny_cell


def op(name, start, dur, scope_path=None):
    stats = [("tf_op", scope_path)] if scope_path else []
    return Ev(name, start, dur, stats)


def program_trace(op_stat="tf_op"):
    """A window [0, 1000) with a wave span holding program spans, and a
    device whose ops run under the phase scopes."""
    host = Plane("/host:CPU", [Line("python", [
        Ev("bench.window", 0, 1000),
        Ev("bench.step", 0, 600),
        Ev("repro.serve.step", 10, 580),
        Ev("repro.serve.pack", 10, 40),
        Ev("repro.engine.run", 50, 450),
        Ev("repro.serve.answer", 500, 80),
        Ev("bench.idle_wait", 700, 200),
    ]), Line("reader", [Ev("repro.ooc.read", 920, 40, [("rows", 7)])])])
    body = "jit(exact_knn)/while/body/closed_call/"

    def scoped(name, start, dur, path):
        if op_stat == "tf_op":
            return op(name, start, dur, path)
        text = f'%{name} = f32[8] fusion(), metadata={{op_name="{path}"}}'
        if op_stat == "name":
            return Ev(text, start, dur)
        return Ev(name, start, dur, [("long_name", text)])

    dev = Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev("jit_exact_knn(1)", 60, 400),
                             Ev("jit_slice(2)", 520, 10)]),
        Line("XLA Ops", [
            scoped("while.1", 60, 400, "jit(exact_knn)/while"),
            scoped("fusion.1", 60, 100, body + "seed/gather"),
            scoped("lb_sax.7", 200, 150, body + "candidates/jit(lb_sax_matrix)"
                   "/lb_sax/pallas_call"),
            scoped("fusion.9", 360, 60, body + "refine/while/body/add"),
            scoped("fusion.4", 430, 20, body + "scan/while/body/add"),
            op("slice.2", 520, 10),                      # no name at all
        ]),
    ])
    return Profile([host, dev])


@pytest.mark.parametrize("op_stat", ["tf_op", "long_name", "name"])
def test_program_trace(op_stat):
    red = spans.reduce(program_trace(op_stat))
    assert red.window_s == pytest.approx(1000e-9)
    # device busy: [60, 460) and [520, 530); idle 590 ns
    assert red.idle_s == pytest.approx(590e-9)
    gaps = red.idle_gaps
    # [0, 10) bench.step; [10, 50) pack; [50, 60) run (its dispatch);
    # [460, 500) run (the wait's tail); [500, 520) + [530, 580) answer;
    # [580, 590) repro.serve.step; [590, 600) bench.step; [600, 700)
    # none; [700, 900) idle_wait; [900, 920) + [960, 1000) none;
    # [920, 960) the read on another thread
    assert gaps == pytest.approx({
        "bench.step": 20e-9, "repro.serve.pack": 40e-9,
        "repro.engine.run": 50e-9, "repro.serve.answer": 70e-9,
        "repro.serve.step": 10e-9, "bench.idle_wait": 200e-9,
        "repro.ooc.read": 40e-9, "host_other": 160e-9})
    assert sum(gaps.values()) == pytest.approx(red.idle_s)
    # idle under the step span holds its nested spans' idle too
    assert red.idle_under["repro.serve.step"] == pytest.approx(170e-9)
    assert red.idle_under["bench.step"] == pytest.approx(190e-9)
    assert red.spans["repro.engine.run"] == (1, pytest.approx(450e-9))
    # self times: the loop less its body; the pallas call under
    # candidates; the slice with no op name is unscoped
    assert red.scopes == pytest.approx({
        "unscoped": 400e-9 - 330e-9 + 10e-9, "seed": 100e-9,
        "candidates": 150e-9, "refine": 60e-9, "scan": 20e-9})
    assert red.module_scopes["jit_exact_knn"]["unscoped"] == \
        pytest.approx(70e-9)
    assert red.module_scopes["jit_slice"] == {"unscoped": pytest.approx(
        10e-9)}
    assert red.name_source == op_stat


@pytest.mark.parametrize("path, scope", [
    ("jit(f)/while/body/closed_call/seed/dynamic_slice", "seed"),
    ("jit(f)/candidates/jit(lb_sax_matrix)/lb_sax/pallas_call",
     "candidates"),
    ("jit(f)/refine/while/body/scan/add", "scan"),       # innermost wins
    ("jit(f)/while/body/rescan/add", "unscoped"),         # whole parts only
    (None, "unscoped"),
])
def test_scope_of(path, scope):
    assert spans.scope_of(path) == scope


HAND_TRACE = test_bench_trace.hand_trace


def with_program_spans():
    prof = HAND_TRACE()
    prof.planes[0].lines[0].events += [
        Ev("repro.serve.step", 110, 480),
        Ev("repro.engine.run", 150, 300),
        Ev("repro.serve.answer", 460, 120),
    ]
    return prof


def test_bench_reduction_unchanged_by_program_spans(monkeypatch):
    # the benchmark's own numbers read exactly as on the trace without them
    monkeypatch.setattr(test_bench_trace, "hand_trace", with_program_spans)
    test_bench_trace.test_hand_trace()
    red = spans.reduce(with_program_spans())
    # the same gaps, now put to the innermost span
    assert sum(red.idle_gaps.values()) == pytest.approx(650e-9)
    assert red.idle_under["bench.step"] == pytest.approx(200e-9)


def test_no_window_span_is_refused():
    prof = program_trace()
    prof.planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError):
        spans.reduce(prof)


@contextlib.contextmanager
def no_part(name):
    yield


def test_recorded_serving_spans(tmp_path):
    """A tiny ``hbm-easy-open`` deployment serving three waves under the
    profiler: each wave's ``repro.serve.step`` holds ``pack``, then
    ``repro.engine.run``, then ``answer``, inside ``bench.step``."""
    cell = tiny_cell("hbm-easy-open")
    cfg = cell["config"]
    key = schedule.prng_key(2**31 + 5)
    data = synth.collection(key, cfg["num_series"], cfg["series_len"])
    reqs = schedule.make_requests(cell["traffic"], 2**31 + 5, 1.0, data,
                                  count=12)
    dep = registry.load_module("deploy", cfg["deployment"]).setup(
        cfg, data, no_part)
    srv = Server(dep.server, dep.engine)
    for i in range(12):                    # compile before the trace
        srv.submit(i, reqs.queries[i], reqs.k[i])
    while srv.outstanding():
        srv.step()
    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=trace.profile_options())
    with jax.profiler.TraceAnnotation("bench.window"):
        for i in range(12):
            srv.submit(i, reqs.queries[i], reqs.k[i])
        while srv.outstanding():
            srv.step()
    jax.profiler.stop_trace()
    pd = trace.load(str(tmp_path))
    w0, w1 = spans.window_of(pd)
    found = sorted(spans.host_spans(pd, w0, w1))
    steps = [(s, e) for s, e, n in found if n == "repro.serve.step"]
    assert steps
    for s0, e0 in steps:
        inside = [n for s, e, n in found
                  if s0 <= s and e <= e0 and n.startswith("repro.")]
        order = [n for n in inside if n in ("repro.serve.pack",
                                            "repro.engine.run",
                                            "repro.serve.answer")]
        assert order[0] == "repro.serve.pack"
        assert order.index("repro.engine.run") < order.index(
            "repro.serve.answer")
        assert "repro.engine.plan" in inside
        outer = [(s, e) for s, e, n in found if n == "bench.step"
                 and s <= s0 and e0 <= e]
        assert len(outer) == 1
    # the CPU runtime's XLA threads stand in for a device's op line
    red = spans.reduce(pd, select=test_bench_trace.cpu_ops)
    assert np.isclose(sum(red.idle_gaps.values()), red.idle_s)
    assert red.idle_under["repro.serve.step"] <= red.idle_under["bench.step"]
