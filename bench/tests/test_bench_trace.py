"""The trace reduction: the busy union, the idle share, the kernel-event sum
and the idle gaps put to host spans, on a hand-made trace and on one
recorded here."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import trace


class Ev:
    def __init__(self, name, start, dur, stats=()):
        self.name, self.start_ns, self.duration_ns = name, start, dur
        self.stats = list(stats)


class Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class Profile:
    def __init__(self, planes):
        self.planes = planes


def hand_trace():
    host = Plane("/host:CPU", [Line("python", [
        Ev("bench.window", 100, 1000),
        Ev("bench.step", 100, 500),
        Ev("bench.idle_wait", 700, 300),
    ])])
    dev = Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev("jit_exact_knn(123)", 50, 550),
                             Ev("jit_other(9)", 900, 200)]),
        Line("XLA Ops", [
            Ev("%while.3 = (f32[4]) while(...)", 50, 250),  # clipped: 100-300
            Ev("%lb_sax_matrix.7 = f32[8] custom-call(), x", 150, 100,
               [("hlo_op", "lb_sax_matrix.7")]),     # nested in while.3
            Ev("%fusion.2 = f32[8] fusion(...)", 400, 100,
               [("long_name", "slice of lb_sax_matrix")]),  # not the kernel
            Ev("%lb_sax_matrix.7 = f32[8] custom-call(), x", 1000, 50),
            Ev("%after = f32[8] add(...)", 1200, 50),       # past the window
        ]),
        Line("Steps", [Ev("step", 0, 2000)]),            # not an op line
    ])
    return Profile([host, dev])


def test_hand_trace():
    red = trace.reduce(hand_trace(), kernels=("lb_sax",))
    assert red.window_s == pytest.approx(1000e-9)
    # union: [100, 300) + [400, 500) + [1000, 1050) = 200 + 100 + 50
    assert red.busy_s == pytest.approx(350e-9)
    assert red.idle_share == pytest.approx(0.65)
    # the kernel's two events, one of them inside the loop's; the fusion
    # that names it only in its metadata is not the kernel
    assert red.kernels["lb_sax"] == pytest.approx(150e-9)
    assert red.kernel_events["lb_sax"] == 2
    gaps = dict(red.idle_gaps)
    # gaps [300, 400) and [500, 600) lie in bench.step, [600, 700) in no
    # span, [700, 1000) in bench.idle_wait, [1050, 1100) in no span
    assert gaps["bench.step"] == pytest.approx(200e-9)
    assert gaps["bench.idle_wait"] == pytest.approx(300e-9)
    assert gaps["host_other"] == pytest.approx(150e-9)
    # self times: the loop less the kernel nested in it
    ops = dict(red.ops)
    assert ops["jit_exact_knn/while.3"] == pytest.approx(100e-9)
    assert ops["jit_exact_knn/lb_sax_matrix.7"] == pytest.approx(100e-9)
    assert ops["jit_exact_knn/fusion.2"] == pytest.approx(100e-9)
    assert ops["jit_other/lb_sax_matrix.7"] == pytest.approx(50e-9)
    assert sum(t for _, t in red.ops) == pytest.approx(red.busy_s)
    assert dict(red.kernel_ops["lb_sax"]) == {
        "jit_exact_knn/lb_sax_matrix.7": pytest.approx(100e-9),
        "jit_other/lb_sax_matrix.7": pytest.approx(50e-9)}


def test_self_times_of_nested_loops():
    own = trace.self_times(np.array([0.0, 1.0, 2.0, 6.0]),
                           np.array([10.0, 5.0, 3.0, 8.0]))
    np.testing.assert_allclose(own, [4.0, 3.0, 1.0, 2.0])


def test_union_and_overlap():
    s, e = trace.union(np.array([5.0, 0.0, 2.0, 10.0]),
                       np.array([6.0, 3.0, 4.0, 11.0]))
    np.testing.assert_array_equal(s, [0.0, 5.0, 10.0])
    np.testing.assert_array_equal(e, [4.0, 6.0, 11.0])
    assert trace.overlap(s, e, np.array([3.0]), np.array([10.5])) == 2.5


def test_no_window_span_is_refused():
    prof = hand_trace()
    prof.planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError):
        trace.reduce(prof)


def cpu_ops(plane, line):
    # on the CPU the runtime's XLA threads stand in for a device's op line
    return plane == "/host:CPU" and line.startswith("tf_XLA")


def test_recorded_trace(tmp_path):
    @jax.jit
    def sine_matmul(x):
        return jnp.sin(x) @ x

    x = jnp.ones((512, 512))
    sine_matmul(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=trace.profile_options())
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                sine_matmul(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.idle_wait"):
            jnp.zeros(1).block_until_ready()
    jax.profiler.stop_trace()
    # the CPU names the sine op ``wrapped_sine``
    red = trace.reduce(trace.load(str(tmp_path)), kernels=("wrapped_sine",),
                       select=cpu_ops)
    assert red.devices == 1
    assert 0 < red.busy_s <= red.window_s
    assert 0.0 <= red.idle_share < 1.0
    assert 0 < red.kernels["wrapped_sine"] <= red.busy_s
    assert red.kernel_events["wrapped_sine"] >= 3
    assert {g[0] for g in red.idle_gaps} <= {"bench.step", "bench.idle_wait",
                                             "host_other"}
    # the default selection finds no device on the CPU
    with pytest.raises(ValueError):
        trace.reduce(trace.load(str(tmp_path)))
