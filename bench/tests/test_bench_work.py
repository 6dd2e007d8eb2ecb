"""The peak table and the LB_SAX work function, on hand-worked cases, and
the measurement path's refusal of a device that is not a TPU."""
import types

import numpy as np
import pytest

import bench.run
from bench import harness, peaks, work
from bench.readers import Context, lb_sax_least_seconds, lb_sax_roofline
from bench.serving import Wave

V5E = peaks.peaks("TPU v5 lite")


def test_peak_table():
    assert V5E["hbm_bytes_per_s"] == 819e9
    assert V5E["bf16_flops_per_s"] == 197e12
    assert V5E["hbm_bytes"] == 16e9
    assert "TPU v5e" in V5E["source"]
    with pytest.raises(LookupError):
        peaks.peaks("cpu")


def test_lb_sax_bytes_by_hand():
    # 1024 codes of 16 bytes, one float32 bound per code, 16 PAA floats,
    # two 256-entry float32 tables: 16384 + 4096 + 64 + 2048
    assert work.lb_sax_bytes(1, 1024) == 22592
    # 32 queries: the codes once, 32 bounds per code
    assert work.lb_sax_bytes(32, 1024) == (16384 + 32 * 4096 + 32 * 64
                                           + 2048)
    assert work.lb_sax_flops(2, 10) == 6 * 2 * 10 * 16


def test_least_seconds_names_its_bound():
    t, bound = work.least_seconds(1e6, 819e9, V5E)
    assert bound == "hbm" and t == pytest.approx(1.0)
    t, bound = work.least_seconds(197e12 * 2, 1.0, V5E)
    assert bound == "compute" and t == pytest.approx(2.0)


def ctx(waves, before, kernel_s, num_series=1 << 20):
    cell = {"config": {"num_series": num_series}}
    window = types.SimpleNamespace(
        waves=waves, before=before,
        delta=lambda key: (waves[-1].counters[key] - before[key]
                           if waves else None))
    red = types.SimpleNamespace(kernels={"lb_sax": kernel_s})
    return Context(cell=cell, window=window, trace=red, peak=V5E,
                   compiles={})


def test_lb_sax_roofline_in_memory():
    # each wave bounds all 2^20 codes for its served queries, reading each
    # code once: 16 code bytes and 4 bound bytes per query per code
    w = [Wave(0, 1, 32, {"queries": 32}), Wave(1, 2, 8, {"queries": 40})]
    c = ctx(w, {"queries": 0}, kernel_s=0.2)
    least = (work.lb_sax_bytes(32, 1 << 20)
             + work.lb_sax_bytes(8, 1 << 20)) / 819e9
    assert least == pytest.approx(((16 + 4 * 32) + (16 + 4 * 8)) * (1 << 20)
                                  / 819e9, rel=1e-4)
    assert lb_sax_least_seconds(c) == pytest.approx(least)
    assert lb_sax_roofline(c) == pytest.approx(least / 0.2 * 100)


def test_no_kernel_events_reads_nothing():
    assert lb_sax_roofline(ctx([], {}, kernel_s=0.0)) is None


def test_measurement_path_refuses_the_cpu(capsys):
    rc = bench.run.main(["--workload", "hbm-easy-open", "--seed", "1",
                         "--seconds", "1"])
    out, err = capsys.readouterr()
    assert rc != 0
    assert "correct" not in out and "needs a TPU" in err
    with pytest.raises(harness.NoChip):
        harness.require_chips(1)


def test_unknown_workload_exits_nonzero(capsys):
    assert bench.run.main(["--workload", "nope", "--seed", "1",
                           "--seconds", "1"]) != 0
    assert capsys.readouterr().out == ""


def test_check_lines_end_stderr(capsys):
    result = {"attempted": 3, "failed": 0,
              "checks": {"missing": {"value": 0, "limit": 0},
                         "dist_gap": {"value": np.float64(1e-7).item(),
                                      "limit": 1e-5}}}
    harness.report_checks(result)
    lines = capsys.readouterr().err.strip().splitlines()
    assert lines[0] == "check missing 0 limit 0"
    assert lines[1].startswith("check dist_gap 1e-07 limit 1e-05")
