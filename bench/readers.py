"""What the per-layer metrics read, shared by their files under
``bench/metrics/``. Each reader takes the run's ``Context`` and returns a
number, or None where it finds nothing to read (the metric then stays out
of the result line)."""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import work


@dataclasses.dataclass
class Context:
    cell: dict
    window: object                # bench.window.Window
    trace: object | None          # bench.trace.Reduction (--trace 1)
    peak: dict                    # bench.peaks entry of the device
    compiles: dict                # CompileMeter.since over the window


def queue_wait_ms(ctx: Context) -> float | None:
    """Median over requests of their wave's start minus their due time."""
    w = ctx.window
    ok = ~np.isnan(w.start)
    if not ok.any():
        return None
    return float(np.median(w.start[ok] - w.due[ok]) * 1e3)


def plan_ms_per_wave(ctx: Context) -> float | None:
    """Engine time per call over the window (``latency.total / calls``)."""
    calls = ctx.window.delta("calls")
    if not calls:
        return None
    return ctx.window.delta("exec_s") / calls * 1e3


def window_compiles(ctx: Context) -> float:
    """Programs lowered inside the window, whether the backend compiled them
    or read them from the persistent cache."""
    return float(ctx.compiles["lowerings"])


def sax_pruning(ctx: Context) -> float | None:
    """Mean LB_SAX pruning ratio of the window's queries, in percent."""
    w = ctx.window
    if not w.waves:
        return None
    a, b = w.waves[-1].counters, w.before
    n = a["known_paths"] - b["known_paths"]
    if n <= 0:
        return None
    total = a["sax_mean"] * a["known_paths"] - b["sax_mean"] * b["known_paths"]
    return total / n * 100.0


def rows_streamed_per_query(ctx: Context) -> float | None:
    rows, queries = (ctx.window.delta("rows_streamed"),
                     ctx.window.delta("queries"))
    if rows is None or not queries:
        return None
    return rows / queries


def read_wait_ms_per_wave(ctx: Context) -> float | None:
    wait, calls = ctx.window.delta("read_wait_s"), ctx.window.delta("calls")
    if wait is None or not calls:
        return None
    return wait / calls * 1e3


def lb_sax_least_seconds(ctx: Context) -> float:
    """The least time the window's LB_SAX filtering could take: each wave
    bounds all the collection's codes for its served queries, and need
    read each code only once for the whole wave."""
    rows = ctx.cell["config"]["num_series"]
    total = 0.0
    for w in ctx.window.waves:
        t, _ = work.least_seconds(work.lb_sax_flops(w.served, rows),
                                  work.lb_sax_bytes(w.served, rows), ctx.peak)
        total += t
    return total


def lb_sax_roofline(ctx: Context) -> float | None:
    """Share of the roofline: least time over the kernel's device time."""
    if ctx.trace is None or not ctx.trace.kernels.get("lb_sax"):
        return None
    return lb_sax_least_seconds(ctx) / ctx.trace.kernels["lb_sax"] * 100.0


def device_idle_share(ctx: Context) -> float | None:
    if ctx.trace is None:
        return None
    return ctx.trace.idle_share * 100.0
