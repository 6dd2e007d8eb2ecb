"""One run of one cell: set up, warm up, measure, check, report.

The order is fixed. Set-up makes the collection and the requests on the
device from the seed, stands the deployment up, and warms up every shape
the cell's traffic uses: with warm-up waves of requests of its own, or,
where the traffic asks for it, by rehearsing the window's own requests
(``bench/loops/closed.py``); ``setup_s`` runs from the process start to
here.
The loop then measures for ``seconds`` (traced with ``--trace 1``). After
the window the device's peak memory is read, the program's state is freed,
the collection is made again from the seed, and every answer of the window
is compared with the plain reference. The last line of standard output is
the result; the last lines of standard error are the numbers compared,
each beside its limit.
"""
from __future__ import annotations

import gc
import json
import os
import shutil
import sys
import time

import jax
import numpy as np

from bench import peaks, reference, registry, schedule, synth, trace
from bench.meter import CompileMeter, part, span
from bench.readers import Context
from bench.serving import Server
from bench.window import warm_up

TRACE_DIR = os.path.join(registry.BENCH, ".traces")
CHECKS = ("missing", "dist_gap", "id_gap")
KERNELS = ("lb_sax",)          # kernels whose device time the trace sums


class NoChip(RuntimeError):
    """The run found no accelerator, or fewer chips than the cell asks."""


def require_chips(chips: int) -> list:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"needs {chips} chips; JAX found {len(devices)}")
    return devices[:chips]


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def emit(obj: dict, out=None) -> None:
    print(json.dumps(obj), file=out or sys.stdout, flush=True)


def run(cell: dict, seed: int, seconds: float, traced: bool, *,
        t_start: float, devices: list, clock=time.perf_counter) -> dict:
    """Run the cell once; returns the result line's object. Earlier lines
    (set-up parts, the loop's lateness) go to standard output as they come."""
    cfg, traffic = cell["config"], cell["traffic"]
    deploy = registry.load_module("deploy", cfg["deployment"])
    loop = registry.load_module("loops", traffic["loop"])
    slots = int(cfg.get("serve", {}).get("batch_slots", 32))
    meter = CompileMeter()
    parts: dict = {}

    def setup_part(name):
        return part(parts, name, meter, clock)

    key = schedule.prng_key(seed)
    rehearse = bool(traffic.get("rehearse"))
    with setup_part("data"):
        data = synth.collection(key, cfg["num_series"], cfg["series_len"])
        reqs = schedule.make_requests(traffic, seed, seconds, data,
                                      count=loop.count(traffic, seconds))
        warm = None if rehearse else schedule.make_requests(
            traffic, seed, seconds, data,
            count=loop.warm_count(traffic, slots), stream=1)
        jax.block_until_ready(data)
    dep = deploy.setup(cfg, data, setup_part)
    del data
    try:
        srv = Server(dep.server, dep.engine)
        warm_all(srv, loop, traffic, reqs if rehearse else warm, seconds,
                 slots, setup_part, meter, clock)
        setup_s = clock() - t_start
        emit({"setup_s": setup_s, "setup_parts": parts})
        log_dir = os.path.join(TRACE_DIR, f"{cell['workload']['name']}-{seed}")
        profiler = {}
        if traced:
            shutil.rmtree(log_dir, ignore_errors=True)
            t_tr = clock()
            jax.profiler.start_trace(log_dir,
                                     profiler_options=trace.profile_options())
            profiler["trace_start_s"] = clock() - t_tr
        mark = meter.mark()
        try:
            window = loop.run(srv, reqs, traffic, seconds, clock)
        finally:
            if traced:
                t_tr = clock()
                jax.profiler.stop_trace()
                profiler["trace_stop_s"] = clock() - t_tr
        compiles = meter.since(mark)
        peak = memory_peak(devices)
    finally:
        dep.close()
        srv = dep = None
        gc.collect()
    emit({"window_s": window.end, "waves": len(window.waves),
          "requests": len(window.rows), "window_compiles": compiles,
          **loop.end_to_end(window), **window.lateness(), **profiler})

    t_ref = clock()
    with span("bench.reference"):
        data = synth.collection(key, cfg["num_series"], cfg["series_len"])
        ks = [reqs.k[i] for i in window.rows]
        checks = reference.compare(data, reqs.queries[window.rows], ks,
                                   window.answers)
        del data
    emit({"reference_s": clock() - t_ref})

    limits = cfg["limits"]
    dev = devices[0]
    result = {"correct": False, "attempted": len(window.rows),
              "failed": window.failed, "metrics": {},
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(jax.devices()),
                         "memory_peak_bytes": peak}}
    if traced:
        t_red = clock()
        red = trace.reduce(trace.load(log_dir), kernels=KERNELS)
        shutil.rmtree(log_dir, ignore_errors=True)
        emit({"trace_reduce_s": clock() - t_red,
              "kernel_events": red.kernel_events,
              "kernel_ops": red.kernel_ops})
        result["device"].update(busy_s=red.busy_s, window_s=red.window_s)
        result["breakdown"] = {"device_ops": red.ops,
                               "idle_gaps": red.idle_gaps}
        ctx = Context(cell=cell, window=window, trace=red,
                      peak=peaks.peaks(dev.device_kind), compiles=compiles)
        for m in cell["per_layer"]:
            value = registry.load_module("metrics", m["name"]).read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
    else:
        found = loop.end_to_end(window)
        found["setup_s"] = setup_s
        for m in cell["end_to_end"]:
            if m["name"] in found:
                result["metrics"][m["name"]] = {"value": found[m["name"]],
                                                "unit": m["unit"]}
    ok = all(float(checks[c]) <= float(limits[c]) for c in CHECKS)
    result["correct"] = bool(ok and result["attempted"] > 0
                             and result["failed"] == 0)
    result["checks"] = {c: {"value": _finite(checks[c]), "limit": limits[c]}
                        for c in CHECKS}
    return result


def warm_all(srv, loop, traffic: dict, reqs, seconds: float, slots: int,
             part, meter, clock) -> None:
    """Every shape the window will meet: the window's own requests
    rehearsed where the traffic asks for it, else warm-up waves of
    ``reqs``."""
    if traffic.get("rehearse"):
        with part("rehearsal"):
            loop.rehearse(srv, reqs, traffic, seconds, clock)
    else:
        warm_up(srv, reqs, slots, part, meter)


def _finite(x):
    """A number JSON can hold: a gap that is not finite reads "inf"."""
    x = float(x)
    return x if np.isfinite(x) else "inf"


def report_checks(result: dict, err=None) -> None:
    err = err or sys.stderr
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=err)
    print(f"check attempted {result['attempted']} failed {result['failed']} "
          f"limit 0", file=err, flush=True)
