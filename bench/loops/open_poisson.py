"""Open loop: requests due at the traffic's fixed Poisson rate.

One thread: submit every request that is due, serve one wave, record when
each answer came, and sleep only while the queue is empty. A request is
timed from its due time, so a long wave delays every request due during
it. The window holds the requests due in ``[0, seconds)``; once they are
all submitted the loop serves until each has its answer, or until
``GRACE_S`` past the close.

End-to-end metrics: ``latency_p50_ms`` and ``latency_p95_ms``, over every
answered request due in the window.
"""
from __future__ import annotations

import time

import numpy as np

from bench.meter import span
from bench.serving import Wave
from bench.window import Window, percentile

GRACE_S = 60.0


def run(srv, reqs, traffic: dict, seconds: float, clock) -> Window:
    n = len(reqs)
    due = np.asarray(reqs.due, np.float64)
    submit = np.full(n, np.nan)
    start = np.full(n, np.nan)
    done = np.full(n, np.nan)
    answers: list = [None] * n
    waves = []
    before = srv.counters()
    t0 = clock()
    nxt = 0
    with span("bench.window"):
        while True:
            now = clock() - t0
            while nxt < n and due[nxt] <= now:
                srv.submit(nxt, reqs.queries[nxt], reqs.k[nxt])
                submit[nxt] = clock() - t0
                nxt += 1
            if srv.outstanding():
                w0 = clock() - t0
                served = srv.step()
                w1 = clock() - t0
                for i, ans in served:
                    start[i], done[i], answers[i] = w0, w1, ans
                waves.append(Wave(w0, w1, len(served), srv.counters()))
            elif nxt < n:
                wait = due[nxt] - (clock() - t0)
                if wait > 0:
                    with span("bench.idle_wait"):
                        time.sleep(wait)
            else:
                break
            if clock() - t0 > seconds + GRACE_S:
                break
        end = clock() - t0
    done[[a is None for a in answers]] = np.nan
    return Window(seconds=seconds, end=end, due=due, submit=submit,
                  start=start, done=done, answers=answers, waves=waves,
                  before=before, rows=np.arange(n))


def end_to_end(window: Window) -> dict:
    lat = window.latency_ms()
    if lat.size == 0:
        return {}
    return {"latency_p50_ms": percentile(lat, 50),
            "latency_p95_ms": percentile(lat, 95)}


def warm_count(traffic: dict, slots: int) -> int:
    return int(traffic["warmup_waves"]) * slots * len(traffic["k"])


def count(traffic: dict, seconds: float) -> int | None:
    return None          # the rate and the window's length set it
