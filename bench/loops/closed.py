"""Closed loop: a fixed number of requests outstanding, over a fixed set.

One thread keeps the traffic's ``outstanding`` requests queued while the
set lasts: it serves one wave, records when each answer came, and submits
as many new requests as were answered. The window serves the whole set and
ends with its last answer, so it times a fixed amount of work, as the
paper's protocol does: a slower program reads a lower rate, whatever its
waves cost and however they fall. The set holds
``requests_per_window_second`` x ``seconds`` requests, a rate the cell
reads today (PERF.md), so the window lasts about ``seconds``. If a wave
serves nothing while requests are queued, the loop stops and those
requests count as missing.

End-to-end metric: ``queries_per_s``, the requests answered over the
window's length.

Which requests ride together in a wave depends only on their order, not
on time, so ``rehearse`` (set-up, where the traffic asks for it) serves
the set once the way the window will and compiles every shape the
window's waves will meet. The out-of-core plan pads its blocks to lengths
that follow the data, so a warm-up on other requests leaves some of them
to compile inside the window. A closed-loop traffic therefore sets
``rehearse``; this loop has no warm-up waves of its own.
"""
from __future__ import annotations

import numpy as np

from bench.meter import span
from bench.serving import Wave
from bench.window import Window


def run(srv, reqs, traffic: dict, seconds: float, clock) -> Window:
    with span("bench.window"):
        return _serve_set(srv, reqs, traffic, seconds, clock)


def rehearse(srv, reqs, traffic: dict, seconds: float, clock) -> int:
    """Serve the set once, as ``run`` will; returns the number of waves."""
    with span("bench.rehearsal"):
        return len(_serve_set(srv, reqs, traffic, seconds, clock).waves)


def _serve_set(srv, reqs, traffic: dict, seconds: float, clock) -> Window:
    n = len(reqs)
    depth = int(traffic["outstanding"])
    due = np.full(n, np.nan)
    start = np.full(n, np.nan)
    done = np.full(n, np.nan)
    answers: list = [None] * n
    waves = []
    before = srv.counters()
    nxt = 0

    def top_up(now: float) -> None:
        nonlocal nxt
        while srv.outstanding() < depth and nxt < n:
            srv.submit(nxt, reqs.queries[nxt], reqs.k[nxt])
            due[nxt] = now
            nxt += 1

    t0 = clock()
    top_up(0.0)
    while srv.outstanding():
        w0 = clock() - t0
        served = srv.step()
        w1 = clock() - t0
        if not served:
            break
        for i, ans in served:
            start[i], done[i], answers[i] = w0, w1, ans
        waves.append(Wave(w0, w1, len(served), srv.counters()))
        top_up(w1)
    end = clock() - t0
    keep = ~np.isnan(due)
    kept_answers = [a for a, ok in zip(answers, keep) if ok]
    done_k = done[keep]
    done_k[[a is None for a in kept_answers]] = np.nan
    return Window(seconds=seconds, end=end, due=due[keep], submit=due[keep],
                  start=start[keep], done=done_k, answers=kept_answers,
                  waves=waves, before=before, rows=np.flatnonzero(keep))


def end_to_end(window: Window) -> dict:
    if window.end <= 0:
        return {}
    return {"queries_per_s": float(window.answered.sum()) / window.end}


def count(traffic: dict, seconds: float) -> int:
    return max(1, round(float(traffic["requests_per_window_second"])
                        * seconds))
