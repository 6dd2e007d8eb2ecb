"""Closed loop: a fixed number of requests outstanding.

One thread keeps the traffic's ``outstanding`` requests queued: it serves
one wave, records when each answer came, and submits as many new requests
as were answered. Waves start until ``seconds`` have passed; the window
ends when the last of them ends, so it holds whole waves only. Requests
still queued then are withdrawn, not failed; but if a wave serves nothing
while requests are queued, the loop stops and those requests count as
missing.

End-to-end metric: ``queries_per_s``, the requests answered in the window
over the window's length.

Which requests ride together in a wave depends only on their order, not
on time, so ``rehearse`` (set-up, where the traffic asks for it) serves
the run's own requests the way the window will and compiles every shape
the window's waves will meet. The out-of-core plan pads its blocks to
lengths that follow the data, so a warm-up on other requests leaves some
of them to compile inside the window. A closed-loop traffic therefore
sets ``rehearse``; this loop has no warm-up waves of its own.
"""
from __future__ import annotations

import numpy as np

from bench.meter import span
from bench.serving import Wave
from bench.window import Window


def run(srv, reqs, traffic: dict, seconds: float, clock) -> Window:
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
        while srv.outstanding() < depth:
            if nxt >= n:
                raise RuntimeError(f"the traffic's pool of {n} requests ran "
                                   f"out; raise its 'pool'")
            srv.submit(nxt, reqs.queries[nxt], reqs.k[nxt])
            due[nxt] = now
            nxt += 1

    stalled = False
    t0 = clock()
    with span("bench.window"):
        top_up(0.0)
        while clock() - t0 < seconds:
            w0 = clock() - t0
            served = srv.step()
            w1 = clock() - t0
            if not served:
                stalled = True
                break
            for i, ans in served:
                start[i], done[i], answers[i] = w0, w1, ans
            waves.append(Wave(w0, w1, len(served), srv.counters()))
            top_up(w1)
        end = clock() - t0
    taken = ~np.isnan(due)
    keep = taken if stalled else taken & ~np.isnan(done)
    withdrawn = int(np.sum(taken & ~keep))
    kept_answers = [a for a, ok in zip(answers, keep) if ok]
    done_k = done[keep]
    done_k[[a is None for a in kept_answers]] = np.nan
    return Window(seconds=seconds, end=end, due=due[keep], submit=due[keep],
                  start=start[keep], done=done_k, answers=kept_answers,
                  waves=waves, before=before, withdrawn=withdrawn,
                  rows=np.flatnonzero(keep))


def rehearse(srv, reqs, traffic: dict, seconds: float, clock, meter) -> int:
    """Serve the run's requests from the first, as ``run`` will, until
    ``seconds`` have passed since the last wave that compiled a program
    (at most ``rehearse_max_s``), then serve what is queued to the end.
    Returns the number of waves served."""
    n = len(reqs)
    depth = int(traffic["outstanding"])
    cap = float(traffic.get("rehearse_max_s", 4 * seconds))
    nxt = waves = 0
    t0 = quiet_since = clock()
    with span("bench.rehearsal"):
        while True:
            now = clock()
            if now - quiet_since < seconds and now - t0 < cap:
                while srv.outstanding() < depth and nxt < n:
                    srv.submit(nxt, reqs.queries[nxt], reqs.k[nxt])
                    nxt += 1
            if not srv.outstanding():
                return waves
            mark = meter.mark()
            if not srv.step():
                return waves
            waves += 1
            if meter.since(mark)["compiled"]:
                quiet_since = clock()


def end_to_end(window: Window) -> dict:
    if window.end <= 0:
        return {}
    return {"queries_per_s": float(window.answered.sum()) / window.end}


def count(traffic: dict) -> int:
    return int(traffic["pool"])
