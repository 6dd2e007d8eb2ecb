"""What a loop hands back, the statistics over it, and the shared warm-up.

Each statistic is taken over all requests of the window and over its whole
length, never as a median of chunks.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from bench.meter import span


@dataclasses.dataclass
class Window:
    """One measured window. Times are seconds since it opened; a request
    that never got an answer has ``done`` NaN."""
    seconds: float                # the length asked for
    end: float                    # when its last answer came
    due: np.ndarray               # per request: when it was due
    submit: np.ndarray            # when the loop submitted it
    start: np.ndarray             # when its wave started
    done: np.ndarray              # when its answer came
    answers: list                 # (dists, ids), or None if failed/missing
    waves: list                   # bench.serving.Wave, in order
    before: dict                  # engine counters as the window opened
    rows: np.ndarray              # the requests these entries are

    @property
    def answered(self) -> np.ndarray:
        return ~np.isnan(self.done)

    @property
    def failed(self) -> int:
        return int(sum(a is None for a in self.answers))

    def latency_ms(self) -> np.ndarray:
        ok = self.answered
        return (self.done[ok] - self.due[ok]) * 1e3

    def lateness(self) -> dict:
        """How late the loop submitted requests after they were due."""
        late = (self.submit - self.due)[~np.isnan(self.submit)] * 1e3
        if late.size == 0:
            return {}
        return {"submit_late_p50_ms": float(np.percentile(late, 50)),
                "submit_late_p95_ms": float(np.percentile(late, 95)),
                "submit_late_max_ms": float(late.max())}

    def delta(self, key: str) -> float | None:
        """A counter's change over the window; None if the engine has no
        such counter."""
        if key not in self.before or not self.waves:
            return None
        return self.waves[-1].counters[key] - self.before[key]


def percentile(values: np.ndarray, q: float) -> float:
    """The q-th percentile of all values, linear between order
    statistics (numpy's default)."""
    if len(values) == 0:
        raise ValueError("no values")
    return float(np.percentile(np.asarray(values, np.float64), q))


def serve_all(srv, reqs, rows: list) -> None:
    """Submit requests ``rows`` and serve them to the end."""
    for i in rows:
        srv.submit(i, reqs.queries[i], reqs.k[i])
    while srv.outstanding():
        if not srv.step():
            raise RuntimeError("the server served nothing while requests "
                               "were outstanding")


def warm_up(srv, reqs, slots: int, part, meter) -> None:
    """Load or compile each k's plan with one full wave, then serve the
    further full waves that ``reqs`` holds until, for each k, one compiles
    no program (a program read back from the persistent cache counts as
    none)."""
    by_k = {}
    for i, k in enumerate(reqs.k):
        by_k.setdefault(k, []).append(i)
    waves = {k: [rows[lo:lo + slots]
                 for lo in range(0, len(rows) - slots + 1, slots)]
             for k, rows in sorted(by_k.items())}
    with part("plans"):
        for k in waves:
            serve_all(srv, reqs, waves[k][0])
            # A wave that is not full comes back from the engine cut to its
            # requests, one eager slice per result field; each fill is a
            # shape of its own, so the cuts are made here, on a full result.
            full = srv.engine.knn(jnp.asarray(reqs.queries[waves[k][0]]),
                                  k=k)
            for fill in range(1, slots):
                jax.block_until_ready([a[:fill] for a in full])
    # the further waves interleave the k values, as the traffic does
    depth = max(len(w) for w in waves.values())
    rest = [waves[k][j] for j in range(1, depth) for k in waves
            if j < len(waves[k])]
    with part("warmup"):
        quiet = 0
        for rows in rest:
            if quiet >= len(waves):
                break
            m0 = meter.mark()
            with span("bench.warmup_wave"):
                serve_all(srv, reqs, rows)
            quiet = quiet + 1 if meter.since(m0)["compiled"] == 0 else 0
