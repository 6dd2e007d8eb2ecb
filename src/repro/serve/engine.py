"""Slot-based continuous-batching serving loops (single host).

Two workload-specific engines share one execution model — a fixed pool of B
slots served by one compiled program per wave, with finished requests freeing
their slot for the next queued request:

* :class:`ServeEngine` — batched LM decode (prefill + per-token decode steps
  over any ModelDef), the production context the dry-run's ``prefill_32k`` /
  ``decode_32k`` cells lower.
* :class:`KnnServeEngine` — batched exact kNN over a
  :class:`repro.core.engine.QueryEngine`: queued queries are drained in
  waves of ``batch_slots``, each wave padded to the slot count so every wave
  hits the engine's compiled-plan cache (one plan for the whole serving
  session).

Both inherit the submit/poll bookkeeping from :class:`SlotQueue`.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.sanitize import ThreadAffinity
from repro.core.engine import ServingTelemetry
from repro.core.probes import HostSyncs, span
from repro.models import ModelDef
from repro.models.arch import ArchConfig


class SlotQueue:
    """Request bookkeeping shared by the slot-based engines: monotonically
    increasing request ids, a FIFO of pending payloads, a result map.

    Results are *claimed*: ``poll``/``drain``/``run`` hand each answer out
    exactly once and drop it from the engine, so a long-running serving
    session does not accumulate its whole answer history in memory.

    The queue is lock-free **by contract**: exactly one thread drives
    submit/step/drain/poll. Under ``REPRO_SANITIZE=1`` the contract is
    enforced — the queue binds to the first touching thread and a foreign
    touch raises ``ThreadOwnershipError`` with both stacks (lockdep's
    ownership half). Use :meth:`rebind_owner` for an intentional handoff.
    """

    def __init__(self):
        self._queue: list[dict] = []
        self._results: dict[int, Any] = {}
        self._next_id = 0
        self._served = 0
        self._affinity = ThreadAffinity(type(self).__name__)

    def rebind_owner(self) -> None:
        """Hand the queue to another thread (releases the sanitizer's
        thread binding; the next touch binds the new owner)."""
        self._affinity.rebind()

    def _enqueue(self, payload: dict) -> int:
        self._affinity.check("_enqueue")
        rid = self._next_id
        self._next_id += 1
        payload["id"] = rid
        self._queue.append(payload)
        return rid

    def _take_wave(self, slots: int) -> list[dict]:
        self._affinity.check("_take_wave")
        wave, self._queue = self._queue[:slots], self._queue[slots:]
        return wave

    def _requeue(self, wave: list[dict]) -> None:
        self._affinity.check("_requeue")
        self._queue[:0] = wave

    def _complete(self, rid: int, result) -> None:
        self._affinity.check("_complete")
        self._results[rid] = result
        self._served += 1

    def _collect(self) -> dict[int, Any]:
        self._affinity.check("_collect")
        out, self._results = self._results, {}
        return out

    def pending(self) -> int:
        """Requests submitted but not yet answered."""
        return len(self._queue)

    def poll(self, rid: int):
        """Claim the result for ``rid``: returns it once, then None (also
        None while the request is still queued)."""
        self._affinity.check("poll")
        return self._results.pop(rid, None)


# ---------------------------------------------------------------------------
# LM decode serving
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_seq: int = 4096
    batch_slots: int = 8
    max_new_tokens: int = 64
    eos_token: int = -1            # -1: disabled
    temperature: float = 0.0       # 0 => greedy


def greedy_sample(logits: jax.Array, key=None, temperature: float = 0.0):
    if temperature and temperature > 0.0:
        return jax.random.categorical(key, logits / temperature, axis=-1)
    return jnp.argmax(logits, axis=-1)


class ServeEngine(SlotQueue):
    """Slot-based batch server over any ModelDef."""

    def __init__(self, model: ModelDef, cfg: ArchConfig, params: dict,
                 scfg: ServeConfig):
        super().__init__()
        self.model = model
        self.cfg = cfg
        self.params = params
        self.scfg = scfg
        self._decode = jax.jit(
            lambda p, t, c: model.decode_step(p, t, cfg, c))

    def submit(self, prompt: np.ndarray, extras: dict | None = None) -> int:
        return self._enqueue({"prompt": np.asarray(prompt),
                              "extras": extras or {}})

    def _prefill_batch(self, requests: list[dict]):
        """Batched prefill over ragged prompts: shorter prompts are
        right-padded with token 0 to the batch max. ``batch["lens"]``
        carries each request's real prompt length so the model projects
        logits at position ``lens[i]-1`` — sampling from the batch-max
        column would read a pad slot for any shorter prompt."""
        b = len(requests)
        lens = np.array([r["prompt"].shape[0] for r in requests], np.int32)
        maxlen = int(lens.max())
        toks = np.zeros((b, maxlen), np.int32)
        for i, r in enumerate(requests):
            toks[i, :r["prompt"].shape[0]] = r["prompt"]
        batch = {"tokens": jnp.asarray(toks)}
        if lens.min() != maxlen:
            batch["lens"] = jnp.asarray(lens)
        for k in requests[0]["extras"]:
            batch[k] = jnp.stack([jnp.asarray(r["extras"][k]) for r in requests])
        cache = self.model.init_cache(self.cfg, b, self.scfg.max_seq)
        logits, cache = self.model.prefill(self.params, batch, self.cfg, cache)
        return logits, cache

    def run(self) -> dict[int, list[int]]:
        """Drain the queue in waves of ``batch_slots``; returns {id: tokens}."""
        scfg = self.scfg
        while self._queue:
            wave = self._take_wave(scfg.batch_slots)
            logits, cache = self._prefill_batch(wave)
            # prefill projects each row's *last real token* (causal attention
            # keeps position lens[i]-1 independent of the pads to its right),
            # so logits[:, -1] is the correct sampling column for every row
            tok = greedy_sample(logits[:, -1], temperature=scfg.temperature)
            out = [[int(t)] for t in np.asarray(tok)]
            live = np.ones(len(wave), bool)
            for _ in range(scfg.max_new_tokens - 1):
                tok2d = tok[:, None].astype(jnp.int32)
                logits, cache = self._decode(self.params, tok2d, cache)
                tok = greedy_sample(logits[:, 0], temperature=scfg.temperature)
                t_np = np.asarray(tok)
                for i in range(len(wave)):
                    if live[i]:
                        out[i].append(int(t_np[i]))
                        if scfg.eos_token >= 0 and t_np[i] == scfg.eos_token:
                            live[i] = False
                if not live.any():
                    break
            for r, o in zip(wave, out):
                self._complete(r["id"], o)
        return self._collect()


# ---------------------------------------------------------------------------
# kNN query serving
# ---------------------------------------------------------------------------

class QueueFull(RuntimeError):
    """Admission control rejected a ``submit``: the pending queue is at
    ``KnnServeConfig.max_queue``. The backpressure signal — callers should
    serve a wave (``step``) or drain before resubmitting."""


@dataclasses.dataclass(frozen=True)
class KnnServeConfig:
    batch_slots: int = 32          # queries per wave (the slot pool)
    k: int | None = None           # None -> the backend's configured k
    wave: bool = False             # serve waves through the fused wave path
    max_queue: int | None = None   # admission bound; None = unbounded
    pack: str = "fifo"             # wave packing: "fifo" | "difficulty"

    def __post_init__(self):
        if not isinstance(self.batch_slots, int) or self.batch_slots < 1:
            raise ValueError(f"batch_slots={self.batch_slots!r}; "
                             "expected an int >= 1")
        if self.max_queue is not None and self.max_queue < 1:
            raise ValueError(f"max_queue={self.max_queue!r}; expected None "
                             "or an int >= 1")
        if self.pack not in ("fifo", "difficulty"):
            raise ValueError(f"pack={self.pack!r}; expected 'fifo' or "
                             "'difficulty'")


class KnnAnswer(NamedTuple):
    dists: np.ndarray              # (k,) squared ED, ascending
    ids: np.ndarray                # (k,) series ids
    path: int                      # access path taken (-1 when unknown)


class KnnFailure(NamedTuple):
    """Claimable per-request failure (``poll``/``drain`` hand it out like
    an answer): the request was invalid or the engine rejected it, and the
    rest of its wave was served normally."""
    error: str                     # "ExceptionType: message"


class KnnServeEngine(SlotQueue):
    """Continuous-batching front end for a :class:`QueryEngine`.

    ``submit`` enqueues one query series and returns a request id; ``step``
    serves one wave of up to ``batch_slots`` *compatible* queued queries
    through the engine (the wave is padded to the slot count, so a
    long-running session compiles exactly one plan per (k, slot-count));
    ``drain`` steps until the queue is empty and returns every completed
    answer.

    Mixed traffic: requests are grouped into compatible sub-waves by their
    ``(k, overrides)`` signature — the head request's signature selects each
    wave, so interleaved k=1/k=10 submits serve in submission order, one
    signature per step, instead of erroring. A request that still fails solo
    (wrong series length, bad override) completes as a claimable
    :class:`KnnFailure` and never blocks the traffic behind it.

    QoS knobs (:class:`KnnServeConfig`): ``wave=True`` routes each wave
    through the engine's fused wave plan (shared descent/BSF/disk fetches);
    ``max_queue`` bounds the pending queue, rejecting further submits with
    :class:`QueueFull` (the backpressure signal); ``pack="difficulty"``
    packs each wave with the compatible peers closest in predicted cost to
    the oldest request (``QueryEngine.estimate_difficulty``), so cheap
    queries are not latency-coupled to expensive wave-mates — while the
    oldest request always ships first, which is the anti-starvation
    guarantee.

    Each request is stamped with ``clock()`` on ``submit``; when its wave
    is taken, the time from its submit to the wave's start adds to
    ``serving.queue_wait_s`` and the request to ``serving.dequeued``.
    """

    def __init__(self, engine, cfg: KnnServeConfig | None = None, *,
                 clock: Callable[[], float] = time.perf_counter):
        super().__init__()
        self.engine = engine
        self.cfg = cfg or KnnServeConfig()
        self._clock = clock
        self._syncs = HostSyncs()
        self._rejected = 0
        self._failed = 0
        self._waves = 0
        self._scored = 0
        self._score_sum = 0.0
        self._queue_wait_s = 0.0
        self._dequeued = 0

    def submit(self, query: np.ndarray, k: int | None = None,
               **overrides: Any) -> int:
        q = np.asarray(query)
        if q.ndim != 1:
            raise ValueError(f"submit() takes one query series, got {q.shape}")
        if (self.cfg.max_queue is not None
                and len(self._queue) >= self.cfg.max_queue):
            self._rejected += 1
            raise QueueFull(f"pending queue at max_queue="
                            f"{self.cfg.max_queue}; step() or drain() first")
        return self._enqueue({"q": q, "k": k, "ov": overrides, "score": None,
                              "t": self._clock()})

    @staticmethod
    def _sig(r: dict) -> tuple:
        """Compatibility signature: requests sharing it can ride one wave
        (one compiled plan, one SearchConfig)."""
        return (r["k"], tuple(sorted(r["ov"].items())))

    def _score(self, reqs: list[dict]) -> None:
        """Attach a predicted-cost score to each unscored request (cached on
        the payload — a request is scored at most once per lifetime)."""
        todo = [r for r in reqs if r["score"] is None]
        if not todo:
            return
        try:
            scores = self.engine.estimate_difficulty(
                np.stack([r["q"] for r in todo]))
        except ValueError:  # ragged/invalid queries surface at serve time
            scores = None
        if scores is None:
            for r in todo:
                r["score"] = 0.0
            return
        for r, s in zip(todo, np.asarray(scores)):
            r["score"] = float(s)
            self._score_sum += float(s)
            self._scored += 1

    def _next_wave(self) -> list[dict]:
        """Up to ``batch_slots`` compatible requests. The head (oldest)
        request's signature selects the sub-wave; with ``pack="difficulty"``
        it is joined by the compatible peers closest to its predicted cost
        instead of strict FIFO order."""
        if not self._queue:
            return []
        head = self._queue[0]
        sig = self._sig(head)
        compat = [r for r in self._queue if self._sig(r) == sig]
        if self.cfg.pack == "difficulty" and len(compat) > self.cfg.batch_slots:
            self._score(compat)
            peers = sorted(compat[1:],
                           key=lambda r: abs(r["score"] - head["score"]))
            wave = [head] + peers[:self.cfg.batch_slots - 1]
        else:
            wave = compat[:self.cfg.batch_slots]
        taken = {id(r) for r in wave}
        self._queue = [r for r in self._queue if id(r) not in taken]
        return wave

    def step(self) -> int:
        """Serve one compatible sub-wave; returns the number of requests
        answered (failures included — each completes as a claimable
        :class:`KnnFailure`). Never livelocks: every selected request
        leaves the queue with a result, success or not."""
        with span("repro.serve.step"):
            start = self._clock()
            with span("repro.serve.pack"):
                wave = self._next_wave()
            if not wave:
                return 0
            self._queue_wait_s += sum(start - r["t"] for r in wave)
            self._dequeued += len(wave)
            try:
                self._serve(wave)
            except Exception:
                # head-of-line isolation: one bad request (wrong length, bad
                # override) must not poison its wave-mates — serve each
                # member solo, completing the ones that still fail as
                # failures
                for r in wave:
                    try:
                        self._serve([r])
                    except Exception as e:
                        self._failed += 1
                        self._complete(r["id"],
                                       KnnFailure(f"{type(e).__name__}: {e}"))
            self._waves += 1
            return len(wave)

    def _serve(self, wave: list[dict]) -> None:
        slots = self.cfg.batch_slots
        k = wave[0]["k"] if wave[0]["k"] is not None else self.cfg.k
        ov = wave[0]["ov"]
        with span("repro.serve.pack"):
            q = np.stack([r["q"] for r in wave])
            if len(wave) < slots:  # pad the partial wave to the slot pool
                q = np.concatenate(
                    [q, np.zeros((slots - len(wave), q.shape[1]), q.dtype)])
            q = jnp.asarray(q)
        res = self.engine.knn(q, k=k, valid_rows=len(wave),
                              wave=self.cfg.wave, **ov)
        with span("repro.serve.answer"):
            dists = self._syncs.read(res.dists)
            ids = self._syncs.read(res.ids)
            paths = self._syncs.read(res.path)
            for i, r in enumerate(wave):
                self._complete(r["id"], KnnAnswer(
                    dists=dists[i], ids=ids[i], path=int(paths[i])))

    def drain(self) -> dict[int, KnnAnswer | KnnFailure]:
        """Serve until the queue is empty; returns (and claims) every
        unclaimed completed answer (failed requests as KnnFailure)."""
        while self.step():
            pass
        return self._collect()

    def telemetry(self):
        """The engine's :class:`repro.core.engine.Telemetry` with the
        ``serving`` section filled in, and this front end's reads of each
        wave's answers added to ``host_syncs``."""
        t = self.engine.telemetry()
        t.host_syncs += self._syncs.count
        t.serving = ServingTelemetry(
            pending=self.pending(),
            served=self._served,
            unclaimed=len(self._results),
            batch_slots=self.cfg.batch_slots,
            waves=self._waves,
            wave_mode=self.cfg.wave,
            pack=self.cfg.pack,
            max_queue=self.cfg.max_queue,
            rejected=self._rejected,
            failed=self._failed,
            difficulty_scored=self._scored,
            difficulty_mean=self._score_sum / max(self._scored, 1),
            queue_wait_s=self._queue_wait_s,
            dequeued=self._dequeued)
        return t
