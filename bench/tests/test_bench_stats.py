"""The arithmetic of the end-to-end metrics: percentiles over every request
of the window, and a rate over all of the window's time, so that a stall
anywhere in it moves them."""
import numpy as np
import pytest

from bench import registry
from bench.loops import closed, open_poisson
from bench.schedule import Requests
from bench.serving import Server
from bench.window import Window, percentile
from repro.api import KnnResult, KnnServeConfig, KnnServeEngine


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


class FakeEngine:
    def __init__(self):
        self.calls = 0

    def telemetry(self):
        class T:
            pass
        t = T()
        t.calls, t.queries = self.calls, 0
        t.latency = type("L", (), {"total": 0.0})()
        t.plan_cache = type("P", (), {"compiles": 0})()
        t.paths = type("Pa", (), dict(scan_eapca=0, scan_sax=0, pruned=0,
                                      forced_scan=0))()
        t.pruning = type("Pr", (), dict(sax_mean=0.0, eapca_mean=0.0))()
        t.ooc = None
        return t


class FakeServer:
    """Serves up to ``slots`` queued requests a wave; each wave advances the
    clock by ``wave_s``, and wave number ``stall_at`` by ``stall_s``."""

    def __init__(self, clock, wave_s=0.1, slots=4, stall_at=None,
                 stall_s=0.0):
        self.clock, self.wave_s, self.slots = clock, wave_s, slots
        self.stall_at, self.stall_s = stall_at, stall_s
        self.queue, self.results, self.next_id, self.waves = [], {}, 0, 0

    def submit(self, q, k=None):
        rid = self.next_id
        self.next_id += 1
        self.queue.append(rid)
        return rid

    def pending(self):
        return len(self.queue)

    def step(self):
        wave, self.queue = self.queue[:self.slots], self.queue[self.slots:]
        if not wave:
            return 0
        self.clock.t += self.wave_s
        if self.waves == self.stall_at:
            self.clock.t += self.stall_s
        self.waves += 1
        for rid in wave:
            self.results[rid] = type("A", (), dict(dists=np.zeros(1),
                                                   ids=np.zeros(1)))()
        return len(wave)

    def poll(self, rid):
        return self.results.pop(rid, None)


def requests(n, due=None):
    return Requests(hardness=("1%",) * n, k=(1,) * n, due=due,
                    queries=np.zeros((n, 4), np.float32))


def open_window(monkeypatch, **fake):
    clock = Clock()
    monkeypatch.setattr(open_poisson.time, "sleep", clock.sleep)
    srv = Server(FakeServer(clock, **fake), FakeEngine())
    due = np.arange(100) * 0.05                # 20 per second for 5 s
    return open_poisson.run(srv, requests(100, due), {}, 5.0, clock)


def test_open_loop_times_from_due(monkeypatch):
    w = open_window(monkeypatch)
    assert w.failed == 0 and w.answered.all()
    lat = w.latency_ms()
    # every request is answered by the wave that follows its due time
    assert np.all(lat >= 0) and np.all(lat <= 200 + 1e-6)
    assert w.end >= w.due[-1]


def test_a_stall_moves_the_tail(monkeypatch):
    base = open_poisson.end_to_end(open_window(monkeypatch))
    stalled = open_poisson.end_to_end(
        open_window(monkeypatch, stall_at=40, stall_s=1.0))
    assert stalled["latency_p95_ms"] > base["latency_p95_ms"] + 500
    # the stall delays every request due during it, and the queue behind it
    assert stalled["latency_p50_ms"] >= base["latency_p50_ms"]


def test_percentile_is_over_all_requests():
    lat = np.concatenate([np.full(90, 10.0), np.full(10, 1000.0)])
    w = Window(seconds=1.0, end=1.0, due=np.zeros(100), submit=np.zeros(100),
               start=np.zeros(100), done=lat / 1e3, answers=[(1, 1)] * 100,
               waves=[], before={}, rows=np.arange(100))
    m = open_poisson.end_to_end(w)
    assert m["latency_p50_ms"] == pytest.approx(10.0)
    assert m["latency_p95_ms"] == pytest.approx(np.percentile(lat, 95))
    # a median of chunk p95s would miss the slow tenth in 9 chunks of 10
    chunks = np.median([np.percentile(c, 95) for c in lat.reshape(10, 10)])
    assert m["latency_p95_ms"] > chunks


def closed_window(**fake):
    clock = Clock()
    srv = Server(FakeServer(clock, **fake), FakeEngine())
    return closed.run(srv, requests(80), {"outstanding": 8}, 2.0, clock)


def test_closed_loop_rate_over_whole_window():
    w = closed_window(wave_s=0.1, slots=4)
    m = closed.end_to_end(w)
    # 4 requests per 0.1 s wave: the set of 80 takes 20 waves, 2 s
    assert m["queries_per_s"] == pytest.approx(40.0)
    assert w.answered.all() and len(w.rows) == 80
    assert w.end == pytest.approx(2.0) and len(w.waves) == 20


def test_a_stall_moves_the_rate():
    base = closed.end_to_end(closed_window(wave_s=0.1, slots=4))
    stalled = closed.end_to_end(closed_window(wave_s=0.1, slots=4,
                                              stall_at=3, stall_s=0.5))
    assert stalled["queries_per_s"] < base["queries_per_s"] * 0.85


def test_closed_loop_stall_counts_missing():
    class Stuck(FakeServer):
        def step(self):
            return 0

    clock = Clock()
    srv = Server(Stuck(clock), FakeEngine())
    w = closed.run(srv, requests(100), {"outstanding": 8}, 1.0, clock)
    assert w.failed == 8 and len(w.rows) == 8


def test_percentile_needs_values():
    with pytest.raises(ValueError):
        percentile(np.zeros(0), 50)


def test_open_loop_metrics_listed_for_its_cells():
    # every end-to-end metric but set-up comes from the loop of each cell
    # that lists it
    bench = registry.benchmark()
    w = Window(seconds=1.0, end=1.0, due=np.zeros(4), submit=np.zeros(4),
               start=np.zeros(4), done=np.full(4, 0.5), answers=[(1, 1)] * 4,
               waves=[], before={}, rows=np.arange(4))
    for m in bench["end_to_end"]:
        if m["name"] == "setup_s":
            continue
        for cell in m["workloads"]:
            traffic = [c for c in bench["workloads"] if c["name"] == cell][0]
            loop = registry.load_module("loops", registry.load_json(
                "traffic", traffic["traffic"])["loop"])
            assert m["name"] in loop.end_to_end(w), (m["name"], cell)


class ByK(FakeServer):
    """Serves, like ``KnnServeEngine``, the queued requests of the head's k
    in order, and logs each wave as the queries it held."""

    def __init__(self, clock, **kw):
        super().__init__(clock, **kw)
        self.k, self.q, self.log = {}, {}, []

    def submit(self, q, k=None):
        rid = super().submit(q, k)
        self.k[rid], self.q[rid] = k, int(q[0])
        return rid

    def step(self):
        if not self.queue:
            return 0
        k = self.k[self.queue[0]]
        wave = [r for r in self.queue if self.k[r] == k][:self.slots]
        self.queue = [r for r in self.queue if r not in wave]
        self.clock.t += self.wave_s
        self.waves += 1
        self.log.append(tuple(self.q[r] for r in wave))
        for rid in wave:
            self.results[rid] = type("A", (), dict(dists=np.zeros(1),
                                                   ids=np.zeros(1)))()
        return len(wave)


class CostByK(ByK):
    """``ByK`` whose waves of k = 10 take ``slow`` times those of k = 1."""

    def __init__(self, clock, slow=3.5, **kw):
        super().__init__(clock, **kw)
        self.slow = slow

    def step(self):
        k = self.k[self.queue[0]] if self.queue else None
        served = super().step()
        if k == 10:
            self.clock.t += (self.slow - 1) * self.wave_s
        return served


def mixed_k(n, seed=3):
    ks = np.random.default_rng(seed).choice([1, 10], n)
    return Requests(hardness=("1%",) * n, k=tuple(int(k) for k in ks),
                    due=None,
                    queries=np.repeat(np.arange(n, dtype=np.float32)[:, None],
                                      4, axis=1))


def test_rehearsal_serves_the_windows_waves():
    clock = Clock()
    fake = ByK(clock, wave_s=0.1, slots=4)
    srv = Server(fake, FakeEngine())
    reqs = mixed_k(300)
    traffic = {"outstanding": 8}
    waves = closed.rehearse(srv, reqs, traffic, 2.0, clock)
    assert srv.outstanding() == 0
    rehearsed, fake.log = fake.log, []
    clock.t += 7.3
    w = closed.run(srv, reqs, traffic, 2.0, clock)
    # the whole set, in the same waves, some of them not full
    assert waves == len(w.waves) == len(rehearsed) and fake.log == rehearsed
    assert sorted(q for wave in rehearsed for q in wave) == list(range(300))
    assert min(len(wave) for wave in rehearsed) < 4


def test_closed_loop_rate_falls_with_every_slowdown():
    # waves of one k cost 3.5 times those of the other; a window of whole
    # waves up to a time would hold a count of them that jumps with speed
    # and read some slowdowns as gains; a fixed set cannot
    rates = []
    for scale in np.linspace(1.0, 1.3, 31):
        clock = Clock()
        srv = Server(CostByK(clock, wave_s=0.1 * scale, slots=4),
                     FakeEngine())
        w = closed.run(srv, mixed_k(200), {"outstanding": 8}, 2.0, clock)
        assert w.answered.all() and len(w.rows) == 200
        rates.append(closed.end_to_end(w)["queries_per_s"])
    assert np.all(np.diff(rates) < 0)


class Answers:
    """A query engine that answers at once."""

    def knn(self, q, k, valid_rows, **_):
        n = q.shape[0]
        per_query = np.zeros((n,), np.int32)
        return KnnResult(dists=np.zeros((n, k), np.float32),
                         positions=np.zeros((n, k), np.int32),
                         ids=np.zeros((n, k), np.int32), path=per_query,
                         eapca_pr=per_query, sax_pr=per_query,
                         accessed=per_query, visited_leaves=per_query)


def test_front_end_serves_the_same_waves_at_any_speed():
    # the program's own front end packs a closed loop's waves by the order
    # of the requests alone, so the rehearsal meets the window's shapes
    def waves(wave_s):
        clock = Clock()

        class Timed(KnnServeEngine):
            def step(self):
                clock.t += wave_s
                return super().step()

        srv = Server(Timed(Answers(), KnnServeConfig(batch_slots=32)),
                     FakeEngine())
        w = closed.run(srv, mixed_k(500), {"outstanding": 64}, 2.0, clock)
        assert w.answered.all() and len(w.rows) == 500
        return [tuple(w.rows[w.start == t]) for t in np.unique(w.start)]

    fast, slow = waves(0.1), waves(0.37)
    assert fast == slow and len(fast) > 500 // 32
