#!/usr/bin/env python3
"""Find the knee of open-loop traffic on one configuration, on the chip.

    python3 bench/sweep.py --config synth256-hbm --traffic easy-open \\
        --traffic hard-open --seed 5 --seconds 20 --out chiprun_out/sweep.jsonl

Builds the configuration once, then for each traffic: serves full waves to
time them (capacity = slots / wave time), and runs the open loop for
``--seconds`` at fixed fractions of that capacity. Each rate's line gives
the latency percentiles, how many of the window's requests were still
unanswered when the last one was due (the backlog), and how the latency of
the window's last third compares with its first third. The knee is the
highest rate whose backlog does not grow over the window; the traffic file
then takes 0.8 of it. This is a tool for choosing a cell's rate, not a
cell: the benchmark's runs never search for a rate.
"""
import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
FRACTIONS = (0.5, 0.7, 0.8, 0.9, 1.0, 1.1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", action="append", required=True)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

    import jax
    import numpy as np

    from bench import harness, registry, schedule, synth
    from bench.meter import CompileMeter, part
    from bench.run import use_own_cache
    from bench.serving import Server
    from bench.window import percentile, serve_all, warm_up

    harness.require_chips(1)
    use_own_cache(jax)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    out = open(args.out, "a")

    def emit(obj):
        harness.emit(obj)
        print(json.dumps(obj), file=out, flush=True)

    cfg = registry.load_json("configs", args.config)
    deploy = registry.load_module("deploy", cfg["deployment"])
    slots = int(cfg.get("serve", {}).get("batch_slots", 32))
    meter = CompileMeter()
    parts = {}
    clock = time.perf_counter
    key = schedule.prng_key(args.seed)
    data = synth.collection(key, cfg["num_series"], cfg["series_len"])
    dep = deploy.setup(cfg, data, lambda n: part(parts, n, meter, clock))
    srv = Server(dep.server, dep.engine)
    emit({"config": args.config, "setup_parts": parts})
    loop = registry.load_module("loops", "open_poisson")
    try:
        for t_i, name in enumerate(args.traffic):
            traffic = registry.load_json("traffic", name)
            warm = schedule.make_requests(
                traffic, args.seed, 1.0, data,
                count=loop.warm_count(traffic, slots) * 2, stream=10 + t_i)
            warm_up(srv, warm, slots, lambda n: part(parts, n, meter, clock),
                    meter)
            # full waves, each k in turn
            times = []
            by_k = {}
            for i, k in enumerate(warm.k):
                by_k.setdefault(k, []).append(i)
            for k, rows in sorted(by_k.items()):
                for lo in range(len(rows) - 2 * slots, len(rows) - slots + 1,
                                slots):
                    t0 = clock()
                    serve_all(srv, warm, rows[lo:lo + slots])
                    times.append(clock() - t0)
            wave_s = float(np.mean(times))
            capacity = slots / wave_s
            emit({"traffic": name, "full_wave_s": times,
                  "capacity_per_s": capacity})
            for f in FRACTIONS:
                rate = capacity * f
                tr = dict(traffic, rate_per_s=rate)
                reqs = schedule.make_requests(tr, args.seed + 1, args.seconds,
                                              data)
                w = loop.run(srv, reqs, tr, args.seconds, clock)
                lat = w.latency_ms()
                third = len(lat) // 3
                due_last = w.due[-1]
                backlog = int(np.sum(~(w.done <= due_last)))
                emit({"traffic": name, "fraction": f, "rate_per_s": rate,
                      "requests": len(w.rows), "waves": len(w.waves),
                      "mean_fill": float(np.mean([x.served for x in w.waves])),
                      "p50_ms": percentile(lat, 50),
                      "p95_ms": percentile(lat, 95),
                      "first_third_p50_ms": percentile(lat[:third], 50),
                      "last_third_p50_ms": percentile(lat[-third:], 50),
                      "backlog_at_last_due": backlog,
                      "drain_s": w.end - due_last, "failed": w.failed})
    finally:
        dep.close()
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
