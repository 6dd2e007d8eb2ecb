"""The four-chip disk cell at a test size, on four host devices in a child
process (this process keeps one): a sound run comes out correct and its
per-layer counters read; with the exchange between the shards broken, or
one answer altered, it comes out not correct."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from bench import registry

CELL = "dist-ooc-4chip-batch"

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    sys.path[:0] = [os.getcwd(), os.path.join(os.getcwd(), "src")]
    import json
    import time
    import warnings; warnings.simplefilter("ignore", RuntimeWarning)
    import jax
    import numpy as np
    from bench import harness, registry
    from bench.readers import Context
    from bench.tests.tiny import tiny_cell
    from repro.api import QueryEngine
    from repro.distributed import ooc

    def small():
        cell = tiny_cell(%(cell)r)
        cfg = cell["config"]
        cfg.update(memory_budget_mb=1, build_chunk=4096)
        # few seeded leaves, so phases 2-3 prune against the exchanged bound
        cfg["search"] = dict(cfg.get("search", {}), l_max=2)
        return cell

    loop = registry.load_module("loops", "closed")
    serve = loop.run
    windows = []

    def keep(*a, **kw):
        windows.append(serve(*a, **kw))
        return windows[-1]

    loop.run = keep

    def exchange_halved():
        merge = ooc._BoundExchange._merge_all

        def half(self):
            merge(self)
            self.bound = self.bound * np.float32(0.5)

        ooc._BoundExchange._merge_all = half

    def answer_altered():
        knn = QueryEngine.knn

        def alter(self, q, **kw):
            res = knn(self, q, **kw)
            return res._replace(ids=res.ids.at[0, 0].add(1))

        QueryEngine.knn = alter

    fault = sys.argv[1]
    if fault != "sound":
        warm_all = harness.warm_all

        def warm_then_break(*a, **kw):
            warm_all(*a, **kw)
            {"exchange": exchange_halved, "answer": answer_altered}[fault]()

        harness.warm_all = warm_then_break
    cell = small()
    res = harness.run(cell, 2**31 + 17, 1.0, False,
                      t_start=time.perf_counter(), devices=jax.devices())
    ctx = Context(cell=cell, window=windows[-1], trace=None, peak={},
                  compiles={"lowerings": 0})
    res["counters"] = {
        m["name"]: registry.load_module("metrics", m["name"]).read(ctx)
        for m in cell["per_layer"] if m["source"] == "program_counter"}
    print("RESULT " + json.dumps(res))
""") % {"cell": CELL}


def _run(fault: str) -> dict:
    root = os.path.dirname(registry.BENCH)
    out = subprocess.run([sys.executable, "-c", _SCRIPT, fault], cwd=root,
                         capture_output=True, text=True, timeout=600,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    lines = [ln for ln in out.stdout.splitlines() if ln.startswith("RESULT ")]
    assert lines, (out.stdout[-2000:], out.stderr[-3000:])
    return json.loads(lines[-1][len("RESULT "):])


def test_sound_run_is_correct():
    res = _run("sound")
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["count"] == 4
    assert set(res["metrics"]) >= {"setup_s", "queries_per_s"}
    got = res["counters"]
    assert set(got) == {"fetches_per_wave.batch",
                        "rows_streamed_per_query.batch",
                        "plan_ms_per_wave.batch",
                        "read_wait_ms_per_wave.batch",
                        "window_compiles.batch"}
    assert got.pop("window_compiles.batch") == 0
    assert all(v is not None and v > 0 for v in got.values()), got


@pytest.mark.parametrize("fault", ["exchange", "answer"])
def test_broken_path_is_not_correct(fault):
    res = _run(fault)
    assert not res["correct"], res["checks"]
