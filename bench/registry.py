"""Everything a cell needs, found by name.

* a cell is an entry of ``workloads`` in ``BENCHMARK.json``;
* its configuration is ``bench/configs/<config>.json``, whose
  ``deployment`` key names the module ``bench/deploy/<deployment>.py``
  that stands the system up;
* its traffic is ``bench/traffic/<traffic>.json``, whose ``loop`` key
  names the driver ``bench/loops/<loop>.py``;
* each per-layer metric is read by ``bench/metrics/<name>.py``.

A later cell brings its own files under these names; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _checked(name: str, what: str) -> str:
    if not isinstance(name, str) or not NAME.match(name):
        raise LookupError(f"bad {what} name {name!r}")
    return name


def _path(kind: str, name: str, ext: str, root: str) -> str:
    path = os.path.join(root, kind, _checked(name, kind) + ext)
    if not os.path.isfile(path):
        raise LookupError(f"unknown {kind[:-1] if kind.endswith('s') else kind}"
                          f" {name!r}: no {os.path.relpath(path, ROOT)}")
    return path


def load_json(kind: str, name: str, root: str = BENCH) -> dict:
    with open(_path(kind, name, ".json", root)) as f:
        return json.load(f)


def load_module(kind: str, name: str, root: str = BENCH):
    path = _path(kind, name, ".py", root)
    modname = f"bench.{kind}.{name.replace('.', '_').replace('-', '_')}"
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[modname]
        raise
    return mod


def benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(name: str, bench: dict | None = None, root: str = BENCH) -> dict:
    """The cell ``name`` with its configuration, traffic and metric lists."""
    bench = bench or benchmark(os.path.dirname(root))
    _checked(name, "workload")
    found = [w for w in bench["workloads"] if w["name"] == name]
    if not found:
        raise LookupError(f"unknown workload {name!r}; BENCHMARK.json has "
                          f"{[w['name'] for w in bench['workloads']]}")
    w = found[0]
    config = load_json("configs", w["config"], root)
    traffic = load_json("traffic", w["traffic"], root)
    end_to_end = [m for m in bench["end_to_end"]
                  if name in m.get("workloads", [name])]
    reported = {m["name"] for m in end_to_end}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return {"workload": w, "config": config, "traffic": traffic,
            "end_to_end": end_to_end, "per_layer": per_layer}
