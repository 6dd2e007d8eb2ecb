"""The program's own spans and phase scopes in a device trace.

``bench/trace.py`` puts the window's idle time to the benchmark's spans
(``bench.*``) and ranks device ops by their HLO names. This module reads
the same ``ProfileData`` (load it once, pass it to both) and puts the
same time to the program's parts:

* host spans are the ``jax.profiler.TraceAnnotation`` events of the host
  planes named ``repro.*`` (the program's) or ``bench.*`` (the
  benchmark's), the window span ``bench.window`` aside;
* each idle gap of the first device inside the window is cut where a span
  opens or closes, and each piece goes to the innermost span open over it
  (the one that opened last), or to ``host_other`` where none is: the
  pieces add up to the window's idle time;
* ``idle_under`` gives, per span name, the idle time that any span of that
  name covers, its nested spans included (the idle inside
  ``repro.serve.step``);
* a device op belongs to a phase scope (``seed``, ``candidates``,
  ``refine``, ``scan``; ``jax.named_scope`` in ``repro/core/search.py``)
  when the scope is a path component of its framework op name, the
  innermost such component if there are several; ops under none count as
  ``unscoped``. Each op counts its self time (its length less the ops
  nested in it, as in ``bench/trace.py``), clipped to the window, per
  program and scope. The framework op name is read from the op event's
  ``tf_op`` stat, else from an ``op_name="..."`` in its ``long_name`` stat
  or in its name; ``name_source`` says which one the trace carried.
"""
from __future__ import annotations

import collections
import dataclasses
import heapq
import re

import numpy as np

from bench.trace import (WINDOW_SPAN, _modules, device_ops_line, op_name,
                         overlap, self_times, union)

PREFIXES = ("repro.", "bench.")
SCOPES = ("seed", "candidates", "refine", "scan")
UNSCOPED = "unscoped"
NO_SPAN = "host_other"
OP_NAME = re.compile(r'op_name="([^"]*)"')


@dataclasses.dataclass
class SpanReduction:
    window_s: float
    idle_s: float                 # first device, inside the window
    idle_gaps: dict               # innermost span name -> idle seconds
    idle_under: dict              # span name -> idle seconds it covers
    spans: dict                   # span name -> (count, seconds), in window
    scopes: dict                  # scope -> device self seconds
    module_scopes: dict           # program -> {scope: device self seconds}
    name_source: str | None       # where the op names came from


def _stat(ev, key):
    for k, v in ev.stats:
        if k == key:
            return v
    return None


def framework_name(ev) -> tuple[str | None, str | None]:
    """The op's framework name (``jit(f)/while/body/seed/...``) and the
    place it was found, or (None, None)."""
    tf_op = _stat(ev, "tf_op")
    if tf_op:
        return str(tf_op), "tf_op"
    for source, text in (("long_name", _stat(ev, "long_name")),
                         ("name", ev.name)):
        m = OP_NAME.search(str(text or ""))
        if m:
            return m.group(1), source
    return None, None


def scope_of(name: str | None) -> str:
    """The innermost phase scope among the path components of ``name``."""
    for part in reversed((name or "").split("/")):
        if part in SCOPES:
            return part
    return UNSCOPED


def host_spans(pd, w0: float, w1: float) -> list:
    """[(start, end, name)] of the named host spans that meet the window,
    clipped to it (the window span itself left out)."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith(PREFIXES) or ev.name == WINDOW_SPAN:
                    continue
                s = max(ev.start_ns, w0)
                e = min(ev.start_ns + ev.duration_ns, w1)
                if e > s:
                    out.append((float(s), float(e), ev.name))
    return out


def window_of(pd) -> tuple[float, float]:
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW_SPAN:
                    return ev.start_ns, ev.start_ns + ev.duration_ns
    raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")


def innermost_idle(gs, ge, spans: list) -> dict:
    """Idle seconds per innermost open span over the gaps [gs, ge)."""
    cuts = sorted({*gs, *ge, *(s for s, _, _ in spans),
                   *(e for _, e, _ in spans)})
    by_start = sorted(spans)
    out = collections.Counter()
    heap: list = []                           # (-start, end, name)
    nxt = g = 0
    for a, b in zip(cuts[:-1], cuts[1:]):
        while g < len(gs) and ge[g] <= a:
            g += 1
        if g == len(gs):
            break
        if not gs[g] <= a < ge[g]:
            continue
        while nxt < len(by_start) and by_start[nxt][0] <= a:
            s, e, name = by_start[nxt]
            heapq.heappush(heap, (-s, e, name))
            nxt += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        out[heap[0][2] if heap else NO_SPAN] += (b - a) / 1e9
    return dict(out)


def reduce(pd, select=device_ops_line) -> SpanReduction:
    """Reduce a loaded ``ProfileData``; ``select(plane, line)`` picks the
    lines of device operations, as in ``bench.trace.reduce``."""
    w0, w1 = window_of(pd)
    spans = host_spans(pd, w0, w1)
    first = None
    scopes = collections.Counter()
    module_scopes: dict = collections.defaultdict(collections.Counter)
    source = None
    for plane in pd.planes:
        lines = [ln for ln in plane.lines if select(plane.name, ln.name)]
        if not lines:
            continue
        st, en, evs = [], [], []
        for ev in (ev for ln in lines for ev in ln.events):
            s = max(ev.start_ns, w0)
            e = min(ev.start_ns + ev.duration_ns, w1)
            if e > s:
                st.append(s)
                en.append(e)
                evs.append(ev)
        st, en = np.asarray(st, float), np.asarray(en, float)
        if first is None:
            first = union(st, en)
        ms, me, mn = _modules(plane)
        where = np.searchsorted(ms, st, side="right") - 1
        own = self_times(st, en)
        names: dict = {}                      # op text -> (scope, source)
        for i, ev in enumerate(evs):
            if ev.name not in names:
                fw, src = framework_name(ev)
                names[ev.name] = (scope_of(fw), src)
            scope, src = names[ev.name]
            source = source or src
            m = where[i]
            mod = mn[m] if m >= 0 and st[i] < me[m] else op_name(ev.name)
            scopes[scope] += own[i] / 1e9
            module_scopes[mod][scope] += own[i] / 1e9
    if first is None:
        raise ValueError("the trace holds no device operations")
    us, ue = first
    gs = np.concatenate([[w0], ue])
    ge = np.concatenate([us, [w1]])
    keep = ge > gs
    gs, ge = gs[keep], ge[keep]
    by_name = collections.defaultdict(list)
    for s, e, name in spans:
        by_name[name].append((s, e))
    under, counts = {}, {}
    for name, ivs in by_name.items():
        a = np.asarray(ivs, float)
        ss, se = union(a[:, 0], a[:, 1])
        under[name] = overlap(gs, ge, ss, se) / 1e9
        counts[name] = (len(ivs), float((a[:, 1] - a[:, 0]).sum()) / 1e9)
    return SpanReduction(
        window_s=(w1 - w0) / 1e9,
        idle_s=float((ge - gs).sum()) / 1e9,
        idle_gaps=innermost_idle(gs, ge, spans),
        idle_under=under,
        spans=counts,
        scopes=dict(scopes),
        module_scopes={m: dict(c) for m, c in module_scopes.items()},
        name_source=source)
