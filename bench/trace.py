"""Device trace: capture around the measured window, and its reduction.

The capture is JAX's profiler with the Python tracer off, so the host side
holds only the benchmark's own spans (``jax.profiler.TraceAnnotation``,
names starting ``bench.``) and the runtime's. The reduction reads the
``.xplane.pb`` with ``jax.profiler.ProfileData``:

* the window is the host span ``bench.window``;
* a device is a plane named ``/device:<KIND>:<n>``; its operations are the
  events of its ``XLA Ops`` line, clipped to the window;
* busy time is the union of those intervals, averaged over the devices;
  the idle share is 1 - busy / window;
* an operation is named by its program (the ``XLA Modules`` event it lies
  in) and its HLO instruction; the longest are ranked by self time, their
  length less the operations nested in them (a loop spans its body);
* a kernel's time is the union of the operations whose HLO instruction
  name starts with the kernel's name: the Pallas call's own op
  (``lb_sax_matrix.7``), not the slices around it that carry the name in
  their metadata;
* each idle gap of the first device is put to the host span it overlaps,
  and the idle seconds are summed per span name (``host_other`` where no
  benchmark span was open).
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

import numpy as np

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
TOP = 10


def profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    return opts


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                 # averaged over the devices
    devices: int
    ops: list                     # [[module/op, self seconds]], longest first
    idle_gaps: list               # [[host span, idle seconds]]
    kernels: dict                 # pattern -> device seconds (union)
    kernel_events: dict           # pattern -> number of events
    kernel_ops: dict              # pattern -> [[module/op, seconds]]

    @property
    def idle_share(self) -> float:
        return max(0.0, 1.0 - self.busy_s / self.window_s)


def union(starts: np.ndarray, ends: np.ndarray):
    """Disjoint sorted (starts, ends) covering the given intervals."""
    if len(starts) == 0:
        return np.zeros(0), np.zeros(0)
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > reach[:-1]
    idx = np.flatnonzero(new)
    out_s = s[idx]
    out_e = np.append(reach[idx[1:] - 1], reach[-1])
    return out_s, out_e


def overlap(a_s, a_e, b_s, b_e) -> float:
    """Total overlap of two disjoint sorted interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a_s) and j < len(b_s):
        lo = max(a_s[i], b_s[j])
        hi = min(a_e[i], b_e[j])
        if hi > lo:
            total += hi - lo
        if a_e[i] < b_e[j]:
            i += 1
        else:
            j += 1
    return total


def self_times(starts: np.ndarray, ends: np.ndarray) -> np.ndarray:
    """Each interval's length less what the intervals nested in it cover
    (a loop's event spans the events of its body)."""
    order = np.lexsort((-ends, starts))
    own = ends - starts
    stack: list = []
    for i in order:
        s, e = starts[i], ends[i]
        while stack and ends[stack[-1]] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(e, ends[stack[-1]]) - s
        stack.append(i)
    return np.maximum(own, 0.0)


def _host_spans(pd) -> dict:
    spans = collections.defaultdict(list)
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans[ev.name].append((ev.start_ns, ev.start_ns
                                           + ev.duration_ns))
    return spans


def _matches(ev, patterns) -> list:
    name = op_name(ev.name)
    return [p for p in patterns if name.startswith(p)]


def op_name(text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...), ...`` -> ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%")


def device_ops_line(plane: str, line: str) -> bool:
    """Whether a trace line holds a device's operations."""
    return bool(DEVICE_PLANE.match(plane)) and line == OPS_LINE


def _modules(plane):
    """(starts, ends, names) of the programs on a device's module line."""
    for line in plane.lines:
        if line.name == MODULES_LINE:
            evs = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                          ev.name.split("(", 1)[0]) for ev in line.events)
            if evs:
                s, e, n = zip(*evs)
                return np.asarray(s, float), np.asarray(e, float), list(n)
    return np.zeros(0), np.zeros(0), []


def reduce(pd, kernels=(), select=device_ops_line) -> Reduction:
    """Reduce a loaded ``ProfileData`` to the window's device numbers.
    ``select(plane name, line name)`` picks the lines of device operations;
    each plane with such lines counts as one device."""
    spans = _host_spans(pd)
    if not spans.get(WINDOW_SPAN):
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    w0, w1 = spans[WINDOW_SPAN][0]
    window_ns = w1 - w0
    patterns = tuple(kernels)
    busy, first_union = [], None
    by_name = collections.Counter()
    k_iv = {p: [] for p in patterns}
    k_ops = {p: collections.Counter() for p in patterns}
    # the name test is made once per op text
    verdict = {}
    for plane in pd.planes:
        lines = [ln for ln in plane.lines if select(plane.name, ln.name)]
        if not lines:
            continue
        st, en, names, hits = [], [], [], []
        for ev in (ev for ln in lines for ev in ln.events):
            s = max(ev.start_ns, w0)
            e = min(ev.start_ns + ev.duration_ns, w1)
            if e <= s:
                continue
            st.append(s)
            en.append(e)
            names.append(ev.name)
            if patterns:
                h = verdict.get(ev.name)
                if h is None:
                    h = verdict[ev.name] = _matches(ev, patterns)
                hits.append(h)
        st, en = np.asarray(st, float), np.asarray(en, float)
        ms, me, mn = _modules(plane)
        where = np.searchsorted(ms, st, side="right") - 1
        own = self_times(st, en)
        for i, text in enumerate(names):
            m = where[i]
            mod = mn[m] + "/" if m >= 0 and st[i] < me[m] else ""
            label = mod + op_name(text)
            by_name[label] += own[i]
            for p in hits[i] if patterns else ():
                k_iv[p].append((st[i], en[i]))
                k_ops[p][label] += en[i] - st[i]
        us, ue = union(st, en)
        busy.append(float((ue - us).sum()))
        if first_union is None:
            first_union = (us, ue)
    if not sum(busy):
        raise ValueError("the trace holds no device operations")
    gaps = []
    if first_union is not None:
        us, ue = first_union
        gs = np.concatenate([[w0], ue])
        ge = np.concatenate([us, [w1]])
        keep = ge > gs
        gs, ge = gs[keep], ge[keep]
        covered = 0.0
        for name, ivs in spans.items():
            if name == WINDOW_SPAN:
                continue
            a = np.asarray(ivs, float)
            ss, se = union(a[:, 0], a[:, 1])
            t = overlap(gs, ge, ss, se)
            if t > 0:
                gaps.append([name, t / 1e9])
                covered += t
        rest = float((ge - gs).sum()) - covered
        if rest > 0:
            gaps.append(["host_other", rest / 1e9])
    gaps.sort(key=lambda g: -g[1])
    ksec = {}
    for p, ivs in k_iv.items():
        a = np.asarray(ivs, float).reshape(-1, 2)
        ks, ke = union(a[:, 0], a[:, 1])
        ksec[p] = float((ke - ks).sum()) / 1e9
    return Reduction(
        window_s=window_ns / 1e9,
        busy_s=float(np.mean(busy)) / 1e9,
        devices=len(busy),
        ops=[[n, t / 1e9] for n, t in by_name.most_common(TOP)],
        idle_gaps=gaps[:TOP],
        kernels=ksec,
        kernel_events={p: len(v) for p, v in k_iv.items()},
        kernel_ops={p: [[n, t / 1e9] for n, t in c.most_common(TOP)]
                    for p, c in k_ops.items()})


def load(log_dir: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(find_xplane(log_dir))

