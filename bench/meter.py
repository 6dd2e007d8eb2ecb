"""Compile counts from JAX's own monitoring events, and host spans.

``CompileMeter`` counts, per part of a run: ``lowerings``, the programs JAX
had to trace and lower because its in-process cache missed; of those,
``cache_loads`` read back from the persistent compilation cache and
``compiled`` compiled by the backend; and ``compile_s``, the seconds spent
compiling or loading them. In the measured window all should read 0.
"""
from __future__ import annotations

import contextlib

import jax

# wraps a backend compile or a persistent-cache read alike
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileMeter:
    def __init__(self):
        self.programs = 0
        self.compile_s = 0.0
        self.lowerings = 0
        self.cache_loads = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.programs += 1
            self.compile_s += secs
        elif event == LOWER_EVENT:
            self.lowerings += 1

    def _on_event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            self.cache_loads += 1

    def mark(self) -> tuple:
        return self.programs, self.compile_s, self.lowerings, self.cache_loads

    def since(self, mark: tuple) -> dict:
        loads = self.cache_loads - mark[3]
        return {"lowerings": self.lowerings - mark[2],
                "compiled": self.programs - mark[0] - loads,
                "cache_loads": loads,
                "compile_s": self.compile_s - mark[1]}


def span(name: str):
    """A host span in the profiler's trace (free when no trace runs)."""
    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def part(parts: dict, name: str, meter: CompileMeter, clock):
    """Time one part of the set-up, with its compiles."""
    t0, m0 = clock(), meter.mark()
    with span(f"bench.setup.{name}"):
        yield
    parts[name] = {"s": clock() - t0, **meter.since(m0)}
