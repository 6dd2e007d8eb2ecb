"""Host spans and counted host syncs of the serving path.

A span is a ``jax.profiler.TraceAnnotation`` named ``repro.<layer>.<part>``:
it lands in the profiler's trace on the same clock as the device's
operations, and costs next to nothing when no profiler runs. Its arguments
are host ints already at hand, never a value read from the device.

A host sync is a blocking device-to-host read or an explicit wait for the
device. Each one on the serving path goes through a :class:`HostSyncs`,
which counts it; the count is reported as ``host_syncs`` in telemetry.
"""
from __future__ import annotations

import jax
import numpy as np

span = jax.profiler.TraceAnnotation


class HostSyncs:
    """Counts the host syncs made through it."""

    def __init__(self):
        self.count = 0

    def read(self, x) -> np.ndarray:
        """``np.asarray(x)``: one device-to-host read."""
        self.count += 1
        return np.asarray(x)

    def wait(self, x):
        """``jax.block_until_ready(x)``: one wait for the device."""
        self.count += 1
        return jax.block_until_ready(x)
