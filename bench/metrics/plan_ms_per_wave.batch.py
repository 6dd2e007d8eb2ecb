"""Query engine: engine time per wave over the window (telemetry latency.total / calls), in ms."""
from bench.readers import plan_ms_per_wave as read  # noqa: F401
