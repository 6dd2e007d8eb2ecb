"""Query engine: programs lowered inside the window (compiled or read from the cache); should read 0."""
from bench.readers import window_compiles as read  # noqa: F401
