"""Serving front end: median over the window's requests of their wave's start minus their due time, in ms."""
from bench.readers import queue_wait_ms as read  # noqa: F401
