"""Disk read and staging: time the stream waited on reads per wave, in ms."""
from bench.readers import read_wait_ms_per_wave as read  # noqa: F401
