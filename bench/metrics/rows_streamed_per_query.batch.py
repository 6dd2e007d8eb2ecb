"""Out-of-core streaming: raw rows read from the store per query over the window."""
from bench.readers import rows_streamed_per_query as read  # noqa: F401
