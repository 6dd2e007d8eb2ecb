"""Search plan: mean LB_SAX pruning ratio of the window's queries, in percent."""
from bench.readers import sax_pruning as read  # noqa: F401
