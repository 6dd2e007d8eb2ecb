"""Kernel: LB_SAX share of its HBM roofline over the window, in percent."""
from bench.readers import lb_sax_roofline as read  # noqa: F401
