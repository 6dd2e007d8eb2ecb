"""Device: share of the traced window with no operation on the device, in percent."""
from bench.readers import device_idle_share as read  # noqa: F401
