"""Share of the traced window in which the device ran nothing, in %."""
from bench.readers import device_idle_pct as read  # noqa: F401
