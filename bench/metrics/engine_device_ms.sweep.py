"""Device time of the engine's program per engine call, in ms, from the
trace: one fused group of a sweep query."""
from bench.readers import engine_device_ms as read  # noqa: F401
