"""Device time of one serving-step program (device trace)."""
from bench.readers import step_ms as read  # noqa: F401
