"""Mean wait from a request's due time to its admission (host clock)."""
from bench.readers import queue_wait_mean_ms as read  # noqa: F401
