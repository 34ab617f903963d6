"""Device idle share of the traced window (device trace)."""
from bench.readers import idle_share as read  # noqa: F401
