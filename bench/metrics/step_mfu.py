"""The whole step's share of the chip's bf16 peak: model FLOPs of the real
tokens processed in the window over the window (host clock)."""
from bench.readers import step_mfu as read  # noqa: F401
