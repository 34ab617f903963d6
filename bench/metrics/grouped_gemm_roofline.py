"""grouped_gemm's share of its roofline (device trace, costs from shapes)."""
from bench.readers import grouped_gemm_roofline as read  # noqa: F401
