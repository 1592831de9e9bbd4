"""``ttft.moe_gmm_roofline``: read by ``benchmark/moe_metrics.py``."""
from benchmark.moe_metrics import gmm_roofline as read  # noqa: F401
