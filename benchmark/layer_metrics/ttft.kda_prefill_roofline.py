"""``ttft.kda_prefill_roofline``: read by ``benchmark/kda_metrics.py``."""
from benchmark.kda_metrics import prefill_roofline as read  # noqa: F401
