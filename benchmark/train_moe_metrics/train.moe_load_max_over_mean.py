"""``train.moe_load_max_over_mean``: read by ``benchmark/moe_metrics.py``."""
from benchmark.moe_metrics import load_max_over_mean as read  # noqa: F401
