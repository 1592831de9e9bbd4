"""``train.kda_s_per_step``: read by ``benchmark/kimi_metrics.py``."""
from benchmark.kimi_metrics import kda_seconds_per_step as read  # noqa: F401
