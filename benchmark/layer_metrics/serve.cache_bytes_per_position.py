"""``serve.cache_bytes_per_position``: read by ``benchmark/mla_metrics.py``."""
from benchmark.mla_metrics import cache_bytes_per_position as read  # noqa: F401
