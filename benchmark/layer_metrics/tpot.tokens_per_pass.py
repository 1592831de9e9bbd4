"""``tpot.tokens_per_pass``: read by ``benchmark/sdar_metrics.py``."""
from benchmark.sdar_metrics import tokens_per_pass as read  # noqa: F401
