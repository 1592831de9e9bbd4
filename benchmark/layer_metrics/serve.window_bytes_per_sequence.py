"""``serve.window_bytes_per_sequence``: read by ``benchmark/mimo_metrics.py``."""
from benchmark.mimo_metrics import window_bytes_per_sequence as read  # noqa: F401
