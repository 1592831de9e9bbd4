"""``serve.state_bytes_per_sequence``: read by ``benchmark/kda_metrics.py``."""
from benchmark.kda_metrics import state_bytes_per_sequence as read  # noqa: F401
