"""``train.attn_s_per_step``: read by ``benchmark/scope_metrics.py``."""
from benchmark.scope_metrics import scope_seconds_per_step as read  # noqa: F401
