"""``train.kda_core_s_per_step``: read by ``benchmark/afmoe_metrics.py``."""
from benchmark.afmoe_metrics import scope_seconds_per_step as read  # noqa: F401
