"""``train.unscoped_frac``: read by ``benchmark/scope_metrics.py``."""
from benchmark.scope_metrics import unscoped_frac as read  # noqa: F401
