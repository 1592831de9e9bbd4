"""``train.step_hbm_frac``: read by ``benchmark/scope_metrics.py``."""
from benchmark.scope_metrics import step_hbm_frac as read  # noqa: F401
