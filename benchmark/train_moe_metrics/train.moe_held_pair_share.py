"""``train.moe_held_pair_share``: read by ``benchmark/mla_metrics.py``."""
from benchmark.mla_metrics import held_pair_share as read  # noqa: F401
