"""``tpot.block_attn_roofline``: read by ``benchmark/sdar_metrics.py``."""
from benchmark.sdar_metrics import block_attn_roofline as read  # noqa: F401
