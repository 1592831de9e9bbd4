"""``tpot.block_decode_attn_roofline``: read by ``benchmark/sdar_block_metrics.py``."""
from benchmark.sdar_block_metrics import decode_attn_roofline as read  # noqa: F401
