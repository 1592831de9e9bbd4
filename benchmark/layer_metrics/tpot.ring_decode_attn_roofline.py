"""``tpot.ring_decode_attn_roofline``: read by ``benchmark/mimo_metrics.py``."""
from benchmark.mimo_metrics import ring_decode_attn_roofline as read  # noqa: F401
