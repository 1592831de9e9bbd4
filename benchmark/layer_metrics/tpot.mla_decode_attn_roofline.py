"""``tpot.mla_decode_attn_roofline``: read by ``benchmark/mla_metrics.py``."""
from benchmark.mla_metrics import decode_attn_roofline as read  # noqa: F401
