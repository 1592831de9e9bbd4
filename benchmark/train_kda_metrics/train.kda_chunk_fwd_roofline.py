"""``train.kda_chunk_fwd_roofline``: read by ``benchmark/kimi_metrics.py``."""
from benchmark.kimi_metrics import kda_chunk_roofline as read  # noqa: F401
