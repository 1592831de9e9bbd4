"""``train.mla_flash_roofline``: read by ``benchmark/kimi_metrics.py``."""
from benchmark.kimi_metrics import mla_flash_roofline as read  # noqa: F401
