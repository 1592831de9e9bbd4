"""``ttft.block_flash_roofline``: read by ``benchmark/sdar_metrics.py``."""
from benchmark.sdar_metrics import block_flash_roofline as read  # noqa: F401
