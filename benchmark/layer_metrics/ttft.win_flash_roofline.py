"""``ttft.win_flash_roofline``: read by ``benchmark/mimo_metrics.py``."""
from benchmark.mimo_metrics import flash_roofline as read  # noqa: F401
