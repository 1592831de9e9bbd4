"""``train.win_flash_roofline``: read by ``benchmark/afmoe_metrics.py``."""
from benchmark.afmoe_metrics import win_flash_roofline as read  # noqa: F401
