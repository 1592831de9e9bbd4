"""``tpot.block_pass_device_p50_s``: read by ``benchmark/sdar_block_metrics.py``."""
from benchmark.sdar_block_metrics import pass_device as read  # noqa: F401
