"""``tpot.block_tokens_per_pass``: read by ``benchmark/sdar_block_metrics.py``."""
from benchmark.sdar_block_metrics import tokens_per_pass as read  # noqa: F401
