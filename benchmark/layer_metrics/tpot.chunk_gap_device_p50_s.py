"""``tpot.chunk_gap_device_p50_s``: read by ``benchmark/dispatch_trace.py``."""
from benchmark.dispatch_trace import chunk_gap as read  # noqa: F401
