"""``tpot.chunks_behind_frac``: read by ``benchmark/dispatch_trace.py``."""
from benchmark.dispatch_trace import chunks_behind as read  # noqa: F401
