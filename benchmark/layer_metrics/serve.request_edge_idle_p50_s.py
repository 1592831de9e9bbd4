"""``serve.request_edge_idle_p50_s``: read by ``benchmark/dispatch_trace.py``."""
from benchmark.dispatch_trace import request_edge_idle as read  # noqa: F401
