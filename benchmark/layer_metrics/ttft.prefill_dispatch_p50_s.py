"""``ttft.prefill_dispatch_p50_s``: read by ``benchmark/dispatch_trace.py``."""
from benchmark.dispatch_trace import prefill_dispatch as read  # noqa: F401
