"""``serve.idle_in_program_frac``: read by ``benchmark/dispatch_trace.py``."""
from benchmark.dispatch_trace import idle_in_program as read  # noqa: F401
