"""``tpot.tick_wait_excess_p50_s``: read by ``benchmark/program_spans.py``."""
from benchmark.program_spans import tick_wait_excess as read  # noqa: F401
