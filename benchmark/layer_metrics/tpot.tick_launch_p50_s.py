"""``tpot.tick_launch_p50_s``: read by ``benchmark/program_spans.py``."""
from benchmark.program_spans import span_percentile as read  # noqa: F401
