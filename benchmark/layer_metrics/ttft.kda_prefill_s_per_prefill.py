"""``ttft.kda_prefill_s_per_prefill``: read by ``benchmark/program_spans.py``."""
from benchmark.program_spans import kernel_seconds_per_step as read  # noqa: F401
