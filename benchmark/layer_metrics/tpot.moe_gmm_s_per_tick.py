"""``tpot.moe_gmm_s_per_tick``: read by ``benchmark/program_spans.py``."""
from benchmark.program_spans import kernel_seconds_per_step as read  # noqa: F401
