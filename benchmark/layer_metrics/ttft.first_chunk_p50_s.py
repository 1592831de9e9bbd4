"""``ttft.first_chunk_p50_s``: read by ``benchmark/program_spans.py``."""
from benchmark.program_spans import first_chunk as read  # noqa: F401
