"""``train.door_compiles_in_window``: read by ``benchmark/program_spans.py``."""
from benchmark.program_spans import door_compiles as read  # noqa: F401
