"""The repo's benchmark: the yardstick later PRs are measured with.

Everything a number depends on lives here, where a PR that claims a gain
cannot change it: traffic generation (``traffic.py``), the drivers that
offer it (``drivers/``), the reduction from spans, counters and the device
trace to metrics (``readers.py``, ``trace_reduce.py``), the table of peaks
(``peaks.json``), and per model family (``families/<family>.py``) the
operation and byte counts and the plain reference, with the comparison that
decides ``correct`` (``systems.py``). From the program it takes only the
system under test (``systems.py`` and a family's ``build_model`` are all
that import ``deepspeed_tpu``).

``BENCHMARK.json`` at the repo root names the cells and metrics; each
configuration, traffic mix, metric and model family is a file found by that
name (``manifest.py``, ``families/``). ``PERF.md`` says why each exists.
"""
