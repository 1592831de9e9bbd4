"""The three traffic drivers. A traffic file's ``driver`` key names one.

Each driver is ``run(system, traffic, seed, seconds, rec, tracer, clock)``
and returns the run's record: the window, every step or request with its
host-clock stamps, and what was attempted and what failed. The metric
readers (``benchmark/readers.py``) see only that record, the recorder's
spans, the counters and the reduced trace.
"""

import importlib

# driver name -> (module, kind of system it drives)
DRIVERS = {"train_stream": "train", "closed_loop": "serve",
           "open_loop": "serve"}


def get(name):
    if name not in DRIVERS:
        raise SystemExit(f"benchmark: unknown traffic driver {name!r} "
                         f"(have: {sorted(DRIVERS)})")
    return importlib.import_module(f"benchmark.drivers.{name}"), DRIVERS[name]
