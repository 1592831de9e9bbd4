"""Distributed-aware logging.

TPU-native counterpart of the reference's ``deepspeed/utils/logging.py`` (152 LoC):
a singleton logger plus ``log_dist`` that only emits on chosen ranks. On TPU the
"rank" is the JAX process index (one process per host), so rank filtering keys off
``jax.process_index()`` rather than torch.distributed.
"""

from __future__ import annotations

import functools
import logging
import os
import sys

LOG_LEVELS = {
    "debug": logging.DEBUG,
    "info": logging.INFO,
    "warning": logging.WARNING,
    "error": logging.ERROR,
    "critical": logging.CRITICAL,
}

_FORMAT = "[%(asctime)s] [%(levelname)s] [%(name)s:%(lineno)d:%(funcName)s] %(message)s"


@functools.lru_cache(None)
def _create_logger(name: str = "DeepSpeedTPU", level: int = logging.INFO) -> logging.Logger:
    lg = logging.getLogger(name)
    lg.setLevel(level)
    lg.propagate = False
    handler = logging.StreamHandler(stream=sys.stdout)
    handler.setFormatter(logging.Formatter(_FORMAT))
    lg.addHandler(handler)
    return lg


def _default_level() -> int:
    return LOG_LEVELS.get(os.environ.get("DSTPU_LOG_LEVEL", "info").lower(), logging.INFO)


logger = _create_logger("DeepSpeedTPU", _default_level())


def _process_index() -> int:
    """Current global rank, WITHOUT initialising a jax backend: before one is
    up the launcher's env answers. A log line must not be what makes a later
    ``jax.distributed.initialize`` impossible, nor what claims the chip in a
    process that only meant to spawn the one that needs it."""
    import jax
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        return jax.process_index()
    return int(os.environ.get("JAX_PROCESS_ID") or os.environ.get("RANK") or 0)


def log_dist(message: str, ranks=None, level: int = logging.INFO) -> None:
    """Log ``message`` only on the given process ranks (``[-1]`` or None = all).

    Mirrors the semantics of the reference's ``log_dist`` (deepspeed/utils/logging.py).
    """
    my_rank = _process_index()
    if ranks is None or len(ranks) == 0 or -1 in ranks or my_rank in ranks:
        logger.log(level, f"[Rank {my_rank}] {message}")


def print_rank_0(message: str) -> None:
    if _process_index() == 0:
        print(message, flush=True)


def should_log_le(max_log_level_str: str) -> bool:
    """True when the logger's effective level is <= the named level."""
    if max_log_level_str.lower() not in LOG_LEVELS:
        raise ValueError(f"{max_log_level_str} is not one of {list(LOG_LEVELS)}")
    return logger.getEffectiveLevel() <= LOG_LEVELS[max_log_level_str.lower()]


def get_caller_func(frame: int = 3) -> str:
    import sys as _sys

    return _sys._getframe(frame).f_code.co_name
