"""Rank-aware logging.

Equivalent role to the reference's ``deepspeed/utils/logging.py`` (``logger``,
``log_dist(ranks=...)``, rank-0 helpers), re-expressed for a JAX process model:
"rank" is ``jax.process_index()`` rather than an env-var torch rank.
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

_FORMAT = "[%(asctime)s] [%(levelname)s] [%(name)s:%(lineno)d] %(message)s"


@functools.lru_cache(None)
def _create_logger(name: str = "deepspeed_tpu", level: int = logging.INFO) -> logging.Logger:
    lg = logging.getLogger(name)
    lg.setLevel(level)
    lg.propagate = False
    handler = logging.StreamHandler(stream=sys.stdout)
    handler.setFormatter(logging.Formatter(_FORMAT))
    lg.addHandler(handler)
    return lg


logger = _create_logger(
    level=LOG_LEVELS.get(os.environ.get("DSTPU_LOG_LEVEL", "info").lower(), logging.INFO)
)


def set_log_level(level: str | int) -> None:
    if isinstance(level, str):
        level = LOG_LEVELS[level.lower()]
    logger.setLevel(level)


def _process_index() -> int:
    """This process's rank, without initializing a jax backend: a log line
    must never be what takes the chip from a child that needs it. A job that
    has not called ``jax.distributed.initialize`` is one process, rank 0."""
    import jax

    if not jax.distributed.is_initialized():
        return 0
    return jax.process_index()


def log_dist(message: str, ranks: list[int] | None = None, level: int = logging.INFO) -> None:
    """Log ``message`` only on the listed process ranks (None or [-1] = all)."""
    my_rank = _process_index()
    if ranks is None or -1 in ranks or my_rank in ranks:
        logger.log(level, f"[Rank {my_rank}] {message}")


def print_rank_0(message: str) -> None:
    if _process_index() == 0:
        logger.info(message)


def warning_once(message: str, _seen: set = set()) -> None:  # noqa: B006 - intentional cache
    if message not in _seen:
        _seen.add(message)
        logger.warning(message)
