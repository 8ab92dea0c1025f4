"""VLOG-style logging (SURVEY §5 metrics/logging: glog ``VLOG(n)`` +
fluid/log_helper.py).

``vlog(level, msg)`` emits when ``FLAGS_log_level >= level`` — level 0 is
always-on (warnings/errors go through the standard logger regardless).
"""
from __future__ import annotations

import logging
import sys
from typing import Any

from . import flags as _flags
from .flags import get_flags  # noqa: F401  (public re-export)

__all__ = ["get_logger", "vlog"]

_logger = None

# vlog is called on hot paths where the message is usually suppressed —
# cache the log_level flag keyed on the registry's mutation counter so a
# disabled call costs two attribute reads and a compare, not a locked
# dict-building get_flags round-trip.  set_flags/define_flag bump the
# counter, which invalidates this cache.
_cached_level = None
_cached_version = -1


class _StderrHandler(logging.StreamHandler):
    """Writes to ``sys.stderr`` as it is at emit time, not as it was when
    the first caller logged: a process that swaps the stream later (a
    test's capture, a daemon's redirect) still sees the lines."""

    def __init__(self):
        logging.Handler.__init__(self)

    @property
    def stream(self):
        return sys.stderr


def get_logger() -> logging.Logger:
    global _logger
    if _logger is None:
        logger = logging.getLogger("paddle_tpu")
        if not logger.handlers:
            h = _StderrHandler()
            h.setFormatter(logging.Formatter(
                "%(asctime)s [paddle_tpu] %(levelname)s %(message)s"))
            logger.addHandler(h)
            logger.setLevel(logging.INFO)
            logger.propagate = False
        _logger = logger
    return _logger


def vlog(level: int, msg: str, *args: Any) -> None:
    """Emit ``msg`` when FLAGS_log_level >= level (glog VLOG semantics)."""
    global _cached_level, _cached_version
    v = _flags._version
    if v != _cached_version:
        _cached_level = int(get_flags(["log_level"])["log_level"])
        _cached_version = v
    if _cached_level >= level:
        get_logger().info(msg, *args)
