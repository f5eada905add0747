"""Logging helpers (reference: elasticdl/python/common/log_utils.py [U])."""

from __future__ import annotations

import contextlib
import logging
import sys
import threading

_FORMAT = "[%(asctime)s] [%(levelname)s] [%(name)s] %(message)s"

_default_level = "INFO"
_loggers: dict = {}


#: The lists :func:`capture` is filling, each with its thread's ident.
_captures: list = []


class _Tee(logging.Handler):
    """Copies a record to every open :func:`capture` of its thread."""

    def emit(self, record: logging.LogRecord) -> None:
        for ident, said in _captures:
            if ident == record.thread:
                said.append((record.name, record.levelno, record.getMessage()))


_TEE = _Tee()


@contextlib.contextmanager
def capture():
    """What the framework's loggers say ON THIS THREAD inside the block, as
    ``(logger name, level, message)``: what a trace of the train step logs
    (the attention path, the keep plan) is kept with its compiled program
    (common/program_store.py) and said again by :func:`replay` in a launch
    that restores the program and traces nothing."""
    said: list = []
    entry = (threading.get_ident(), said)
    _captures.append(entry)
    try:
        yield said
    finally:
        _captures.remove(entry)


def replay(said) -> None:
    for name, level, message in said:
        get_logger(name).log(level, "%s", message)


def get_logger(name: str, level: str = "") -> logging.Logger:
    logger = logging.getLogger(name)
    if not logger.handlers:
        handler = logging.StreamHandler(sys.stderr)
        handler.setFormatter(logging.Formatter(_FORMAT))
        logger.addHandler(handler)
        logger.addHandler(_TEE)
        logger.propagate = False
    logger.setLevel((level or _default_level).upper())
    _loggers[name] = logger
    return logger


def set_level(level: str) -> None:
    """Apply --log_level to every framework logger, existing and future
    (master/worker mains call this right after parsing the job config)."""
    global _default_level
    _default_level = level
    for logger in _loggers.values():
        logger.setLevel(level.upper())
