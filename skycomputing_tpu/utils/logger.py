"""Minimal append-to-file logger (reference: ``scaelum/logger/logger.py:4-14``)."""

from __future__ import annotations

import os
import sys
import time
from typing import Optional, TextIO


class Logger:
    """Timestamped line logger writing to a file and/or stderr.

    The reference logger appends to a file and flushes per line; this one does
    the same but also supports ``filename=None`` (stderr only), which the
    single-controller TPU runtime uses by default.

    Levels: ``info`` keeps the historical byte format (``[ts] message`` —
    log-scraping tests parse it); ``warning`` and
    ``error`` insert their level tag after the timestamp.  ``utc=True``
    switches the timestamp to ISO-8601 UTC (``2026-08-04T12:00:00Z``) —
    the format multi-region fleets need, where per-node local clocks make
    interleaved logs unsortable.
    """

    def __init__(self, filename: Optional[str] = None, mode: str = "a",
                 echo: bool = False, utc: bool = False):
        self._filename = filename
        self._echo = echo or filename is None
        self._utc = utc
        self._fh: Optional[TextIO] = None
        if filename is not None:
            parent = os.path.dirname(filename)
            if parent:
                os.makedirs(parent, exist_ok=True)
            self._fh = open(filename, mode)

    def _timestamp(self) -> str:
        if self._utc:
            return time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
        return time.strftime("%Y-%m-%d %H:%M:%S")

    def _emit(self, tag: str, message: str) -> None:
        line = f"[{self._timestamp()}] {tag}{message}"
        if self._fh is not None:
            self._fh.write(line + "\n")
            self._fh.flush()
        if self._echo:
            print(line, file=sys.stderr)

    def info(self, message: str) -> None:
        self._emit("", message)

    def warning(self, message: str) -> None:
        self._emit("WARNING: ", message)

    def error(self, message: str) -> None:
        self._emit("ERROR: ", message)

    def close(self) -> None:
        if self._fh is not None:
            self._fh.close()
            self._fh = None

    def __del__(self):  # pragma: no cover - best effort
        try:
            self.close()
        except Exception:
            pass


__all__ = ["Logger"]
