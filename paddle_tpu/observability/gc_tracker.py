"""Garbage collection as a stall with a name (ISSUE 34 tentpole, part 3).

A collection stops every Python thread: the serving engine's round and a
loader's hand-over stand still for as long as it runs, and nothing in the
records said so. This module is the collector's twin of
`compile_tracker`: one process-wide `gc.callbacks` hook, always on once
installed, that keeps

  * a running total of the seconds and the number of collections, and the
    longest pause — readers take the total at two instants and subtract
    (a round at its open and its close, a loader around a wait);
  * a `pt:gc` span from the hook's `start` call to its `stop` call, so
    that under a profiler a collection lies on the collecting thread's
    line of `/host:CPU`, on the device's clock.

`install()` is called where the first serving engine or DataLoader is
built, not at import: a process that builds neither pays nothing. The
hook costs two clock reads a collection, and one span where a profiler
session or the tracing sink is on.
"""
from __future__ import annotations

import gc
import threading
import time

from jax.profiler import TraceAnnotation

from . import tracing as _tracing


class GcTracker:
    """Instantiable for tests; `TRACKER` is the process's own."""

    def __init__(self):
        self._lock = threading.Lock()
        self._installed = False
        self._seconds = 0.0
        self._count = 0
        self._longest_s = 0.0
        self._t0 = None
        self._span = None

    def install(self):
        with self._lock:
            if not self._installed:
                gc.callbacks.append(self._on_gc)
                self._installed = True

    def _on_gc(self, phase, info):
        # collections never nest and a collection's two calls come from
        # the thread that collects, so one open span is all there is
        if phase == "start":
            # the youngest generation is collected hundreds of times a
            # second: the span is built only where something records it
            if TraceAnnotation.is_enabled() or _tracing.enabled():
                self._span = _tracing.span("gc",
                                           generation=info["generation"])
                self._span.__enter__()
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            dur = time.perf_counter() - self._t0
            self._t0 = None
            self._seconds += dur
            self._count += 1
            if dur > self._longest_s:
                self._longest_s = dur
            if self._span is not None:
                span, self._span = self._span, None
                span.__exit__(None, None, None)

    def seconds(self):
        """Seconds spent collecting since the hook was installed."""
        return self._seconds

    def stats(self):
        return {"installed": self._installed, "seconds": self._seconds,
                "collections": self._count, "longest_s": self._longest_s}


# ---- process-wide default tracker ---------------------------------------
TRACKER = GcTracker()


def install():
    TRACKER.install()


def seconds():
    return TRACKER.seconds()


def stats():
    return TRACKER.stats()
