"""Host spans of the serving path, on the profiler's clock.

``span("engine.step")`` opens a ``jax.profiler.TraceAnnotation`` named
``repro.engine.step``.  While a profiler session runs, the span lands in
the trace beside the device's programs, so an idle gap on the device can
be read against what the host was doing; with no session it costs a
few microseconds, so spans are always on.  Keyword arguments become the
span's metadata (``span("engine.decode", gid=0, rows=8)``).

Where a counter dict is passed, the span's host seconds
(``time.perf_counter``) are added to its ``host_s`` key, so a layer's
host time is readable without a trace.  Only a layer's outermost spans
take its counter: a child span given one would count its time twice.
"""
from __future__ import annotations

import time
from typing import Dict, Optional

from jax.profiler import TraceAnnotation


class span:
    __slots__ = ("_ann", "_counter", "_t0")

    def __init__(self, name: str, counter: Optional[Dict] = None, **meta):
        self._ann = TraceAnnotation("repro." + name, **meta)
        self._counter = counter

    def __enter__(self):
        self._t0 = time.perf_counter()
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        if self._counter is not None:
            self._counter["host_s"] += time.perf_counter() - self._t0
        return False
