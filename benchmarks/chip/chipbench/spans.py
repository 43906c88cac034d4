"""The benchmark's own spans around the calls it makes into each layer.

Each span is kept in memory as (name, start, end) on the host's
``perf_counter`` and, while the profiler runs, also written into its trace
as a ``jax.profiler.TraceAnnotation`` named ``bench:<name>``, so the trace
reduction can name the device's idle gaps by what the host was doing.
"""

from __future__ import annotations

import contextlib
import time
from typing import List, Tuple

import jax

PREFIX = "bench:"

_spans: List[Tuple[str, float, float]] = []


@contextlib.contextmanager
def span(name: str):
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(PREFIX + name):
        try:
            yield
        finally:
            _spans.append((name, t0, time.perf_counter()))


def recorded(since: float = 0.0) -> List[Tuple[str, float, float]]:
    """Spans that started at or after ``since`` (a ``perf_counter`` reading)."""
    return [s for s in _spans if s[1] >= since]

