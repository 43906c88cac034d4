"""The program's own spans inside the window, for the per-layer readers.

The program's train layer (``repro.train``) records its steps, saves and
restores as spans of one telemetry bundle, ``repro.train.TELEMETRY``, on the
session clock of ``repro.core.telemetry.now`` (``perf_counter`` rebased).
This module is the readers' one import of it.  It selects the spans that
started inside the window; where the program keeps no such bundle, none
started there, or the bundle's bounded buffer dropped any span, it gives
``None`` and the reader reports nothing.
"""

from __future__ import annotations

import statistics
import time
from typing import Iterable, List, Optional


def window(ctx) -> Optional[list]:
    """The train layer's spans that started inside ``ctx.run``'s window, or ``None``."""
    try:
        from repro.core.telemetry import now
        from repro.train import TELEMETRY
    except ImportError:
        return None
    buffer = TELEMETRY.spans
    if getattr(buffer, "dropped", 0):
        return None
    t0 = ctx.run.window_start - (time.perf_counter() - now())
    t1 = t0 + ctx.run.window_s
    return [s for s in buffer.spans() if t0 <= s.start <= t1] or None


def seconds(spans: list, name: str) -> List[float]:
    return [s.end - s.start for s in spans if s.name == name]


def mean(ctx, name: str) -> Optional[float]:
    """Mean seconds of the window's ``name`` spans."""
    values = seconds(window(ctx) or [], name)
    return statistics.fmean(values) if values else None


def per(ctx, name: str, per_name: str) -> Optional[float]:
    """Seconds of all the window's ``name`` spans over the number of its
    ``per_name`` spans."""
    spans = window(ctx) or []
    n = sum(s.name == per_name for s in spans)
    return sum(seconds(spans, name)) / n if n else None


def self_mean(ctx, name: str, children: Iterable[str]) -> Optional[float]:
    """Mean seconds of the window's ``name`` spans, each less its direct
    children named in ``children``."""
    spans = window(ctx) or []
    own = {s.span_id: s.end - s.start for s in spans if s.name == name}
    children = set(children)
    for s in spans:
        if s.parent_id in own and s.name in children:
            own[s.parent_id] -= s.end - s.start
    return statistics.fmean(own.values()) if own else None
