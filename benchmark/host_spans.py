"""Host events that the program's own profiler annotations leave in the
traced sub-window (``view.trace.host``): the event handlers and the
serving loop's waits, which run between cycles and so outside any cycle
trace. A program without such annotations leaves none, and every reader
here then returns None."""

from __future__ import annotations

from typing import Optional


def mean_s(view, name: str) -> Optional[float]:
    """Mean seconds per host event named ``name``; None without any."""
    d = [b - a for n, a, b in view.trace.host if n == name]
    return sum(d) / len(d) * 1e-9 if d else None
