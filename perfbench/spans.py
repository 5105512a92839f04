"""The program's own host spans in a traced window, for the readers of the
host draw and the engine step.

The program marks its host-side layer boundaries with ``eat.*`` profiler
spans, which ``trace.from_xspace`` keeps among the host events by name.
Each reader writes out the names it reads; none is imported from the
program, so a change there cannot move what the benchmark reads.  A
trace's host events carry no thread: a span's children are the spans of
the child names that lie wholly inside it.
"""
from __future__ import annotations

from bisect import bisect_right

from .trace import Trace, union_ns

__all__ = ["named", "prefixed", "whole", "within", "self_ns", "window_share"]


def named(trace: Trace, name: str) -> list[tuple[int, int]]:
    """``(start, end)`` of every host span called ``name``, sorted."""
    return sorted((s, e) for n, s, e in trace.host if n == name)


def prefixed(trace: Trace, prefix: str) -> list[tuple[int, int]]:
    """``(start, end)`` of every host span whose name starts with
    ``prefix``, sorted."""
    return sorted((s, e) for n, s, e in trace.host if n.startswith(prefix))


def whole(trace: Trace, spans) -> list[tuple[int, int]]:
    """The spans that lie wholly inside the window."""
    lo, hi = trace.window
    return [(s, e) for s, e in spans if lo <= s and e <= hi]


def within(spans, parents) -> list[tuple[int, int]]:
    """The spans that lie wholly inside one of ``parents`` (sorted spans
    that do not overlap one another, as one thread's are)."""
    starts = [s for s, _ in parents]
    out = []
    for s, e in spans:
        i = bisect_right(starts, s) - 1
        if i >= 0 and e <= parents[i][1]:
            out.append((s, e))
    return out


def self_ns(parents, children) -> int:
    """Summed time of ``parents`` less the time their children cover."""
    return (sum(e - s for s, e in parents)
            - union_ns(within(children, parents)))


def window_share(trace: Trace, spans) -> float | None:
    """Percent of the window the spans cover (their union, clipped to the
    window); ``None`` where none falls in it."""
    lo, hi = trace.window
    clipped = [(max(s, lo), min(e, hi)) for s, e in spans]
    clipped = [(s, e) for s, e in clipped if e > s]
    if not clipped or trace.window_ns <= 0:
        return None
    return 100.0 * union_ns(clipped) / trace.window_ns
