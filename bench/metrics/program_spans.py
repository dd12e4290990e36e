"""The program's host spans and counters (``repro.obs``) for the readers.

The registry of the run's process stamps spans on ``time.time_ns()``; the
trace's host and device events sit on a timeline that starts at the
profiler session's start.  ``setup_spans`` puts the two on one timeline
and keeps the spans that ended before the traced window began (the
comparison after the window compiles too):

- where the window ran program spans (the compress cell's rank picks),
  they are on both timelines, and the offset between the two is read from
  them;
- otherwise the window ran none, and it lies in the first stretch with no
  program span at least as long as the window: set-up ends where that
  stretch begins.  (Set-up's work is under spans and compiles; its longest
  stretch outside them is one warm-up unit.)

A program without the registry (``repro.obs`` before its host spans) gives
``None`` everywhere, and its readers report nothing.
"""
from __future__ import annotations

import bisect
from typing import List, Optional, Tuple

Span = Tuple[str, int, int]


def registry():
    try:
        from repro.obs import trace
    except ImportError:
        return None
    return getattr(trace, "REGISTRY", None)


def offset_ns(spans: List[Span], host, tol_ns: float = 1e6
              ) -> Optional[int]:
    """Registry time less trace time, from the spans found on both (by
    name, within ``tol_ns``); ``None`` where the trace holds none."""
    names = {n for n, _, _ in spans}
    traced = sorted((s, n) for s, _, n in host if n in names)
    if not traced:
        return None
    starts = {}
    for n, s, _ in spans:
        starts.setdefault(n, []).append(s)
    for v in starts.values():
        v.sort()

    def hits(d):
        k = 0
        for s, n in traced:
            v = starts[n]
            i = bisect.bisect_left(v, s + d - tol_ns)
            k += i < len(v) and v[i] <= s + d + tol_ns
        return k

    s0, n0 = traced[0]
    best = max((hits(r - s0), r - s0) for r in starts[n0])
    return int(best[1]) if best[0] else None


def first_quiet(spans: List[Span], length: float) -> Optional[int]:
    """Start of the first stretch of at least ``length`` ns covered by no
    span."""
    end = None
    for _, s, e in sorted(spans, key=lambda sp: sp[1]):
        if end is not None and s - end >= length:
            return end
        end = e if end is None else max(end, e)
    return end


def setup_spans(ctx) -> Optional[List[Span]]:
    """The registry's spans that ended before the traced window began."""
    reg = registry()
    red = ctx.get("reduced")
    if reg is None or red is None:
        return None
    spans = reg.recent()
    off = offset_ns(spans, red.host)
    cut = red.t0 + off if off is not None else \
        first_quiet(spans, red.t1 - red.t0)
    if cut is None:
        return None
    return [sp for sp in spans if sp[2] <= cut]


def total_s(spans: List[Span], prefix: str,
            outside: str = "") -> Optional[float]:
    """Seconds in the spans named ``prefix``..., leaving out those inside a
    span named ``outside``; ``None`` where there is no such span."""
    encl = [(s, e) for n, s, e in spans if outside and n == outside]
    mine = [(s, e) for n, s, e in spans if n.startswith(prefix) and
            not any(a <= s and e <= b for a, b in encl)]
    if not mine:
        return None
    return sum(e - s for s, e in mine) * 1e-9
