"""Jit-safe phase scopes, host spans and counters (DESIGN.md §8).

Device side: ``phase("hgemv/upsweep")`` wraps a block of traced code in a
``jax.named_scope`` (names the HLO ops for profiles and post-SPMD dumps)
plus a ``jax.profiler.TraceAnnotation`` (labels the host-side region when a
profiler session is active).  Both are *metadata-only*: neither adds a
primitive to the jaxpr, so the annotated HGEMV / distributed-solve programs
stay byte-identical to the unannotated ones — the callback-free /
no-retrace invariants of the solver subsystem hold with annotation enabled,
which is the default.  ``tests/test_obs.py`` and the dist worker assert
``str(jax.make_jaxpr(...))`` equality enabled-vs-disabled.  Every ``phase``
entered during a trace is recorded in ``PHASES_SEEN``.

Host side: ``span("construct/tree")`` times a block of host code,
``count("compress/host-syncs")`` adds to a named counter and
``set_count("dist/exchange-bytes", n)`` sets one, all into one
process-wide ``REGISTRY``:

- a span opens a ``TraceAnnotation`` of its name, so it sits on the host
  plane of a profiler trace, and records its start and end in the registry
  on ``time.time_ns()`` (CLOCK_REALTIME), the clock the profiler stamps
  host events with.  A trace file puts its events on a timeline that
  starts at the session's ``profile_start_time`` (a statistic of its
  "Task Environment" plane): registry time = trace time + that start;
- the registry keeps, per span name, the number of spans and their total
  nanoseconds, every counter, and the ``RECENT`` latest spans with their
  times — a fixed bound, so its memory does not grow with run length;
- JAX's compile-duration events (jaxpr trace, lowering to MLIR, backend
  compile) are recorded by one listener as ``compile/trace``,
  ``compile/lower`` and ``compile/backend`` spans.  A trace nested in an
  outer one (a jitted callee traced inside its caller) is folded into the
  outer span, so compile spans never overlap; persistent-cache hits and
  misses are the counters ``compile/cache-hits`` and
  ``compile/cache-misses``;
- a span used inside a traced function records nothing and opens no
  annotation (it adds no primitive either way); a counter counts also
  there, which is what the ``retrace/<program>`` counters rely on.

``REPRO_OBS_DISABLE=1`` in the environment or ``set_enabled(False)`` turns
all of it off: phases add no scope, spans and counters record nothing.
The switch exists to prove neutrality in tests and to measure the cost of
the host spans; already-jitted executables keep the scopes they were
traced with.
"""
from __future__ import annotations

import collections
import contextlib
import os
import threading
import time
from typing import Dict, Iterator, List, Set, Tuple

import jax
import jax.monitoring
from jax._src.core import trace_state_clean as _not_tracing

# names of every phase entered while enabled (host-side registry; names are
# static python strings, so this never leaks tracers)
PHASES_SEEN: Set[str] = set()

_ENABLED = os.environ.get("REPRO_OBS_DISABLE", "0") != "1"

#: spans kept with their times (the totals keep every span)
RECENT = 65536

Span = Tuple[str, int, int]            # (name, start_ns, end_ns)


def enabled() -> bool:
    return _ENABLED


def set_enabled(flag: bool) -> None:
    """Toggle scopes (for subsequently *traced* programs: already-jitted
    executables keep theirs), host spans and counters."""
    global _ENABLED
    _ENABLED = bool(flag)


class Registry:
    """Process-wide host spans and counters (see module docstring)."""

    def __init__(self, recent: int = RECENT):
        self._lock = threading.Lock()
        self.counts: collections.Counter = collections.Counter()
        self._totals: Dict[str, List[int]] = {}       # name -> [n, ns]
        self._recent: collections.deque = collections.deque(maxlen=recent)
        self.recorded = 0                 # spans kept in the totals

    def _add(self, name: str, start: int, end: int) -> None:
        tot = self._totals.setdefault(name, [0, 0])
        tot[0] += 1
        tot[1] += end - start
        self._recent.append((name, start, end))
        self.recorded += 1

    def add_span(self, name: str, start: int, end: int) -> None:
        with self._lock:
            self._add(name, start, end)

    def add_compile(self, name: str, start: int, end: int) -> None:
        """A compile span; compile spans recorded since ``start`` (they end
        first, being nested in this one) fold into it."""
        with self._lock:
            while self._recent:
                inner, s, e = self._recent[-1]
                if not (inner.startswith("compile/") and s >= start):
                    break
                self._recent.pop()
                tot = self._totals[inner]
                tot[0] -= 1
                tot[1] -= e - s
                self.recorded -= 1
            self._add(name, start, end)

    def count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self.counts[name] += n

    def set_count(self, name: str, n: int) -> None:
        with self._lock:
            self.counts[name] = n

    def totals(self) -> Dict[str, Tuple[int, int]]:
        """Per span name: (spans, total nanoseconds)."""
        with self._lock:
            return {k: (v[0], v[1]) for k, v in self._totals.items()}

    def recent(self) -> List[Span]:
        """The latest spans, oldest first (all of them while fewer than
        ``RECENT`` were recorded; ``recorded - len(recent())`` dropped)."""
        with self._lock:
            return list(self._recent)

    def clear(self, prefix: str = "") -> None:
        """Forget the counters and spans whose name starts with ``prefix``
        (everything by default)."""
        with self._lock:
            for k in [k for k in self.counts if k.startswith(prefix)]:
                del self.counts[k]
            for k in [k for k in self._totals if k.startswith(prefix)]:
                self.recorded -= self._totals.pop(k)[0]
            kept = [sp for sp in self._recent if not sp[0].startswith(prefix)]
            self._recent.clear()
            self._recent.extend(kept)


REGISTRY = Registry()


@contextlib.contextmanager
def phase(name: str) -> Iterator[None]:
    """Annotate the enclosed traced ops as belonging to ``name``.

    Phase names are hierarchical slash-paths ("hgemv/upsweep",
    "precond/vcycle", "mg/level0", ...); nesting ``phase`` blocks nests the
    scopes.  Safe inside ``lax.while_loop``/``scan`` bodies and inside
    ``shard_map`` — it introduces no primitive, no host callback and no
    tracer-dependent python control flow.
    """
    if not _ENABLED:
        yield
        return
    PHASES_SEEN.add(name)
    with jax.named_scope(name), jax.profiler.TraceAnnotation(name):
        yield


@contextlib.contextmanager
def span(name: str) -> Iterator[None]:
    """Time the enclosed host code as ``name`` (see module docstring)."""
    if not _ENABLED or not _not_tracing():
        yield
        return
    start = time.time_ns()
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        REGISTRY.add_span(name, start, time.time_ns())


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name``."""
    if _ENABLED:
        REGISTRY.count(name, n)


def set_count(name: str, n: int) -> None:
    """Set the counter ``name`` to ``n``: a quantity fixed when a program
    is built (bytes per application), not a tally."""
    if _ENABLED:
        REGISTRY.set_count(name, n)


def counter(name: str) -> int:
    """The counter's value (0 if never counted)."""
    return REGISTRY.counts.get(name, 0)


_COMPILE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile/trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile/lower",
    "/jax/core/compile/backend_compile_duration": "compile/backend",
}
_CACHE_COUNTS = {
    "/jax/compilation_cache/cache_hits": "compile/cache-hits",
    "/jax/compilation_cache/cache_misses": "compile/cache-misses",
}


def _on_compile_span(event: str, start: float, end: float, **_) -> None:
    name = _COMPILE_SPANS.get(event)
    if name is not None and _ENABLED:
        REGISTRY.add_compile(name, int(start * 1e9), int(end * 1e9))


def _on_event(event: str, **_) -> None:
    name = _CACHE_COUNTS.get(event)
    if name is not None:
        count(name)


jax.monitoring.register_event_time_span_listener(_on_compile_span)
jax.monitoring.register_event_listener(_on_event)
