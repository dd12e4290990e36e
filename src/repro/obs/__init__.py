"""Observability layer: scopes, host spans, counters, timers, metrics,
export (DESIGN.md §8).

``obs.trace`` holds the jit-neutral phase scopes, the host spans and the
one counter registry; ``obs.timers`` measures phases (segmented replay /
interleaved rounds); ``obs.metrics`` joins measured time with modeled flops
and comm bytes; ``obs.export`` writes Chrome-trace timelines;
``obs.profile_solve`` is the CLI that runs the whole pipeline on the
distributed fractional solve.

Only ``trace`` is imported eagerly — it is on the hot path of ``core``/
``solvers`` and must stay import-light (no numpy/perf dependencies).
"""
from repro.obs.trace import PHASES_SEEN, REGISTRY, count, counter, \
    enabled, phase, set_enabled, span

__all__ = ["phase", "span", "count", "counter", "enabled", "set_enabled",
           "PHASES_SEEN", "REGISTRY", "timers", "metrics", "export"]


def __getattr__(name):
    if name in ("timers", "metrics", "export", "profile_solve"):
        import importlib
        return importlib.import_module(f"repro.obs.{name}")
    raise AttributeError(f"module 'repro.obs' has no attribute {name!r}")
