"""Share of the device's busy time, in percent, in operations whose scope
path holds no phase of the program (``repro.obs.trace.PHASES_SEEN``): the
work that no per-layer metric can name."""
from bench.trace_reduce import matches


def read(ctx):
    try:
        from repro.obs.trace import PHASES_SEEN
    except ImportError:
        return None
    phases = list(PHASES_SEEN)
    seen = {}
    busy = unscoped = 0.0
    for ops in ctx["reduced"].devices:
        for o in ops:
            named = seen.get(o.scope)
            if named is None:
                named = seen[o.scope] = any(matches(o.scope, p)
                                            for p in phases)
            busy += o.own
            if not named:
                unscoped += o.own
    return 100.0 * unscoped / busy if busy else None
