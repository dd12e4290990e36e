"""Device-to-host reads per recompression: the program's counters
``compress/host-syncs`` over ``compress/calls``."""
from bench.metrics.program_spans import registry


def read(ctx):
    reg = registry()
    if reg is None:
        return None
    calls = reg.counts.get("compress/calls", 0)
    syncs = reg.counts.get("compress/host-syncs", 0)
    return syncs / calls if calls and syncs else None
