"""Gigabytes per second of a distributed H^2 application's exchange: the
bytes one chip ships per application (program counter
``dist/exchange-bytes``, the comm model's) over its device time in the
exchange (``exchange_ms``)."""
from bench.metrics.exchange_ms import read as exchange_ms


def read(ctx):
    try:
        from repro.obs.trace import counter
    except ImportError:
        return None
    nbytes = counter("dist/exchange-bytes")
    ms = exchange_ms(ctx)
    if not nbytes or not ms:
        return None
    return nbytes / (ms * 1e-3) / 1e9
