"""Seconds of set-up in the H^2 construction stages (spans ``construct/*``)
outside D's assembly (``build/d-assembly``, which has its own metric)."""
from bench.metrics.program_spans import setup_spans, total_s


def read(ctx):
    spans = setup_spans(ctx)
    return None if spans is None else \
        total_s(spans, "construct/", outside="build/d-assembly")
