"""Seconds of set-up spent assembling the fractional operator's diagonal D
(span ``build/d-assembly``: the extended grid, K-hat's construction, its
ones-matvec and the restriction)."""
from bench.metrics.program_spans import setup_spans, total_s


def read(ctx):
    spans = setup_spans(ctx)
    return None if spans is None else total_s(spans, "build/d-assembly")
