"""Seconds of set-up in JAX's compile stages (spans ``compile/trace``,
``compile/lower``, ``compile/backend``; a trace nested in another counts
once)."""
from bench.metrics.program_spans import setup_spans, total_s


def read(ctx):
    spans = setup_spans(ctx)
    return None if spans is None else total_s(spans, "compile/")
