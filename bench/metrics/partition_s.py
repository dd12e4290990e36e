"""Seconds of set-up in the block-row partition of the operator and the
placement of each block row on its chip (program span
``build/partition``)."""
from bench.metrics.program_spans import setup_spans, total_s


def read(ctx):
    spans = setup_spans(ctx)
    return None if spans is None else total_s(spans, "build/partition")
