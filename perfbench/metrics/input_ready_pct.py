"""input_ready_pct: rounds that found their batch waiting."""

from perfbench.harness import span_reader


def read(ctx):
    return span_reader.ready_pct(ctx)
