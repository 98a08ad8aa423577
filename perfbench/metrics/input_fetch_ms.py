"""input_fetch_ms: what a round's input costs, hidden or not."""

from perfbench.harness import span_reader


def read(ctx):
    return span_reader.per_round_ms(ctx, ("data_fetch",))
