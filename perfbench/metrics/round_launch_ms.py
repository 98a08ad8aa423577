"""round_launch_ms: the call of the round's executable until it returns."""

from perfbench.harness import span_reader


def read(ctx):
    return span_reader.per_round_ms(ctx, ("round_launch",))
