"""round_stage_ms: the host's staging of a round's small arguments."""

from perfbench.harness import span_reader


def read(ctx):
    return span_reader.per_round_ms(ctx, ("round_stage",))
