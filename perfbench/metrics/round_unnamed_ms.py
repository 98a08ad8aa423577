"""round_unnamed_ms: what maps to no phase of the round: the honesty check."""

from perfbench.harness import phase_reader


def read(ctx):
    return phase_reader.phase_ms(ctx, None)
