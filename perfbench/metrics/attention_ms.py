"""attention_ms: attention proper (scope fed_attention, nested in the client
step). Nothing where the program names no such phase."""

from perfbench.harness import phase_reader

PHASE = "fed_attention"


def read(ctx):
    if PHASE not in phase_reader.program_phases():
        return None
    return phase_reader.phase_ms(ctx, (PHASE,))
