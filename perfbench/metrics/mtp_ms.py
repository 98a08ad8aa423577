"""mtp_ms: the multi-token-prediction module outside its block's attention,
latent projections and experts (scope fed_mtp, nested in the client step).
Nothing where the program names no such phase."""

from perfbench.harness import phase_reader

PHASE = "fed_mtp"


def read(ctx):
    if PHASE not in phase_reader.program_phases():
        return None
    return phase_reader.phase_ms(ctx, (PHASE,))
