"""mla_latent_ms: what surrounds latent attention's kernel (scope fed_latent,
nested in the client step). Nothing where the program names no such phase."""

from perfbench.harness import phase_reader

PHASE = "fed_latent"


def read(ctx):
    if PHASE not in phase_reader.program_phases():
        return None
    return phase_reader.phase_ms(ctx, (PHASE,))
