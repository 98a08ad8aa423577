"""mla_moe_ms: the routed expert layer in the latent-attention family (scope
fed_moe; moe_ms reads the same scope in family laguna_moe). Nothing where
the program names no such phase."""

from perfbench.harness import phase_reader

PHASE = "fed_moe"


def read(ctx):
    if PHASE not in phase_reader.program_phases():
        return None
    return phase_reader.phase_ms(ctx, (PHASE,))
