"""mla_attention_ms: attention proper in the latent-attention family (scope
fed_attention; attention_ms reads the same scope in family laguna_moe).
Nothing where the program names no such phase."""

from perfbench.harness import phase_reader

PHASE = "fed_attention"


def read(ctx):
    if PHASE not in phase_reader.program_phases():
        return None
    return phase_reader.phase_ms(ctx, (PHASE,))
