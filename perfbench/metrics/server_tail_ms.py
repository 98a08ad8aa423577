"""server_tail_ms: the server tail (scope fed_server_tail)."""

from perfbench.harness import phase_reader


def read(ctx):
    return phase_reader.phase_ms(ctx, ("fed_server_tail",))
