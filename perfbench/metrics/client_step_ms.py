"""client_step_ms: the client step (scope fed_client_step, less the
encodes nested in it)."""

from perfbench.harness import phase_reader


def read(ctx):
    return phase_reader.phase_ms(ctx, ("fed_client_step",))
