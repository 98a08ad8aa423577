"""byte_ledger_ms: the byte ledger (scope fed_byte_ledger)."""

from perfbench.harness import phase_reader


def read(ctx):
    return phase_reader.phase_ms(ctx, ("fed_byte_ledger",))
