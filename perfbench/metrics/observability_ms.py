"""observability_ms: what the round computes only to be observed (scopes
fed_signals, fed_layer_signals, fed_client_stats)."""

from perfbench.harness import phase_reader


def read(ctx):
    return phase_reader.phase_ms(ctx, ("fed_signals", "fed_layer_signals",
                                      "fed_client_stats"))
