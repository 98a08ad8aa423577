"""runtime_init_s: building the cell's FedRuntime and its first state."""

from perfbench.harness import span_reader


def read(ctx):
    return span_reader.setup_s(("runtime_init", "init_state"))
