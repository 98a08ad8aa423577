"""first_gather_s: the DeviceStore's first batch, its compile included."""

from perfbench.harness import span_reader


def read(ctx):
    return span_reader.setup_s(("data_gather_first",))
