"""sketch_encode_ms: every sketch encode of client gradients (scope
fed_sketch_encode): the kernel and its XLA glue."""

from perfbench.harness import phase_reader


def read(ctx):
    return phase_reader.phase_ms(ctx, ("fed_sketch_encode",))
