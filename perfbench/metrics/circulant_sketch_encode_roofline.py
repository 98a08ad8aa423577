"""Roofline share of the Pallas encode kernel: the algorithm needs one
encode of the round's gradient (read d floats, write the table)."""

from perfbench.harness import arith, readers


def read(ctx):
    return readers.kernel_roofline(ctx, "circulant_sketch_encode",
                                   arith.sketch_encode_bytes)
