"""Roofline share of the Pallas decode kernel: the algorithm needs one
decode of the error table per round (read the table, write d estimates)."""

from perfbench.harness import arith, readers


def read(ctx):
    return readers.kernel_roofline(ctx, "circulant_sketch_decode",
                                   arith.sketch_decode_bytes)
