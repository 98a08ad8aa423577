"""Roofline share of the blocked attention kernels (``splash_mqa_*``:
forward, dq, dkv). Work: for every layer, query head and block of 512 x
512 the layer's mask keeps, 7 matrix products of 2 x 512 x 512 x head_dim
operations (forward q k^T and p v; backward q k^T again, dv, dp, dq, dk),
for every sequence of the round. The forward pass that remat repeats is in
the time and not in the work. Bytes (q, k, v, o and their cotangents once
each, bf16) are far under the compute bound and are counted for the form."""

import re

from perfbench.harness import arith

KERNEL = re.compile(r"^splash_mqa_")
BLOCK = 512               # the program's models/gpt2.GROUPED_ATTN_BLOCK
PRODUCTS = 7


def kept_blocks(S, window, block=BLOCK):
    """Blocks of the (S / block)^2 grid that hold a visible pair: key j is
    visible to query i iff 0 <= i - j (< window on a window layer)."""
    n = S // block
    kept = 0
    for qi in range(n):
        for ki in range(n):
            far = (qi + 1) * block - 1 - ki * block       # largest i - j
            near = qi * block - ((ki + 1) * block - 1)    # smallest i - j
            kept += far >= 0 and (window is None or near < window)
    return kept


def attention_work(config, S, sequences):
    """(operations, bytes) of a round's attention."""
    D, KV = config["head_dim"], config["num_key_value_heads"]
    flops = bytes_ = 0.0
    for l in range(config["num_hidden_layers"]):
        H = config["num_attention_heads_per_layer"][l]
        window = (config["sliding_window"]
                  if config["layer_types"][l] == "sliding_attention"
                  else None)
        flops += (PRODUCTS * 2.0 * BLOCK * BLOCK * D * H
                  * kept_blocks(S, window))
        bytes_ += 2.0 * 2 * S * D * (2 * H + 2 * KV)
    return flops * sequences, bytes_ * sequences


def read(ctx):
    if ctx.get("trace") is None or ctx.get("peaks") is None:
        return None
    from perfbench.families.laguna_moe import cell_shapes, kernel_seconds
    seconds = kernel_seconds(ctx, KERNEL)
    if seconds <= 0:
        return None
    flops, bytes_ = attention_work(*cell_shapes(ctx["facts"]))
    return arith.roofline_pct(bytes_, flops, seconds, ctx["peaks"])[0]
