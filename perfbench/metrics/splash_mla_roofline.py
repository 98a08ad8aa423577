"""Roofline share of latent attention's blocked kernels (``splash_mqa_*``
or ``splash_mha_*``: forward, dq, dkv; whichever form the program runs).
Work, from the cell's files alone and the same whatever implements it: for
every block of the model (``num_hidden_layers`` and the prediction
modules'), head, sequence of the round and 512 x 512 block the causal mask
keeps, 2 x 512 x 512 x (4 x qk + 3 x v) operations: forward q k^T (qk
wide) and p v (v wide); backward q k^T again (qk), dv (v), dp (v), dq
(qk), dk (qk). A forward pass that remat repeats, or zero padding of the
heads, is in the time and not in the work. Bytes (q, k, v, o and their
cotangents once each, bf16) are far under the compute bound and are
counted for the form."""

import re

from perfbench.harness import arith

KERNEL = re.compile(r"^splash_(mqa|mha)_")
BLOCK = 512               # the program's models/gpt2.GROUPED_ATTN_BLOCK


def kept_blocks(S, block=BLOCK):
    """Blocks of the (S / block)^2 grid that hold a pair j <= i."""
    n = S // block
    return n * (n + 1) // 2


def attention_work(config, S, sequences):
    """(operations, bytes) of a round's latent attention."""
    H = config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    v = config["v_head_dim"]
    blocks = config["num_hidden_layers"] + config["num_nextn_predict_layers"]
    flops = (2.0 * BLOCK * BLOCK * (4 * qk + 3 * v) * H * kept_blocks(S)
             * blocks)
    # q, k, dq, dk at qk; v, o, dv, do at v
    bytes_ = 2.0 * S * H * 4 * (qk + v) * blocks
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
