"""Bytes and operations an algorithm needs, computed from its shapes.

Kept with the benchmark so that no PR that claims a gain can move the
numerator of a utilization or the byte count a client is charged.
"""

from __future__ import annotations

WIRE_BYTES = {"float32": 4, "bfloat16": 2}


def upload_bytes_per_client(mode, d, table_shape=None, wire_dtype="float32",
                            k=None):
    """Simulated upload of one participating client in one round.

    sketch: the (rows, cols) table as it is *resolved* (after any
    alignment of the width) in the wire dtype. uncompressed, true_topk,
    fedavg: the dense gradient, 4 bytes a coordinate. local_topk: k
    values (the reference's byte table counts values, not indices)."""
    if mode == "sketch":
        rows, cols = table_shape
        if wire_dtype not in WIRE_BYTES:
            raise ValueError(f"no byte arithmetic for wire dtype "
                             f"{wire_dtype!r}")
        return float(rows * cols * WIRE_BYTES[wire_dtype])
    if mode == "local_topk":
        return 4.0 * k
    return 4.0 * d


def sketch_encode_bytes(d, rows, cols):
    """HBM bytes the circulant Count Sketch encode needs: read the d-long
    f32 vector once, write the (rows, cols) f32 table once. Signs and
    shifts are a few KB and are not counted."""
    return 4.0 * (d + rows * cols)


def sketch_decode_bytes(d, rows, cols):
    """Decode: read the table once, write d estimates once."""
    return 4.0 * (d + rows * cols)


def roofline_pct(bytes_needed, flops_needed, seconds, peaks):
    """Share of the roofline: the least time the chip could take (the
    larger of bytes over peak bandwidth and operations over peak rate)
    over the time it took. Returns (percent, which bound)."""
    t_mem = bytes_needed / peaks["hbm_bytes_per_s"]
    t_flop = flops_needed / peaks["bf16_flops"]
    bound = "memory" if t_mem >= t_flop else "compute"
    return 100.0 * max(t_mem, t_flop) / seconds, bound


def mfu_pct(model_flops, seconds, chips, peaks):
    """Model-FLOPs utilization: operations the forward and backward
    passes require (recomputation not counted) over what ``chips`` chips
    could do in ``seconds``."""
    return 100.0 * model_flops / (seconds * chips * peaks["bf16_flops"])


def matmul_flops(fn, *args):
    """Operations of the convolutions and matrix products ``fn(*args)``
    traces to, 2 per multiply-add, counted from the shapes in its jaxpr
    (nothing is lowered or compiled, so the count is the same on every
    backend). Elementwise work, reductions and pooling are not counted:
    in a convolutional network they are under 1% of the total."""
    import jax
    import numpy as np

    def walk(jaxpr):
        total = 0.0
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name == "conv_general_dilated":
                lhs, rhs = (v.aval.shape for v in eqn.invars[:2])
                out = eqn.outvars[0].aval.shape
                dn = eqn.params["dimension_numbers"]
                in_ch = lhs[dn.lhs_spec[1]] // eqn.params[
                    "feature_group_count"]
                window = np.prod([rhs[i] for i in dn.rhs_spec[2:]])
                total += 2.0 * np.prod(out) * in_ch * window
            elif name == "dot_general":
                lhs = eqn.invars[0].aval.shape
                (contract, _), _ = eqn.params["dimension_numbers"]
                total += 2.0 * np.prod(eqn.outvars[0].aval.shape) * np.prod(
                    [lhs[i] for i in contract])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                total += walk(sub)
        return total

    return float(walk(jax.make_jaxpr(fn)(*args).jaxpr))
