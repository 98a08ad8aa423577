"""Rotary, scale, gate and the change of layout around the blocked
attention kernel, one pass a tensor in the tensor's own dtype.

The projections write q, k and the attention output as (S, H x D)
matrices; the blocked kernel (``models/gpt2.blocked_grouped_kernel``)
reads and writes q and its output as (KV, G, S, D) and k as (KV, S, D).
With D a multiple of 128, the G query heads of KV head j at rows s..s+t
are the block (t, G x D) at column block j of the matrix and the block
(1, G, t, D) of the head-major array, so a Pallas kernel's block index
maps do the transposition and its body the arithmetic, in float32 in
VMEM, a grid step a KV head and t positions:

- ``rope_to_heads``: q and k in one call: rotate (``rotate_half`` as a
  lane rotation, tables of width D), scale q, go head-major. Its
  transpose is the same kernel with the sine negated and the two layouts
  exchanged.
- ``gate_from_heads``: multiply head h by its per-position gate, go back
  to (S, H x D). Its transpose reads the cotangent, the kernel's output
  and the gate once and writes both cotangents.

What crosses HBM is each tensor once in and once out, in its dtype
(bfloat16 in the benchmark); nothing float32 of that size is written.
The values are those of ``models/laguna.apply_rope`` followed by the
scale, and of the float32 gate product: the same roundings at the same
places (after the rotation, after the scale, after the gate).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the names the kernels carry in the compiled program and in a device
# trace (the rotary kernel under one name in both directions)
ROPE_KERNEL_NAME = "head_rope_relayout"
GATE_KERNEL_NAME = "head_gate_relayout"
GATE_BWD_KERNEL_NAME = "head_gate_relayout_bwd"

# bytes of q's block a grid step moves one way: the G heads of ``_rows``
# positions. Two buffers a block and two to four blocks a kernel stay
# within the 16 MiB of VMEM a kernel has without asking. No
# ``vmem_limit_bytes``: what one kernel asks for is taken from what XLA
# may keep in VMEM anywhere in the program (48 MiB here moved the output
# head's gradient accumulator of the chunked cross-entropy back to HBM,
# +65 ms a Laguna round; PERF.md, PR 35)
BLOCK_BYTES = 1 << 20
# positions a pass of a kernel's inner loop: the loop keeps a kernel's
# code, and with it the seconds Mosaic takes over each of the 30 calls a
# client step holds, at one tile a head
_TILE_ROWS = 64


def _rows(S: int, row_bytes: int) -> int:
    """Positions a grid step: the largest power-of-two divisor of S whose
    block of ``row_bytes`` a position stays within ``BLOCK_BYTES``, and
    no fewer than 16 (one bfloat16 tile)."""
    t = 16
    while S % (2 * t) == 0 and 2 * t * row_bytes <= BLOCK_BYTES:
        t *= 2
    return t


def _lane_tables(cos, sin, D: int):
    """``rope_tables``' (S, half) pair as full-width rows: (cos, cos, 1)
    and (-sin, +sin, 0) over the two rotated halves and the rest that
    passes, so that ``x * c + rotate_half(x) * s`` is the rotation."""
    S, half = cos.shape
    rest = D - 2 * half
    c = jnp.concatenate([cos, cos, jnp.ones((S, rest), cos.dtype)], axis=-1)
    s = jnp.concatenate([-sin, sin, jnp.zeros((S, rest), sin.dtype)],
                        axis=-1)
    return c, s


def _partner(x, half: int):
    """Lane l of the first rotated half reads l + half, of the second
    l - half; what the lanes past ``2 * half`` read meets a zero."""
    D = x.shape[-1]
    up = pltpu.roll(x, D - half, 1)
    if 2 * half == D:
        return up
    lane = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(lane < half, up, pltpu.roll(x, half, 1))


def _tiles(t, body):
    """``body(rows)`` over the t positions of a block, ``_TILE_ROWS`` at
    a time."""
    rt = min(t, _TILE_ROWS)

    def step(r, carry):
        body(pl.ds(pl.multiple_of(r * rt, rt), rt))
        return carry

    lax.fori_loop(0, t // rt, step, None)


def _rope_kernel(q_ref, k_ref, c_ref, s_ref, oq_ref, ok_ref, *, G, D, half,
                 scale, to_heads):
    dt = oq_ref.dtype

    def rounded(y):
        return y.astype(dt).astype(jnp.float32)

    def tile(rows):
        c, s = c_ref[rows, :], s_ref[rows, :]

        def rotated(x, scale):
            x = x.astype(jnp.float32)
            if scale is not None and not to_heads:
                x = rounded(x * scale)
            y = x * c + _partner(x, half) * s
            if scale is not None and to_heads:
                y = rounded(y) * scale
            return y.astype(dt)

        for g in range(G):
            cols = slice(g * D, (g + 1) * D)
            if to_heads:
                oq_ref[0, 0, g, rows, :] = rotated(q_ref[0, rows, cols],
                                                   scale)
            else:
                oq_ref[0, rows, cols] = rotated(q_ref[0, 0, g, rows, :],
                                                scale)
        if to_heads:
            ok_ref[0, 0, rows, :] = rotated(k_ref[0, rows, :], None)
        else:
            ok_ref[0, rows, :] = rotated(k_ref[0, 0, rows, :], None)

    _tiles(c_ref.shape[0], tile)


def _call(kernel, name, ins, outs, out_shapes, *, B, KV, G, S, D, itemsize,
          interpret):
    """``pallas_call`` over (B, S / t, KV) steps, a KV head's G query
    heads of t positions each. ``ins`` and ``outs`` name each operand's
    block: ``flat_q`` / ``flat_k`` of the (B, S, KV x G x D) and (B, S,
    KV x D) matrices, ``major_q`` / ``major_k`` of the (B, KV, G, S, D)
    and (B, KV, S, D) arrays, ``gate`` of a (B, KV, S, G) array,
    ``table`` of an (S, D) table (fetched once for the KV steps)."""
    t = _rows(S, G * D * itemsize)
    if S % t or D % 128:
        raise ValueError(f"S = {S} is no multiple of {t} positions or "
                         f"D = {D} of the 128 lanes")
    specs = {
        "flat_q": pl.BlockSpec((1, t, G * D), lambda b, i, j: (b, i, j)),
        "flat_k": pl.BlockSpec((1, t, D), lambda b, i, j: (b, i, j)),
        "major_q": pl.BlockSpec((1, 1, G, t, D),
                                lambda b, i, j: (b, j, 0, i, 0)),
        "major_k": pl.BlockSpec((1, 1, t, D), lambda b, i, j: (b, j, i, 0)),
        "gate": pl.BlockSpec((1, 1, t, G), lambda b, i, j: (b, j, i, 0)),
        "table": pl.BlockSpec((t, D), lambda b, i, j: (i, 0)),
    }
    return pl.pallas_call(
        kernel, grid=(B, S // t, KV),
        in_specs=[specs[k] for k in ins],
        out_specs=[specs[k] for k in outs], out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name=name)


def _rope_call(q, k, c, s, *, KV, half, scale, to_heads, interpret):
    """``to_heads``: q (B, S, KV x G x D) and k (B, S, KV x D) -> (B, KV,
    G, S, D) and (B, KV, S, D), rotated, q then scaled; else the other
    way, q scaled then both rotated (the transpose's order; the caller
    negates ``s``)."""
    B, (S, D) = q.shape[0], c.shape
    G = (q.shape[-1] // (KV * D)) if to_heads else q.shape[2]
    if scale is not None:
        # the constant the plain path multiplies by: rounded to q's dtype
        scale = float(np.asarray(scale, dtype=q.dtype))
    here, there = ("flat", "major") if to_heads else ("major", "flat")
    shapes = (((B, KV, G, S, D), (B, KV, S, D)) if to_heads
              else ((B, S, KV * G * D), (B, S, KV * D)))
    return _call(
        functools.partial(_rope_kernel, G=G, D=D, half=half, scale=scale,
                          to_heads=to_heads),
        ROPE_KERNEL_NAME,
        [here + "_q", here + "_k", "table", "table"],
        [there + "_q", there + "_k"],
        [jax.ShapeDtypeStruct(shape, x.dtype)
         for shape, x in zip(shapes, (q, k))],
        B=B, KV=KV, G=G, S=S, D=D, itemsize=q.dtype.itemsize,
        interpret=interpret)(q, k, c, s)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _rope_to_heads(q, k, c, s, KV, half, scale, interpret):
    return tuple(_rope_call(q, k, c, s, KV=KV, half=half, scale=scale,
                            to_heads=True, interpret=interpret))


def _rope_fwd(q, k, c, s, KV, half, scale, interpret):
    return _rope_to_heads(q, k, c, s, KV, half, scale, interpret), (c, s)


def _rope_bwd(KV, half, scale, interpret, tables, cts):
    c, s = tables
    dq, dk = _rope_call(*cts, c, -s, KV=KV, half=half, scale=scale,
                        to_heads=False, interpret=interpret)
    return dq, dk, jnp.zeros_like(c), jnp.zeros_like(s)


_rope_to_heads.defvjp(_rope_fwd, _rope_bwd)


def rope_to_heads(q, k, cos, sin, *, head_dim, scale=None, interpret=False):
    """q (B, S, H x D) and k (B, S, KV x D) as the projections write them
    -> (B, KV, H / KV, S, D) and (B, KV, S, D), D = ``head_dim``: the
    first ``2 * cos.shape[-1]`` dimensions of every head rotated as
    ``models/laguna.apply_rope`` rotates them, q then (``scale`` not
    None) rounded to its dtype and multiplied by ``scale``. ``cos`` and
    ``sin`` are ``rope_tables``' (S, half) pair and carry no gradient:
    they are functions of the positions."""
    c, s = _lane_tables(lax.stop_gradient(cos), lax.stop_gradient(sin),
                        head_dim)
    return _rope_to_heads(q, k, c, s, k.shape[-1] // head_dim,
                          cos.shape[-1], scale, interpret)


def _gate_kernel(o_ref, g_ref, out_ref, *, G, D):
    def tile(rows):
        g = g_ref[0, 0, rows, :]                             # (rows, G) f32
        for h in range(G):
            out_ref[0, rows, h * D:(h + 1) * D] = (
                o_ref[0, 0, h, rows, :].astype(jnp.float32)
                * g[:, h:h + 1]).astype(out_ref.dtype)

    _tiles(g_ref.shape[2], tile)


def _gate_bwd_kernel(ct_ref, o_ref, g_ref, do_ref, dg_ref, *, G, D):
    def tile(rows):
        g = g_ref[0, 0, rows, :]
        lane = lax.broadcasted_iota(jnp.int32, g.shape, 1)
        dg = jnp.zeros_like(g)
        for h in range(G):
            ct = ct_ref[0, rows, h * D:(h + 1) * D].astype(jnp.float32)
            do_ref[0, 0, h, rows, :] = (ct * g[:, h:h + 1]).astype(
                do_ref.dtype)
            col = jnp.sum(ct * o_ref[0, 0, h, rows, :].astype(jnp.float32),
                          axis=-1, keepdims=True)
            dg = jnp.where(lane == h, col, dg)
        dg_ref[0, 0, rows, :] = dg

    _tiles(g_ref.shape[2], tile)


def _gate_layout(o):
    B, KV, G, S, D = o.shape
    return dict(B=B, KV=KV, G=G, S=S, D=D, itemsize=o.dtype.itemsize)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _gate_from_heads(o, gate, interpret):
    """``gate`` (B, KV, S, G)."""
    lay = _gate_layout(o)
    B, KV, G, S, D = o.shape
    return _call(
        functools.partial(_gate_kernel, G=G, D=D), GATE_KERNEL_NAME,
        ["major_q", "gate"], ["flat_q"],
        [jax.ShapeDtypeStruct((B, S, KV * G * D), o.dtype)], **lay,
        interpret=interpret)(o, gate)[0]


def _gate_fwd(o, gate, interpret):
    return _gate_from_heads(o, gate, interpret), (o, gate)


def _gate_bwd(interpret, res, ct):
    o, gate = res
    return tuple(_call(
        functools.partial(_gate_bwd_kernel, G=o.shape[2], D=o.shape[4]),
        GATE_BWD_KERNEL_NAME, ["flat_q", "major_q", "gate"],
        ["major_q", "gate"],
        [jax.ShapeDtypeStruct(o.shape, o.dtype),
         jax.ShapeDtypeStruct(gate.shape, gate.dtype)], **_gate_layout(o),
        interpret=interpret)(ct, o, gate))


_gate_from_heads.defvjp(_gate_fwd, _gate_bwd)


def gate_from_heads(o, gate, *, interpret=False):
    """o (B, KV, G, S, D) as the blocked kernel writes it, gate (B, S, H)
    float32 -> (B, S, H x D) in o's dtype for the output projection:
    ``(o.astype(float32) * gate[..., None]).astype(o.dtype)`` of the
    plain path, transposed on the way. (The gate goes in as (B, KV, S, G),
    an XLA transpose of S x H floats.)"""
    B, KV, G, S, _ = o.shape
    return _gate_from_heads(
        o, gate.reshape(B, S, KV, G).transpose(0, 2, 1, 3), interpret)
