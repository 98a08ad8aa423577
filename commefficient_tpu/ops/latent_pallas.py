"""Latent attention's rotary and change of layout around the blocked
attention kernel, one pass a tensor in the tensor's own dtype.

The projections of ``models/joyai.JoyAIBlock`` write q as an (S, H x 192)
matrix (a head: 128 lanes without position, then 64 rotary lanes that
hold 32 interleaved pairs), the latent up-projection as (S, H x 256) (a
head: k's 128, then v's 128), and the rotary key that all heads share as
(S, 64). The blocked kernel (``models/gpt2.blocked_grouped_kernel``, one
query head a KV head) reads q as (H, 1, S, 192), k as (H, S, 192) and v
as (H, S, 128), and writes its output as (H, 1, S, 128), which the output
projection reads as (S, H x 128). Pallas kernels whose block index maps
do the transposition and whose bodies do the arithmetic, in float32 in
VMEM, a grid step two heads (four for the output) of t positions:

- ``qkv_to_heads``: q's pairs rotated in place, rounded, scaled, rounded;
  k = k's 128 lanes and the shared rotary key on every head; v. Its
  transpose (``QKV_BWD_KERNEL_NAME``) scales q's cotangent, rounds it and
  rotates it back, returns k's and v's to the rows, and sums the shared
  key's over the heads in float32, rounded once.
- ``heads_to_rows``: the output to rows; its transpose the other way
  (the same kernel name).

192 is not a multiple of the 128 lanes. The two heads of a step are 384
lanes of q's rows, the second starting half-way through a lane tile, so
the body moves it by 64 lanes (a roll and a select a tile) on its way to
its own rows, and back in the transpose.

The pairs stay where the projection writes them, on q and k alike: a
permutation of the plain path's half-split layout (``interleaved_rope``)
that leaves every score unchanged. The values are the plain path's: the
rotation in float32, then the rounding, the scale (rounded to the dtype)
and the rounding again. What crosses HBM is each tensor once in and once
out, in its dtype; nothing float32 shaped by the heads.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the kernels' bodies loop over 64 positions at a time, as Laguna's do: the
# loop keeps each kernel's code, and the seconds Mosaic takes over it, small
from commefficient_tpu.ops.rope_pallas import _tiles

# the names the kernels carry in the compiled program and in a device trace
QKV_KERNEL_NAME = "latent_qkv_relayout"
QKV_BWD_KERNEL_NAME = "latent_qkv_relayout_bwd"
O_KERNEL_NAME = "latent_o_relayout"

# the widths the kernels are written for (every published model of the
# DeepSeek-V3 key set): q and k 128 + 64 rotary, v 128
NOPE, ROPE, V_DIM = 128, 64, 128
_LANES = 128
# bytes a grid step moves in and out, blocks of every operand together:
# two buffers each stay within the 16 MiB of VMEM a kernel has without
# asking. No ``vmem_limit_bytes``: what one kernel asks for is taken from
# what XLA may keep in VMEM anywhere in the program (``ops/rope_pallas.py``)
STEP_BYTES = 4 << 20
# heads a grid step of the output's change of layout
_O_HEADS = 4


def fits(heads: int, nope: int, rope: int, v_dim: int) -> bool:
    """Whether the kernels take latent attention of these widths."""
    return (nope, rope, v_dim) == (NOPE, ROPE, V_DIM) and heads % 2 == 0


def _rows(S: int, row_bytes: int) -> int:
    """Positions a grid step: the largest power-of-two divisor of S whose
    ``row_bytes`` a position stay within ``STEP_BYTES``, and no fewer
    than 16 (one bfloat16 tile)."""
    t = 16
    while S % (2 * t) == 0 and 2 * t * row_bytes <= STEP_BYTES:
        t *= 2
    if S % t:
        raise ValueError(f"S = {S} is no multiple of {t} positions")
    return t


def pair_tables(cos, sin):
    """``rope_tables``' (S, R/2) pair as the rows of one lane tile: pair i
    on lanes (2i, 2i + 1) of the first R lanes, (cos, cos) and (-sin,
    +sin), then (1, 0) on the lanes that pass, so that ``x * c +
    partner(x) * s`` rotates the pairs in place."""
    S, half = cos.shape
    rest = _LANES - 2 * half
    c = jnp.concatenate([jnp.repeat(cos, 2, axis=-1),
                         jnp.ones((S, rest), cos.dtype)], axis=-1)
    s = jnp.concatenate([jnp.stack([-sin, sin], axis=-1).reshape(S, -1),
                         jnp.zeros((S, rest), sin.dtype)], axis=-1)
    return c, s


def _partner(x):
    """Lane 2i reads 2i + 1 and lane 2i + 1 reads 2i."""
    lane = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(lane % 2 == 0, pltpu.roll(x, x.shape[1] - 1, 1),
                     pltpu.roll(x, 1, 1))


def _widen(x):
    """A (rows, 64) value as the first half of a lane tile (the second
    half repeats it and is never read)."""
    return jnp.concatenate([x, x], axis=1)


def _rotated(x, c, s):
    """The pairs of x (rows, 128) rotated in place by the lane tables."""
    return x * c + _partner(x) * s


def _qkv_kernel(q_ref, kv_ref, kva_ref, c_ref, s_ref, oq_ref, ok_ref,
                ov_ref, *, scale):
    dt, f32 = oq_ref.dtype, jnp.float32
    rope_at = kva_ref.shape[2] - ROPE

    def tile(rows):
        c, s = c_ref[rows, :], s_ref[rows, :]
        c2, s2 = pltpu.roll(c, 64, 1), pltpu.roll(s, 64, 1)

        def q_out(y):     # round the rotation, scale, round again
            return (y.astype(dt).astype(f32) * scale).astype(dt).astype(f32)

        # q's 384 lanes of the two heads as three lane tiles: head 0's
        # 128; its 64 rotary lanes and head 1's first 64; head 1's next 64
        # and its 64 rotary lanes
        x0, x1, x2 = (q_ref[0, rows, i * 128:(i + 1) * 128].astype(f32)
                      for i in range(3))
        y0 = q_out(x0)
        y1 = q_out(_rotated(x1, c, s))
        y2 = q_out(_rotated(x2, c2, s2))
        r1, r2 = pltpu.roll(y1, 64, 1), pltpu.roll(y2, 64, 1)
        first = lax.broadcasted_iota(jnp.int32, y1.shape, 1) < 64
        oq_ref[0, 0, 0, rows, 0:128] = y0.astype(dt)
        oq_ref[0, 0, 0, rows, 128:192] = y1[:, :64].astype(dt)
        oq_ref[0, 1, 0, rows, 0:128] = jnp.where(first, r1, r2).astype(dt)
        oq_ref[0, 1, 0, rows, 128:192] = r2[:, :64].astype(dt)
        kr = _widen(kva_ref[0, rows, rope_at:rope_at + ROPE].astype(f32))
        kr = _rotated(kr, c, s)[:, :ROPE].astype(dt)
        for g in range(2):
            ok_ref[0, g, rows, 0:128] = kv_ref[0, rows, 256 * g:256 * g + 128]
            ok_ref[0, g, rows, 128:192] = kr
            ov_ref[0, g, rows, :] = kv_ref[0, rows,
                                           256 * g + 128:256 * (g + 1)]

    _tiles(c_ref.shape[0], tile)


def _qkv_bwd_kernel(dq_ref, dk_ref, dv_ref, c_ref, s_ref, oq_ref, okv_ref,
                    okr_ref, acc_ref, *, scale):
    """The transpose: the rotation's with the sine negated; the shared
    key's cotangent summed over the heads in ``acc_ref`` (float32), then
    rounded, rotated back and rounded at the last pair of heads."""
    dt, f32 = oq_ref.dtype, jnp.float32
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

    def tile(rows):
        c, s = c_ref[rows, :], -s_ref[rows, :]
        c2, s2 = pltpu.roll(c, 64, 1), pltpu.roll(s, 64, 1)

        def q_in(x):      # the transpose scales first, and rounds
            return (x.astype(f32) * scale).astype(dt).astype(f32)

        a0 = q_in(dq_ref[0, 0, 0, rows, 0:128])
        b0 = q_in(_widen(dq_ref[0, 0, 0, rows, 128:192]))
        a1 = q_in(dq_ref[0, 1, 0, rows, 0:128])
        b1 = q_in(_widen(dq_ref[0, 1, 0, rows, 128:192]))
        r = pltpu.roll(a1, 64, 1)
        first = lax.broadcasted_iota(jnp.int32, r.shape, 1) < 64
        x1 = jnp.where(first, b0, r)
        x2 = jnp.where(first, r, pltpu.roll(b1, 64, 1))
        oq_ref[0, rows, 0:128] = a0.astype(dt)
        oq_ref[0, rows, 128:256] = _rotated(x1, c, s).astype(dt)
        oq_ref[0, rows, 256:384] = _rotated(x2, c2, s2).astype(dt)
        acc = acc_ref[rows, :]
        for g in range(2):
            okv_ref[0, rows, 256 * g:256 * g + 128] = dk_ref[0, g, rows,
                                                             0:128]
            okv_ref[0, rows, 256 * g + 128:256 * (g + 1)] = dv_ref[0, g,
                                                                   rows, :]
            acc = acc + dk_ref[0, g, rows, 128:192].astype(f32)
        acc_ref[rows, :] = acc

    def shared(rows):
        c, s = c_ref[rows, :], -s_ref[rows, :]
        x = _widen(acc_ref[rows, :].astype(dt).astype(f32))
        okr_ref[0, rows, :] = _rotated(x, c, s)[:, :ROPE].astype(dt)

    t = c_ref.shape[0]
    _tiles(t, tile)
    pl.when(j == pl.num_programs(2) - 1)(lambda: _tiles(t, shared))


def _qkv_call(kernel, name, ins, outs, out_shapes, *, B, S, H, lora,
              itemsize, interpret, scratch=()):
    """``pallas_call`` over (B, S / t, H / 2) steps, two heads of t
    positions each: each operand's block is named in ``ins`` and
    ``outs``, the rows of q, kv and the latent projection (``kv_a``, the
    rotary key in its last 64 lanes), the heads of q, k and v, the rotary
    key's cotangent (``shared``) and the lane tables."""
    width = lora + ROPE
    # what a position moves a step, lanes as VMEM stores them (192 in
    # 256): q 384 in and 512 out, kv 512, the latent rows, k 512, v 256;
    # the float32 tables
    lanes = 384 + 512 + 512 + 512 + 256 + -(-width // 128) * 128
    t = _rows(S, itemsize * lanes + 2 * 4 * _LANES)
    specs = {
        "q_rows": pl.BlockSpec((1, t, 2 * (NOPE + ROPE)),
                               lambda b, i, j: (b, i, j)),
        "kv_rows": pl.BlockSpec((1, t, 2 * (NOPE + V_DIM)),
                                lambda b, i, j: (b, i, j)),
        "latent_rows": pl.BlockSpec((1, t, width), lambda b, i, j: (b, i, 0)),
        "shared": pl.BlockSpec((1, t, ROPE), lambda b, i, j: (b, i, 0)),
        "table": pl.BlockSpec((t, _LANES), lambda b, i, j: (i, 0)),
        "q_heads": pl.BlockSpec((1, 2, 1, t, NOPE + ROPE),
                                lambda b, i, j: (b, j, 0, i, 0)),
        "k_heads": pl.BlockSpec((1, 2, t, NOPE + ROPE),
                                lambda b, i, j: (b, j, i, 0)),
        "v_heads": pl.BlockSpec((1, 2, t, V_DIM),
                                lambda b, i, j: (b, j, i, 0)),
    }
    return pl.pallas_call(
        kernel, grid=(B, S // t, H // 2),
        in_specs=[specs[k] for k in ins],
        out_specs=[specs[k] for k in outs], out_shape=out_shapes,
        scratch_shapes=[pltpu.VMEM((t,) + shape, jnp.float32)
                        for shape in scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name=name)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _qkv_to_heads(q, kv, kv_a, c, s, width, scale, interpret):
    """``width``: kv_a's, which its cotangent takes."""
    B, S, _ = q.shape
    H = kv.shape[-1] // (NOPE + V_DIM)
    shapes = ((B, H, 1, S, NOPE + ROPE), (B, H, S, NOPE + ROPE),
              (B, H, S, V_DIM))
    return tuple(_qkv_call(
        functools.partial(_qkv_kernel, scale=scale), QKV_KERNEL_NAME,
        ["q_rows", "kv_rows", "latent_rows", "table", "table"],
        ["q_heads", "k_heads", "v_heads"],
        [jax.ShapeDtypeStruct(shape, q.dtype) for shape in shapes],
        B=B, S=S, H=H, lora=width - ROPE, itemsize=q.dtype.itemsize,
        interpret=interpret)(q, kv, kv_a, c, s))


def _qkv_fwd(q, kv, kv_a, c, s, width, scale, interpret):
    return (_qkv_to_heads(q, kv, kv_a, c, s, width, scale, interpret),
            (c, s))


def _qkv_bwd(width, scale, interpret, tables, cts):
    c, s = tables
    dq, dk, dv = cts
    B, H, S, _ = dk.shape
    dt = dq.dtype
    dq, dkv, dkr = _qkv_call(
        functools.partial(_qkv_bwd_kernel, scale=scale), QKV_BWD_KERNEL_NAME,
        ["q_heads", "k_heads", "v_heads", "table", "table"],
        ["q_rows", "kv_rows", "shared"],
        [jax.ShapeDtypeStruct((B, S, H * (NOPE + ROPE)), dt),
         jax.ShapeDtypeStruct((B, S, H * (NOPE + V_DIM)), dt),
         jax.ShapeDtypeStruct((B, S, ROPE), dt)],
        B=B, S=S, H=H, lora=width - ROPE, itemsize=dt.itemsize,
        interpret=interpret, scratch=[(ROPE,)])(dq, dk, dv, c, s)
    dkv_a = jnp.pad(dkr, ((0, 0), (0, 0), (width - ROPE, 0)))
    return dq, dkv, dkv_a, jnp.zeros_like(c), jnp.zeros_like(s)


_qkv_to_heads.defvjp(_qkv_fwd, _qkv_bwd)


def qkv_to_heads(q, kv, kv_a, tables, *, scale, interpret=False):
    """q (B, S, H x 192), kv (B, S, H x 256) and kv_a (B, S, L + 64), the
    latent projection with the rotary key that all heads share in its
    last 64 lanes, as the projections write them -> q (B, H, 1, S, 192),
    its rotary lanes' pairs rotated in place, rounded to q's dtype,
    multiplied by ``scale`` (rounded to that dtype) and rounded; k (B, H,
    S, 192), each head's 128 lanes of kv then the rotated shared key; v
    (B, H, S, 128), each head's other 128. ``tables``: ``pair_tables``,
    which carry no gradient (functions of the positions). kv_a's
    cotangent is zero but in the last 64 lanes."""
    c, s = (lax.stop_gradient(x) for x in tables)
    # the constant the plain path multiplies by: rounded to q's dtype
    scale = float(np.asarray(scale, dtype=q.dtype))
    return _qkv_to_heads(q, kv, kv_a, c, s, kv_a.shape[-1], scale,
                         interpret)


def _to_rows_kernel(o_ref, out_ref):
    def tile(rows):
        for g in range(o_ref.shape[1]):
            out_ref[0, rows, g * V_DIM:(g + 1) * V_DIM] = o_ref[0, g, 0, rows,
                                                                :]

    _tiles(o_ref.shape[3], tile)


def _to_heads_kernel(ct_ref, out_ref):
    def tile(rows):
        for g in range(out_ref.shape[1]):
            out_ref[0, g, 0, rows, :] = ct_ref[0, rows,
                                               g * V_DIM:(g + 1) * V_DIM]

    _tiles(out_ref.shape[3], tile)


def _o_call(kernel, x, to_rows, interpret):
    if to_rows:
        B, H, _, S, _ = x.shape
        shape = (B, S, H * V_DIM)
    else:
        B, S, width = x.shape
        H = width // V_DIM
        shape = (B, H, 1, S, V_DIM)
    G = _O_HEADS if H % _O_HEADS == 0 else 1
    t = _rows(S, 2 * G * V_DIM * x.dtype.itemsize)
    heads = pl.BlockSpec((1, G, 1, t, V_DIM), lambda b, i, j: (b, j, 0, i, 0))
    rows = pl.BlockSpec((1, t, G * V_DIM), lambda b, i, j: (b, i, j))
    return pl.pallas_call(
        kernel, grid=(B, S // t, H // G),
        in_specs=[heads if to_rows else rows],
        out_specs=rows if to_rows else heads,
        out_shape=jax.ShapeDtypeStruct(shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret, name=O_KERNEL_NAME)(x)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _heads_to_rows(o, interpret):
    return _o_call(_to_rows_kernel, o, True, interpret)


def _o_fwd(o, interpret):
    return _heads_to_rows(o, interpret), None


def _o_bwd(interpret, _, ct):
    return (_o_call(_to_heads_kernel, ct, False, interpret),)


_heads_to_rows.defvjp(_o_fwd, _o_bwd)


def heads_to_rows(o, *, interpret=False):
    """The blocked kernel's output (B, H, 1, S, 128) -> (B, S, H x 128),
    the output projection's rows; its transpose is the way back."""
    return _heads_to_rows(o, interpret)
