"""Pallas TPU kernels for the circulant count sketch's encode/decode.

The jnp implementation in ops/circulant.py compiles the per-(row, block)
static rolls into r·m separate slice+concat HLO ops (1,185 at the GPT-2
config: m=237 blocks, r=5 rows), each paying XLA's fixed per-op cost —
measured (chained on-device, d=124M, c=524288, v5e) ~26 ms encode and
~129 ms decode. These kernels fuse each direction into ONE
``pallas_call``.

Design history (all numbers measured the same way):
- v1 DMA'd whole (8, c) row-groups: 16 MB blocks double-buffered against
  ~16 MB VMEM — the Mosaic compile never terminated.
- v2 lane-tiled with two-tile gathers + dynamic ``pltpu.roll``:
  68/94 ms — DMA-descriptor-bound (19k small DMAs × ~5 us latency).
- v3 streamed big blocks / kept the table resident: 67/110 ms — the
  residual cost is the DYNAMIC ``pltpu.roll`` itself (Mosaic lowers a
  dynamic lane rotate as a multi-stage shift network; a 10-roll/step
  ablation costs +100 ms over the 23 ms copy floor).
- v4 eliminates rotates entirely: shifts are restricted to
  multiples of 1024 = 8 sublanes × 128 lanes (``make_circulant_sketch``
  applies that granularity whenever c % 1024 == 0 — see the statistics
  note there), so every span of a conceptual roll starts on a vreg
  boundary and comes out of a VMEM-resident, wrap-padded
  (rows, c/128 (+span), 128) view with ONE sublane-dynamic slice — pure
  address arithmetic, no data movement beyond the copy itself.
  Measured: decode 21 ms (6× over the roll path), with the whole table
  loaded into VMEM once (constant index map).
- v5 (PR 27) is v4 with the encode's grid turned inside out.
  v4's encode ran a grid (lane tiles, blocks) and DMA'd one whole
  wrap-padded block per step, so the d-long input crossed HBM once per
  lane tile: 163 times at c = 500,736 = 3·163·1024 (largest aligned
  tile ≤ 65,536 is 3,072), 16.75 GB and 22.13 ms a call at d = 25.5M;
  8 times at c = 2^19, 4.49 GB and 5.94 ms at d = 124.4M — both the
  kernel's own traffic at HBM speed. Now the grid is (blocks,): block b
  is fetched once, the whole (r, c) table is the resident accumulator
  (constant index map, written back once) and the lane tiles are a
  ``fori_loop`` inside the kernel; ``encode_hbm_bytes`` is the
  traffic figure that would have shown the fault. Same additions in the
  same order: the table is bit-identical to v4's. Measured a call, v5e:
  0.57 ms at c = 500,736, d = 25.5M and 2.05 ms at c = 2^19,
  d = 124.4M, bound by the VPU's sign hash (r·m·c murmur evaluations),
  not by HBM. The decode is v4's, untouched.
- v6 (PR 29) gives the decode a block range: grid step b decodes block
  ``first + b``, ``first`` one more prefetched scalar, so the sharded
  server tail, whose coordinate range follows ``axis_index``, runs the
  kernel over the whole blocks that cover its range and slices
  (``pallas_decode_range``) where it ran five per-element gathers a
  chunk — 295 ms of a 372 ms round on four v5e chips at d = 25.5M,
  against 0.19 ms for the 14 of 51 blocks a chip needs. The whole
  decode is the same kernel at ``first = 0`` over all m blocks.
- v7 (PR 31) takes the encode's input as the ravel left it. v5's
  caller handed the kernel a scaled, zero-padded and wrap-padded copy:
  ``vals * scale``, ``jnp.pad`` to m·c and ``_wrap_pad`` to
  (m, c/128 + sub, 128) were three XLA instructions, each one read and
  one write of d floats in HBM before every call — 113 of a 1,518 ms
  round at d = 3.9e8 and 36 of 399 ms at d = 1.24e8, eight calls a
  round, against 51 and 16 ms for the kernel itself. Now the input
  BlockSpec is the plain (1, c/128, 128) block of the (m, c/128, 128)
  view (a bitcast of the m·c-long vector), the scale is a third
  prefetched scalar, and each grid step fills a VMEM scratch of
  (c/128 + sub, 128) with ``block * scale`` and the block's own first
  ``sub`` rows; the tile loop is v5's, reading the scratch. The product
  is taken once an element, as XLA took it, and the additions are
  v5's in v5's order: the table is bit-identical to v5's
  ``encode(pad(v * scale))`` (on the chip at m = 51, 238 and 744).
  Measured a call inside a scan that produces its gradient, v5e,
  v5 -> v7: kernel 0.573 -> 0.588 ms (c = 500,736, d = 25.5M), 2.050 ->
  2.160 (c = 2^19, d = 124.4M), 6.398 -> 6.740 (d = 389.6M): the fill is
  1.35 cycles a vreg beside the hash's 5 a vreg and row; everything
  else in the call 1.31 -> 0.59, 9.06 -> 4.57, 29.3 -> 15.1 ms, what is
  left being the ravel. Tried and dropped, all bit-identical: walking
  the block in input order and wrapping the *output* span a vreg at a
  time (no scratch at all) 3.79 ms at d = 124.4M, a read-modify-write
  at a dynamic address orders itself after the one before; per-vreg
  dynamic loads straight from the input block 5.59 ms.

Exactness vs the roll path is asserted in interpret mode by
tests/test_ops.py and against numpy on the TPU at flagship scale.
Used AUTOMATICALLY for BOTH encode and decode on TPU when the sketch's
shifts are 1024-aligned and the wrap-padded table fits the VMEM
residency budget. (History: encode began opt-in — under the per-client
vmap round it measured ~equal to the XLA static-roll path; the round-4
fused-clients round encodes the summed gradient ONCE, where the pallas
encode lifts the flagship GPT-2 round 76.5k -> 85.2k tok/s.) The
``--pallas`` config flag controls the policy: ``off`` disables, ``auto``
(default) and ``on`` enable when eligible. Replaces the external CUDA
CSVec hot path (reference fed_worker.py:312-320).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from commefficient_tpu.ops.sketch import _mix32
from commefficient_tpu.ops.topk import median_axis0

_U32 = jnp.uint32
_GOLDEN = 0x9E3779B9

# shift granularity that makes every span start a whole number of vregs
# (8 sublanes x 128 lanes) into the row — the no-rotate enabler
SHIFT_ALIGN = 1024

# both kernels keep the table resident in VMEM, the decode wrap-padded
# as its input (table_vmem_bytes, capped here), the encode as its
# accumulator. Checked on a v5e under jax 0.9.0 / libtpu 0.0.34: the
# decode compiles under Mosaic's default scoped-VMEM limit at 10.1 MB
# (r=5, c=500,736) and 11.8 MB (r=5, c=524,288), PR 21. The encode,
# which Pallas gives two buffers of its table and of its input block
# and which keeps the scaled, wrap-padded block in a scratch of its
# own, asks for them with vmem_limit_bytes (_encode_vmem_limit): 30.2 MB
# and 31.5 MB at those two geometries, and the most any sketch under
# this budget asks for, 67.0 MB at r=1, c=3,140,608, compiles
# (tests/test_tpu_compile.py) against the core's 128 MiB.
TABLE_VMEM_BUDGET = 12 << 20

# lane-tile width of the decode's streamed output spans (a DMA unit)
_CT_MAX = 65536

# lane-tile width of the encode's in-kernel loop. Not a DMA unit: one
# (row, tile) step loads a span, hashes its signs and adds it into the
# resident table, so the tile only has to keep that working set near
# the vregs (8192 lanes = 8 vregs a span)
_ENCODE_CT_MAX = 8192

# sublane-rows one step of the encode's fill loop scales and copies: 32
# vregs of loads, products and stores with nothing between them to wait
# for. A lane tile a step (24 rows at c = 500,736) measured +9.7% on the
# kernel where this measures +2.7% (design history above, v7)
_FILL_ROWS = 256


def _lane_tile(c: int, cap: int | None = None) -> int:
    """Largest divisor of c that is a multiple of SHIFT_ALIGN and ≤
    ``cap`` (default _CT_MAX). Callers guarantee c % SHIFT_ALIGN == 0,
    so SHIFT_ALIGN itself is always a valid fallback."""
    cap = _CT_MAX if cap is None else cap
    for n in range(1, c // SHIFT_ALIGN + 1):
        if c % n == 0 and (c // n) % SHIFT_ALIGN == 0 and c // n <= cap:
            return c // n
    raise ValueError(f"c={c} has no {SHIFT_ALIGN}-aligned lane tile")


def _encode_tile(c: int) -> int:
    return _lane_tile(c, _ENCODE_CT_MAX)


def table_vmem_bytes(c: int, r: int) -> int:
    """Bytes of the decode kernel's resident wrap-padded f32 table."""
    return 4 * r * (c + _lane_tile(c))


def encode_hbm_bytes(c: int, r: int, m: int) -> int:
    """HBM bytes one ``pallas_encode`` call moves by its own BlockSpecs:
    each of the m input blocks fetched once as it lies, the (r, c)
    table written back once. Against the d + r·c floats the algorithm
    needs, it says how many passes over the input the grid makes."""
    return 4 * (m * c + r * c)


def _encode_vmem_limit(c: int, r: int) -> int:
    """Scoped-VMEM request of the encode: Pallas holds two buffers of
    the resident table and of the streamed input block, the kernel one
    scratch of the scaled block with its wrap; 4 MiB over that for
    Mosaic's own temporaries."""
    return 4 * (2 * (r * c + c) + c + _encode_tile(c)) + (4 << 20)


def _signs2d(start, sub, key):
    """(sub, 128) ±1 signs for global coordinates [start, start+128·sub)
    in vreg layout — the same murmur stream as CirculantSketch._sign_of.
    ``start`` may be a traced scalar."""
    idx = (start
           + 128 * lax.broadcasted_iota(jnp.int32, (sub, 128), 0)
           + lax.broadcasted_iota(jnp.int32, (sub, 128), 1)).astype(_U32)
    h = _mix32(idx * key + _U32(_GOLDEN))
    # Mosaic can't cast uint32 -> f32 directly; the top bit is 0/1 so an
    # int32 hop is exact
    return 1.0 - 2.0 * (h >> 31).astype(jnp.int32).astype(jnp.float32)


def _decode_kernel(first_ref, shifts_ref, keys_ref, t_ref, out_ref, *,
                   c, r, m, ct):
    t = pl.program_id(1)
    # grid step b decodes block first + b; ``first`` is a prefetched
    # scalar, so a shard's block range may depend on its axis_index
    blk = first_ref[0] + pl.program_id(0)
    # a cover that runs past block m-1 holds only coordinates >= d there
    # (the caller zeroes them): any column of the shift table will do
    sb = jnp.minimum(blk, m - 1)
    sub = ct // 128
    ests = []
    for j in range(r):
        # est[i] = sign(blk·c+i) · table[j, (i + s) mod c]: the span
        # starts q = (t·ct + s) mod c into the row; with s 1024-aligned,
        # q//128 is a whole vreg offset and the wrap padding makes the
        # slice contiguous — no rotate
        q = (t * ct + shifts_ref[j, sb]) % c
        span = t_ref[j, pl.ds(q // 128, sub)]            # (sub, 128)
        ests.append(_signs2d(blk * c + t * ct, sub, keys_ref[j]) * span)
    out_ref[0, 0] = median_axis0(jnp.stack(ests, axis=0))


def _encode_kernel(shifts_ref, keys_ref, scale_ref, v_ref, out_ref, buf_ref,
                   *, c, r, ct):
    b = pl.program_id(0)
    sub, csub = ct // 128, c // 128

    @pl.when(b == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    # the block as the spans want it, made here in VMEM and not by XLA
    # in HBM: scaled once an element, its first ``sub`` rows appended so
    # a mod-c span never wraps. Chunks of _FILL_ROWS and what is left
    # of a c/128 they do not divide
    scale = scale_ref[0]
    rows = min(_FILL_ROWS, csub)

    def fill(i, carry):
        at = pl.ds(pl.multiple_of(i * rows, 8), rows)
        buf_ref[at] = v_ref[0, at] * scale
        return carry

    lax.fori_loop(0, csub // rows, fill, 0)
    if csub % rows:
        at = pl.ds(csub // rows * rows, csub % rows)
        buf_ref[at] = v_ref[0, at] * scale
    buf_ref[pl.ds(csub, sub)] = buf_ref[pl.ds(0, sub)]

    # table[j, i] += sign(b·c + (i − s) mod c) · v_b[(i − s) mod c]: lane
    # tile t of row j reads the span that starts back[j] + t·ct (mod c)
    # into the block
    back = [c - shifts_ref[j, b] for j in range(r)]       # in (0, c]
    keys = [keys_ref[j] for j in range(r)]
    lanes = (128 * lax.broadcasted_iota(jnp.int32, (sub, 128), 0)
             + lax.broadcasted_iota(jnp.int32, (sub, 128), 1))

    def tile(t, carry):
        for j in range(r):
            q = t * ct + back[j]
            q = q - jnp.where(q >= c, c, 0)
            span = buf_ref[pl.ds(pl.multiple_of(q // 128, 8), sub)]
            # the span crosses the block's mod-c seam at most once, so
            # one conditional subtract realizes the mod
            pos = q + lanes
            pos = pos - jnp.where(pos >= c, c, 0)
            h = _mix32((b * c + pos).astype(_U32) * keys[j] + _U32(_GOLDEN))
            # ±1 · span is span with the hash's top bit xored into its
            # sign: the same murmur stream as CirculantSketch._sign_of
            signed = lax.bitcast_convert_type(
                lax.bitcast_convert_type(span, _U32) ^ (h & _U32(1 << 31)),
                jnp.float32)
            out_ref[j, pl.ds(pl.multiple_of(t * sub, 8), sub)] += signed
        return carry

    lax.fori_loop(0, c // ct, tile, 0)


# stable kernel names: what a compiled round's HLO and a device trace
# show for the two Mosaic custom calls
ENCODE_KERNEL_NAME = "circulant_sketch_encode"
DECODE_KERNEL_NAME = "circulant_sketch_decode"


def _wrap_pad(x3, sub):
    """(..., n, 128) -> (..., n+sub, 128) with the first ``sub``
    sublane-rows appended, so a mod-n span never wraps."""
    return jnp.concatenate([x3, x3[..., :sub, :]], axis=-2)


@functools.partial(jax.jit, static_argnames=("c", "r", "m", "interpret"))
def pallas_encode(vec_padded, shifts, sign_keys, scale=1.0, *, c, r, m,
                  interpret=False):
    """(m*c,) zero-padded fp32 vector -> the (r, c) table of ``scale``
    times it. ``shifts``: (r, m) int32 multiples of SHIFT_ALIGN;
    ``sign_keys``: (r,) uint32; ``scale``: a scalar, may be traced. The
    vector is read where it lies: the view below is a bitcast, and
    scale and wrap are made in the kernel."""
    ct = _encode_tile(c)
    sub, csub = ct // 128, c // 128
    blocks = vec_padded.astype(jnp.float32).reshape(m, csub, 128)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        # one step a vector block: block b crosses HBM -> VMEM once (ONE
        # DMA) and is added into all r rows of the table, which a
        # constant index map keeps resident for all m steps and writes
        # back once. The steps accumulate, so the axis is sequential
        grid=(m,),
        in_specs=[pl.BlockSpec((1, csub, 128), lambda b, *_: (b, 0, 0))],
        out_specs=pl.BlockSpec((r, csub, 128), lambda b, *_: (0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((csub + sub, 128), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_encode_kernel, c=c, r=r, ct=ct),
        out_shape=jax.ShapeDtypeStruct((r, csub, 128), jnp.float32),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_encode_vmem_limit(c, r)),
        interpret=interpret,
        name=ENCODE_KERNEL_NAME,
    )(shifts, sign_keys, jnp.asarray(scale, jnp.float32).reshape(1), blocks)
    return out.reshape(r, c)


@functools.partial(jax.jit, static_argnames=("c", "r", "nb", "interpret"))
def pallas_decode_blocks(table, shifts, sign_keys, first_block, *, c, r, nb,
                         interpret=False):
    """(r, c) table -> (nb*c,) per-coordinate median estimates of blocks
    [first_block, first_block + nb) of the m = shifts.shape[1] the
    sketch has. ``first_block`` may be traced (the sharded server tail's
    ``axis_index``-dependent range): the kernel takes it as a prefetched
    scalar. Blocks at or past m decode to unspecified finite values."""
    m = shifts.shape[1]
    ct = _lane_tile(c)
    sub, csub, nct = ct // 128, c // 128, c // ct
    t3 = _wrap_pad(table.astype(jnp.float32).reshape(r, csub, 128), sub)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nb, nct),
        # constant index map: the whole wrap-padded table loads into VMEM
        # once and stays resident for all nb·nct steps
        in_specs=[pl.BlockSpec((r, csub + sub, 128),
                               lambda b, t, *_: (0, 0, 0))],
        out_specs=pl.BlockSpec((1, 1, sub, 128),
                               lambda b, t, *_: (b, t, 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, c=c, r=r, m=m, ct=ct),
        out_shape=jax.ShapeDtypeStruct((nb, nct, sub, 128), jnp.float32),
        grid_spec=grid_spec,
        interpret=interpret,
        name=DECODE_KERNEL_NAME,
    )(jnp.asarray(first_block, jnp.int32).reshape(1), shifts, sign_keys, t3)
    return out.reshape(-1)


@functools.partial(jax.jit, static_argnames=("c", "r", "m", "interpret"))
def pallas_decode(table, shifts, sign_keys, *, c, r, m, interpret=False):
    """(r, c) table -> (m*c,) per-coordinate median estimates: every
    block, through the one decode kernel."""
    assert shifts.shape == (r, m), (shifts.shape, r, m)
    return pallas_decode_blocks(table, shifts, sign_keys, 0, c=c, r=r,
                                nb=m, interpret=interpret)


def range_cover_blocks(c: int, length: int) -> int:
    """Whole blocks that cover ``length`` contiguous coordinates from
    any start: the range begins at most c - 1 into its first block."""
    return (c - 1 + length - 1) // c + 1


def pallas_decode_range(table, shifts, sign_keys, start, *, c, r, length,
                        interpret=False):
    """Estimates of the ``length`` contiguous coordinates from global
    index ``start`` (may be traced): the whole blocks that cover the
    range through the decode kernel, then one slice. Equals
    ``pallas_decode(...)[start:start+length]`` bit for bit below m*c;
    the caller zeroes coordinates >= d."""
    start = jnp.asarray(start, jnp.int32)
    first = start // c
    ests = pallas_decode_blocks(table, shifts, sign_keys, first, c=c, r=r,
                                nb=range_cover_blocks(c, length),
                                interpret=interpret)
    return lax.dynamic_slice_in_dim(ests, start - first * c, length)
