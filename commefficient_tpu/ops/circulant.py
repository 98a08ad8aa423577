"""Circulant count sketch — scatter/gather-free count sketch for TPU.

Third sketch implementation (``sketch_impl="circ"``, the default), designed
to combine the other two's strengths:

- the HASH count sketch (ops/sketch.py, exact CSVec semantics — reference
  call sites CommEfficient/fed_worker.py:312-320, fed_aggregator.py:584-595)
  is STABLE under FetchSGD error feedback at real compression ratios
  (cell-zeroing dissipates k/c of the table's error mass per round), but its
  encode/decode are O(d·r) random scatter/gathers — ~250 ms each at the
  flagship config (d≈6.6M, r=5) because TPU scatter/gather serializes;
- the SRHT sketch (ops/rht.py) runs on the MXU in ~15 ms but its
  uniformly-spread JL estimate noise makes top-k error feedback divergent
  whenever r·c << d (see ops/rht.py "Regime of validity").

Construction
------------
Pad d up to m·c and view the vector as m blocks of length c. Row j of the
table is

    t_j = sum_b  roll(sigma_{j,b} * v_b,  s_{j,b})

with per-(row, block) signs sigma (±1, derived on the fly from a murmur
mixer — never materialized at (r, d)) and per-(row, block) cyclic shifts
s_{j,b} drawn once from the seed. This is a genuine count sketch: the
bucket map h_j(b, i) = (i + s_{j,b}) mod c satisfies

- P[h_j(b,i) = h_j(b',i')] = 1/c for b != b' (uniform independent shifts),
- coordinates of the SAME block never collide (strictly better than the
  2-universal bound),

so per-row estimates sigma_{j,b}[i] * t_j[h_j(b,i)] are unbiased with
variance <= ||v||^2/c, and the median over r independent rows gives the
standard CountSketch heavy-hitter guarantee. When c >= d (m = 1) the
round-trip is exact (a roll is invertible), matching the other impls'
lossless limit.

Why it is fast on TPU: the shifts are STATIC (python ints baked at trace
time), so every ``jnp.roll`` compiles to two contiguous slices + concat —
pure HBM-bandwidth data movement, no scatter, no gather, no sort. Encode =
r·(sign-multiply + m static rolls + reduce); decode = r·m static rolls of
the (c,) table rows + sign-multiply + median-of-r comparator network.
Measured at the flagship CV config: ~5 ms vs the hash impl's ~250 ms per
op. When c % 1024 == 0 the shifts are additionally drawn at vreg
granularity (see ``make_circulant_sketch`` for why the statistics are
unchanged) and decode runs as a fused Pallas kernel
(ops/circulant_pallas.py — 21 ms vs the roll path's 129 ms at the GPT-2
scale d=124M, where r·m static roll OPS otherwise dominate at ~70 us of
fixed XLA per-op cost each).

Error feedback: a k-sparse update encodes into <= k·r occupied cells, and
``dense_transform = False``, so the server applies the reference's exact
cell-zeroing rule (fed_aggregator.py:596-611) — the stable dynamics, same
as the hash impl (validated at r·c << d in tests/test_learning.py).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from commefficient_tpu.ops.sketch import _mix32, loop_token_zero
from commefficient_tpu.ops.topk import (clip_by_l2_norm, median_axis0, topk,
                                        topk_with_idx)

_U32 = jnp.uint32


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class CirculantSketch:
    """(d -> r x c) circulant count sketch.

    ``shifts`` is a static tuple-of-tuples (r, m) of python ints — part of
    the pytree aux data so every ``roll`` gets a compile-time shift. Sign
    keys are arrays (jit arguments, like the hash impl's keys).
    """

    sign_keys: jax.Array            # (r,) uint32
    shifts: Tuple[Tuple[int, ...], ...]  # (r, m) static
    d: int
    c: int
    r: int
    num_blocks: int                 # decode memory chunking over the m axis
    # pallas kernel policy (config.py --pallas): "auto" = fused encode
    # AND decode when eligible, XLA paths otherwise; "on" = the same,
    # but on the TPU backend an ineligible sketch is an error
    # (make_circulant_sketch) instead of a quiet 6x; "off" = XLA only
    pallas: str = "auto"

    dense_transform = False

    def tree_flatten(self):
        return ((self.sign_keys,),
                (self.shifts, self.d, self.c, self.r, self.num_blocks,
                 self.pallas))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], *aux)

    # ------------------------------------------------------------- layout

    @property
    def m(self) -> int:
        return -(-self.d // self.c)  # ceil: number of length-c blocks

    @property
    def table_shape(self) -> Tuple[int, int]:
        return (self.r, self.c)

    def empty_table(self, dtype=jnp.float32) -> jax.Array:
        return jnp.zeros(self.table_shape, dtype)

    def _sign_of(self, row: int, idx: jax.Array) -> jax.Array:
        """±1 sign of global coordinates ``idx`` in ``row`` — the ONE
        definition of the sign stream (murmur mixer, ops/sketch.py);
        encode, decode and encode_at must all agree on it."""
        h = _mix32(idx.astype(_U32) * self.sign_keys[row]
                   + _U32(0x9E3779B9))
        return 1.0 - 2.0 * (h >> 31).astype(jnp.float32)

    def _signs(self, row: int, b0: int = 0,
               nb: Optional[int] = None) -> jax.Array:
        """±1 signs for blocks [b0, b0+nb) of one row — no (r, d) table,
        and decode chunks only ever materialize their own block range."""
        nb = self.m - b0 if nb is None else nb
        idx = b0 * self.c + jnp.arange(nb * self.c, dtype=_U32)
        return self._sign_of(row, idx).reshape(nb, self.c)

    # ---------------------------------------------------------------- ops

    # above this many blocks the unrolled static rolls stop paying off:
    # tracing/compile time scales with m, so switch to one (m, c) gather
    # per row (same semantics; only arises at extreme d/c ratios — the
    # flagship configs have m <= ~250)
    _UNROLL_MAX_BLOCKS = 512

    def _row_shift_idx(self, j: int, sign: int, b0: int = 0,
                       nb: Optional[int] = None) -> jax.Array:
        """(nb, c) column indices implementing per-block rolls by
        ``sign * shifts[j]`` for blocks [b0, b0+nb) as one
        take_along_axis."""
        nb = self.m - b0 if nb is None else nb
        s = jnp.asarray(self.shifts[j][b0:b0 + nb], jnp.int32)[:, None]
        k = jnp.arange(self.c, dtype=jnp.int32)[None, :]
        return (k - sign * s) % self.c

    def pallas_blocker(self) -> Optional[str]:
        """The condition that keeps this sketch off the fused pallas
        kernels (ops/circulant_pallas.py), or None when they serve it.
        They need: ``--pallas`` not off, the TPU backend, more than one
        block, a SHIFT_ALIGN-granular column count AND shift table
        (``make_circulant_sketch`` generates aligned shifts whenever
        c % 1024 == 0 — the reference's default c=500,000 = 2^5·5^6 can
        never align; pick e.g. --num_cols 524288), and the wrap-padded
        table within the decode kernel's VMEM residency budget."""
        from commefficient_tpu.ops.circulant_pallas import (
            SHIFT_ALIGN, TABLE_VMEM_BUDGET, table_vmem_bytes)
        if self.pallas == "off":
            return "--pallas off"
        if jax.default_backend() != "tpu":
            return f"backend is {jax.default_backend()!r}, not 'tpu'"
        if self.m <= 1:
            return (f"d={self.d} <= num_cols={self.c}: one block, the "
                    "roll is a single slice pair")
        if self.c % SHIFT_ALIGN:
            return f"num_cols={self.c} is not a multiple of {SHIFT_ALIGN}"
        if any(s % SHIFT_ALIGN for row in self.shifts for s in row):
            return f"shift table is not {SHIFT_ALIGN}-aligned"
        need = table_vmem_bytes(self.c, self.r)
        if need > TABLE_VMEM_BUDGET:
            return (f"wrap-padded {self.r}x{self.c} table is {need} bytes, "
                    f"over TABLE_VMEM_BUDGET={TABLE_VMEM_BUDGET}")
        return None

    def _pallas_eligible(self) -> bool:
        return self.pallas_blocker() is None

    @property
    def kernel_path(self) -> str:
        """Which implementation encode/decode take for this sketch on
        this backend: ``"pallas"`` (both fused kernels) or ``"xla"``
        (static rolls, or the gather form past _UNROLL_MAX_BLOCKS)."""
        return "pallas" if self._pallas_eligible() else "xla"

    def _use_pallas_decode(self) -> bool:
        # default ON when eligible: measured 21 ms vs the roll path's
        # 129 ms at the flagship d=124M config
        return self._pallas_eligible()

    def _use_pallas_encode(self) -> bool:
        # ON when eligible: one call of the one-pass kernel measured
        # 0.57 ms at d=25.5M and 2.05 ms at d=124M on a v5e
        # (ops/circulant_pallas.py v5) where the static-roll path pays
        # r·m roll ops (~26 ms at d=124M). Kept as a separate seam from
        # decode in case the two policies ever diverge.
        return self._pallas_eligible()

    def encode(self, vec: jax.Array) -> jax.Array:
        assert vec.ndim == 1 and vec.shape[0] == self.d, (vec.shape, self.d)
        m, c = self.m, self.c
        if self._use_pallas_encode():
            return self._pallas_encode(vec)
        vp = jnp.pad(vec.astype(jnp.float32), (0, m * c - self.d)).reshape(
            m, c)
        rows = []
        for j in range(self.r):
            sv = self._signs(j) * vp                       # (m, c)
            if m <= self._UNROLL_MAX_BLOCKS:
                # static per-block rolls: slice+slice+concat each
                rolled = jnp.stack(
                    [jnp.roll(sv[b], self.shifts[j][b]) for b in range(m)])
            else:
                rolled = jnp.take_along_axis(
                    sv, self._row_shift_idx(j, sign=1), axis=1)
            rows.append(rolled.sum(axis=0))
        return jnp.stack(rows)

    def _pallas_encode(self, vec: jax.Array, scale=None) -> jax.Array:
        """The table of ``scale * vec`` by the fused kernel, which scales
        and wrap-pads in VMEM: a vector that comes m·c long (the ravel
        of ``encode_grad_tree``, zeros from d on) is read where it lies;
        a (d,) one pays the one pad to m·c."""
        from commefficient_tpu.ops.circulant_pallas import pallas_encode
        m, c = self.m, self.c
        vec = vec.astype(jnp.float32)
        if vec.shape[0] != m * c:
            vec = jnp.pad(vec, (0, m * c - self.d))
        return pallas_encode(vec, jnp.asarray(self.shifts, jnp.int32),
                             self.sign_keys,
                             1.0 if scale is None else scale,
                             c=c, r=self.r, m=m)

    def encode_accum(self, table: jax.Array, vals: jax.Array,
                     start: int = 0, scale=None,
                     token: Optional[jax.Array] = None) -> jax.Array:
        """Accumulating range encode: ``table + encode(v)`` for the
        vector ``v`` holding ``vals`` at global coordinates
        ``[start, start + len(vals))`` and zero elsewhere — without ever
        materializing a (d,)-sized buffer (only this range's blocks are
        resident). The streaming entry point of the fused-encode client
        path (core/client.py): per-microbatch gradients accumulate into
        the O(r·c) carry, chunk by chunk.

        ``start`` must be a STATIC python int (the per-block shifts are
        compile-time constants — that is what makes the roll path
        scatter-free; a traced-offset caller should use
        :meth:`encode_vals_at`, whose bucket map is pure arithmetic).
        ``scale`` multiplies the values before encoding (linearity);
        ``token`` is any loop-varying scalar defeating while-loop sign
        hoisting (ops/sketch.py loop_token_zero). The whole-vector call
        (``start == 0``, d long or already m·c long with zeros from d
        on) routes through the fused Pallas encode kernel when eligible
        — the accumulate is then one table add, and nothing d-long
        happens in XLA but the pad of a (d,) vector: the kernel takes
        the scale as a scalar and makes its wrap in VMEM."""
        assert vals.ndim == 1, vals.shape
        assert table.shape == self.table_shape, (table.shape,
                                                 self.table_shape)
        start = int(start)
        assert start >= 0 and start + vals.shape[0] <= self.m * self.c, (
            start, vals.shape, self.d)
        m, c = self.m, self.c
        if start == 0 and vals.shape[0] in (self.d, m * c) \
                and self._use_pallas_encode():
            return table + self._pallas_encode(vals, scale)
        vals = vals.astype(jnp.float32)
        if scale is not None:
            vals = vals * scale
        n = vals.shape[0]
        b0 = start // c
        o0 = start - b0 * c
        nb = -(-(o0 + n) // c)
        vp = jnp.pad(vals, (o0, nb * c - o0 - n)).reshape(nb, c)
        zu = loop_token_zero(token)
        # the token is folded into the SCALAR offset before it meets the
        # iota: written ``const + arange + zu`` (left-assoc), the
        # ``const + arange`` pair is a nullary all-constant fusion XLA
        # hoists out of the scan and keeps resident for every range at
        # once (measured: L per-layer u32 base vectors alive together on
        # the streaming-backward path); ``arange + (zu + const)`` keeps
        # every index vector data-dependent on the loop-varying token
        idx0 = jnp.arange(nb * c, dtype=_U32) + (zu + _U32(b0 * c))
        for j in range(self.r):
            signs = self._sign_of(j, idx0).reshape(nb, c)
            sv = signs * vp
            if nb <= self._UNROLL_MAX_BLOCKS:
                rolled = jnp.stack(
                    [jnp.roll(sv[b], self.shifts[j][b0 + b])
                     for b in range(nb)])
            else:
                rolled = jnp.take_along_axis(
                    sv, self._row_shift_idx(j, sign=1, b0=b0, nb=nb),
                    axis=1)
            table = table.at[j].add(rolled.sum(axis=0))
        return table

    def _buckets_of(self, j: int, idx: jax.Array) -> jax.Array:
        """Bucket of global coordinate i in row j:
        (i mod c + shifts[j][i // c]) mod c — the ONE definition shared by
        encode_at and decode_at (signs come from ``_sign_of``)."""
        s = jnp.asarray(self.shifts[j], jnp.int32)[idx // self.c]
        return (idx.astype(jnp.int32) % self.c + s) % self.c

    def encode_at(self, vec: jax.Array, idx: jax.Array) -> jax.Array:
        """Encode a k-sparse vector given its support indices: equals
        ``encode(vec)`` when vec is zero outside ``idx``, at O(k·r)
        scatter-add cost instead of the O(d·r) roll pass (~2 ms vs ~87 ms
        at d=124M, k=50k — this runs every round for the server's
        error-feedback re-encode)."""
        return self.encode_vals_at(vec[idx], idx)

    def encode_vals_at(self, vals: jax.Array, idx: jax.Array) -> jax.Array:
        """``encode_at`` taking the k support VALUES directly — no dense
        (d,) staging buffer (the subtractive-EF momentum masking's path,
        core/server.py)."""
        rows = []
        for j in range(self.r):
            rows.append(jax.ops.segment_sum(self._sign_of(j, idx) * vals,
                                            self._buckets_of(j, idx),
                                            num_segments=self.c))
        return jnp.stack(rows)

    def decode_at(self, table: jax.Array, idx: jax.Array) -> jax.Array:
        """Median-of-r estimates of the coordinates ``idx`` only: equals
        ``decode(table)[idx]`` at O(k·r) gather cost instead of the O(d·r)
        full decode (used by the subtractive error-feedback rule's
        momentum masking, core/server.py)."""
        ests = []
        for j in range(self.r):
            ests.append(self._sign_of(j, idx)
                        * table[j, self._buckets_of(j, idx)])
        return median_axis0(jnp.stack(ests))

    def decode_range(self, table: jax.Array, start, length: int
                     ) -> jax.Array:
        """Median-of-r estimates of the ``length`` contiguous
        coordinates starting at global index ``start``: equals
        ``decode(table)[start:start+length]`` for coordinates < d, and
        EXACTLY 0 beyond d (mesh padding must never win a top-k).

        ``start`` may be a TRACED scalar (the sharded server tail's
        ``axis_index``-dependent slice, core/server.py) — the static
        per-block shifts cannot be selected at trace time then. Which
        form runs is decided where ``decode`` decides it, from what the
        code sees (backend, c, shifts: ``pallas_blocker``):

        - the Pallas kernels serve the sketch (the TPU): the decode
          kernel over the whole blocks that cover the range, first
          block a prefetched scalar, then one ``dynamic_slice``
          (ops/circulant_pallas.pallas_decode_range) — 14 of 51 blocks
          a chip at d = 25.5M over four chips;
        - otherwise (the CPU, ``--pallas off``, unaligned c, one
          block): the ``decode_at`` gather form (the ONE shared
          bucket/sign definition) chunk by chunk: peak memory
          O(r * chunk), no (d,)-sized buffer. On the TPU that is one
          element at a time (9.4 ns each, 295 ms a round at that d).

        Same estimate values either way — kernel, rolls and gathers
        move the same table cells through the same median.
        """
        assert table.shape == self.table_shape, (table.shape,
                                                 self.table_shape)
        assert length >= 1, length
        start = jnp.asarray(start, jnp.int32)
        if self._use_pallas_decode():
            from commefficient_tpu.ops.circulant_pallas import (
                pallas_decode_range)
            ests = pallas_decode_range(
                table, jnp.asarray(self.shifts, jnp.int32), self.sign_keys,
                start, c=self.c, r=self.r, length=length)
            idx = start + jnp.arange(length, dtype=jnp.int32)
            return jnp.where(idx < self.d, ests, 0.0)
        bl = min(self.c, length)
        nb = -(-length // bl)
        base = jnp.arange(bl, dtype=jnp.int32)

        def body(_, off):
            idx = start + off + base          # (bl,) global coordinates
            ests = jnp.stack([self._sign_of(j, idx)
                              * table[j, self._buckets_of(j, idx)]
                              for j in range(self.r)])
            return None, jnp.where(idx < self.d, median_axis0(ests), 0.0)

        if nb == 1:
            return body(None, jnp.int32(0))[1][:length]
        _, ests = jax.lax.scan(body, None,
                               jnp.arange(nb, dtype=jnp.int32) * bl)
        return ests.reshape(-1)[:length]

    def decode(self, table: jax.Array) -> jax.Array:
        assert table.shape == self.table_shape, (table.shape,
                                                 self.table_shape)
        m, c = self.m, self.c
        if self._use_pallas_decode():
            from commefficient_tpu.ops.circulant_pallas import pallas_decode
            return pallas_decode(table, jnp.asarray(self.shifts, jnp.int32),
                                 self.sign_keys, c=c, r=self.r,
                                 m=m)[: self.d]
        # chunk the m axis so peak memory is O(r * m/num_blocks * c) on
        # both implementations of the per-block shift
        chunk = max(1, -(-m // max(1, self.num_blocks)))
        outs = []
        for b0 in range(0, m, chunk):
            mb = min(chunk, m - b0)
            if m > self._UNROLL_MAX_BLOCKS:
                ests = jnp.stack([
                    jnp.take_along_axis(
                        jnp.broadcast_to(table[j], (mb, c)),
                        self._row_shift_idx(j, sign=-1, b0=b0, nb=mb),
                        axis=1)
                    for j in range(self.r)])              # (r, mb, c)
            else:
                ests = jnp.stack([
                    jnp.stack([jnp.roll(table[j], -self.shifts[j][b])
                               for b in range(b0, b0 + mb)])
                    for j in range(self.r)])              # (r, mb, c)
            signs = jnp.stack(
                [self._signs(j, b0, mb) for j in range(self.r)])
            outs.append(median_axis0(ests * signs).reshape(-1))
        return jnp.concatenate(outs)[: self.d]

    def unsketch(self, table: jax.Array, k: int, approx: bool = False):
        return topk(self.decode(table), k, approx=approx)

    def unsketch_with_idx(self, table: jax.Array, k: int,
                          approx: bool = False):
        return topk_with_idx(self.decode(table), k, approx=approx)

    def l2estimate(self, table: jax.Array) -> jax.Array:
        return jnp.median(jnp.linalg.norm(table, axis=1))

    def clip(self, table: jax.Array, clip: float) -> jax.Array:
        return clip_by_l2_norm(table, clip)

    # --wire_dtype int8 entry points (ops/wire.py): the wire quantizes
    # TABLE CELLS, so it is sketch-impl-agnostic — mirrored on
    # CountSketch so wire consumers stay implementation-blind
    def quantize_wire(self, table: jax.Array, block: int, *, seed: int,
                      round_idx, salt=0):
        from commefficient_tpu.ops.wire import quantize_table
        return quantize_table(table, block, seed=seed,
                              round_idx=round_idx, salt=salt)

    def dequantize_wire(self, q: jax.Array, scale: jax.Array,
                        block: int) -> jax.Array:
        from commefficient_tpu.ops.wire import dequantize_table
        return dequantize_table(q, scale, block)


def make_circulant_sketch(d: int, c: int, r: int, num_blocks: int = 1,
                          seed: int = 42,
                          pallas: str = "auto") -> CirculantSketch:
    """Shift granularity: when c % 1024 == 0, shifts are drawn as uniform
    MULTIPLES of 1024 (= 8 sublanes x 128 lanes). That makes every span
    of a per-block roll start on a TPU vreg boundary, which is what lets
    the pallas decode kernel extract it with one sublane-dynamic slice
    instead of a dynamic rotate (ops/circulant_pallas.py v4 — measured
    6x). Statistics under the coarser shifts: two coordinates i (block
    b), i' (block b') collide iff s_b − s_b' ≡ i' − i (mod c), which has
    probability 1024/c when i ≡ i' (mod 1024) and 0 otherwise — the
    bucket map partitions coordinates into residue classes mod 1024,
    colliding 1024x more often within a class and never across. Averaged
    over coordinates the per-row estimate variance is still ≤ ||v||²/c,
    but it is NOT the per-pair 1/c bound: a vector whose heavy
    coordinates concentrate in one residue class sees up to 1024x the
    per-row variance, and because the class partition is shared by every
    row (alignment is what the pallas kernel needs, so it cannot be
    de-correlated per row), the median over rows does not restore the
    worst case. Model gradients have no mechanism tying magnitude to
    i mod 1024 of the flattened parameter index, which is why the
    aligned construction is the default for aligned c — but a user who
    needs the exact CountSketch per-pair guarantee should pick an
    unaligned c (e.g. the reference's 500,000), which keeps 1-granular
    shifts at the cost of the fused pallas decode. (Same-block
    coordinates still never collide, in either construction.)"""
    rng = np.random.RandomState(seed)
    m = -(-d // c)
    if c % 1024 == 0:
        shifts = tuple(
            tuple(int(s) * 1024 for s in rng.randint(0, c // 1024, size=m))
            for _ in range(r))
    else:
        shifts = tuple(tuple(int(s) for s in rng.randint(0, c, size=m))
                       for _ in range(r))
    sign_keys = rng.randint(0, 2**32, size=(r,),
                            dtype=np.uint64).astype(np.uint32) | 1
    cs = CirculantSketch(jnp.asarray(sign_keys), shifts, d=d, c=c, r=r,
                         num_blocks=num_blocks, pallas=pallas)
    if m > CirculantSketch._UNROLL_MAX_BLOCKS and cs.kernel_path == "xla":
        # the Pallas kernels take m as a grid length and serve any m
        import warnings
        warnings.warn(
            f"circulant sketch with m = ceil(d/c) = {m} blocks exceeds "
            f"_UNROLL_MAX_BLOCKS={CirculantSketch._UNROLL_MAX_BLOCKS} on "
            "the XLA path: encode/decode fall back from static rolls to a "
            "take_along_axis gather, which is ~100x slower on TPU "
            "(measured 2,673 ms/op at d=124M in the gather regime vs "
            "26 ms static-roll encode). Increase num_cols so that "
            "d/num_cols <= 512, or pick a geometry the Pallas kernels "
            f"serve ({cs.pallas_blocker()}).", stacklevel=2)
    if pallas == "on" and jax.default_backend() == "tpu":
        blocker = cs.pallas_blocker()
        if blocker is not None:
            raise ValueError(
                "--pallas on: the fused sketch kernels cannot serve this "
                f"sketch ({blocker}); fix the geometry or pass --pallas "
                "auto to accept the XLA path")
    return cs
