"""Segment (per-group) reductions over the flat federated vector.

The layer-wise attribution layer (telemetry/layer_signals.py) reduces
round quantities over the (d,)-long coordinate line — the aggregated
gradient, the applied update, the EF accumulators — into one small
``(G,)`` vector per signal, where ``G`` is the number of named
parameter groups. A group is a set of half-open coordinate ranges
``(start, end, group)`` in ravel order (``GroupSpec.ranges``: about a
hundred of them for forty groups), and the reductions work from those
STATIC ranges: no d-long group-id map exists, on the host, on the
device or in the round's arguments (until PR 32 a ``(d_pad,)`` int32
map rode along as a call-time argument, 4 B a parameter resident for
the whole run: 1.45 of the Laguna round's 12.00 GiB).

Everything reduces through one small form, a RANGE-MASKED REDUCTION:
``n`` entries, entry ``i`` covering coordinates ``[lo_i, hi_i)`` and
carrying one value a column, are summed into the ``R`` ranges by one
shared ``(n, R)`` compare (``start_r <= lo_i and hi_i <= end_r``) that
fuses into the reduce; a static ``(R, G)`` table folds ranges into
groups. What differs by operand is what an entry is:

- a K-SPARSE operand (the update of the sketch and true top-k rules,
  given as its k winner indices and values; the exact top-k winners of
  the heavy-hitter attribution): an entry is a winner, ``n = k``, and
  nothing d-long is read at all (``group_sums_at``);
- a DENSE operand (the uncompressed update, the dense gradient and
  error): ONE pass reduces 1,024-wide blocks to ``d/1024`` partial
  sums; an entry is a block, which counts where it lies inside one
  range. The at most ``R + 1`` blocks that a range boundary cuts are
  fetched as rows and reduced per element, exactly; so is the tail
  past the last whole block (``group_sums_dense``). The block's
  position is ``offset + 1024 b``, so a chip of a mesh reduces its own
  coordinate shard from its own offset and ONE small psum recombines
  (telemetry/layer_signals.py wraps it; the dryrun's collective ledger
  gates the launch count).

Not a scatter-add: that is the natural way to write this and the wrong
one on a TPU, measured on a v5e (PR 21): the scatter serializes (63.6 ms
for three columns at d = 6.6M, G = 8, against 0.76 ms as masked
reductions — it was 79% of the ResNet-9 round), and XLA lays a batched
``(d, C)`` scatter operand out with C padded to 128 lanes, 47 GB at
d = 92M, C = 2, so the GPT-2 round did not compile at all.

A coordinate that lies in no range — mesh ``d_pad`` padding past d, a
winner index past d — lands in no group: padding can never leak mass
into a real group (pinned by tests/test_layer_signals.py against a
numpy reference).
"""

from __future__ import annotations

from typing import Callable, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 1024

Ranges = Sequence[Tuple[int, int, int]]


def _bounds(ranges: Ranges):
    """The ranges as ``(starts, ends, groups)`` int32 columns, sorted,
    with neighbours of one group merged (a leaf-by-leaf walk names the
    same group many times in a row)."""
    merged = []
    for start, end, g in sorted(ranges):
        if merged and merged[-1][2] == g and merged[-1][1] == start:
            merged[-1][1] = end
        elif end > start:
            merged.append([start, end, g])
    cols = np.asarray(merged, np.int32).reshape(-1, 3)
    return cols[:, 0], cols[:, 1], cols[:, 2]


def _inside(lo: jax.Array, width: int, starts, ends) -> jax.Array:
    """``(n, R)`` bool: entry i, covering ``[lo_i, lo_i + width)``,
    lies inside range r."""
    return ((lo[:, None] >= starts[None, :])
            & (lo[:, None] + width <= ends[None, :]))


def _masked_sum(hit: jax.Array, col: jax.Array) -> jax.Array:
    """``out[r] = sum_{hit[i, r]} col[i]`` as float32, (R,)."""
    return jnp.where(hit, col.astype(jnp.float32)[:, None], 0.0).sum(axis=0)


def _fold_groups(range_sums: jax.Array, groups, n_groups: int) -> jax.Array:
    """(R, C) range sums -> (G, C) group sums (static membership)."""
    member = jnp.asarray(groups[:, None] == np.arange(n_groups)[None, :])
    return jnp.where(member[:, :, None], range_sums[:, None, :],
                     0.0).sum(axis=0)


def group_sums_at(idx: jax.Array, cols: Sequence[jax.Array],
                  ranges: Ranges, n_groups: int) -> jax.Array:
    """Per-group sums of a k-sparse operand given as its coordinates:
    ``out[g, j] = sum_{idx[i] in group g} cols[j][i]``, (G, C) float32.
    O(k R) compares and no pass over d: the update's mass and support
    count from its k winners, the heavy-hitter attribution's winner
    counts."""
    starts, ends, groups = _bounds(ranges)
    hit = _inside(idx.astype(jnp.int32), 1, starts, ends)
    sums = jnp.stack([_masked_sum(hit, c) for c in cols], axis=-1)
    return _fold_groups(sums, groups, n_groups)


def group_sums_dense(operands: Sequence[Tuple[jax.Array,
                                              Sequence[Callable]]],
                     ranges: Ranges, n_groups: int, offset=0) -> jax.Array:
    """Per-group sums of dense operands in one read of each: every
    ``(x, fns)`` pair gives one column a function, ``out[g, j] =
    sum_{i in group g} fn_j(x_j)[i - offset]`` (``fn`` elementwise), so
    the result is (G, C) float32. The operands share one length L and
    stand for the coordinates ``[offset, offset + L)`` (``offset`` a
    python int or a traced scalar: a mesh shard's start). See the
    module note for the form."""
    starts, ends, groups = _bounds(ranges)
    length = operands[0][0].shape[0]
    assert all(x.shape == (length,) for x, _ in operands), [
        x.shape for x, _ in operands]
    offset = jnp.asarray(offset, jnp.int32)
    nb, tail = divmod(length, BLOCK)
    sums = []
    if nb:
        lo = offset + BLOCK * jnp.arange(nb, dtype=jnp.int32)
        whole = _inside(lo, BLOCK, starts, ends)
        # the blocks a range boundary cuts: each cut point's block, once
        # (the points are sorted, so a repeat follows its first), unless
        # it lies past this operand or inside one range after all
        cuts = jnp.asarray(np.unique(np.concatenate([starts, ends])))
        block = (cuts - offset) // BLOCK
        fresh = jnp.concatenate([jnp.ones((1,), bool),
                                 block[1:] != block[:-1]])
        row = jnp.clip(block, 0, nb - 1)
        row_lo = offset + BLOCK * row
        # unsigned: a signed traced start costs three scalar operations
        # a slice to wrap a negative index that cannot occur
        row_at = (BLOCK * row).astype(jnp.uint32)
        cut = ((block == row) & fresh
               & ~_inside(row_lo, BLOCK, starts, ends).any(axis=1))
        coords = row_lo[:, None] + jnp.arange(BLOCK, dtype=jnp.int32)
        split = (_inside(coords.reshape(-1), 1, starts, ends)
                 & jnp.repeat(cut, BLOCK)[:, None])
    if tail:
        rest = _inside(offset + nb * BLOCK
                       + jnp.arange(tail, dtype=jnp.int32), 1, starts, ends)
    for x, fns in operands:
        if nb:
            # (8, 128) tiles: on the TPU this view of a flat vector is a
            # bitcast, where (nb, 1024) would be a d-long relayout copy
            blocks = x[: nb * BLOCK].reshape(nb, 8, BLOCK // 8)
            # the cut blocks straight from x, so that the view above has
            # the reduce as its one reader and fuses into it; one slice a
            # cut, which XLA fuses into one copy (as one gather the TPU
            # runs a loop of four small operations a row)
            rows = jnp.stack([jax.lax.dynamic_slice(x, (at,), (BLOCK,))
                              for at in row_at])
        for fn in fns:
            col = jnp.zeros((starts.shape[0],), jnp.float32)
            if nb:
                col += _masked_sum(whole, fn(blocks).sum(axis=(1, 2)))
                col += _masked_sum(split, fn(rows).reshape(-1))
            if tail:
                col += _masked_sum(rest, fn(x[nb * BLOCK:]))
            sums.append(col)
    return _fold_groups(jnp.stack(sums, axis=-1), groups, n_groups)


def square(x: jax.Array) -> jax.Array:
    """Squared-L2 mass (energy) of a coordinate, float32. Energies are
    additive, so group masses sum to ``||x||^2`` up to fp addition
    order."""
    x = x.astype(jnp.float32)
    return x * x


def nonzero(x: jax.Array) -> jax.Array:
    """1.0 on the support (e.g. the update's top-k support), float32."""
    return (x != 0).astype(jnp.float32)
