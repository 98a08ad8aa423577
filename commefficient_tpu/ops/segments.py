"""Segment (per-group) reductions over the flat federated vector.

The layer-wise attribution layer (telemetry/layer_signals.py) reduces
dense (d,)-shaped round quantities — the aggregated gradient, the
applied update, the EF accumulators — into one small ``(G,)`` vector per
signal, where ``G`` is the number of named parameter groups, keyed by a
precomputed int32 group-id map (``gid[i]`` = the group owning ravel
coordinate ``i``).

The d-sized reductions are G MASKED REDUCTIONS over one shared compare
(``gid == g``), not a scatter-add. The compare and select fuse into the
reduce, so nothing (G, d)-shaped is built, and under GSPMD a sharded
operand reduces shard-locally with one small psum — never a per-group
collective unroll (the round-5 regression class; the dryrun's collective
ledger gates it). A scatter-add is the natural way to write this and the
wrong one on a TPU, measured on a v5e (PR 21): the scatter serializes
(63.6 ms for three columns at d = 6.6M, G = 8, against 0.76 ms in this
form — it was 79% of the ResNet-9 round), and XLA lays a batched
``(d, C)`` scatter operand out with C padded to 128 lanes, 47 GB at
d = 92M, C = 2, so the GPT-2 round did not compile at all (10.2 ms at
G = 40 in this form). Cost grows with G; the group layouts in use have
G <= ~40.

Out-of-group coordinates (mesh ``d_pad`` padding) carry ``gid == G``,
which matches no group — padding can never leak mass into a real group
(pinned by tests/test_layer_signals.py against a numpy reference).
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp


def _group_sum(x: jax.Array, gid: jax.Array, n_groups: int) -> jax.Array:
    """``out[g] = sum_{gid==g} x`` as float32 (see the module note)."""
    hit = (gid[None, : x.shape[0]]
           == jnp.arange(n_groups, dtype=gid.dtype)[:, None])
    return jnp.where(hit, x.astype(jnp.float32)[None, :], 0.0).sum(axis=1)


def group_sq_mass(x: jax.Array, gid: jax.Array,
                  n_groups: int) -> jax.Array:
    """Per-group squared-L2 mass (energy): ``out[g] = sum_{gid==g} x^2``.
    Conservation: ``out.sum() == ||x||^2`` up to fp addition order when
    every coordinate of ``x`` carries an in-range gid (padding
    coordinates of a mesh-padded vector are identically zero AND
    dropped, so either mechanism alone preserves the identity)."""
    x = x.astype(jnp.float32)
    return _group_sum(x * x, gid, n_groups)


def group_count(mask: jax.Array, gid: jax.Array,
                n_groups: int) -> jax.Array:
    """Per-group count of True coordinates (e.g. the update's top-k
    support): ``out[g] = |{i : gid[i]==g and mask[i]}|`` as float32."""
    return _group_sum(mask, gid, n_groups)


def group_sum_cols(cols: Sequence[jax.Array], gid: jax.Array,
                   n_groups: int) -> jax.Array:
    """Per-group sums of C same-length columns: ``cols`` holds C (L,)
    vectors, the result is (G, C) with ``out[g, j] = sum_{gid==g}
    cols[j][i]``. The columns are never stacked at length L."""
    return jnp.stack([_group_sum(c, gid, n_groups) for c in cols], axis=-1)


def group_sum_at(vals: jax.Array, idx: jax.Array, gid: jax.Array,
                 n_groups: int) -> jax.Array:
    """Segment-sum of ``vals`` over the groups owning the COORDINATES
    ``idx`` (the k top-k winner indices): ``out[g] = sum_{gid[idx[j]]==g}
    vals[j]``. O(k) gather + scatter — the winner-attribution primitive
    (counts when ``vals`` is all-ones, recovered-winner counts when it
    is the update's support at the winners)."""
    return jnp.zeros((n_groups,), jnp.float32).at[gid[idx]].add(
        vals.astype(jnp.float32), mode="drop")
