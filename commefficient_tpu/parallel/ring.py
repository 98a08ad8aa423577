"""Ring attention: causal attention with the sequence axis sharded over the
device mesh.

NEW SCOPE beyond the reference, which has no long-context machinery at all
(SURVEY.md §5: max sequence = a padded PersonaChat batch, no ring/Ulysses/
blockwise anywhere). Required here because long-context is first-class for
this framework: with ``seq`` sharded over N devices each chip holds S/N
tokens, K/V blocks rotate around the ring via ``lax.ppermute`` (one ICI hop
per step, compute overlaps the N-1 hops), and softmax is accumulated online
(flash-attention style: running max ``m``, normalizer ``l``, weighted sum
``o``) so the full S x S score matrix never materializes.

Numerics: fp32 accumulators regardless of input dtype; causality enforced
from *global* token positions, so the result equals dense causal attention
exactly (see tests/test_ring.py).

Surfaces:
- ``ring_attention_inner(q, k, v, axis_name, num_shards)`` — call inside an
  existing ``shard_map``/pjit; q,k,v are the local (..., S/N, H, D) shards.
- ``make_ring_attention(mesh, axis)`` — standalone wrapper returning a
  drop-in ``attn_impl`` for ``models.gpt2`` modules: full (..., S, H, D)
  arrays in/out, shard_map applied internally.
"""

from __future__ import annotations

import functools
import math
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NEG = -1e30


def ring_attention_inner(q: jax.Array, k: jax.Array, v: jax.Array,
                         axis_name: str, num_shards: int) -> jax.Array:
    """Causal ring attention on per-device shards.

    q, k, v: (..., Sl, H, D) local blocks (Sl = S / num_shards, in ring
    order: shard i holds global positions [i*Sl, (i+1)*Sl)).
    Returns the local (..., Sl, H, D) attention output.
    """
    Sl, H, D = q.shape[-3:]
    scale = 1.0 / math.sqrt(D)
    my = lax.axis_index(axis_name)
    qpos = my * Sl + jnp.arange(Sl)                       # global q positions
    qf = q.astype(jnp.float32)

    batch_shape = q.shape[:-3]
    # accumulators start identical on every device but become
    # device-varying after the first step — mark them varying up front
    # (shard_map's check would otherwise reject the scan carry)
    m0, l0, o0 = jax.tree.map(
        lambda t: lax.pcast(t, (axis_name,), to="varying"),
        (jnp.full(batch_shape + (H, Sl), NEG, jnp.float32),
         jnp.zeros(batch_shape + (H, Sl), jnp.float32),
         jnp.zeros(batch_shape + (Sl, H, D), jnp.float32)))

    perm = [(i, (i + 1) % num_shards) for i in range(num_shards)]

    def step(carry, _):
        k_blk, v_blk, src, m, l, o = carry
        logits = jnp.einsum("...qhd,...khd->...hqk", qf,
                            k_blk.astype(jnp.float32)) * scale
        kpos = src * Sl + jnp.arange(Sl)                  # global k positions
        causal = qpos[:, None] >= kpos[None, :]           # (Sl, Sl)
        logits = jnp.where(causal, logits, NEG)

        blk_max = logits.max(axis=-1)                     # (..., H, Sl)
        m_new = jnp.maximum(m, blk_max)
        p = jnp.exp(logits - m_new[..., None])            # (..., H, Sl, Sl)
        p = jnp.where(causal, p, 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("...hqk,...khd->...qhd", p,
                        v_blk.astype(jnp.float32))
        o = o * jnp.moveaxis(corr, -2, -1)[..., None] + pv
        m = m_new

        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        src = lax.ppermute(src, axis_name, perm)
        return (k_blk, v_blk, src, m, l, o), None

    init = (k, v, my, m0, l0, o0)
    (_, _, _, m, l, o), _ = lax.scan(step, init, None, length=num_shards)
    denom = jnp.maximum(jnp.moveaxis(l, -2, -1), 1e-30)[..., None]
    return (o / denom).astype(q.dtype)


def make_ring_attention(mesh: Mesh, axis: str = "seq") -> Callable:
    """Drop-in ``attn_impl`` for the GPT-2 modules: takes full
    (..., S, H, D) arrays, shards S over ``axis`` and runs the ring."""
    n = mesh.shape[axis]

    def attn(q, k, v):
        nd = q.ndim
        # build a PartitionSpec placing `axis` at dim -3
        ax_spec = P(*([None] * (nd - 3) + [axis, None, None]))
        inner = functools.partial(ring_attention_inner, axis_name=axis,
                                  num_shards=n)
        return shard_map(inner, mesh=mesh,
                         in_specs=(ax_spec, ax_spec, ax_spec),
                         out_specs=ax_spec)(q, k, v)

    return attn
