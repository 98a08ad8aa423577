"""Laguna: a decoder with window and full attention layers of different
head counts and a routed expert layer that holds a share of its experts.

Built from the lists of the published ``config.json`` (``layer_types``,
``num_attention_heads_per_layer``, ``mlp_layer_types``): pre-RMSNorm
blocks, grouped-query attention (H query heads over 8 KV heads of 128)
with a per-head sigmoid gate on its output, rotary positions (YaRN on the
first half of the head on full layers, plain on window layers), a causal
window of ``sliding_window`` keys on the window layers, SwiGLU, and from
layer 1 on a 256-way softmax router over experts of width 512 with one
shared expert, untied input and output embeddings.

The expert layer is told which experts it holds (``experts_held``, a
range of ids): the router keeps its published width, routing is over all
experts, and the layer adds only what its own experts give. On one chip
nothing stands in for the absent ones; with every expert held it is the
whole layer. The layer is dropless with static shapes: every held expert
is applied to every token in one batched product over the held experts,
weighted by the router (zero where the token did not choose it).

Router product, softmax and top-k are float32 at highest precision: under
bfloat16 the choice of experts flips against a float32 reference.
"""

from __future__ import annotations

import dataclasses
import json
import math
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from commefficient_tpu.models.gpt2 import (GROUPED_ATTN_RESIDUAL,
                                           auto_grouped_attention,
                                           blocked_grouped_kernel,
                                           runs_blocked_kernel)
# the decoder layers this model shares with models/joyai.py; the names
# stay importable from here
from commefficient_tpu.models.layers import (MOE_COUNTERS,  # noqa: F401
                                             ExpertLayer, RMSNorm, SwiGLU,
                                             linear, moe_counters)
from commefficient_tpu.ops.rope_pallas import gate_from_heads, rope_to_heads
from commefficient_tpu.telemetry.profiling import phase

FULL, SLIDING = "full_attention", "sliding_attention"
@dataclasses.dataclass(frozen=True)
class RopeSpec:
    """One entry of the published ``rope_parameters``."""
    rope_theta: float = 10000.0
    rope_type: str = "default"
    partial_rotary_factor: float = 1.0
    factor: float = 1.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None

    @classmethod
    def from_dict(cls, d):
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in names})


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192
    num_hidden_layers: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    num_experts: int = 256            # the router's width, as published
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    sliding_window: int = 512
    moe_routed_scaling_factor: float = 2.5
    layer_types: Tuple[str, ...] = ()
    mlp_layer_types: Tuple[str, ...] = ()
    num_attention_heads_per_layer: Tuple[int, ...] = ()
    full_rope: RopeSpec = RopeSpec()
    sliding_rope: RopeSpec = RopeSpec()
    # ids [lo, hi) of the experts this chip holds in every sparse layer
    experts_held: Tuple[int, int] = (0, 256)
    # the expert layer's routing rule (models/layers.ROUTER_SCORING)
    router_scoring: str = "softmax"
    compute_dtype: Any = jnp.bfloat16
    # recompute every block in the backward pass, keeping of its interior
    # only what carries GROUPED_ATTN_RESIDUAL: the blocked attention
    # kernel's output and logsumexp where that kernel runs (TPU, S >= 1024,
    # S a multiple of 512), nothing on the plain path. No flag selects it.
    remat: bool = False

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @classmethod
    def from_hf(cls, hf: dict, **overrides) -> "LagunaConfig":
        """From a ``config.json`` in the published key set. A file may
        state a chip's share beside it: ``experts_held`` ([lo, hi) ids)
        and, where its ``num_experts`` counts the held ones,
        ``num_experts_published`` (the router's width). The per-layer
        lists may be longer than ``num_hidden_layers``: the leading layers
        are taken. ``overrides`` are this class's own fields."""
        hf = {**hf, **{k: v for k, v in overrides.items() if k in hf}}
        L = int(hf["num_hidden_layers"])
        n_experts = int(hf.get("num_experts_published", hf["num_experts"]))
        rope = hf["rope_parameters"]
        heads = hf.get("num_attention_heads_per_layer") or [
            hf["num_attention_heads"]] * L
        kw = {f.name: hf[f.name] for f in dataclasses.fields(cls)
              if f.name in hf}
        kw.update(
            num_experts=n_experts,
            layer_types=tuple(hf["layer_types"][:L]),
            mlp_layer_types=tuple(hf["mlp_layer_types"][:L]),
            num_attention_heads_per_layer=tuple(int(h) for h in heads[:L]),
            full_rope=RopeSpec.from_dict(rope[FULL]),
            sliding_rope=RopeSpec.from_dict(rope[SLIDING]),
            experts_held=hf.get("experts_held", (0, n_experts)))
        kw.update(overrides)
        kw["experts_held"] = tuple(int(i) for i in kw["experts_held"])
        return cls(**kw)

    @classmethod
    def from_json(cls, path: str, **overrides) -> "LagunaConfig":
        with open(path) as f:
            return cls.from_hf(json.load(f), **overrides)


def rope_tables(spec: RopeSpec, head_dim: int, positions):
    """(cos, sin), each (S, rotary_dim/2) float32. ``yarn`` follows
    ``transformers``' ``_compute_yarn_parameters``: interpolated and
    extrapolated inverse frequencies blended by a linear ramp between the
    correction dimensions of ``beta_fast`` and ``beta_slow``, cos and sin
    scaled by ``attention_factor``."""
    dim = int(head_dim * spec.partial_rotary_factor)
    base = float(spec.rope_theta)
    pos_freqs = base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    inv_freq, scale = 1.0 / pos_freqs, 1.0
    if spec.rope_type == "yarn":
        factor = float(spec.factor)
        scale = (spec.attention_factor if spec.attention_factor is not None
                 else (0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0))
        orig = spec.original_max_position_embeddings

        def correction_dim(rotations):
            return (dim * math.log(orig / (rotations * 2 * math.pi))
                    / (2 * math.log(base)))

        low = max(math.floor(correction_dim(spec.beta_fast)), 0)
        high = min(math.ceil(correction_dim(spec.beta_slow)), dim - 1)
        if low == high:
            high += 0.001
        ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                        / (high - low), 0.0, 1.0)
        extrapolation = 1.0 - ramp
        inv_freq = (inv_freq / factor * (1.0 - extrapolation)
                    + inv_freq * extrapolation)
    elif spec.rope_type != "default":
        raise ValueError(f"unknown rope_type {spec.rope_type!r}")
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale


def apply_rope(x, cos, sin):
    """Rotate the first ``2 * cos.shape[-1]`` dimensions of x (..., S, H,
    D) in the half-split convention (``rotate_half``); the rest pass.
    The plain path's rotary (CPU, S < 1,024, the float32 reference): it
    slices and concatenates the last dimension in float32, which on the
    TPU is several float32 (S, H, D) arrays through HBM; where the
    blocked attention kernel runs, ``rope_gated_blocked_attention`` does
    the same arithmetic in one pass."""
    half = cos.shape[-1]
    xf = x.astype(jnp.float32)
    x1, x2, rest = xf[..., :half], xf[..., half:2 * half], xf[..., 2 * half:]
    c, s = cos[:, None, :], sin[:, None, :]
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], axis=-1)
    return out.astype(x.dtype)


def rope_gated_blocked_attention(q, k, v, gate, cos, sin, window=None):
    """``apply_rope`` on q and k, ``blocked_grouped_kernel`` and the gate,
    for q (..., S, H, D), k and v (..., S, KV, D) that are views of the
    projections' (..., S, H x D) outputs, gate (..., S, H) float32 ->
    the gated output (..., S, H, D). Every head-shaped tensor crosses HBM
    once a pass in its own dtype: ``ops/rope_pallas.py``'s kernels rotate,
    scale and go head-major in one pass over q and k, and gate and go
    back in one; v's transpose is a bfloat16 copy; the backward passes
    are those kernels' transposes. Same values as the plain path (same
    float32 arithmetic, same roundings)."""
    lead, (S, H, D), KV = q.shape[:-3], q.shape[-3:], k.shape[-2]
    flat = lambda t: t.reshape((-1, S, t.shape[-2] * D))
    qb, kb = rope_to_heads(flat(q), flat(k), cos, sin, head_dim=D,
                           scale=1.0 / math.sqrt(D))
    with phase("fed_attention"):
        vb = v.reshape((-1, S, KV, D)).transpose(0, 2, 1, 3)
        o = blocked_grouped_kernel(S, H, KV, window)(qb, kb, vb)
    o = gate_from_heads(o, gate.reshape((-1, S, H)))
    return o.reshape(lead + (S, H, D))


class LagunaBlock(nn.Module):
    """One pre-norm block: attention, then the dense or the expert layer.
    Two paths through the attention, chosen by what the code can see and
    by no flag. Where ``attn_impl`` runs the blocked kernel
    (``runs_blocked_kernel``: TPU, S a multiple of 512, from 1,024 up
    under ``auto``) and ``head_dim`` is a multiple of 128,
    ``rope_gated_blocked_attention``: q, k and the gated output cross HBM
    once a pass each, in ``compute_dtype``, as the projections write and
    read them. Elsewhere (the CPU, short sequences, the float32
    reference, small heads) the plain path: ``apply_rope``, ``attn_impl``
    on (..., S, H, D), the gate product in float32. ``fed_attention``
    wraps the attention proper on both: not the projections, rotary or
    gate, nor on the blocked path q's scale."""
    cfg: LagunaConfig
    layer: int
    attn_impl: Callable = auto_grouped_attention

    @nn.compact
    def __call__(self, x, positions, valid=None):
        cfg, i = self.cfg, self.layer
        dt = cfg.compute_dtype
        H, KV, D = (cfg.num_attention_heads_per_layer[i],
                    cfg.num_key_value_heads, cfg.head_dim)
        sliding = cfg.layer_types[i] == SLIDING
        rope = cfg.sliding_rope if sliding else cfg.full_rope

        h = RMSNorm(cfg.rms_norm_eps, name="input_norm")(x).astype(dt)
        heads = lambda t, n: t.reshape(t.shape[:-1] + (n, D))
        q = heads(linear(H * D, dt, "q_proj")(h), H)
        k = heads(linear(KV * D, dt, "k_proj")(h), KV)
        v = heads(linear(KV * D, dt, "v_proj")(h), KV)
        gate = jax.nn.sigmoid(
            linear(H, dt, "g_proj")(h).astype(jnp.float32))
        cos, sin = rope_tables(rope, D, positions)
        window = cfg.sliding_window if sliding else None
        if D % 128 == 0 and runs_blocked_kernel(self.attn_impl, q.shape[-3]):
            o = rope_gated_blocked_attention(q, k, v, gate, cos, sin, window)
        else:
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
            with phase("fed_attention"):
                o = self.attn_impl(q, k, v, window=window)
            o = (o.astype(jnp.float32) * gate[..., None]).astype(dt)
        x = x + linear(cfg.hidden_size, dt, "o_proj")(
            o.reshape(o.shape[:-2] + (H * D,)))

        hn = RMSNorm(cfg.rms_norm_eps, name="post_norm")(x)
        if cfg.mlp_layer_types[i] == "dense":
            y = SwiGLU(cfg.intermediate_size, cfg.hidden_size, dt,
                       name="mlp")(hn.astype(dt))
            counts = None
        else:
            with phase("fed_moe"):
                y, counts = ExpertLayer(cfg, name="moe")(hn, valid)
            y = y + SwiGLU(cfg.shared_expert_intermediate_size,
                           cfg.hidden_size, dt,
                           name="shared_expert")(hn.astype(dt))
        return x + y, counts


class LagunaLM(nn.Module):
    """``input_ids`` (..., S) -> (hidden (..., S, E) float32 after the
    final norm, the (V, E) output head, the expert layers' counters).
    The vocabulary projection is the loss's (``losses._chunked_lm_nll``).
    ``valid`` (..., S) marks the positions that are tokens (None: all).
    Padding follows the tokens, attention is causal and the loss puts no
    label on padding, so what any layer computes at a padded position
    reaches neither the loss nor a gradient: the expert layers skip them.
    ``cfg.remat`` wraps each block in ``nn.remat`` with a policy that saves
    ``GROUPED_ATTN_RESIDUAL`` alone: where the blocked attention kernel
    runs, its output and logsumexp survive to the backward pass and the
    forward kernel runs once a layer; everything else in the block (norms,
    projections, rotary, gate, router, experts) is recomputed. On the plain
    attention path nothing carries the name and the remat is the full one.
    Which attention path a block takes, and what then crosses HBM around
    the kernel, is ``LagunaBlock``'s to say."""

    cfg: LagunaConfig
    attn_impl: Callable = auto_grouped_attention

    @nn.compact
    def __call__(self, input_ids, valid=None):
        cfg = self.cfg
        embed = self.param("embed_tokens", nn.initializers.normal(0.02),
                           (cfg.vocab_size, cfg.hidden_size))
        head = self.param("lm_head", nn.initializers.normal(0.02),
                          (cfg.vocab_size, cfg.hidden_size))
        positions = jnp.arange(input_ids.shape[-1])
        x = embed[input_ids].astype(cfg.compute_dtype)
        block_cls = (nn.remat(
            LagunaBlock, static_argnums=(),
            policy=jax.checkpoint_policies.save_only_these_names(
                GROUPED_ATTN_RESIDUAL)) if cfg.remat else LagunaBlock)
        per_layer = []
        for i in range(cfg.num_hidden_layers):
            x, counts = block_cls(cfg, i, self.attn_impl,
                                  name=f"layers_{i}")(x, positions, valid)
            if counts is not None:
                per_layer.append(counts)
        hidden = RMSNorm(cfg.rms_norm_eps, name="norm")(x)
        return hidden, head, moe_counters(per_layer)


def laguna_model_flops(cfg: LagunaConfig, tokens: int, S: int) -> float:
    """Forward + backward operations for ``tokens`` positions in sequences
    of S (2 per multiply-add, backward twice the forward, recomputation
    not counted): the parameters that act on a position (a held expert at
    its expected ``top-k x held / experts`` hits), scores and values over
    min(S, window) keys on window layers and over the causal half on full
    layers, and the output head; the embedding lookup is not a product."""
    E, D, KV = cfg.hidden_size, cfg.head_dim, cfg.num_key_value_heads
    per_tok = cfg.vocab_size * E
    expert = 3 * E * cfg.moe_intermediate_size
    for i in range(cfg.num_hidden_layers):
        H = cfg.num_attention_heads_per_layer[i]
        per_tok += E * (2 * H * D + 2 * KV * D + H)
        keys = (min(S, cfg.sliding_window)
                if cfg.layer_types[i] == SLIDING else S / 2)
        per_tok += 2 * H * D * keys
        if cfg.mlp_layer_types[i] == "dense":
            per_tok += 3 * E * cfg.intermediate_size
        else:
            per_tok += (E * cfg.num_experts
                        + 3 * E * cfg.shared_expert_intermediate_size
                        + expert * cfg.num_experts_per_tok * cfg.n_held
                        / cfg.num_experts)
    return 3.0 * 2.0 * per_tok * tokens
