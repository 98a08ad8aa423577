"""JoyAI-LLM-Flash: a decoder with latent attention (MLA), a sigmoid
router with a selection bias over a routed expert layer that holds a share
of its experts, and one multi-token-prediction module.

Built from the published ``config.json`` (the DeepSeek-V3 family's key
set). Pre-RMSNorm residual blocks, no biases, ``silu``. x is (S, E).

Latent attention, every layer, H heads: ``c_q = RMSNorm(x W_qa)``;
``q = c_q W_qb`` -> (H, nope + rope) = [q_nope ; q_rope].
``[c_kv ; k_rope] = x W_kva``; ``c_kv = RMSNorm(c_kv)``; ``c_kv W_kvb`` ->
(H, nope + v) = [k_nope ; v]. Rotary on q_rope (per head) and on k_rope
(one vector a position, shared by all heads), pairs (2i, 2i+1) by
``pos x theta^(-2i/rope)`` (``rope_interleave``). ``k = [k_nope ;
k_rope]``; causal scores over sqrt(nope + rope), float32 softmax, values
v_head_dim wide; ``x += concat(o) W_o``. The plain path holds the rotary
dimensions half-split (all even members, then all odd), the fused path
around the blocked kernel holds the pairs where the projection writes
them: each a fixed permutation of them on q and k alike, which leaves
every score unchanged (``JoyAIBlock``).

Layers below ``first_k_dense_replace``: SwiGLU of ``intermediate_size``.
The others: ``models/layers.ExpertLayer`` under the rule ``sigmoid_bias``
(scores ``sigmoid(x W_r)``, the k largest of score + bias chosen, weighted
by score / sum of the chosen scores, times ``routed_scaling_factor``) plus
one ungated shared expert. The layer is told which experts it holds
(``experts_held``), routes over all of them and adds only what its own
give, as in ``models/laguna.py``.

Multi-token prediction (DeepSeek-V3 technical report, arXiv:2412.19437,
section 2.2), one module: with h_i the last block's output at position i
(before the final norm) and t_{i+1} the next token, ``h'_i =
[RMSNorm_h(h_i) ; RMSNorm_e(Emb(t_{i+1}))] W_eh``, one sparse block of the
kind above, the module's own final norm, the main model's output head; it
is trained to predict t_{i+2} (``losses.make_joyai_loss``).
"""

from __future__ import annotations

import dataclasses
import functools
import json
import math
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from commefficient_tpu.models.gpt2 import (GROUPED_ATTN_RESIDUAL,
                                           auto_grouped_attention,
                                           blocked_grouped_kernel,
                                           runs_blocked_kernel)
from commefficient_tpu.models.laguna import RopeSpec, rope_tables
from commefficient_tpu.models.layers import (MOE_COUNTERS, ExpertLayer,
                                             RMSNorm, SwiGLU, linear,
                                             moe_counters)
from commefficient_tpu.ops import latent_pallas
from commefficient_tpu.ops.latent_pallas import heads_to_rows, qkv_to_heads
from commefficient_tpu.telemetry.profiling import phase

# what the training loss reports after (loss, accuracy), in this order
# (losses.make_joyai_loss; the round event carries them)
ROUND_COUNTERS = ("main_nll", "mtp_nll") + MOE_COUNTERS


@dataclasses.dataclass(frozen=True)
class JoyAIConfig:
    vocab_size: int = 129280
    hidden_size: int = 2048
    intermediate_size: int = 7168
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 32000000.0
    first_k_dense_replace: int = 1
    n_routed_experts: int = 256       # the router's width, as published
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    num_nextn_predict_layers: int = 1
    # ids [lo, hi) of the experts this chip holds in every sparse layer
    experts_held: Tuple[int, int] = (0, 256)
    compute_dtype: Any = jnp.bfloat16
    # as LagunaConfig.remat: every block recomputed in the backward pass,
    # the blocked attention kernel's output and logsumexp kept
    remat: bool = False

    # what models/layers.ExpertLayer reads of a configuration
    num_experts = property(lambda self: self.n_routed_experts)
    n_held = property(lambda self: self.experts_held[1]
                      - self.experts_held[0])
    moe_routed_scaling_factor = property(
        lambda self: self.routed_scaling_factor)
    router_scoring = "sigmoid_bias"   # scoring_func sigmoid, noaux_tc

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @classmethod
    def from_hf(cls, hf: dict, **overrides) -> "JoyAIConfig":
        """From a ``config.json`` in the published key set. A file may
        state a chip's share beside it, as ``LagunaConfig.from_hf`` reads
        one: ``experts_held`` ([lo, hi) ids) and, where its
        ``n_routed_experts`` counts the held ones,
        ``n_routed_experts_published`` (the router's width);
        ``num_hidden_layers`` leading layers are built. What this module
        does not build is refused: a group limit on the router, another
        scoring function than the sigmoid, scaled rotary, half-split
        published rotary, unnormalised top-k weights, more or fewer than
        one prediction module."""
        hf = {**hf, **{k: v for k, v in overrides.items() if k in hf}}
        for key, want in (("n_group", 1), ("topk_group", 1),
                          ("rope_scaling", None), ("rope_interleave", True),
                          ("norm_topk_prob", True), ("moe_layer_freq", 1),
                          ("scoring_func", "sigmoid"),
                          ("num_nextn_predict_layers", 1)):
            if hf.get(key, want) != want:
                raise ValueError(f"JoyAIConfig: {key} = {hf[key]!r} is not "
                                 f"built here (only {want!r})")
        n_experts = int(hf.get("n_routed_experts_published",
                               hf["n_routed_experts"]))
        kw = {f.name: hf[f.name] for f in dataclasses.fields(cls)
              if f.name in hf}
        kw.update(n_routed_experts=n_experts,
                  experts_held=hf.get("experts_held", (0, n_experts)))
        kw.update(overrides)
        kw["experts_held"] = tuple(int(i) for i in kw["experts_held"])
        return cls(**kw)

    @classmethod
    def from_json(cls, path: str, **overrides) -> "JoyAIConfig":
        with open(path) as f:
            return cls.from_hf(json.load(f), **overrides)


def interleaved_rope(x, cos, sin):
    """Rotate the pairs (2i, 2i+1) of x (..., S, H, R) by the angles of
    (cos, sin), each (S, R/2), and hold the result half-split: the R/2
    rotated even members, then the R/2 odd ones. Float32 arithmetic, x's
    dtype out. The plain path's rotary (the CPU, short sequences, the
    float32 program) and the reference for the values of the fused path's
    (``ops/latent_pallas.py``), which rotates the pairs in place: the same
    products and roundings, another permutation of the lanes on q and k
    alike."""
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


class JoyAIBlock(nn.Module):
    """One pre-norm block: latent attention, then the dense or the expert
    layer. Two paths through latent attention, chosen by what the code can
    see and by no flag. Where ``attn_impl`` runs the blocked kernel
    (``runs_blocked_kernel``: TPU, S a multiple of 512, from 1,024 up
    under ``auto``) at the widths ``ops/latent_pallas.py`` takes (q and k
    128 + 64, v 128), the fused path: the shared rotary key (S x 64) is
    rotated in XLA, and q, k and v go from the projections' rows to the
    kernel's (H, 1, S, D) layout in one pass a tensor (``qkv_to_heads``:
    q's pairs rotated in place and scaled, k's rotary lanes the shared
    key on every head), the output back in one (``heads_to_rows``); the
    backward passes are those kernels' transposes. Elsewhere the plain
    path, the reference for the values: ``interleaved_rope``, the
    concatenations, ``attn_impl`` on (..., S, H, D). ``fed_latent`` wraps
    both low-rank projection pairs, the two latent norms, rotary and, on
    the fused path, the kernels around the attention; ``fed_attention``
    the attention proper; ``fed_moe`` the routed layer. W_o, the block's
    two norms, the dense layer and the shared expert are the enclosing
    scope's."""
    cfg: JoyAIConfig
    sparse: bool
    attn_impl: Callable = auto_grouped_attention

    @nn.compact
    def __call__(self, x, rope, valid=None):
        cfg = self.cfg
        dt, eps = cfg.compute_dtype, cfg.rms_norm_eps
        H, dn, dr, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim, cfg.v_head_dim)
        S = x.shape[-2]
        fused = (runs_blocked_kernel(self.attn_impl, S)
                 and latent_pallas.fits(H, dn, dr, dv))

        h = RMSNorm(eps, name="input_norm")(x).astype(dt)
        with phase("fed_latent"):
            c_q = RMSNorm(eps, name="q_a_layernorm")(
                linear(cfg.q_lora_rank, dt, "q_a_proj")(h)).astype(dt)
            q = linear(H * (dn + dr), dt, "q_b_proj")(c_q)
            kv_a = linear(cfg.kv_lora_rank + dr, dt,
                          "kv_a_proj_with_mqa")(h)
            c_kv = RMSNorm(eps, name="kv_a_layernorm")(
                kv_a[..., :cfg.kv_lora_rank]).astype(dt)
            kv = linear(H * (dn + dv), dt, "kv_b_proj")(c_kv)
            cos, sin, pairs = rope
            if fused:
                rows = lambda t: t.reshape((-1, S, t.shape[-1]))
                q, k, v = qkv_to_heads(rows(q), rows(kv), rows(kv_a), pairs,
                                       scale=1.0 / math.sqrt(dn + dr))
            else:
                heads = lambda t: t.reshape(t.shape[:-1] + (H, -1))
                q, kv = heads(q), heads(kv)
                q_rope = interleaved_rope(q[..., dn:], cos, sin)
                k_rope = interleaved_rope(
                    kv_a[..., None, cfg.kv_lora_rank:], cos, sin)
                q = jnp.concatenate([q[..., :dn], q_rope], axis=-1)
                k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
                    k_rope, kv.shape[:-1] + (dr,))], axis=-1)
                v = kv[..., dn:]
        with phase("fed_attention"):
            o = (blocked_grouped_kernel(S, H, H)(q, k, v) if fused
                 else self.attn_impl(q, k, v))
        if fused:
            with phase("fed_latent"):
                o = heads_to_rows(o).reshape(x.shape[:-1] + (H * dv,))
        else:
            o = o.reshape(o.shape[:-2] + (H * dv,))
        x = x + linear(cfg.hidden_size, dt, "o_proj")(o)

        hn = RMSNorm(eps, name="post_norm")(x)
        if not self.sparse:
            y = SwiGLU(cfg.intermediate_size, cfg.hidden_size, dt,
                       name="mlp")(hn.astype(dt))
            counts = None
        else:
            with phase("fed_moe"):
                y, counts = ExpertLayer(cfg, name="moe")(hn, valid)
            y = y + SwiGLU(cfg.moe_intermediate_size * cfg.n_shared_experts,
                           cfg.hidden_size, dt,
                           name="shared_expert")(hn.astype(dt))
        return x + y, counts


def rotary_tables(cfg: JoyAIConfig, positions):
    """What every block's rotary reads, made once a forward pass (under
    ``fed_latent``) and handed to the blocks: ``rope_tables``' (cos, sin)
    for the plain path and their ``latent_pallas.pair_tables`` for the
    fused one (the compiler drops what the path does not read)."""
    with phase("fed_latent"):
        cos, sin = rope_tables(RopeSpec(rope_theta=cfg.rope_theta),
                               cfg.qk_rope_head_dim, positions)
        return cos, sin, latent_pallas.pair_tables(cos, sin)


@functools.lru_cache(maxsize=None)
def _block_cls(remat: bool):
    """``JoyAIBlock``, or with ``remat`` the block recomputed in the
    backward pass but for what carries ``GROUPED_ATTN_RESIDUAL``."""
    return (nn.remat(
        JoyAIBlock, static_argnums=(),
        policy=jax.checkpoint_policies.save_only_these_names(
            GROUPED_ATTN_RESIDUAL)) if remat else JoyAIBlock)


class MTPModule(nn.Module):
    """The multi-token-prediction module: (h (..., S, E) before the final
    norm, the embedding of each position's next token) -> (its hidden
    states after its own final norm, its block's counters)."""
    cfg: JoyAIConfig
    attn_impl: Callable = auto_grouped_attention

    @nn.compact
    def __call__(self, h, next_embed, rope, valid=None):
        cfg = self.cfg
        dt, eps = cfg.compute_dtype, cfg.rms_norm_eps
        both = jnp.concatenate([RMSNorm(eps, name="hnorm")(h),
                                RMSNorm(eps, name="enorm")(next_embed)],
                               axis=-1).astype(dt)
        x = linear(cfg.hidden_size, dt, "eh_proj")(both)
        x, counts = _block_cls(cfg.remat)(cfg, True, self.attn_impl,
                                    name="layer")(x, rope, valid)
        return RMSNorm(eps, name="norm")(x), counts


class JoyAILM(nn.Module):
    """``input_ids`` (..., S) -> (hidden (..., S, E) float32 after the
    final norm, the prediction module's hidden (..., S, E) float32 after
    its own, the (V, E) output head, the expert layers' counters). The
    vocabulary projections are the loss's. Position i of the module's
    stream is built from h_i and token i+1 and predicts token i+2; its
    last position has no next token (it is given the last one again) and
    carries no label. ``valid`` (..., S) marks the positions that are
    tokens (None: all); as in ``LagunaLM`` the expert layers skip the
    others, and the module's skip the positions whose next token is
    padding. ``cfg.remat``: see ``LagunaLM``."""

    cfg: JoyAIConfig
    attn_impl: Callable = auto_grouped_attention

    @nn.compact
    def __call__(self, input_ids, valid=None):
        cfg = self.cfg
        embed = self.param("embed_tokens", nn.initializers.normal(0.02),
                           (cfg.vocab_size, cfg.hidden_size))
        head = self.param("lm_head", nn.initializers.normal(0.02),
                          (cfg.vocab_size, cfg.hidden_size))
        rope = rotary_tables(cfg, jnp.arange(input_ids.shape[-1]))
        x = embed[input_ids].astype(cfg.compute_dtype)
        per_layer = []
        for i in range(cfg.num_hidden_layers):
            x, counts = _block_cls(cfg.remat)(
                cfg, i >= cfg.first_k_dense_replace, self.attn_impl,
                name=f"layers_{i}")(x, rope, valid)
            if counts is not None:
                per_layer.append(counts)
        hidden = RMSNorm(cfg.rms_norm_eps, name="norm")(x)
        with phase("fed_mtp"):
            shift = lambda t: jnp.concatenate([t[..., 1:], t[..., -1:]], -1)
            if valid is not None:
                valid = valid & shift(valid).at[..., -1].set(False)
            hidden_mtp, counts = MTPModule(cfg, self.attn_impl, name="mtp")(
                x, embed[shift(input_ids)].astype(cfg.compute_dtype),
                rope, valid)
        per_layer.append(counts)
        return hidden, hidden_mtp, head, moe_counters(per_layer)


def joyai_model_flops(cfg: JoyAIConfig, tokens: int, S: int) -> float:
    """Forward + backward operations for ``tokens`` positions in sequences
    of S (2 per multiply-add, backward twice the forward, recomputation
    not counted): the parameters that act on a position (a held expert at
    its expected ``top-k x held / experts`` hits), scores over the causal
    half at the q/k width and values at v's, in every layer and in the
    prediction module's block, and the output head twice; the embedding
    lookups are not products."""
    E, H = cfg.hidden_size, cfg.num_attention_heads
    attention = (E * cfg.q_lora_rank + cfg.q_lora_rank * H * cfg.qk_head_dim
                 + E * (cfg.kv_lora_rank + cfg.qk_rope_head_dim)
                 + cfg.kv_lora_rank * H * (cfg.qk_nope_head_dim
                                           + cfg.v_head_dim)
                 + H * cfg.v_head_dim * E
                 + H * (cfg.qk_head_dim + cfg.v_head_dim) * S / 2)
    expert = 3 * E * cfg.moe_intermediate_size
    sparse = (E * cfg.num_experts + expert * cfg.n_shared_experts
              + expert * cfg.num_experts_per_tok * cfg.n_held
              / cfg.num_experts)
    dense = 3 * E * cfg.intermediate_size
    n_dense = min(cfg.first_k_dense_replace, cfg.num_hidden_layers)
    per_tok = (cfg.num_hidden_layers * attention + n_dense * dense
               + (cfg.num_hidden_layers - n_dense) * sparse
               + cfg.vocab_size * E
               # the prediction module: W_eh, one sparse block, the head
               + 2 * E * E + attention + sparse + cfg.vocab_size * E)
    return 3.0 * 2.0 * per_tok * tokens
