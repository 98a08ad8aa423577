"""Shared building blocks for the model zoo.

TPU-first conventions that differ from the reference's torch models
(CommEfficient/models/*):

- **NHWC layout.** Flax/XLA convolutions are fastest channel-last on TPU;
  the reference's NCHW is a CUDA/cuDNN artifact.
- **Stateless BatchNorm.** The reference's ``do_batchnorm`` path keeps
  running statistics (models/resnet9.py:17-29) which are mutable state a
  functional, vmapped-per-client federated step cannot thread (and which are
  exactly what breaks under tiny non-iid client batches — the reason the
  reference grew its Fixup/LayerNorm variants, models/resnets.py:87-97).
  ``BatchStatNorm`` normalizes with the *current* batch statistics in both
  train and eval, which under per-client vmap gives each simulated client
  its own statistics — the federated-correct semantics.
- **Scalar Fixup params** (scale/bias) are rank-0 arrays, matching the
  reference's ``nn.Parameter(torch.zeros(1))`` (models/fixup_resnet18.py:8-22)
  in effect.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import lax


def conv3x3(features: int, stride: int = 1, groups: int = 1,
            dilation: int = 1, name: Optional[str] = None) -> nn.Conv:
    return nn.Conv(features, (3, 3), strides=(stride, stride),
                   padding=dilation, feature_group_count=groups,
                   kernel_dilation=(dilation, dilation), use_bias=False,
                   name=name)


def conv1x1(features: int, stride: int = 1,
            name: Optional[str] = None) -> nn.Conv:
    return nn.Conv(features, (1, 1), strides=(stride, stride),
                   padding="VALID", use_bias=False, name=name)


def max_pool(x: jax.Array, window: int, stride: Optional[int] = None,
             padding: Any = "VALID") -> jax.Array:
    stride = stride if stride is not None else window
    return nn.max_pool(x, (window, window), (stride, stride), padding)


def global_avg_pool(x: jax.Array) -> jax.Array:
    return x.mean(axis=(1, 2))


def global_max_pool(x: jax.Array) -> jax.Array:
    return x.max(axis=(1, 2))


class BatchStatNorm(nn.Module):
    """BatchNorm without running statistics (always batch stats).

    Learned per-channel scale/bias; normalization over (N, H, W). See module
    docstring for why this replaces the reference's stateful BatchNorm2d.

    EVAL CAVEAT (measured, round 4): because eval batches normalize by
    their OWN statistics, the stat noise of a small eval batch compounds
    with depth — a 50-layer torchvision resnet50 evaluated with 8-image
    batches returns chance-level accuracy on data it fits to 94% train
    accuracy, while the same checkpoint evaluated with 256-image batches
    tracks train accuracy. Shallow stacks (ResNet-9) are robust at batch
    8. Use ``--valid_batch_size`` >= 64 with deep batch-normed models
    (cv_train warns); or pick ``norm='layer'`` for batch-size-free eval.
    """

    epsilon: float = 1e-5
    scale_init: Callable = nn.initializers.ones
    bias_init: Callable = nn.initializers.zeros

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        c = x.shape[-1]
        scale = self.param("scale", self.scale_init, (c,))
        bias = self.param("bias", self.bias_init, (c,))
        mean = x.mean(axis=(0, 1, 2), keepdims=True)
        var = x.var(axis=(0, 1, 2), keepdims=True)
        y = (x - mean) * jax.lax.rsqrt(var + self.epsilon)
        return y * scale + bias


class SpatialLayerNorm(nn.Module):
    """LayerNorm over the full (H, W, C) feature map of each example —
    the semantics of the reference's ``nn.LayerNorm((C, hw, hw))`` with
    explicit static spatial shapes (models/resnets.py:87-97). Shape-agnostic
    here because normalized axes are all non-batch axes."""

    epsilon: float = 1e-5

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        shape = x.shape[1:]
        scale = self.param("scale", nn.initializers.ones, shape)
        bias = self.param("bias", nn.initializers.zeros, shape)
        mean = x.mean(axis=(1, 2, 3), keepdims=True)
        var = x.var(axis=(1, 2, 3), keepdims=True)
        y = (x - mean) * jax.lax.rsqrt(var + self.epsilon)
        return y * scale + bias


class Scalar(nn.Module):
    """A single learned scalar, used multiplicatively or additively by the
    Fixup blocks."""

    init_value: float = 0.0

    @nn.compact
    def __call__(self) -> jax.Array:
        return self.param(
            "value", lambda _key: jnp.asarray(self.init_value, jnp.float32))


def make_norm(norm: str) -> Callable[..., nn.Module]:
    """Norm factory: 'batch' -> BatchStatNorm, 'layer' -> SpatialLayerNorm,
    'none' -> identity."""
    if norm == "batch":
        return BatchStatNorm
    if norm == "layer":
        return SpatialLayerNorm
    if norm == "none":
        return lambda **kw: (lambda x: x)  # type: ignore[return-value]
    raise ValueError(f"unknown norm {norm!r}")


def fixup_conv_init(num_layers: int) -> Callable:
    """He-init scaled by L^(-1/2) for the first conv of a Fixup block
    (reference models/fixup_resnet18.py:88-94)."""
    he = nn.initializers.variance_scaling(2.0, "fan_out", "normal")

    def init(key, shape, dtype=jnp.float32):
        return he(key, shape, dtype) * num_layers ** (-0.5)

    return init


# ---------------------------------------------------------------------------
# Decoder layers shared by the language models built from a published
# ``config.json`` (models/laguna.py, models/joyai.py).

# what such a model reports beside the loss, per microbatch (core/client.py
# averages them over a client's items): tokens per held expert over the
# sparse layers, the share of the routed slots that land on held experts,
# and the slots of held experts the dispatch could not take (always 0)
MOE_COUNTERS = ("tokens_per_expert_min", "tokens_per_expert_mean",
                "tokens_per_expert_max", "held_share", "dropped")

# how a router turns its logits into (which experts, with what weights):
#   softmax       softmax over all experts, the k largest, renormalised
#   sigmoid_bias  sigmoid scores s; the k largest of s + b, where b is the
#                 ``e_score_correction_bias`` leaf; weights s / sum of the
#                 chosen s. b enters the selection alone: no gradient
ROUTER_SCORING = ("softmax", "sigmoid_bias")


class RMSNorm(nn.Module):
    eps: float

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        xf = x.astype(jnp.float32)
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        return xf * lax.rsqrt(var + self.eps) * scale


def linear(features, dt, name):
    return nn.Dense(features, use_bias=False, dtype=dt, name=name,
                    kernel_init=nn.initializers.normal(0.02))


class SwiGLU(nn.Module):
    width: int
    out: int
    dtype: Any

    @nn.compact
    def __call__(self, x):
        g = linear(self.width, self.dtype, "gate_proj")(x)
        u = linear(self.width, self.dtype, "up_proj")(x)
        return linear(self.out, self.dtype, "down_proj")(nn.silu(g) * u)


class ExpertLayer(nn.Module):
    """Router over all experts, the held experts' part of the sum.

    Every held expert is applied to every token, as one batched product
    over the held experts, and its output weighted by what the router
    gave it there: zero where the token did not choose it. No token can be
    dropped, the shapes are static and so is the time. A dispatch that
    sorts the routed slots by expert and runs grouped products over the
    rows in use does a thirty-second of these operations when routing is
    uniform, and it was built first (PERF.md, PR 28): with a dropless
    guarantee its time follows the router, whose choices for the tokens of
    one sequence are strongly correlated, and the round's time moved by 2%
    from seed to seed. With as many experts held as a token chooses, every
    token on every held expert is also that dispatch's worst case.
    ``valid`` (the shape of xn less its last axis) marks the positions
    that are tokens; the others are given nothing and counted nowhere.

    ``cfg`` is the model's configuration (``LagunaConfig``,
    ``JoyAIConfig``): ``hidden_size``, ``moe_intermediate_size``,
    ``num_experts`` (the router's width), ``experts_held``, ``n_held``,
    ``num_experts_per_tok``, ``moe_routed_scaling_factor``,
    ``compute_dtype`` and ``router_scoring``, one of ``ROUTER_SCORING``:
    the rule is data of the configuration, everything after the choice
    is the one code path."""
    cfg: Any

    @nn.compact
    def __call__(self, xn, valid=None):
        cfg = self.cfg
        dt = cfg.compute_dtype
        E, I = cfg.hidden_size, cfg.moe_intermediate_size
        lo, hi = cfg.experts_held
        G, k = cfg.n_held, cfg.num_experts_per_tok
        lead = xn.shape[:-1]
        x = xn.reshape(-1, E)                                # (T, E) f32
        T = x.shape[0]
        init = nn.initializers.normal(0.02)
        w_r = self.param("router", init, (E, cfg.num_experts))
        w_gate = self.param("experts_gate", init, (G, E, I)).astype(dt)
        w_up = self.param("experts_up", init, (G, E, I)).astype(dt)
        w_down = self.param("experts_down", init, (G, I, E)).astype(dt)

        logits = jnp.dot(x, w_r.astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)
        if cfg.router_scoring == "softmax":
            top_p, top_e = lax.top_k(jax.nn.softmax(logits, axis=-1), k)
        elif cfg.router_scoring == "sigmoid_bias":
            bias = self.param("e_score_correction_bias",
                              nn.initializers.zeros, (cfg.num_experts,))
            scores = jax.nn.sigmoid(logits)
            top_e = lax.top_k(scores + bias, k)[1]
            top_p = jnp.take_along_axis(scores, top_e, axis=-1)
        else:
            raise ValueError(f"unknown router_scoring "
                             f"{cfg.router_scoring!r}: {ROUTER_SCORING}")
        top_w = top_p / top_p.sum(-1, keepdims=True)         # (T, k)

        held = (top_e >= lo) & (top_e < hi)
        if valid is not None:
            held &= valid.reshape(-1, 1)
        # (G, T): the router's weight of held expert g on token t, or 0
        chosen = held[None] & (top_e[None] - lo
                               == jnp.arange(G)[:, None, None])
        w = (top_w[None] * chosen).sum(-1)
        xc = x.astype(dt)
        h = (nn.silu(jnp.einsum("te,gei->gti", xc, w_gate))
             * jnp.einsum("te,gei->gti", xc, w_up))
        # weighted before the down projection, which then sums over the
        # held experts in float32: no (G, T, E) array
        y = jnp.einsum("gti,gie->te", h * w[..., None].astype(dt), w_down,
                       preferred_element_type=jnp.float32)
        y = y * cfg.moe_routed_scaling_factor
        tokens = chosen.any(-1).sum(-1)                      # (G,)
        n_held_slots = held.sum()
        counts = {"tokens": tokens.astype(jnp.float32),
                  "held_share": n_held_slots / jnp.float32(T * k),
                  # routed slots of held experts that got no product
                  "dropped": (n_held_slots - tokens.sum()).astype(
                      jnp.float32)}
        return y.reshape(lead + (E,)).astype(dt), counts


def moe_counters(per_layer):
    """The ``MOE_COUNTERS`` of one forward pass, over held experts and
    sparse layers; zeros for a model without a sparse layer."""
    if not per_layer:
        return {name: jnp.zeros(()) for name in MOE_COUNTERS}
    tokens = jnp.stack([c["tokens"] for c in per_layer])     # (layers, G)
    return {
        "tokens_per_expert_min": tokens.min(),
        "tokens_per_expert_mean": tokens.mean(),
        "tokens_per_expert_max": tokens.max(),
        "held_share": jnp.stack([c["held_share"] for c in per_layer]).mean(),
        "dropped": jnp.stack([c["dropped"] for c in per_layer]).sum(),
    }
