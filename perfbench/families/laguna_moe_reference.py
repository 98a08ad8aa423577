"""Plain reference for family ``laguna_moe``: the decoder of poolside's
Laguna-XS.2 ``config.json`` with next-token cross-entropy, in
straightforward ``jax.numpy``, float32, highest matmul precision: Python
loops over layers and over experts, dense masks, every expert applied to
every token and weighted by the router, no kernels, no sorting, no
dispatch. It reads the program's parameter pytree by its names and calls
nothing of the program.

Architecture as published: pre-RMSNorm residual blocks (eps 1e-6);
attention with ``num_attention_heads_per_layer[l]`` query heads over 8 KV
heads of 128, query head i reading KV head i // (H / 8), no biases; rotary
positions in the ``rotate_half`` convention, on full layers over the first
64 dimensions with YaRN (``transformers``' ``_compute_yarn_parameters``:
theta 500,000, factor 64, original 4,096, beta 64 / 1, cos and sin times
``attention_factor``), on window layers over all 128 with theta 10,000;
causal scores / sqrt(128), on window layers key j visible to query i iff
0 <= i - j < 512; dense SwiGLU of 8,192 in layer 0, from layer 1 a 256-way
router, top 8, scaled by 2.5, plus a shared expert; final RMSNorm; untied
output head.

Departures from the published description, all shared with the program
(they are the configuration file's ``assumed``):
- the attention output is gated per head, g = sigmoid(x W_g) with one
  scalar a head (``gating: true``; per head as in the sibling S-2.1);
- no q/k normalisation (the config has no key for one);
- ``silu`` in every SwiGLU (``hidden_act`` is absent);
- router: softmax over all 256, the top 8 renormalised to sum 1;
- the shared expert is not gated.
Departures that only make it fit beside 3.9e8 float32 parameters and
their gradient on one chip, and change no number: attention is computed in
blocks of queries against all keys and the loss in chunks of tokens, each
under ``jax.checkpoint``, as is each layer.

The expert layer is given the same share as the program
(``experts_held``): what the absent experts would add is left out.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

LM_IGNORE = -100
Q_BLOCK = 256
LOSS_CHUNK = 1024


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _inv_freq(rope, head_dim):
    """(inverse frequencies, factor on cos and sin) of one rope entry."""
    dim = int(head_dim * rope.get("partial_rotary_factor", 1.0))
    base = float(rope["rope_theta"])
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rope.get("rope_type", "default") == "default":
        return (1.0 / pos_freqs).astype(np.float32), 1.0
    assert rope["rope_type"] == "yarn", rope
    factor = float(rope["factor"])
    orig = rope["original_max_position_embeddings"]
    attention_factor = rope.get("attention_factor")
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0

    def correction_dim(num_rotations):
        return (dim * math.log(orig / (num_rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(rope.get("beta_fast", 32))), 0)
    high = min(math.ceil(correction_dim(rope.get("beta_slow", 1))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    extrapolation_factor = 1 - ramp
    inv = (1.0 / (factor * pos_freqs) * (1 - extrapolation_factor)
           + 1.0 / pos_freqs * extrapolation_factor)
    return inv.astype(np.float32), float(attention_factor)


def _rotate(x, rope, head_dim):
    """x (S, H, D) with rotary positions 0..S-1 on its leading dims."""
    inv, scale = _inv_freq(rope, head_dim)
    S = x.shape[0]
    freqs = jnp.arange(S, dtype=jnp.float32)[:, None] * jnp.asarray(inv)
    emb = jnp.concatenate([freqs, freqs], -1)              # (S, dim)
    cos, sin = jnp.cos(emb) * scale, jnp.sin(emb) * scale
    dim = emb.shape[-1]
    xr, xp = x[..., :dim], x[..., dim:]
    half = dim // 2
    rotated = jnp.concatenate([-xr[..., half:], xr[..., :half]], -1)
    xr = xr * cos[:, None, :] + rotated * sin[:, None, :]
    return jnp.concatenate([xr, xp], -1)


def _attention(q, k, v, window):
    """q (S, H, D), k and v (S, KV, D): dense mask, a block of queries at
    a time against every key."""
    S, H, D = q.shape
    KV = k.shape[1]
    k = jnp.repeat(k, H // KV, axis=1)                     # head i <- i // G
    v = jnp.repeat(v, H // KV, axis=1)
    blk = Q_BLOCK if S % Q_BLOCK == 0 else S
    j = jnp.arange(S)[None, :]

    @jax.checkpoint
    def block(args):
        qb, start = args
        i = start + jnp.arange(blk)[:, None]
        mask = j <= i
        if window is not None:
            mask = mask & (i - j < window)
        s = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(D)
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(block, (q.reshape(S // blk, blk, H, D),
                              jnp.arange(0, S, blk)))
    return out.reshape(S, H, D)


def _swiglu(x, p, mm):
    return mm(jax.nn.silu(mm(x, p["gate_proj"]["kernel"]))
              * mm(x, p["up_proj"]["kernel"]), p["down_proj"]["kernel"])


def _experts(h, p, config, experts_held, mm):
    k = config["num_experts_per_tok"]
    with jax.default_matmul_precision("highest"):
        r = h @ p["router"]                                 # float32 always
    prob = jax.nn.softmax(r, axis=-1)
    top_p, top_e = jax.lax.top_k(prob, k)
    top_w = top_p / top_p.sum(-1, keepdims=True)
    y = jnp.zeros_like(h)
    lo, hi = experts_held
    for e in range(lo, hi):
        w_e = (top_w * (top_e == e)).sum(-1)               # 0 where not chosen
        out = mm(jax.nn.silu(mm(h, p["experts_gate"][e - lo]))
                 * mm(h, p["experts_up"][e - lo]), p["experts_down"][e - lo])
        y = y + w_e[:, None] * out
    return config["moe_routed_scaling_factor"] * y


def hidden_states(params, ids, config, experts_held, dtype=jnp.float32):
    """Final-norm hidden states (S, E) of one sequence of token ids."""
    P = params["params"]
    eps, D = config["rms_norm_eps"], config["head_dim"]
    KV = config["num_key_value_heads"]
    S = ids.shape[0]

    def mm(a, b):
        return (a.astype(dtype) @ b.astype(dtype)).astype(jnp.float32)

    def layer(x, p, l):
        H = config["num_attention_heads_per_layer"][l]
        kind = config["layer_types"][l]
        rope = config["rope_parameters"][kind]
        h = _rms(x, p["input_norm"]["scale"], eps)
        q = _rotate(mm(h, p["q_proj"]["kernel"]).reshape(S, H, D), rope, D)
        k = _rotate(mm(h, p["k_proj"]["kernel"]).reshape(S, KV, D), rope, D)
        v = mm(h, p["v_proj"]["kernel"]).reshape(S, KV, D)
        window = (config["sliding_window"] if kind == "sliding_attention"
                  else None)
        o = _attention(q.astype(dtype).astype(jnp.float32),
                       k.astype(dtype).astype(jnp.float32),
                       v.astype(dtype).astype(jnp.float32), window)
        gate = jax.nn.sigmoid(mm(h, p["g_proj"]["kernel"]))   # (S, H)
        x = x + mm((o * gate[..., None]).reshape(S, H * D),
                   p["o_proj"]["kernel"])
        h = _rms(x, p["post_norm"]["scale"], eps)
        if config["mlp_layer_types"][l] == "dense":
            return x + _swiglu(h, p["mlp"], mm)
        return (x + _experts(h, p["moe"], config, experts_held, mm)
                + _swiglu(h, p["shared_expert"], mm))

    x = P["embed_tokens"][ids]
    for l in range(config["num_hidden_layers"]):
        x = jax.checkpoint(layer, static_argnums=(2,))(
            x, P[f"layers_{l}"], l)
    return _rms(x, P["norm"]["scale"], eps)


def make_loss(config, experts_held, pad_id, variant=None):
    """``loss(params, batch, mask) -> scalar``: mean over the labelled
    tokens of the valid items of -log softmax(hidden W_head^T)[next id].
    ``batch["input_ids"]`` is (items, candidates, S); pad positions carry
    no label. ``config`` holds the published keys (the router's width
    under ``num_experts``). ``variant="bf16"`` is the deliberately wrong
    reference (bfloat16 matmul operands)."""
    dtype = jnp.bfloat16 if variant == "bf16" else jnp.float32

    @jax.checkpoint
    def chunk_nll(h, head, labels):
        logits = (h.astype(dtype) @ head.T.astype(dtype)).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits)
        nll = -jnp.take_along_axis(
            logp, jnp.maximum(labels, 0)[:, None], axis=-1)[:, 0]
        return (nll * (labels != LM_IGNORE)).sum()

    def loss(params, batch, mask):
        with jax.default_matmul_precision("highest"):
            ids_all = batch["input_ids"]
            head = params["params"]["lm_head"]
            num = den = 0.0
            for n in range(ids_all.shape[0]):
                for c in range(ids_all.shape[1]):
                    ids = ids_all[n, c]
                    hidden = hidden_states(params, ids, config, experts_held,
                                           dtype)
                    labels = jnp.where(ids == pad_id, LM_IGNORE, ids)[1:]
                    hidden = hidden[:-1]
                    m = mask[n].astype(jnp.float32)
                    for s in range(0, labels.shape[0], LOSS_CHUNK):
                        num = num + m * chunk_nll(
                            hidden[s:s + LOSS_CHUNK], head,
                            labels[s:s + LOSS_CHUNK])
                    den = den + m * (labels != LM_IGNORE).sum()
            return num / jnp.maximum(den, 1.0)

    return loss
