"""Plain reference for family ``joyai_moe``: the decoder of
jdopensource's JoyAI-LLM-Flash ``config.json`` (the DeepSeek-V3 family's
key set) with its next-token and multi-token-prediction cross-entropies,
in straightforward ``jax.numpy``, float32, highest matmul precision:
Python loops over layers and over experts, dense masks, every expert
applied to every token and weighted by the router, no kernels, no
sorting, no dispatch. It reads the program's parameter pytree by its
names and calls nothing of the program.

Architecture as published: pre-RMSNorm residual blocks (eps 1e-6), no
biases, ``silu``. Latent attention in every layer, H = 32 heads:
``c_q = RMSNorm(x W_qa)`` (1536), ``q = c_q W_qb`` -> (H, 192) = [q_nope
128 ; q_rope 64]; ``[c_kv (512) ; k_rope (64)] = x W_kva``, ``c_kv =
RMSNorm(c_kv)``, ``c_kv W_kvb`` -> (H, 256) = [k_nope 128 ; v 128]; rotary
on q_rope of every head and on k_rope, one vector a position shared by
all heads, the pairs (2i, 2i+1) rotated by ``pos x 32,000,000^(-2i/64)``
(``rope_interleave: true``, ``rope_scaling: null``); ``k = [k_nope ;
k_rope]``; causal scores / sqrt(192), softmax, values 128 wide;
``x += concat(o) W_o``. Layer 0: SwiGLU of 7,168. From layer 1: scores
``s = sigmoid(x W_r)`` over all 256 experts, the 8 of largest ``s + b``
(``e_score_correction_bias``; ``n_group = topk_group = 1``: no group
limit) chosen, weighted by ``s_e / sum of the chosen s``
(``norm_topk_prob``) times 2.5 (``routed_scaling_factor``), experts
SwiGLU of 768, plus one ungated shared expert. Final RMSNorm, untied
head. One multi-token-prediction module (``num_nextn_predict_layers: 1``;
DeepSeek-V3 technical report, arXiv:2412.19437, section 2.2): ``h'_i =
[RMSNorm_h(h_i) ; RMSNorm_e(Emb(t_{i+1}))] W_eh``, one sparse block of the
kind above, its own final norm, the main model's head, trained on
t_{i+2}; ``L = L_main + 0.3 L_mtp``, each a mean over its own labels.

Set here because the file does not say (the configuration file's
``assumed``, the same in the program): lambda = 0.3; h_i is taken before
the final norm; h's half of W_eh's input comes first; the bias is a leaf
that enters the selection alone (no gradient, no balancing update); no
auxiliary loss.
Departures that only make it fit beside 4.9e8 float32 parameters and
their gradient on one chip, and change no number: attention is computed
in blocks of queries against all keys and the losses in chunks of tokens,
each under ``jax.checkpoint``, as is each layer.

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
MTP_COEF = 0.3


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rotate_pairs(x, theta):
    """x (S, H, R): the pair (2i, 2i+1) of position p rotated by the angle
    ``p x theta^(-2i/R)``, in place (interleaved, as published)."""
    S, R = x.shape[0], x.shape[-1]
    inv = (1.0 / float(theta) ** (np.arange(0, R, 2, dtype=np.float64) / R)
           ).astype(np.float32)
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] * jnp.asarray(inv)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], -1)
    return out.reshape(x.shape)


def _attention(q, k, v):
    """q and k (S, H, D), v (S, H, Dv): dense causal mask, a block of
    queries at a time against every key; scores / sqrt(D)."""
    S, H, D = q.shape
    blk = Q_BLOCK if S % Q_BLOCK == 0 else S
    j = jnp.arange(S)[None, :]

    @jax.checkpoint
    def block(args):
        qb, start = args
        i = start + jnp.arange(blk)[:, None]
        s = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(D)
        p = jax.nn.softmax(jnp.where((j <= i)[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(block, (q.reshape(S // blk, blk, H, D),
                              jnp.arange(0, S, blk)))
    return out.reshape(S, H, v.shape[-1])


def _swiglu(x, p, mm):
    return mm(jax.nn.silu(mm(x, p["gate_proj"]["kernel"]))
              * mm(x, p["up_proj"]["kernel"]), p["down_proj"]["kernel"])


def _experts(h, p, config, experts_held, mm, variant=None):
    """The held experts' part of one routed layer on h (T, E).
    ``variant="bias_in_weights"`` is a deliberately wrong one: the
    selection bias also enters the weights."""
    k = config["num_experts_per_tok"]
    with jax.default_matmul_precision("highest"):
        r = h @ p["router"]                                 # float32 always
    score = jax.nn.sigmoid(r)
    biased = score + p["e_score_correction_bias"]
    top_e = jax.lax.top_k(biased, k)[1]
    top_s = jnp.take_along_axis(
        biased if variant == "bias_in_weights" else score, top_e, -1)
    top_w = top_s / top_s.sum(-1, keepdims=True)
    y = jnp.zeros_like(h)
    lo, hi = experts_held
    for e in range(lo, hi):
        w_e = (top_w * (top_e == e)).sum(-1)               # 0 where not chosen
        out = mm(jax.nn.silu(mm(h, p["experts_gate"][e - lo]))
                 * mm(h, p["experts_up"][e - lo]), p["experts_down"][e - lo])
        y = y + w_e[:, None] * out
    return config["routed_scaling_factor"] * y


def _block(x, p, config, experts_held, sparse, dtype, variant=None):
    """One block on x (S, E) float32."""
    eps, H = config["rms_norm_eps"], config["num_attention_heads"]
    dn, dr = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    dv, rank = config["v_head_dim"], config["kv_lora_rank"]
    S = x.shape[0]

    def mm(a, b):
        return (a.astype(dtype) @ b.astype(dtype)).astype(jnp.float32)

    rounded = lambda t: t.astype(dtype).astype(jnp.float32)
    h = _rms(x, p["input_norm"]["scale"], eps)
    c_q = _rms(mm(h, p["q_a_proj"]["kernel"]),
               p["q_a_layernorm"]["scale"], eps)
    q = mm(c_q, p["q_b_proj"]["kernel"]).reshape(S, H, dn + dr)
    kv_a = mm(h, p["kv_a_proj_with_mqa"]["kernel"])
    c_kv = _rms(kv_a[:, :rank], p["kv_a_layernorm"]["scale"], eps)
    kv = mm(c_kv, p["kv_b_proj"]["kernel"]).reshape(S, H, dn + dv)
    theta = config["rope_theta"]
    q_rope = _rotate_pairs(q[..., dn:], theta)
    k_rope = _rotate_pairs(kv_a[:, None, rank:], theta)     # (S, 1, dr)
    q = jnp.concatenate([q[..., :dn], q_rope], -1)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_rope, (S, H, dr))], -1)
    o = _attention(rounded(q), rounded(k), rounded(kv[..., dn:]))
    x = x + mm(o.reshape(S, H * dv), p["o_proj"]["kernel"])
    h = _rms(x, p["post_norm"]["scale"], eps)
    if not sparse:
        return x + _swiglu(h, p["mlp"], mm)
    return (x + _experts(h, p["moe"], config, experts_held, mm, variant)
            + _swiglu(h, p["shared_expert"], mm))


def hidden_states(params, ids, config, experts_held, dtype=jnp.float32,
                  variant=None):
    """(final-norm hidden states (S, E) of the main stream, the
    prediction module's (S, E) after its own norm) of one sequence of
    token ids. Position i of the second is built from h_i and token i+1;
    its last position is given the last token again and means nothing."""
    P = params["params"]
    eps = config["rms_norm_eps"]
    embed = P["embed_tokens"]

    def layer(x, p, sparse):
        return _block(x, p, config, experts_held, sparse, dtype, variant)

    x = embed[ids]
    for l in range(config["num_hidden_layers"]):
        x = jax.checkpoint(layer, static_argnums=(2,))(
            x, P[f"layers_{l}"], l >= config["first_k_dense_replace"])
    hidden = _rms(x, P["norm"]["scale"], eps)

    M = P["mtp"]
    nxt = jnp.concatenate([ids[1:], ids[-1:]])
    both = jnp.concatenate([_rms(x, M["hnorm"]["scale"], eps),
                            _rms(embed[nxt], M["enorm"]["scale"], eps)], -1)
    xm = (both.astype(dtype) @ M["eh_proj"]["kernel"].astype(dtype)).astype(
        jnp.float32)
    xm = jax.checkpoint(layer, static_argnums=(2,))(xm, M["layer"], True)
    return hidden, _rms(xm, M["norm"]["scale"], eps)


def make_loss(config, experts_held, pad_id, variant=None):
    """``loss(params, batch, mask) -> scalar``: ``L_main + 0.3 L_mtp``,
    each the mean over its labelled tokens of the valid items of
    -log softmax(hidden W_head^T)[label]: the main stream's label at
    position i is token i+1, the prediction module's token i+2; pad
    positions carry none. ``batch["input_ids"]`` is (items, candidates,
    S). ``config`` holds the published keys (the router's width under
    ``n_routed_experts``). Deliberately wrong variants: ``"bf16"``
    (bfloat16 matmul operands), ``"no_mtp"`` (the second term left out),
    ``"bias_in_weights"`` (see ``_experts``)."""
    dtype = jnp.bfloat16 if variant == "bf16" else jnp.float32

    @jax.checkpoint
    def chunk_nll(h, head, labels):
        logits = (h.astype(dtype) @ head.T.astype(dtype)).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits)
        nll = -jnp.take_along_axis(
            logp, jnp.maximum(labels, 0)[:, None], axis=-1)[:, 0]
        return (nll * (labels != LM_IGNORE)).sum()

    def stream(hidden, head, labels):
        """(sum of -log p, labelled positions) of hidden[i] on labels[i]."""
        num = 0.0
        for s in range(0, labels.shape[0], LOSS_CHUNK):
            num = num + chunk_nll(hidden[s:s + LOSS_CHUNK], head,
                                  labels[s:s + LOSS_CHUNK])
        return num, (labels != LM_IGNORE).sum()

    def loss(params, batch, mask):
        with jax.default_matmul_precision("highest"):
            ids_all = batch["input_ids"]
            head = params["params"]["lm_head"]
            sums = [0.0, 0.0, 0.0, 0.0]      # main num, den; mtp num, den
            for n in range(ids_all.shape[0]):
                for c in range(ids_all.shape[1]):
                    ids = ids_all[n, c]
                    hidden, hidden_mtp = hidden_states(
                        params, ids, config, experts_held, dtype, variant)
                    labels = jnp.where(ids == pad_id, LM_IGNORE, ids)
                    m = mask[n].astype(jnp.float32)
                    parts = (stream(hidden[:-1], head, labels[1:])
                             + stream(hidden_mtp[:-2], head, labels[2:]))
                    sums = [s + m * part for s, part in zip(sums, parts)]
            main = sums[0] / jnp.maximum(sums[1], 1.0)
            if variant == "no_mtp":
                return main
            return main + MTP_COEF * sums[2] / jnp.maximum(sums[3], 1.0)

    return loss
