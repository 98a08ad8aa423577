"""Plain reference for family ``gpt2_doubleheads``: GPT-2 (Radford et al.
2019; the public ``gpt2`` ``config.json``) with the DoubleHeads loss of the
reference's ``gpt2_train.py`` (Wolf et al., TransferTransfo: LM cross-entropy
on the gold reply plus multiple-choice cross-entropy over the candidates),
in straightforward ``jax.numpy``, float32, highest matmul precision: a Python
loop over layers, full (tokens x vocabulary) logits, no scan, no remat, no
chunked cross-entropy, no microbatching, no kernels.

Architecture as published: learned token and position embeddings (token-type
embeddings share the token table, as in ``GPT2DoubleHeadsModel``), pre-LN
blocks, causal softmax attention scaled by 1/sqrt(head size), MLP 4x with the
tanh GELU (``gelu_new``), final LayerNorm, LM head tied to the token table.
Departure shared with the program because it defines the parameters: the
multiple-choice head is one bias-free linear unit on the hidden state at
``mc_token_ids`` (the MC softmax is invariant to a bias).

It reads the program's parameter pytree by its names (layers stacked on a
leading axis under ``transformer/h/block``); it calls nothing of the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

LM_IGNORE = -100


def _ln(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def hidden_states(params, input_ids, token_type_ids, n_head, eps, dtype):
    tr = params["params"]["transformer"]
    wte, wpe = tr["wte"], tr["wpe"]
    S = input_ids.shape[-1]
    x = wte[input_ids] + wpe[jnp.arange(S)] + wte[token_type_ids]
    blocks = tr["h"]["block"]
    n_layer = blocks["c_attn"]["kernel"].shape[0]
    E = x.shape[-1]
    D = E // n_head
    causal = jnp.tril(jnp.ones((S, S), bool))

    def dense(h, p):
        return (h.astype(dtype) @ p["kernel"].astype(dtype)
                ).astype(jnp.float32) + p["bias"]

    for l in range(n_layer):
        p = jax.tree.map(lambda t: t[l], blocks)
        h = _ln(x, p["ln_1"], eps)
        q, k, v = jnp.split(dense(h, p["c_attn"]), 3, axis=-1)
        heads = lambda t: t.reshape(t.shape[:-1] + (n_head, D))
        q, k, v = heads(q), heads(k), heads(v)
        att = jnp.einsum("...qhd,...khd->...hqk", q.astype(dtype),
                         k.astype(dtype)).astype(jnp.float32)
        att = jnp.where(causal, att / math.sqrt(D), -jnp.inf)
        att = jax.nn.softmax(att, axis=-1)
        a = jnp.einsum("...hqk,...khd->...qhd", att.astype(dtype),
                       v.astype(dtype)).astype(jnp.float32)
        x = x + dense(a.reshape(a.shape[:-2] + (E,)), p["c_proj"])
        h = _ln(x, p["ln_2"], eps)
        x = x + dense(_gelu_new(dense(h, p["c_fc"])), p["mlp_proj"])
    return _ln(x, tr["ln_f"], eps), wte


def make_loss(n_head, eps, lm_coef=1.0, mc_coef=1.0, variant=None):
    """``loss(params, batch, mask) -> scalar``. ``batch`` leaves are
    (items, candidates, S) token arrays, ``mc_token_ids`` (items,
    candidates) and ``mc_label`` (items,). ``variant="bf16"`` is the
    deliberately wrong reference of the tests (bfloat16 matmuls)."""
    dtype = jnp.bfloat16 if variant == "bf16" else jnp.float32

    def loss(params, batch, mask):
        with jax.default_matmul_precision("highest"):
            m = mask.astype(jnp.float32)
            hidden, wte = hidden_states(
                params, batch["input_ids"], batch["token_type_ids"],
                n_head, eps, dtype)
            logits = (hidden.astype(dtype) @ wte.T.astype(dtype)
                      ).astype(jnp.float32)
            logp = jax.nn.log_softmax(logits[..., :-1, :])
            labels = batch["lm_labels"][..., 1:]
            valid = (labels != LM_IGNORE) * m[:, None, None]
            nll = -jnp.take_along_axis(
                logp, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
            lm = (nll * valid).sum() / jnp.maximum(valid.sum(), 1.0)
            mc_h = jnp.take_along_axis(
                hidden, batch["mc_token_ids"][..., None, None],
                axis=-2)[..., 0, :]
            mc_logits = (mc_h @ params["params"]["mc_head"]["kernel"]
                         )[..., 0]
            mc_logp = jax.nn.log_softmax(mc_logits, axis=-1)
            mc_nll = -jnp.take_along_axis(
                mc_logp, batch["mc_label"][:, None], axis=-1)[:, 0]
            mc = (mc_nll * m).sum() / jnp.maximum(m.sum(), 1.0)
            return lm_coef * lm + mc_coef * mc

    return loss
