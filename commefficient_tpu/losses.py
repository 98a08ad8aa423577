"""Loss builders satisfying the core.client loss contract.

The reference passes ``compute_loss_train`` / ``compute_loss_val`` closures
into ``FedModel`` (cv_train.py:67-83, 389); here the equivalent closures map
``(params_pytree, batch_dict, mask) -> (mean_loss, (metrics...))`` with masked
means, and own the mixed-precision policy: parameters are cast to
``compute_dtype`` (bfloat16 by default — the MXU-native dtype) for the
forward/backward while the federated vector and all server state stay fp32.
"""

from __future__ import annotations

import functools
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def _cast(tree, dtype):
    return jax.tree.map(
        lambda t: t.astype(dtype) if jnp.issubdtype(t.dtype, jnp.floating)
        else t, tree)


# Bytes of the logits' cotangents (tokens x V, in the dtype of the hidden
# states) that the chunked cross-entropy's backward keeps between two
# passes over the head's (V, E) float32 gradient: it sets how many chunks
# make a group (``_ce_groups``). Measured on a v5e (PERF.md section 6, PR
# 39): one float32 sequence of 32 chunks of 128 tokens is one group at
# V = 16,160 (265 MB by this count; the TPU compiler stores them in
# bfloat16, half that) and at V = 12,544, and each step from 4 chunks a
# group to 32 was faster; GPT-2's chunk of 8 x 2 x 128 tokens x 50,262
# (412 MB) stays a group of its own.
CE_GROUP_BYTES = 256 << 20


def _ce_groups(nch, chunk_bytes):
    """(groups, chunks a group) for ``nch`` chunks whose logits' cotangent
    is ``chunk_bytes`` each: the fewest groups that keep a group's
    cotangents within ``CE_GROUP_BYTES``, of equal size (the last one
    padded by fewer chunks than there are groups)."""
    groups = -(-nch // max(1, min(nch, CE_GROUP_BYTES // chunk_bytes)))
    return groups, -(-nch // groups)


def _ce_chunks(hidden, labels, chunk, nch):
    """The shifted stream as ``nch`` scan slices: hidden states
    (nch, B, C, chunk, E) of positions 0..S-2 and their next-token labels
    (nch, B, C, chunk), padded at the end with unlabelled positions."""
    h, lab = hidden[..., :-1, :], labels[..., 1:]
    B, C, T, E = h.shape
    pad = nch * chunk - T
    h = jnp.pad(h, ((0, 0), (0, 0), (0, pad), (0, 0)))
    lab = jnp.pad(lab, ((0, 0), (0, 0), (0, pad)), constant_values=-100)
    return (h.reshape(B, C, nch, chunk, E).transpose(2, 0, 1, 3, 4),
            lab.reshape(B, C, nch, chunk).transpose(2, 0, 1, 3))


def _ce_forward(hidden, wte, labels, m, chunk, with_acc):
    """``_chunked_lm_nll``'s results, and what its backward keeps beside
    its arguments: the per-token logsumexp (nch, B, C, chunk) and the
    clamped count of labelled tokens."""
    nch = max(1, -(-(hidden.shape[-2] - 1) // chunk))
    w = wte.astype(hidden.dtype)

    def body(carry, inp):
        num, den, hits = carry
        hc, lc = inp                                  # (B, C, chunk, ...)
        tok_valid = ((lc != -100) * m[:, None, None]).astype(jnp.float32)
        logits = (hc @ w.T).astype(jnp.float32)
        # log_softmax's own arithmetic, with its two halves kept apart
        top = logits.max(-1)
        shifted = logits - top[..., None]
        lse = jnp.log(jnp.exp(shifted).sum(-1))
        nll = lse - jnp.take_along_axis(
            shifted, jnp.maximum(lc, 0)[..., None], axis=-1)[..., 0]
        if with_acc:
            hits = hits + ((jnp.argmax(logits, -1) == lc) * tok_valid).sum()
        return ((num + (nll * tok_valid).sum(), den + tok_valid.sum(), hits),
                top + lse)

    (num, den, hits), lse = lax.scan(
        body, (jnp.zeros(()),) * 3, _ce_chunks(hidden, labels, chunk, nch))
    den = jnp.maximum(den, 1.0)
    out = (num / den, hits / den) if with_acc else num / den
    return out, lse, den


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _chunked_lm_nll(hidden, wte, labels, m, chunk, with_acc=False):
    """Shifted LM cross-entropy without ever materializing the full
    (tokens, vocab) logits: scan the sequence in ``chunk``-token slices,
    projecting + log-softmaxing each slice and accumulating the masked
    NLL sums. fp32 accumulation; the dense path's math up to sum
    reordering (tests/test_losses.py). ``wte`` is the (V, E) output
    head, tied or not. ``with_acc``: also return the share of labelled
    tokens whose largest logit is the label.

    Differentiable once, in ``hidden`` and ``wte``, by a backward of its
    own (``_ce_backward``). Kept between the passes: the arguments, the
    per-token logsumexp (4 B a token) and the count of labelled tokens;
    no logits. The backward recomputes a chunk's logits in float32, forms
    their cotangent in ``hidden``'s dtype and takes ``hidden``'s gradient
    from it chunk by chunk, but sums the head's gradient once a group of
    G chunks, in one product over the group's kept cotangents: the (V, E)
    float32 accumulator crosses HBM nch / G times a call, not nch times
    (autodiff of the scan carried it through every chunk). G follows the
    shapes (``_ce_groups``): as many chunks as keep G x B x C x chunk x V
    cotangents within ``CE_GROUP_BYTES``; G = 1 is autodiff's schedule.
    Peak memory is O(chunk x V) float32 + that budget — the enabler for
    microbatch >= 8 at the 32k-token GPT-2 round (the full fp32 logits +
    cotangent were ~1.6 GB per microbatch step)."""
    return _ce_forward(hidden, wte, labels, m, chunk, with_acc)[0]


def _ce_fwd(hidden, wte, labels, m, chunk, with_acc):
    out, lse, den = _ce_forward(hidden, wte, labels, m, chunk, with_acc)
    return out, (hidden, wte, labels, m, lse, den)


def _ce_backward(chunk, with_acc, residuals, ct):
    hidden, wte, labels, m, lse, den = residuals
    B, C, S, E = hidden.shape
    V, dt = wte.shape[0], hidden.dtype
    scale = (ct[0] if with_acc else ct) / den         # the accuracy has none
    nch = lse.shape[0]
    groups, G = _ce_groups(nch, B * C * chunk * V * dt.itemsize)
    h, lab = _ce_chunks(hidden, labels, chunk, groups * G)
    lse = jnp.pad(lse, ((0, groups * G - nch), (0, 0), (0, 0), (0, 0)))
    grouped = lambda t: t.reshape((groups, G) + t.shape[1:])
    w = wte.astype(dt)

    def chunk_body(_, inp):
        hc, lc, lse_c = inp
        weight = (lc != -100) * m[:, None, None] * scale
        logits = (hc @ w.T).astype(jnp.float32)
        p = jnp.exp(logits - lse_c[..., None])
        dlogits = ((p - (jnp.arange(V) == lc[..., None]))
                   * weight[..., None]).astype(dt)
        return None, (dlogits @ w, dlogits)

    def group_body(dw, inp):
        hg, lg, lse_g = inp
        _, (dh, dlogits) = lax.scan(chunk_body, None, (hg, lg, lse_g))
        return dw + jnp.einsum("gbctv,gbcte->ve", dlogits, hg,
                               preferred_element_type=jnp.float32), dh

    dw, dh = lax.scan(group_body, jnp.zeros((V, E), jnp.float32),
                      (grouped(h), grouped(lab), grouped(lse)))
    dh = dh.reshape((groups * G, B, C, chunk, E)).transpose(1, 2, 0, 3, 4)
    dh = dh.reshape(B, C, groups * G * chunk, E)[..., :S - 1, :]
    return (jnp.pad(dh, ((0, 0), (0, 0), (0, 1), (0, 0))),
            dw.astype(wte.dtype), None, None)


_chunked_lm_nll.defvjp(_ce_fwd, _ce_backward)


def _gpt2_losses(model, params, batch, mask, seq_axis=None, seq_shards=1,
                 lm_chunk: int = 0):
    """Shared DoubleHeads forward: (lm_nll_per_token, mc_loss, mc_acc).

    ``seq_axis``: set when the model runs seq-sharded inside a shard_map
    (ring attention). The next-token label shift then crosses shard
    boundaries — each shard fetches its right neighbour's first label
    column via ``ppermute`` — and the masked token means psum over the
    axis, so every shard computes the identical GLOBAL loss (its gradient
    contribution stays local to its tokens; the runtime sums shards).

    ``lm_chunk`` > 0 (dense path only): compute the LM loss via
    _chunked_lm_nll instead of full-vocab logits."""
    if lm_chunk > 0 and seq_axis is not None:
        # fail fast: silently falling back to full-vocab logits would OOM
        # exactly the runs that asked for the memory-bounded path
        raise ValueError(
            "lm_chunk is not supported together with a seq mesh axis yet "
            "(the seq branch computes its own cross-shard label shift on "
            "full logits); drop --lm_chunk or the seq axis")
    m = mask.astype(jnp.float32)                      # (B,)
    if lm_chunk > 0 and seq_axis is None:
        hidden, wte, mc_logits = model.apply(
            params, batch["input_ids"], batch["mc_token_ids"],
            batch["token_type_ids"], method="hidden_and_mc")
        lm_loss = _chunked_lm_nll(hidden, wte, batch["lm_labels"], m,
                                  lm_chunk)
        return (lm_loss,) + _mc_metrics(mc_logits, batch, m)

    lm_logits, mc_logits = model.apply(
        params, batch["input_ids"], batch["mc_token_ids"],
        batch["token_type_ids"])

    if seq_axis is None:
        sh_logits = lm_logits[..., :-1, :]            # (B, C, S-1, V)
        sh_labels = batch["lm_labels"][..., 1:]       # (B, C, S-1)
    else:
        # label for local position t is labels[t+1]; the last local
        # position needs the NEXT shard's first label (the global last
        # shard has no successor -> -100)
        labels = batch["lm_labels"]
        perm = [(i, (i - 1) % seq_shards) for i in range(seq_shards)]
        nxt = lax.ppermute(labels[..., :1], seq_axis, perm)
        is_last = lax.axis_index(seq_axis) == seq_shards - 1
        nxt = jnp.where(is_last, -100, nxt)
        sh_logits = lm_logits                         # (B, C, S_loc, V)
        sh_labels = jnp.concatenate([labels[..., 1:], nxt], axis=-1)
    tok_valid = ((sh_labels != -100)
                 * m[:, None, None]).astype(jnp.float32)
    safe_labels = jnp.maximum(sh_labels, 0)
    logp = jax.nn.log_softmax(sh_logits)
    tok_nll = -jnp.take_along_axis(
        logp, safe_labels[..., None], axis=-1)[..., 0]
    num, den = (tok_nll * tok_valid).sum(), tok_valid.sum()
    if seq_axis is not None:
        num = lax.psum(num, seq_axis)
        den = lax.psum(den, seq_axis)
    lm_loss = num / jnp.maximum(den, 1.0)
    return (lm_loss,) + _mc_metrics(mc_logits, batch, m)


def _mc_metrics(mc_logits, batch, m):
    mc_logp = jax.nn.log_softmax(mc_logits, axis=-1)  # (B, C)
    mc_nll = -jnp.take_along_axis(
        mc_logp, batch["mc_label"][:, None], axis=-1)[:, 0]
    denom = jnp.maximum(m.sum(), 1.0)
    mc_loss = (mc_nll * m).sum() / denom
    acc = (((jnp.argmax(mc_logits, -1) == batch["mc_label"]) * m).sum()
           / denom)
    return mc_loss, acc


def make_gpt2_train_loss(model, lm_coef: float = 1.0, mc_coef: float = 1.0,
                         seq_axis=None, seq_shards: int = 1,
                         lm_chunk: int = 0):
    """DoubleHeads training loss (reference gpt2_train.py:88-99):
    ``lm_coef * lm_loss + mc_coef * mc_loss`` where the LM loss is shifted
    cross-entropy over the gold candidate's reply tokens and the MC loss is
    cross-entropy over candidates. Metrics: (mc accuracy,). Pass
    ``seq_axis``/``seq_shards`` matching the model's when it runs
    seq-sharded; ``lm_chunk`` > 0 enables the memory-bounded chunked LM
    cross-entropy (dense path)."""

    def loss_fn(params, batch, mask):
        lm_loss, mc_loss, acc = _gpt2_losses(
            model, params, batch, mask, seq_axis=seq_axis,
            seq_shards=seq_shards, lm_chunk=lm_chunk)
        return lm_coef * lm_loss + mc_coef * mc_loss, (acc,)

    return loss_fn


def make_gpt2_val_loss(model, seq_axis=None, seq_shards: int = 1,
                       lm_chunk: int = 0):
    """Validation metrics (reference test_gpt2, gpt2_train.py:55-86):
    per-token LM NLL (=> ppl on the host) and MC accuracy."""

    def loss_fn(params, batch, mask):
        lm_loss, _, acc = _gpt2_losses(
            model, params, batch, mask, seq_axis=seq_axis,
            seq_shards=seq_shards, lm_chunk=lm_chunk)
        return lm_loss, (acc,)

    return loss_fn


def make_laguna_loss(model, pad_id: int, lm_chunk: int = 128,
                     counters: bool = True):
    """Next-token cross-entropy for ``models/laguna.LagunaLM`` on the
    ``core/client`` contract: labels are ``input_ids`` shifted by one, pad
    positions carry none; the vocabulary projection goes through the same
    chunked scan as GPT-2's, with the model's untied head. ``batch``
    leaves are (items, candidates, S) as PERSONA packs them; every
    candidate is a sequence. Metrics: (token accuracy,) and, with
    ``counters`` (the training loss), the expert layers'
    ``models.laguna.MOE_COUNTERS`` after it."""
    from commefficient_tpu.models.laguna import MOE_COUNTERS

    def loss_fn(params, batch, mask):
        ids = batch["input_ids"]
        hidden, head, moe = model.apply(params, ids, ids != pad_id)
        labels = jnp.where(ids == pad_id, -100, ids)
        chunk = lm_chunk if lm_chunk > 0 else ids.shape[-1]
        loss, acc = _chunked_lm_nll(hidden, head, labels,
                                    mask.astype(jnp.float32), chunk,
                                    with_acc=True)
        extra = tuple(lax.stop_gradient(moe[k]) for k in MOE_COUNTERS)
        return loss, (acc,) + (extra if counters else ())

    # how many results the round carries for this loss (FedRuntime reads it)
    loss_fn.num_results = 2 + (len(MOE_COUNTERS) if counters else 0)
    return loss_fn


def make_joyai_loss(model, pad_id: int, lm_chunk: int = 128,
                    mtp_coef: float = 0.3, counters: bool = True):
    """``L_main + mtp_coef x L_mtp`` for ``models/joyai.JoyAILM`` on the
    ``core/client`` contract, each a mean cross-entropy over its non-pad
    labels through the same chunked scan and the one untied head: the
    main stream's labels are ``input_ids`` shifted by one, the prediction
    module's by two (its stream has S - 2 labelled positions). ``batch``
    as for ``make_laguna_loss``. With ``counters`` (the training loss)
    the results are ``(loss, (token accuracy, main_nll, mtp_nll) +
    MOE_COUNTERS)``: ``models.joyai.ROUND_COUNTERS`` after the accuracy.
    Without (validation): the main stream's cross-entropy alone, which
    is what a perplexity is of, and its accuracy."""
    from commefficient_tpu.models.joyai import ROUND_COUNTERS
    from commefficient_tpu.telemetry.profiling import phase

    def loss_fn(params, batch, mask):
        ids = batch["input_ids"]
        hidden, hidden_mtp, head, moe = model.apply(params, ids,
                                                    ids != pad_id)
        labels = jnp.where(ids == pad_id, -100, ids)
        m = mask.astype(jnp.float32)
        chunk = lm_chunk if lm_chunk > 0 else ids.shape[-1]
        main, acc = _chunked_lm_nll(hidden, head, labels, m, chunk,
                                    with_acc=True)
        if not counters:
            return main, (acc,)
        with phase("fed_mtp"):
            # position i of the module's stream against token i + 2
            mtp = _chunked_lm_nll(hidden_mtp[..., :-1, :], head,
                                  labels[..., 1:], m, chunk)
        named = {**moe, "main_nll": main, "mtp_nll": mtp}
        return main + mtp_coef * mtp, (acc,) + tuple(
            lax.stop_gradient(named[k]) for k in ROUND_COUNTERS)

    loss_fn.num_results = 2 + (len(ROUND_COUNTERS) if counters else 0)
    return loss_fn


def make_cv_loss(model, compute_dtype: str = "bfloat16",
                 frozen_params=None) -> Callable:
    """Masked softmax cross-entropy + top-1 accuracy (reference
    compute_loss_train/val, cv_train.py:67-83).

    ``frozen_params``: optional pytree of non-trained parameters (finetune
    mode — the reference shrinks the federated vector to just the trainable
    head, cv_train.py:377-384); merged under the trained params at apply time.
    """
    dtype = jnp.dtype(compute_dtype)

    def loss_fn(params, batch, mask) -> Tuple[jax.Array, Tuple[jax.Array]]:
        if frozen_params is not None:
            params = {"params": {**frozen_params["params"],
                                 **params["params"]}}
        x = batch["image"].astype(dtype)
        logits = model.apply(_cast(params, dtype), x).astype(jnp.float32)
        labels = batch["target"]
        logp = jax.nn.log_softmax(logits)
        ce = -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
        m = mask.astype(jnp.float32)
        denom = jnp.maximum(m.sum(), 1.0)
        loss = (ce * m).sum() / denom
        acc = ((jnp.argmax(logits, axis=1) == labels) * m).sum() / denom
        return loss, (acc,)

    return loss_fn
