"""GPT-2 with LM + multiple-choice heads, Flax from scratch.

Parity target: the reference's external ``GPT2DoubleHeadsModel`` from
``pytorch_transformers`` (gpt2_train.py:4-6, 262-285): token + learned
position + token-type embeddings, pre-LN causal transformer, LM head tied to
the token embedding, and a multiple-choice head that scores each candidate
from the hidden state at its ``mc_token_id`` (the last token). The reference
resizes embeddings after adding 5 special tokens
(``add_special_tokens_``, gpt2_train.py:101-112) — here ``num_added_tokens``
sizes the table up front and ``load_hf_weights`` pads the pretrained rows.

TPU-native choices: bfloat16 activations with fp32 LayerNorm/softmax
accumulation; attention is pluggable (``attn_impl``) so the same module runs
dense single-chip attention or ring attention over a ``seq`` mesh axis
(parallel/ring.py) for long-context — new scope beyond the reference, which
has no sequence parallelism (SURVEY.md §5).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import lax

NUM_SPECIAL_TOKENS = 5  # <bos> <eos> <speaker1> <speaker2> <pad>


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    num_added_tokens: int = NUM_SPECIAL_TOKENS
    layer_norm_eps: float = 1e-5
    compute_dtype: Any = jnp.bfloat16
    # rematerialize each block on the backward pass (jax.checkpoint):
    # trades recompute FLOPs for HBM — the standard long-context memory move
    remat: bool = False
    # selective-remat policy name (jax.checkpoint_policies attribute, e.g.
    # "dots_with_no_batch_dims_saveable"): save matmul outputs, recompute
    # the cheap elementwise rest — spends a little of the memory remat
    # freed to skip most of the recompute FLOPs. Empty = full remat.
    remat_policy: str = ""
    # lax.scan over the layer stack (stacked block params) instead of
    # unrolling n_layer blocks into the graph: XLA compiles ONE block body,
    # cutting compile time ~n_layer-fold for deep models — essential when
    # the whole federated round (vmap over clients x grad x microbatch scan)
    # wraps the model
    scan_layers: bool = True

    @property
    def total_vocab(self) -> int:
        return self.vocab_size + self.num_added_tokens

    @classmethod
    def small(cls, **kw) -> "GPT2Config":
        """A tiny config for tests/smoke (not a reference size)."""
        base = dict(vocab_size=256, n_positions=128, n_embd=64, n_layer=2,
                    n_head=4)
        base.update(kw)
        return cls(**base)


def gpt2_model_flops(gcfg: "GPT2Config", tokens: int, S: int) -> float:
    """Analytic fwd+bwd model FLOPs for ``tokens`` tokens of this config
    at sequence length S (2 FLOPs per MAC; backward = 2x forward):

    - block matmuls: qkv 3E^2 + attn proj E^2 + mlp 8E^2 = 12E^2 MACs
      per token per layer,
    - attention scores+values: 2*S*E MACs per token per layer (causal
      masking not discounted — consistent with common MFU practice),
    - tied LM head: E*V MACs per token.

    This is the MFU numerator for the scanned GPT-2 round: XLA's
    ``cost_analysis`` counts each ``lax.scan`` body once (no trip-count
    multiply), under-reporting the microbatch/layer-scanned round ~10x —
    so both ``bench_gpt2.py`` and the ``gpt2_train`` driver feed this
    closed form to ``telemetry/utilization.py`` instead.
    """
    E, L, V = gcfg.n_embd, gcfg.n_layer, gcfg.total_vocab
    fwd_per_tok = 2 * (12 * E * E * L + 2 * S * E * L + E * V)
    return 3.0 * fwd_per_tok * tokens


def dense_causal_attention(q, k, v, dropout_rng=None):
    """Plain causal attention: q,k,v (..., S, H, D) -> (..., S, H, D).
    fp32 softmax accumulation regardless of input dtype."""
    S = q.shape[-3]
    scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("...qhd,...khd->...hqk", q, k).astype(jnp.float32)
    logits = logits * scale
    mask = jnp.tril(jnp.ones((S, S), bool))
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("...hqk,...khd->...qhd", probs, v)


def flash_causal_attention(q, k, v, dropout_rng=None, _warn_fallback=False):
    """Fused-softmax causal attention via the TPU Pallas flash kernel
    (jax.experimental.pallas.ops.tpu.flash_attention): never materializes
    the (H, S, S) logits tensor, so attention activation memory drops from
    O(S^2) to O(S) — which is what lets the flagship GPT-2 round turn
    block remat OFF (the logits tensors were the microbatch-8 memory
    wall) and skip the ~33% backward recompute. Falls back to the dense
    path off-TPU and for sequence lengths the kernel's lane tiling cannot
    cover (S % 128 != 0); an EXPLICIT --attn_impl flash request warns on
    that fallback (``_warn_fallback``, set by resolve_attn) so users don't
    attribute dense-path memory/speed to flash (ADVICE r4)."""
    S, D = q.shape[-3], q.shape[-1]
    if jax.default_backend() != "tpu" or S % 128:
        if _warn_fallback:
            import warnings
            warnings.warn(
                "attn_impl='flash' was requested but the kernel is "
                f"ineligible here (backend={jax.default_backend()!r}, "
                f"S={S}{'' if S % 128 == 0 else ' % 128 != 0'}): running "
                "DENSE attention instead — memory/speed will be the dense "
                "path's (e.g. the PERSONA default max_seq_len=280 is "
                "unaligned; pick a multiple of 128)", stacklevel=2)
        return dense_causal_attention(q, k, v)
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        BlockSizes, flash_attention)
    lead = q.shape[:-3]
    H = q.shape[-2]

    def to4(t):  # (..., S, H, D) -> (B, H, S, D)
        return jnp.moveaxis(t.reshape((-1,) + t.shape[-3:]), -2, 1)

    # the kernel requires its block sizes to DIVIDE S; S % 128 == 0 is
    # guaranteed above, so the largest dividing power-of-two block <= 512
    # always exists (512 itself need not divide e.g. S=640)
    blk = max(b for b in (512, 256, 128) if S % b == 0)
    sizes = BlockSizes(block_q=blk, block_k_major=blk, block_k=blk,
                       block_b=1, block_q_major_dkv=blk,
                       block_k_major_dkv=blk, block_k_dkv=blk,
                       block_q_dkv=blk, block_k_major_dq=blk,
                       block_k_dq=blk, block_q_dq=blk)
    out = flash_attention(to4(q), to4(k), to4(v), causal=True,
                          sm_scale=1.0 / math.sqrt(D), block_sizes=sizes)
    return jnp.moveaxis(out, 1, -2).reshape(lead + (S, H, D))


def auto_causal_attention(q, k, v, dropout_rng=None):
    """Measured-crossover policy (scripts/bench_longctx.py, one v5e):
    dense wins below S=1024 (at S=256 the flash grid overhead exceeds
    what fusing a small softmax saves — 485 vs 410 ms on the flagship
    round); flash wins from S=1024 up and holds ~30% MFU flat where the
    dense path collapses (S=4096: 3.05x — 49.7k vs 16.3k tok/s). The
    sequence length is static at trace time, so this dispatch costs
    nothing."""
    if q.shape[-3] >= 1024:
        return flash_causal_attention(q, k, v)
    return dense_causal_attention(q, k, v)


def dense_grouped_attention(q, k, v, window=None):
    """Plain causal grouped-query attention: q (..., S, H, D), k
    (..., S, KV, D) and v (..., S, KV, Dv) with H a multiple of KV ->
    (..., S, H, Dv); scores over sqrt(D), the width of q and k, which v
    need not share (latent attention: 192 and 128). Query head
    i reads KV head i // (H / KV). ``window``: key j is visible to query
    i iff 0 <= i - j < window (None: every earlier key). fp32 softmax."""
    S, H, D = q.shape[-3:]
    KV = k.shape[-2]
    qg = q.reshape(q.shape[:-2] + (KV, H // KV, D))
    logits = jnp.einsum("...qkgd,...skd->...kgqs", qg, k).astype(
        jnp.float32) / math.sqrt(D)
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    mask = j <= i
    if window is not None:
        mask &= i - j < window
    probs = jax.nn.softmax(jnp.where(mask, logits, -1e30), axis=-1)
    out = jnp.einsum("...kgqs,...skd->...qkgd", probs.astype(v.dtype), v)
    return out.reshape(q.shape[:-1] + v.shape[-1:])


GROUPED_ATTN_BLOCK = 512
# ``auto``: the blocked kernel from this sequence length up
GROUPED_ATTN_AUTO_FROM = 1024
# the ``checkpoint_name`` of what the blocked kernel's backward rule reads
# beside q, k and v: its output and logsumexp
GROUPED_ATTN_RESIDUAL = "grouped_attn_residual"


def blocked_grouped_kernel(S, H, KV, window=None):
    """The TPU's blocked Pallas kernel
    (jax.experimental.pallas.ops.tpu.splash_attention, its multi-query
    form mapped over batch and KV heads) in the layout it reads and
    writes: q (B, KV, H / KV, S, D) already scaled by 1/sqrt(D), k
    (B, KV, S, D) and v (B, KV, S, Dv) -> (B, KV, H / KV, S, Dv); the
    kernel takes both widths from its operands. No (H, S, S) scores in HBM,
    and the blocks the mask removes entirely (above the diagonal; on a
    window layer also below the band) are never visited, forward or
    backward. Its output and logsumexp carry the name
    ``GROUPED_ATTN_RESIDUAL``, so a ``jax.checkpoint`` around the caller
    whose policy saves that name (``models/laguna.LagunaLM``) does not run
    the forward kernel a second time; outside a checkpoint the name is the
    identity."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)
    blk = GROUPED_ATTN_BLOCK
    one = (sm.CausalMask((S, S)) if window is None
           else sm.LocalMask((S, S), window_size=(window - 1, 0), offset=0))
    sizes = sk.BlockSizes(
        block_q=blk, block_kv=blk, block_kv_compute=blk, block_q_dkv=blk,
        block_kv_dkv=blk, block_kv_dkv_compute=blk, block_q_dq=blk,
        block_kv_dq=blk)
    kernel = sk.make_splash_mqa_single_device(
        sm.MultiHeadMask([one] * (H // KV)), block_sizes=sizes,
        residual_checkpoint_name=GROUPED_ATTN_RESIDUAL)
    return jax.vmap(jax.vmap(kernel))


def _blocked_kernel_runs(S):
    return jax.default_backend() == "tpu" and S % GROUPED_ATTN_BLOCK == 0


def splash_grouped_attention(q, k, v, window=None):
    """``dense_grouped_attention``'s contract through
    ``blocked_grouped_kernel``: the scale, the reshapes and the
    transposes between (..., S, H, D) and the kernel's (B, KV, G, S, D)
    are XLA's, each a pass over its tensor in HBM (a caller that holds
    q, k and the output as the projections read and write them does all
    of it in one pass a tensor: ``models/laguna.LagunaBlock``). Off the
    TPU, or where S is not a multiple of the block, the plain path,
    which names nothing."""
    S, H, D = q.shape[-3:]
    KV = k.shape[-2]
    if not _blocked_kernel_runs(S):
        return dense_grouped_attention(q, k, v, window)
    qb = (q * (1.0 / math.sqrt(D))).astype(q.dtype).reshape(
        (-1, S, KV, H // KV, D)).transpose(0, 2, 3, 1, 4)
    kb = k.reshape((-1, S, KV, D)).transpose(0, 2, 1, 3)
    vb = v.reshape((-1, S, KV, v.shape[-1])).transpose(0, 2, 1, 3)
    out = blocked_grouped_kernel(S, H, KV, window)(qb, kb, vb)
    return out.transpose(0, 3, 1, 2, 4).reshape(q.shape[:-1] + v.shape[-1:])


def auto_grouped_attention(q, k, v, window=None):
    """The blocked kernel from S = 1024 up (as ``auto_causal_attention``;
    at S = 4096 the plain path's scores are 3.2 GB a sequence)."""
    if q.shape[-3] >= GROUPED_ATTN_AUTO_FROM:
        return splash_grouped_attention(q, k, v, window)
    return dense_grouped_attention(q, k, v, window)


def runs_blocked_kernel(attn_impl, S):
    """Whether ``attn_impl`` (an entry of ``GROUPED_ATTN_IMPLS``) runs
    ``blocked_grouped_kernel`` on sequences of S here: the test
    ``splash_grouped_attention`` and ``auto_grouped_attention`` make."""
    if attn_impl is auto_grouped_attention:
        return S >= GROUPED_ATTN_AUTO_FROM and _blocked_kernel_runs(S)
    return attn_impl is splash_grouped_attention and _blocked_kernel_runs(S)


GROUPED_ATTN_IMPLS = {"dense": dense_grouped_attention,
                      "flash": splash_grouped_attention,
                      "auto": auto_grouped_attention}


ATTN_IMPLS = {"dense": dense_causal_attention,
              # explicit flash requests warn when the eligibility check
              # falls back to dense (auto's fallbacks stay silent: its
              # dense dispatch below S=1024 is the measured-crossover
              # POLICY, not a degradation)
              "flash": functools.partial(flash_causal_attention,
                                         _warn_fallback=True),
              "auto": auto_causal_attention}


def resolve_attn(name: str, grouped: bool = False) -> Callable:
    """Config-string -> attention callable (config.py --attn_impl);
    ``grouped``: the window- and GQA-aware entries ``(q, k, v, window)``."""
    impls = GROUPED_ATTN_IMPLS if grouped else ATTN_IMPLS
    try:
        return impls[name]
    except KeyError:
        raise ValueError(f"unknown attn_impl {name!r}: "
                         f"want one of {sorted(impls)}") from None


class Block(nn.Module):
    cfg: GPT2Config
    attn_impl: Callable = dense_causal_attention

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        H, D = cfg.n_head, cfg.n_embd // cfg.n_head
        dt = cfg.compute_dtype

        h = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=jnp.float32,
                         name="ln_1")(x).astype(dt)
        qkv = nn.Dense(3 * cfg.n_embd, dtype=dt, name="c_attn")(h)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        split = lambda t: t.reshape(t.shape[:-1] + (H, D))
        a = self.attn_impl(split(q), split(k), split(v))
        a = a.reshape(a.shape[:-2] + (cfg.n_embd,))
        x = x + nn.Dense(cfg.n_embd, dtype=dt, name="c_proj")(a)

        h = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=jnp.float32,
                         name="ln_2")(x).astype(dt)
        h = nn.Dense(4 * cfg.n_embd, dtype=dt, name="c_fc")(h)
        h = nn.gelu(h, approximate=True)
        x = x + nn.Dense(cfg.n_embd, dtype=dt, name="mlp_proj")(h)
        return x


class _ScanBody(nn.Module):
    """carry/out adapter so ``nn.scan`` can drive a plain x->x Block."""

    block_cls: Callable
    cfg: GPT2Config
    attn_impl: Callable

    @nn.compact
    def __call__(self, x, _):
        return self.block_cls(self.cfg, self.attn_impl, name="block")(x), None


class GPT2Backbone(nn.Module):
    """``seq_axis``/``seq_shards``: when set, the module expects to run
    INSIDE a shard_map whose mesh has that axis, with every (..., S, ...)
    input already holding only the local S/seq_shards token shard: position
    ids become global (offset by the shard index), and attention runs as
    ring attention over the axis (parallel/ring.py) — the long-context
    configuration the reference lacks entirely (SURVEY.md §5)."""

    cfg: GPT2Config
    attn_impl: Callable = dense_causal_attention
    seq_axis: Optional[str] = None
    seq_shards: int = 1

    @nn.compact
    def __call__(self, input_ids, token_type_ids=None, position_ids=None):
        cfg = self.cfg
        S = input_ids.shape[-1]
        wte = self.param("wte", nn.initializers.normal(0.02),
                         (cfg.total_vocab, cfg.n_embd))
        wpe = self.param("wpe", nn.initializers.normal(0.01),
                         (cfg.n_positions, cfg.n_embd))
        if position_ids is None:
            if self.seq_axis is not None:
                position_ids = (lax.axis_index(self.seq_axis) * S
                                + jnp.arange(S))
            else:
                position_ids = jnp.arange(S)
        x = wte[input_ids] + wpe[position_ids]
        if token_type_ids is not None:
            x = x + wte[token_type_ids]
        x = x.astype(cfg.compute_dtype)
        attn = self.attn_impl
        if self.seq_axis is not None:
            from commefficient_tpu.parallel.ring import ring_attention_inner
            attn = functools.partial(ring_attention_inner,
                                     axis_name=self.seq_axis,
                                     num_shards=self.seq_shards)
        if cfg.remat and cfg.remat_policy:
            if not hasattr(jax.checkpoint_policies, cfg.remat_policy):
                raise ValueError(
                    f"unknown remat_policy {cfg.remat_policy!r}: must be "
                    "an attribute of jax.checkpoint_policies (e.g. "
                    "dots_with_no_batch_dims_saveable)")
            block_cls = nn.remat(
                Block,
                policy=getattr(jax.checkpoint_policies, cfg.remat_policy))
        elif cfg.remat:
            block_cls = nn.remat(Block)
        else:
            block_cls = Block
        if cfg.scan_layers:
            scanned = nn.scan(
                _ScanBody, variable_axes={"params": 0},
                split_rngs={"params": True}, length=cfg.n_layer,
                metadata_params={nn.meta.PARTITION_NAME: None})
            x, _ = scanned(block_cls, cfg, attn, name="h")(x, None)
        else:
            for i in range(cfg.n_layer):
                x = block_cls(cfg, attn, name=f"h{i}")(x)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=jnp.float32,
                         name="ln_f")(x)
        return x, wte


class GPT2DoubleHeads(nn.Module):
    """LM + MC heads over the backbone.

    ``input_ids``/``token_type_ids``: (..., S); ``mc_token_ids``: (...,) index
    of the scoring token per sequence. Returns (lm_logits fp32 (..., S, V),
    mc_logits fp32 (...,)).
    """

    cfg: GPT2Config
    attn_impl: Callable = dense_causal_attention
    seq_axis: Optional[str] = None
    seq_shards: int = 1

    def __call__(self, input_ids, mc_token_ids, token_type_ids=None):
        hidden, wte, mc_logits = self.hidden_and_mc(input_ids, mc_token_ids,
                                                    token_type_ids)
        lm_logits = (hidden @ wte.T.astype(hidden.dtype)).astype(jnp.float32)
        return lm_logits, mc_logits

    @nn.compact
    def hidden_and_mc(self, input_ids, mc_token_ids, token_type_ids=None):
        """Backbone output WITHOUT the (tokens, vocab) LM projection:
        (hidden, wte, mc_logits). The chunked-CE loss path
        (losses._chunked_lm_nll) projects and softmaxes vocab logits
        chunk-by-chunk instead — at microbatch 8 the full fp32 logits
        tensor alone is ~0.8 GB and (with its cotangent) is what capped
        the GPT-2 round's microbatch size."""
        hidden, wte = GPT2Backbone(self.cfg, self.attn_impl,
                                   seq_axis=self.seq_axis,
                                   seq_shards=self.seq_shards,
                                   name="transformer")(
            input_ids, token_type_ids)
        # mc_head is bias-free: a bias on a 1-unit head shifts every
        # candidate's logit equally, which the MC softmax is invariant to —
        # and bias-freeness lets the seq-sharded branch psum LOGIT
        # contributions (linear), so the kernel's gradient flows only from
        # the owning shard's tokens instead of duplicating across the axis
        mc_head = nn.Dense(1, use_bias=False, dtype=jnp.float32,
                           name="mc_head")
        if self.seq_axis is not None:
            # mc_token_ids are GLOBAL positions; exactly one seq shard owns
            # each and contributes; the psum replicates the logits
            S = hidden.shape[-2]
            local = mc_token_ids - lax.axis_index(self.seq_axis) * S
            owned = (local >= 0) & (local < S)
            li = jnp.clip(local, 0, S - 1)
            contrib = jnp.take_along_axis(
                hidden, li[..., None, None], axis=-2)[..., 0, :]
            contrib = jnp.where(owned[..., None], contrib, 0.0)
            mc_logits = lax.psum(mc_head(contrib)[..., 0], self.seq_axis)
        else:
            mc_hidden = jnp.take_along_axis(
                hidden, mc_token_ids[..., None, None], axis=-2)[..., 0, :]
            mc_logits = mc_head(mc_hidden)[..., 0]
        return hidden, wte, mc_logits


class GPT2LMHead(nn.Module):
    """Pure LM variant (no MC head) for generic language modeling."""

    cfg: GPT2Config
    attn_impl: Callable = dense_causal_attention

    @nn.compact
    def __call__(self, input_ids, token_type_ids=None):
        hidden, wte = GPT2Backbone(self.cfg, self.attn_impl,
                                   name="transformer")(
            input_ids, token_type_ids)
        return (hidden @ wte.T.astype(hidden.dtype)).astype(jnp.float32)


# HF GPT-2 uses Conv1D: weights already (in, out) — matches Dense
_HF_OF = {("c_attn", "kernel"): "attn.c_attn.weight",
          ("c_attn", "bias"): "attn.c_attn.bias",
          ("c_proj", "kernel"): "attn.c_proj.weight",
          ("c_proj", "bias"): "attn.c_proj.bias",
          ("c_fc", "kernel"): "mlp.c_fc.weight",
          ("c_fc", "bias"): "mlp.c_fc.bias",
          ("mlp_proj", "kernel"): "mlp.c_proj.weight",
          ("mlp_proj", "bias"): "mlp.c_proj.bias",
          ("ln_1", "scale"): "ln_1.weight",
          ("ln_1", "bias"): "ln_1.bias",
          ("ln_2", "scale"): "ln_2.weight",
          ("ln_2", "bias"): "ln_2.bias"}


def load_state_dict(params, cfg: GPT2Config, sd):
    """Fill a ``GPT2DoubleHeads``/``GPT2LMHead`` param pytree from an
    HF-GPT-2-layout ``name -> ndarray`` mapping (``wte.weight``,
    ``h.<i>.attn.c_attn.weight``, ..., as produced by
    ``GPT2Model.state_dict()``), padding the embedding table for the added
    special tokens with the mean embedding — the effect of the reference's
    post-``add_special_tokens_`` resize (gpt2_train.py:101-112, 262-285).

    Pure mapping, no I/O: missing keys raise ``KeyError`` and wrong shapes
    raise ``ValueError`` loudly (a key-mapping bug must never ship silently
    — VERDICT r4 missing #3). Handles both layer layouts: ``scan_layers``
    (one ``h/block`` subtree, layer axis stacked as each leaf's leading
    dim) and unrolled ``h<i>`` blocks. Fixture-tested end to end in
    tests/test_gpt2.py (synthesized checkpoint -> forward parity)."""
    import numpy as np

    def put(subtree, leaf, value):
        want = np.shape(subtree[leaf])
        if tuple(want) != np.shape(value):
            raise ValueError(
                f"HF weight shape {np.shape(value)} does not match target "
                f"leaf {leaf!r} shape {tuple(want)}")
        subtree[leaf] = jnp.asarray(value)

    p = jax.tree.map(lambda t: t, params)  # shallow copy
    tr = p["params"]["transformer"]
    wte = np.asarray(sd["wte.weight"])
    pad = np.tile(wte.mean(0, keepdims=True),
                  (cfg.total_vocab - wte.shape[0], 1))
    put(tr, "wte", np.concatenate([wte, pad], 0))
    put(tr, "wpe", np.asarray(sd["wpe.weight"])[: cfg.n_positions])

    if cfg.scan_layers:
        b = tr["h"]["block"]
        for (mod, leaf), hf_name in _HF_OF.items():
            put(b[mod], leaf, np.stack(
                [np.asarray(sd[f"h.{i}.{hf_name}"])
                 for i in range(cfg.n_layer)]))
    else:
        for i in range(cfg.n_layer):
            b = tr[f"h{i}"]
            for (mod, leaf), hf_name in _HF_OF.items():
                put(b[mod], leaf, np.asarray(sd[f"h.{i}.{hf_name}"]))
    put(tr["ln_f"], "scale", np.asarray(sd["ln_f.weight"]))
    put(tr["ln_f"], "bias", np.asarray(sd["ln_f.bias"]))
    return p


def load_hf_weights(params, cfg: GPT2Config, checkpoint: str = "gpt2"):
    """Thin I/O adapter over ``load_state_dict``: pull a local HuggingFace
    torch GPT-2 checkpoint's state dict and map it in. Returns the updated
    pytree, or None when transformers/the checkpoint is unavailable
    (zero-egress environments fall back to random init). Only the
    import/download can fail soft — mapping errors from ``load_state_dict``
    propagate loudly."""
    try:
        from transformers import GPT2Model  # noqa: WPS433
        hf = GPT2Model.from_pretrained(checkpoint, local_files_only=True)
    except Exception:
        return None
    sd = {k: v.detach().numpy() for k, v in hf.state_dict().items()}
    return load_state_dict(params, cfg, sd)
