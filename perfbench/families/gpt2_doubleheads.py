"""Family ``gpt2_doubleheads``: GPT-2 with LM and multiple-choice heads on
PersonaChat-shaped items, driven as ``gpt2_train`` drives it
(``GPT2DoubleHeads``, ``make_gpt2_train_loss``), at the vocabulary the
configuration file states (not the 8,197 rows ``gpt2_train.main`` falls to
offline). The plain reference is ``gpt2_doubleheads_reference.py``.
"""

from __future__ import annotations

import types

DEFAULT_LR = 0.16         # gpt2_train's own default
SAMPLE_UNIT = "tok"


def parse(flags):
    from commefficient_tpu.config import parse_args
    return parse_args(flags, default_lr=DEFAULT_LR).replace(
        dataset_name="PERSONA")


def gpt2_config(cfg, config):
    import jax.numpy as jnp
    from commefficient_tpu.models.gpt2 import GPT2Config
    return GPT2Config(
        vocab_size=config["vocab_size"], n_positions=config["n_positions"],
        n_embd=config["n_embd"], n_layer=config["n_layer"],
        n_head=config["n_head"],
        num_added_tokens=config["num_added_tokens"],
        layer_norm_eps=config["layer_norm_epsilon"],
        compute_dtype=jnp.dtype(cfg.compute_dtype), remat=cfg.do_remat,
        remat_policy=cfg.remat_policy)


def build(cfg, config, seed):
    import jax
    import jax.numpy as jnp
    from commefficient_tpu.losses import make_gpt2_train_loss
    from commefficient_tpu.models.gpt2 import GPT2DoubleHeads, resolve_attn
    from perfbench.harness.datasets import make_dataset

    b = types.SimpleNamespace()
    b.gcfg = gpt2_config(cfg, config)
    b.model = GPT2DoubleHeads(b.gcfg, attn_impl=resolve_attn(cfg.attn_impl))
    S, C = cfg.max_seq_len, cfg.num_candidates
    ids = jnp.zeros((1, C, S), jnp.int32)
    b.params = jax.jit(b.model.init)(jax.random.PRNGKey(seed), ids,
                                     jnp.zeros((1, C), jnp.int32), ids)
    b.loss_fn = make_gpt2_train_loss(b.model, cfg.lm_coef, cfg.mc_coef,
                                     lm_chunk=cfg.lm_chunk)
    b.dataset = make_dataset(seed, config["data"],
                             vocab_size=config["vocab_size"], seq_len=S,
                             num_candidates=C)
    b.store_name = "PERSONA"
    b.samples_per_round = cfg.num_workers * cfg.local_batch_size * C * S
    b.coefs = (cfg.lm_coef, cfg.mc_coef)
    return b


def lr_array(built, cfg, runtime, lr):
    import jax.numpy as jnp
    return jnp.asarray(lr, jnp.float32)


def model_flops_per_round(built, cfg):
    """Analytic forward + backward operations of one round (2 per
    multiply-add, backward twice the forward, recomputation under remat
    not counted, causal masking not discounted). Copied from
    ``models/gpt2.py:gpt2_model_flops``; per token and layer 12 E^2
    (qkv 3, attention projection 1, MLP 8) + 2 S E for scores and values,
    plus E V for the tied LM head."""
    g = built.gcfg
    E, L, V, S = g.n_embd, g.n_layer, g.total_vocab, cfg.max_seq_len
    fwd_per_tok = 2 * (12 * E * E * L + 2 * S * E * L + E * V)
    return 3.0 * fwd_per_tok * built.samples_per_round


def sample_batch(built, n, seed):
    import jax
    idx = jax.random.choice(jax.random.PRNGKey(seed ^ 0x5A),
                            len(built.dataset), (n,), replace=False)
    return {k: v[idx] for k, v in built.dataset.arrays.items()}


def reference_loss(built, cfg, variant=None):
    from perfbench.families import gpt2_doubleheads_reference as ref
    g = built.gcfg
    return ref.make_loss(n_head=g.n_head, eps=g.layer_norm_eps,
                         lm_coef=built.coefs[0], mc_coef=built.coefs[1],
                         variant=variant)


REFERENCE_SAMPLE = 1       # dialogues (x candidates sequences) on the chip
