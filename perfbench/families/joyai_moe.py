"""Family ``joyai_moe``: ``models/joyai.JoyAILM`` (latent attention, a
sigmoid router with a selection bias over a routed expert layer that
holds a chip's share of its experts, one multi-token-prediction module)
with ``L_main + 0.3 L_mtp`` on PersonaChat-shaped sequences, driven as
``gpt2_train --model joyai`` drives it (``JoyAIConfig.from_hf``,
``make_joyai_loss``). The plain reference is ``joyai_moe_reference.py``.

A configuration file of this family holds the published ``config.json``
keys. Where it states a share, ``n_routed_experts`` counts the experts
held here, ``n_routed_experts_published`` is the router's width,
``experts_held`` the ids [lo, hi), and ``vocab_size`` the rows of the
slice including the generator's five special tokens (the last row is
``<pad>``).
"""

from __future__ import annotations

import types

# what a cell's files say of its shapes, and a kernel's device seconds in
# a trace: the same for every family whose data is sequences
from perfbench.families.laguna_moe import (cell_shapes,  # noqa: F401
                                           kernel_seconds, lr_array,
                                           sample_batch)

DEFAULT_LR = 0.16         # gpt2_train's own default
SAMPLE_UNIT = "tok"
REFERENCE_SAMPLE = 1      # sequences in the on-chip comparison


def parse(flags):
    from commefficient_tpu.config import parse_args
    return parse_args(flags, default_lr=DEFAULT_LR).replace(
        dataset_name="PERSONA", model="joyai")


def build(cfg, config, seed):
    import jax
    import jax.numpy as jnp
    from commefficient_tpu.losses import make_joyai_loss
    from commefficient_tpu.models.gpt2 import NUM_SPECIAL_TOKENS, resolve_attn
    from commefficient_tpu.models.joyai import JoyAIConfig, JoyAILM
    from perfbench.harness.datasets import make_dataset

    b = types.SimpleNamespace()
    b.lcfg = JoyAIConfig.from_hf(
        config, compute_dtype=jnp.dtype(cfg.compute_dtype),
        remat=cfg.do_remat)
    b.model = JoyAILM(b.lcfg, attn_impl=resolve_attn(cfg.attn_impl,
                                                     grouped=True))
    S, C = cfg.max_seq_len, cfg.num_candidates
    b.params = jax.jit(b.model.init)(jax.random.PRNGKey(seed),
                                     jnp.zeros((1, C, S), jnp.int32))
    b.pad_id = b.lcfg.vocab_size - 1
    b.loss_fn = make_joyai_loss(b.model, b.pad_id, lm_chunk=cfg.lm_chunk)
    b.dataset = make_dataset(seed, config["data"],
                             vocab_size=b.lcfg.vocab_size - NUM_SPECIAL_TOKENS,
                             seq_len=S, num_candidates=C)
    b.store_name = "PERSONA"
    b.samples_per_round = cfg.num_workers * cfg.local_batch_size * C * S
    b.config = config
    return b


def model_flops_per_round(built, cfg):
    """``models/joyai.joyai_model_flops``: the parameters that act on a
    position (held experts at their expected hits), scores at 192 and
    values at 128 a head over the causal half in all six blocks, the head
    twice; pad positions are computed and counted; recomputation is
    not."""
    from commefficient_tpu.models.joyai import joyai_model_flops
    return joyai_model_flops(built.lcfg, built.samples_per_round,
                             cfg.max_seq_len)


def reference_loss(built, cfg, variant=None):
    from perfbench.families import joyai_moe_reference as ref
    config = dict(built.config)
    config["n_routed_experts"] = config.get("n_routed_experts_published",
                                            config["n_routed_experts"])
    held = config.get("experts_held", (0, config["n_routed_experts"]))
    return ref.make_loss(config, tuple(held), built.pad_id, variant=variant)
