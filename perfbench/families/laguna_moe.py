"""Family ``laguna_moe``: ``models/laguna.LagunaLM`` (window and full
attention layers of different head counts, a routed expert layer that
holds a chip's share of its experts) with next-token cross-entropy on
PersonaChat-shaped sequences, driven as ``gpt2_train --model laguna``
drives it (``LagunaConfig.from_hf``, ``make_laguna_loss``). The plain
reference is ``laguna_moe_reference.py``.

A configuration file of this family holds the published ``config.json``
keys. Where it states a share, ``num_experts`` counts the experts held
here, ``num_experts_published`` is the router's width, ``experts_held``
the ids [lo, hi), and ``vocab_size`` the rows of the slice including the
generator's five special tokens (the last row is ``<pad>``).
"""

from __future__ import annotations

import types

DEFAULT_LR = 0.16         # gpt2_train's own default
SAMPLE_UNIT = "tok"


def parse(flags):
    from commefficient_tpu.config import parse_args
    return parse_args(flags, default_lr=DEFAULT_LR).replace(
        dataset_name="PERSONA", model="laguna")


def build(cfg, config, seed):
    import jax
    import jax.numpy as jnp
    from commefficient_tpu.losses import make_laguna_loss
    from commefficient_tpu.models.gpt2 import NUM_SPECIAL_TOKENS, resolve_attn
    from commefficient_tpu.models.laguna import LagunaConfig, LagunaLM
    from perfbench.harness.datasets import make_dataset

    b = types.SimpleNamespace()
    b.lcfg = LagunaConfig.from_hf(
        config, compute_dtype=jnp.dtype(cfg.compute_dtype),
        remat=cfg.do_remat)
    b.model = LagunaLM(b.lcfg, attn_impl=resolve_attn(cfg.attn_impl,
                                                      grouped=True))
    S, C = cfg.max_seq_len, cfg.num_candidates
    b.params = jax.jit(b.model.init)(jax.random.PRNGKey(seed),
                                     jnp.zeros((1, C, S), jnp.int32))
    b.pad_id = b.lcfg.vocab_size - 1
    b.loss_fn = make_laguna_loss(b.model, b.pad_id, lm_chunk=cfg.lm_chunk)
    b.dataset = make_dataset(seed, config["data"],
                             vocab_size=b.lcfg.vocab_size - NUM_SPECIAL_TOKENS,
                             seq_len=S, num_candidates=C)
    b.store_name = "PERSONA"
    b.samples_per_round = cfg.num_workers * cfg.local_batch_size * C * S
    b.config = config
    return b


def lr_array(built, cfg, runtime, lr):
    import jax.numpy as jnp
    return jnp.asarray(lr, jnp.float32)


def model_flops_per_round(built, cfg):
    """``models/laguna.laguna_model_flops``: the parameters that act on a
    position (held experts at their expected hits), window layers at
    min(S, window) keys, full layers at the causal half; pad positions
    are computed and counted; recomputation is not."""
    from commefficient_tpu.models.laguna import laguna_model_flops
    return laguna_model_flops(built.lcfg, built.samples_per_round,
                              cfg.max_seq_len)


def sample_batch(built, n, seed):
    import jax
    idx = jax.random.choice(jax.random.PRNGKey(seed ^ 0x5A),
                            len(built.dataset), (n,), replace=False)
    return {k: v[idx] for k, v in built.dataset.arrays.items()}


def reference_loss(built, cfg, variant=None):
    from perfbench.families import laguna_moe_reference as ref
    config = dict(built.config)
    config["num_experts"] = config.get("num_experts_published",
                                       config["num_experts"])
    held = config.get("experts_held", (0, config["num_experts"]))
    return ref.make_loss(config, tuple(held), built.pad_id, variant=variant)


REFERENCE_SAMPLE = 1       # sequences in the on-chip comparison


def cell_shapes(facts):
    """(configuration, S, sequences a round) of a cell of this family, from
    its files alone: what the kernels' operation and byte counts under
    ``metrics/`` start from."""
    import json
    from perfbench.harness import spec
    with open(spec.config_path(facts["config"])) as f:
        config = json.load(f)
    with open(spec.traffic_path(facts["traffic"])) as f:
        flags = list(config["flags"]) + list(json.load(f)["flags"])
    value = lambda name: int(flags[flags.index(name) + 1])
    return config, value("--max_seq_len"), (
        value("--num_workers") * value("--local_batch_size")
        * value("--num_candidates"))


def kernel_seconds(ctx, pattern):
    """Device seconds per traced round of the events whose name matches,
    on the chip where that is largest."""
    return max(sum(t[1] for t in chip["selfs"] if pattern.search(t[0]))
               for chip in ctx["trace"]["chips"].values()
               ) * 1e-9 / ctx["traced_rounds"]
