"""GPT-2 DoubleHeads on federated PersonaChat.

Parity target: reference CommEfficient/gpt2_train.py (365 LoC) — tokenizer +
DoubleHeads model with 5 added special tokens, plain SGD(lr=1) wrapped in the
federated optimizer ("HAVE TO USE SGD FOR FED", gpt2_train.py:287), linear
LR decay to zero (302-307), the same epoch/round loop as the CV driver, and
final perplexity/accuracy evaluation (test_gpt2, 149).

Run:  python -m commefficient_tpu.gpt2_train --mode sketch \
          --error_type virtual --num_workers 4 --local_batch_size -1 ...

``--model laguna --model_checkpoint <config.json>`` trains
``models/laguna.LagunaLM`` (window and full attention, a routed expert
layer that holds a share of its experts) from a ``config.json`` in the
published key set instead: next-token cross-entropy on every non-pad token
of the same PersonaChat packing, through the same runtime, store, sampler
and pipeline. ``--model joyai --model_checkpoint <config.json>`` trains
``models/joyai.JoyAILM`` the same way (latent attention, a sigmoid router
with a selection bias, a multi-token-prediction module whose loss is added
at 0.3). ``CONFIG_MODELS`` is the table of such models.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from commefficient_tpu.config import (FedConfig,
                                      enable_compilation_cache, parse_args)
from commefficient_tpu.core import FedRuntime, PreemptGuard
from commefficient_tpu.cv_train import (
    build_mesh,
    finish_run,
    run_validation,
    setup_checkpointing,
    train as shared_train,
)
from commefficient_tpu.data.fed_persona import (FedPERSONA, HashTokenizer,
                                                get_tokenizer)
from commefficient_tpu.losses import (make_gpt2_train_loss,
                                      make_gpt2_val_loss, make_joyai_loss,
                                      make_laguna_loss)
from commefficient_tpu.models import joyai as joyai_lib
from commefficient_tpu.models import laguna as laguna_lib
from commefficient_tpu.models.gpt2 import (
    NUM_SPECIAL_TOKENS,
    GPT2Config,
    GPT2DoubleHeads,
    gpt2_model_flops,
    load_hf_weights,
    resolve_attn,
)
from commefficient_tpu.utils import TableLogger, TSVLogger, Timer


# batch leaf -> index of its sequence dimension in the per-round arrays
# (leaves mapped to None replicate over the seq axis); leaf shapes are
# (W, B, num_candidates, S) for token arrays
PERSONA_SEQ_SPEC = {"input_ids": 3, "token_type_ids": 3, "lm_labels": 3,
                    "mc_token_ids": None, "mc_label": None}


def build_gpt2(cfg: FedConfig, tokenizer):
    n_vocab = len(tokenizer)
    if cfg.do_test:
        gcfg = GPT2Config.small(vocab_size=n_vocab - 5,
                                remat=cfg.do_remat,
                                remat_policy=cfg.remat_policy)
    else:
        gcfg = GPT2Config(vocab_size=n_vocab - 5,
                          compute_dtype=jnp.dtype(cfg.compute_dtype),
                          remat=cfg.do_remat,
                          remat_policy=cfg.remat_policy)
    return GPT2DoubleHeads(gcfg, attn_impl=resolve_attn(cfg.attn_impl)), gcfg


@dataclasses.dataclass(frozen=True)
class ConfigModel:
    """One ``--model`` that is built from ``--model_checkpoint``, a
    ``config.json`` in its published key set: the configuration class
    (``from_json``; it may read a chip's share), the module, the loss
    builder ``(model, pad_id, lm_chunk, counters)``, what the training
    loss reports after (loss, accuracy), and the model's operations
    ``(config, tokens, S)``."""
    config: type
    module: type
    make_loss: Callable
    counters: Tuple[str, ...]
    flops: Callable


CONFIG_MODELS = {
    "laguna": ConfigModel(laguna_lib.LagunaConfig, laguna_lib.LagunaLM,
                          make_laguna_loss, laguna_lib.MOE_COUNTERS,
                          laguna_lib.laguna_model_flops),
    "joyai": ConfigModel(joyai_lib.JoyAIConfig, joyai_lib.JoyAILM,
                         make_joyai_loss, joyai_lib.ROUND_COUNTERS,
                         joyai_lib.joyai_model_flops),
}


def build_config_model(cfg: FedConfig):
    """(model, its configuration, tokenizer) of a ``CONFIG_MODELS`` entry
    from ``--model_checkpoint``. No public tokenizer is at hand offline:
    words hash into the configuration's vocabulary less the five special
    tokens, which take its last rows."""
    entry = CONFIG_MODELS[cfg.model]
    mcfg = entry.config.from_json(
        cfg.model_checkpoint, compute_dtype=jnp.dtype(cfg.compute_dtype),
        remat=cfg.do_remat)
    model = entry.module(
        mcfg, attn_impl=resolve_attn(cfg.attn_impl, grouped=True))
    return model, mcfg, HashTokenizer(mcfg.vocab_size - NUM_SPECIAL_TOKENS)


def make_gpt2_schedule(cfg: FedConfig):
    """Reference GPT-2 LR trajectory: LINEAR lr -> 0 from step 0
    (gpt2_train.py:302-307) — not the CV triangular ramp. ``--lr_warmup``
    (TPU-native opt-in; the reference has no GPT-2 warmup) prepends a
    linear 0 -> lr ramp peaking at ``--pivot_epoch``, giving GPT-2 the CV
    driver's triangular shape — a stabilizer arm of the round-5 sketch
    study (from-scratch GPT-2 under plain SGD diverges unclipped;
    warmup is the classical alternative to clipping)."""
    from commefficient_tpu.utils import PiecewiseLinear
    lr0 = cfg.lr_scale if cfg.lr_scale is not None else 0.16
    if cfg.lr_warmup:
        pivot = min(float(cfg.pivot_epoch), float(cfg.num_epochs))
        return PiecewiseLinear([0.0, pivot, float(cfg.num_epochs)],
                               [0.0, lr0, 0.0])
    return PiecewiseLinear([0.0, float(cfg.num_epochs)], [lr0, 0.0])


def save_pretrained(out_dir: str, runtime, state, gcfg: GPT2Config,
                    tokenizer) -> None:
    """Reference parity for ``model.save_pretrained(log_dir)`` +
    ``tokenizer.save_pretrained`` + config (fed_aggregator.py:208-211,
    gpt2_train.py:146, 280-283): the saved directory is reloadable as a
    pretrained checkpoint WITHOUT the writing run's code/config in hand —
    weights + model config + tokenizer artifacts together."""
    os.makedirs(out_dir, exist_ok=True)
    from commefficient_tpu.checkpoint import params_fingerprint
    # fingerprint needs only treedef + leaf shapes: eval_shape avoids
    # materializing the full pytree (hundreds of MB at real GPT-2 scale)
    params_shape = jax.eval_shape(runtime.unravel,
                                  runtime.flat_weights(state))
    np.savez(os.path.join(out_dir, "weights.npz"),
             ps_weights=np.asarray(runtime.flat_weights(state)))
    cfg_dict = dataclasses.asdict(gcfg)
    cfg_dict["compute_dtype"] = str(jnp.dtype(gcfg.compute_dtype))
    with open(os.path.join(out_dir, "config.json"), "w") as f:
        json.dump({"model_type": "gpt2_doubleheads", **cfg_dict,
                   "params_fingerprint": params_fingerprint(params_shape)},
                  f, indent=1)
    if hasattr(tokenizer, "save_pretrained"):      # real GPT-2 BPE
        tokenizer.save_pretrained(out_dir)
    else:                                          # offline HashTokenizer
        with open(os.path.join(out_dir, "hash_tokenizer.json"), "w") as f:
            json.dump({"type": "HashTokenizer",
                       "base_vocab": tokenizer.base_vocab}, f)
    print(f"saved pretrained checkpoint to {out_dir}")


def load_pretrained(out_dir: str):
    """Rebuild (model, params, gcfg, tokenizer) from a ``save_pretrained``
    directory. Refuses weight vectors whose layout does not match the
    rebuilt model (fingerprint check)."""
    from commefficient_tpu.checkpoint import params_fingerprint
    from commefficient_tpu.data.fed_persona import HashTokenizer
    with open(os.path.join(out_dir, "config.json")) as f:
        cfg_dict = json.load(f)
    saved_fp = cfg_dict.pop("params_fingerprint", None)
    cfg_dict.pop("model_type", None)
    cfg_dict["compute_dtype"] = jnp.dtype(cfg_dict["compute_dtype"])
    gcfg = GPT2Config(**cfg_dict)
    model = GPT2DoubleHeads(gcfg)
    ids = jnp.zeros((1, 2, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids,
                        jnp.zeros((1, 2), jnp.int32), ids)
    fp = params_fingerprint(params)
    if saved_fp is not None and fp != saved_fp:
        raise ValueError(
            f"{out_dir}: saved weights were written under a different "
            f"parameter layout ({saved_fp} != {fp})")
    from commefficient_tpu.ops import make_unraveler
    _, unravel = make_unraveler(params)
    flat = np.load(os.path.join(out_dir, "weights.npz"))["ps_weights"]
    params = unravel(jnp.asarray(flat))
    hash_fn = os.path.join(out_dir, "hash_tokenizer.json")
    if os.path.exists(hash_fn):
        with open(hash_fn) as f:
            tokenizer = HashTokenizer(json.load(f)["base_vocab"])
    else:
        tokenizer = get_tokenizer(out_dir)
    return model, params, gcfg, tokenizer


def main(argv=None, *, on_finish=None):
    cfg = parse_args(argv, default_lr=0.16)  # reference gpt2 lr lineage
    enable_compilation_cache(cfg)
    np.random.seed(cfg.seed)
    if cfg.do_test:
        cfg = cfg.replace(num_cols=10, num_rows=1, k=10)
    cfg = cfg.replace(dataset_name="PERSONA")

    timer = Timer()
    built = CONFIG_MODELS.get(cfg.model)    # None: GPT-2 DoubleHeads
    if built:
        model, gcfg, tokenizer = build_config_model(cfg)
    else:
        tokenizer = get_tokenizer(cfg.model_checkpoint)
    max_seq_len = cfg.max_seq_len or (64 if cfg.do_test else 280)
    train_ds = FedPERSONA(cfg.dataset_dir, train=True, do_iid=cfg.do_iid,
                          num_clients=cfg.num_clients, tokenizer=tokenizer,
                          num_candidates=cfg.num_candidates,
                          max_seq_len=max_seq_len,
                          max_history=cfg.max_history,
                          personality_permutations=cfg.personality_permutations)
    # same prep config as train (a differing config would invalidate the
    # shared npz cache); permutations only augment the TRAIN pack
    val_ds = FedPERSONA(cfg.dataset_dir, train=False, tokenizer=tokenizer,
                        num_candidates=cfg.num_candidates,
                        max_seq_len=max_seq_len,
                        max_history=cfg.max_history,
                        personality_permutations=cfg.personality_permutations)
    cfg = cfg.replace(num_clients=train_ds.num_clients)

    sample = train_ds.gather(np.zeros((1,), np.int64))
    if built:
        params = jax.jit(model.init)(jax.random.PRNGKey(cfg.seed),
                                     jnp.asarray(sample["input_ids"]))
        print(f"{cfg.model}: {gcfg.num_hidden_layers} layers, experts "
              f"{gcfg.experts_held[0]}-{gcfg.experts_held[1] - 1} of "
              f"{gcfg.num_experts} held, vocabulary {gcfg.vocab_size}; "
              "training from scratch")
    else:
        model, gcfg = build_gpt2(cfg, tokenizer)
        params = model.init(jax.random.PRNGKey(cfg.seed),
                            jnp.asarray(sample["input_ids"]),
                            jnp.asarray(sample["mc_token_ids"]),
                            jnp.asarray(sample["token_type_ids"]))
        loaded = load_hf_weights(params, gcfg, cfg.model_checkpoint)
        if loaded is not None:
            params = loaded
            print("loaded pretrained GPT-2 weights")
        else:
            print("WARNING: no local pretrained GPT-2; training from "
                  "scratch")

    # long-context configuration: --mesh_axes clients,seq runs every
    # client's model with the sequence sharded over the "seq" axis (ring
    # attention, parallel/ring.py) — per-device attention memory drops from
    # O(S^2) to O(S^2/n_seq) and activations to O(S/n_seq). New scope
    # beyond the reference (SURVEY.md §5: no sequence parallelism).
    mesh = build_mesh(cfg)
    seq_shards = (mesh.shape["seq"]
                  if mesh is not None and "seq" in mesh.axis_names else 1)
    round_counters = ()
    if built:
        if seq_shards > 1:
            raise ValueError(f"--model {cfg.model} has no sequence-parallel "
                             "attention; drop the seq mesh axis")
        pad_id = tokenizer.convert_tokens_to_ids("<pad>")
        loss_train = built.make_loss(model, pad_id, lm_chunk=cfg.lm_chunk)
        loss_val = built.make_loss(model, pad_id, lm_chunk=cfg.lm_chunk,
                                   counters=False)
        round_counters = built.counters
    elif seq_shards > 1:
        if max_seq_len % seq_shards:
            raise ValueError(
                f"the seq mesh axis size ({seq_shards}) must divide "
                f"max_seq_len ({max_seq_len})")
        train_model = GPT2DoubleHeads(gcfg, seq_axis="seq",
                                      seq_shards=seq_shards)
        # lm_chunk is passed so the unsupported lm_chunk+seq combination
        # FAILS FAST in the loss builder instead of silently running dense
        loss_train = make_gpt2_train_loss(train_model, cfg.lm_coef,
                                          cfg.mc_coef, seq_axis="seq",
                                          seq_shards=seq_shards,
                                          lm_chunk=cfg.lm_chunk)
        print(f"sequence parallelism: ring attention over {seq_shards} "
              "shards")
    else:
        loss_train = make_gpt2_train_loss(model, cfg.lm_coef, cfg.mc_coef,
                                          lm_chunk=cfg.lm_chunk)
    # validation always runs the dense model (same param pytree); on a
    # mesh the val batch shards over all devices (runtime._val_step_sharded)
    if not built:
        loss_val = make_gpt2_val_loss(model, lm_chunk=cfg.lm_chunk)
    runtime = FedRuntime(cfg, params, loss_train, loss_val,
                         num_clients=train_ds.num_clients,
                         mesh=mesh,
                         seq_spec=(PERSONA_SEQ_SPEC if seq_shards > 1
                                   else None))
    state = runtime.init_state()
    print(f"grad size {runtime.cfg.grad_size}; "
          f"initialized in {timer():.2f}s")

    ckpt_mgr, start_epoch, restored, resume_info = setup_checkpointing(
        cfg, runtime, cfg.model if built else "gpt2_doubleheads")
    if restored is not None:
        state = restored

    from commefficient_tpu.cv_train import make_writer
    from commefficient_tpu.telemetry import maybe_create as make_telemetry
    from commefficient_tpu.utils import make_logdir
    # one logdir shared by telemetry + tensorboard (see cv_train.main);
    # --logdir pins it so a resumed run appends to its predecessor's
    # stream with a `resume` lineage record
    logdir = (cfg.logdir or make_logdir(cfg)
              if cfg.telemetry or cfg.use_tensorboard else None)
    # resolved config (grad_size, auto-sized num_cols) for the manifest
    telemetry = make_telemetry(
        runtime.cfg, "gpt2_train", logdir=logdir,
        resume_info=(None if resume_info is None else {
            "round": resume_info["global_round"],
            "epoch": start_epoch,
            "checkpoint": resume_info["checkpoint"]}))
    if telemetry is not None:
        telemetry.instrument(runtime)
        telemetry.memory_event("init")
    # analytic MFU numerator for the utilization telemetry: the scanned
    # round makes XLA's cost analysis under-count ~10x (models/gpt2.py
    # gpt2_model_flops); tokens/round = W x B x candidates x seq
    round_tokens = (cfg.num_workers * runtime.batch_size
                    * cfg.num_candidates * max_seq_len)
    model_flops = built.flops if built else gpt2_model_flops
    round_flops = model_flops(gcfg, round_tokens, max_seq_len)
    tsv = TSVLogger()
    guard = PreemptGuard(cfg.preempt_grace)
    try:
        state, summary = shared_train(cfg, runtime, state, train_ds, val_ds,
                                      loggers=(TableLogger(), tsv),
                                      timer=timer, ckpt_mgr=ckpt_mgr,
                                      start_epoch=start_epoch,
                                      schedule=make_gpt2_schedule(cfg),
                                      writer=make_writer(cfg, logdir=logdir),
                                      telemetry=telemetry,
                                      model_flops_per_round=round_flops,
                                      resume_info=resume_info, guard=guard,
                                      round_counters=round_counters)
    finally:
        if telemetry is not None:
            telemetry.close()
    print(tsv)
    finish_run(summary, guard, on_finish, runtime, state)

    if summary is not None:
        nll = summary["test_loss"]
        print(f"final val nll {nll:.4f} ppl {math.exp(min(nll, 20)):.2f} "
              f"{'token' if built else 'mc'} acc "
              f"{summary['test_acc']:.4f}")
    if cfg.do_checkpoint and summary is not None and not built:
        # reference parity: weights + config + tokenizer, reloadable
        # without this run's code in hand (fed_aggregator.py:208-211)
        save_pretrained(os.path.join(cfg.checkpoint_path,
                                     "gpt2_doubleheads"),
                        runtime, state, gcfg, tokenizer)
    return summary


if __name__ == "__main__":
    main()
