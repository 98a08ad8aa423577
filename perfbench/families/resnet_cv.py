"""Family ``resnet_cv``: the image classifiers of ``commefficient_tpu.models``
driven as ``cv_train`` drives them (``build_model``, ``make_cv_loss``,
``fixup_lr_multiplier``), on seeded images the benchmark makes itself.

A configuration file of this family gives ``--model`` and ``--dataset_name``
in its flags and the image shape, class count and client layout under
``data``. The plain reference is ``resnet_cv_reference.py`` beside this file.
"""

from __future__ import annotations

import types

DEFAULT_LR = 0.4          # cv_train's own default
SAMPLE_UNIT = "img"


def parse(flags):
    from commefficient_tpu.config import parse_args
    return parse_args(flags, default_lr=DEFAULT_LR)


def build(cfg, config, seed):
    """Model, device-initialised weights, loss and data for ``cfg``."""
    import jax
    import jax.numpy as jnp
    from commefficient_tpu.cv_train import build_model
    from commefficient_tpu.losses import make_cv_loss
    from perfbench.harness.datasets import make_dataset

    data = config["data"]
    b = types.SimpleNamespace()
    b.model = build_model(cfg, data["num_classes"])
    shape = (1, data["height"], data["width"], data["channels"])
    b.params = jax.jit(b.model.init)(jax.random.PRNGKey(seed),
                                     jnp.zeros(shape, jnp.float32))
    b.loss_fn = make_cv_loss(b.model, cfg.compute_dtype)
    b.dataset = make_dataset(seed, data)
    b.store_name = cfg.dataset_name
    b.samples_per_round = cfg.num_workers * cfg.local_batch_size
    b.image_shape = shape[1:]
    b.model_name = cfg.model
    return b


def lr_array(built, cfg, runtime, lr):
    """Fixup models train their scalar biases and scales at a tenth of
    the rate, through a d-long vector, as ``cv_train.main`` does."""
    import jax.numpy as jnp
    if cfg.model.startswith("Fixup"):
        from commefficient_tpu.cv_train import fixup_lr_multiplier
        return lr * fixup_lr_multiplier(built.params,
                                        runtime.initial_weights)
    return jnp.asarray(lr, jnp.float32)


def model_flops_per_round(built, cfg):
    """Operations the forward and backward passes of one round need:
    3 x the forward pass (the backward costs twice the forward), the
    forward being the convolutions and matrix products of the model on one
    client batch, counted from shapes (``arith.matmul_flops``), times the
    clients of a round. Recomputation is not counted."""
    import jax
    import jax.numpy as jnp
    from perfbench.harness import arith
    x = jax.ShapeDtypeStruct((cfg.local_batch_size,) + built.image_shape,
                             jnp.dtype(cfg.compute_dtype))
    fwd = arith.matmul_flops(lambda p, x: built.model.apply(p, x),
                             built.params, x)
    return 3.0 * fwd * cfg.num_workers


def sample_batch(built, n, seed):
    """``n`` seeded items of the data set, as the loss takes them."""
    import jax
    import jax.numpy as jnp
    from commefficient_tpu.data import transforms as T
    idx = jax.random.choice(jax.random.PRNGKey(seed ^ 0x5A),
                            len(built.dataset), (n,), replace=False)
    img = built.dataset.arrays["image"][idx].astype(jnp.float32) / 255.0
    const = {"CIFAR10": "CIFAR10", "CIFAR100": "CIFAR100",
             "ImageNet": "IMAGENET"}[built.store_name]
    img = ((img - jnp.asarray(getattr(T, f"{const}_MEAN"), jnp.float32))
           / jnp.asarray(getattr(T, f"{const}_STD"), jnp.float32))
    return {"image": img, "target": built.dataset.arrays["target"][idx]}


def reference_loss(built, cfg, variant=None):
    from perfbench.families import resnet_cv_reference as ref
    return ref.make_loss(built.model_name, variant=variant,
                         **getattr(built, "reference_kw", {}))


REFERENCE_SAMPLE = 8       # images in the on-chip comparison
