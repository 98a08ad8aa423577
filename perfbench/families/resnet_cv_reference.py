"""Plain reference for family ``resnet_cv``: forward pass and loss in
straightforward ``jax.numpy`` / ``lax.conv``, float32, highest matmul
precision, no bf16 cast, no scan, no remat, no kernels. Gradients come from
``jax.grad`` of this loss.

``FixupResNet50`` follows Zhang et al., "Fixup Initialization" (ICLR 2019,
arXiv:1901.09321), the ImageNet bottleneck ResNet-50 [3, 4, 6, 3] without
normalisation: a scalar bias before every convolution and ReLU, a scalar
multiplier on the residual branch. Departures from the paper, shared with
the program's model (``models/fixup_resnet.py``) because they define which
parameters exist: the shortcut convolution sees ``x + bias1a`` like the
branch, and the stem has one bias after its convolution.

It reads the program's parameter pytree by its names; it calls nothing of
the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
DN = ("NHWC", "HWIO", "NHWC")


def _conv(x, w, stride, pad, dtype):
    return lax.conv_general_dilated(
        x.astype(dtype), w.astype(dtype), (stride, stride),
        [(pad, pad), (pad, pad)], dimension_numbers=DN,
        precision=HIGHEST).astype(jnp.float32)


def _max_pool_3x3_s2(x):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                             (1, 2, 2, 1), [(0, 0), (1, 1), (1, 1), (0, 0)])


def _bottleneck(p, x, stride, dtype):
    s = lambda name: p[name]["value"]
    y = _conv(x + s("bias1a"), p["conv1"]["kernel"], 1, 0, dtype)
    y = jax.nn.relu(y + s("bias1b"))
    y = _conv(y + s("bias2a"), p["conv2"]["kernel"], stride, 1, dtype)
    y = jax.nn.relu(y + s("bias2b"))
    y = _conv(y + s("bias3a"), p["conv3"]["kernel"], 1, 0, dtype)
    y = y * s("scale") + s("bias3b")
    if "shortcut" in p:
        sc = _conv(x + s("bias1a"), p["shortcut"]["kernel"], stride, 0,
                   dtype)
    else:
        sc = x
    return jax.nn.relu(y + sc)


def fixup_resnet_imagenet(params, x, layers=(3, 4, 6, 3),
                          dtype=jnp.float32):
    p = params["params"]
    x = _conv(x, p["stem"]["kernel"], 2, 3, dtype)
    x = jax.nn.relu(x + p["bias1"]["value"])
    x = _max_pool_3x3_s2(x)
    for stage, n in enumerate(layers):
        for i in range(n):
            x = _bottleneck(p[f"stage{stage}_block{i}"], x,
                            2 if stage > 0 and i == 0 else 1, dtype)
    x = x.mean(axis=(1, 2)) + p["bias2"]["value"]
    w, b = p["fc"]["kernel"], p["fc"]["bias"]
    return jnp.dot(x.astype(dtype), w.astype(dtype),
                   precision=HIGHEST).astype(jnp.float32) + b


FORWARD = {"FixupResNet50": fixup_resnet_imagenet}


def make_loss(model_name, variant=None, **model_kw):
    """``loss(params, batch, mask) -> scalar``: masked mean softmax
    cross-entropy. ``variant="bf16"`` is the deliberately wrong reference
    of the tests: the same mathematics with bfloat16 convolutions."""
    fwd = FORWARD[model_name]
    dtype = jnp.bfloat16 if variant == "bf16" else jnp.float32

    def loss(params, batch, mask):
        with jax.default_matmul_precision("highest"):
            logits = fwd(params, batch["image"].astype(jnp.float32),
                         dtype=dtype, **model_kw)
            logp = jax.nn.log_softmax(logits)
            ce = -jnp.take_along_axis(logp, batch["target"][:, None],
                                      axis=1)[:, 0]
            m = mask.astype(jnp.float32)
            return (ce * m).sum() / jnp.maximum(m.sum(), 1.0)

    return loss
