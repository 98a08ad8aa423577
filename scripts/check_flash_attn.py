"""Does the library flash-attention kernel compile on this install, and does
``flash_causal_attention`` agree with the dense path? Forward and gradient,
bf16, at S=256 (below the auto policy's crossover) and S=1024 (where
``--attn_impl auto`` dispatches to it). TPU only: off the TPU the flash
entry point IS the dense path, and the comparison would pass by
construction."""
import os, sys
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax, jax.numpy as jnp, numpy as np
from commefficient_tpu.models.gpt2 import (dense_causal_attention,
                                           flash_causal_attention)
if jax.default_backend() != "tpu":
    sys.exit(f"check_flash_attn: backend is {jax.default_backend()!r}; the "
             "kernel only exists on the TPU")
rng = np.random.RandomState(0)
for shape in [(2, 256, 12, 64), (2, 2, 256, 12, 64), (2, 1024, 12, 64)]:
    q, k, v = (jnp.asarray(rng.randn(*shape), jnp.bfloat16) for _ in range(3))
    flash = jax.jit(flash_causal_attention)
    assert "tpu_custom_call" in flash.lower(q, k, v).compile().as_text()
    d = jax.jit(dense_causal_attention)(q, k, v)
    f = flash(q, k, v)
    err = float(jnp.max(jnp.abs(d.astype(jnp.float32) - f.astype(jnp.float32))))
    print(shape, "fwd max err", err)
    # grad parity through a scalar loss
    def loss(fn, q, k, v):
        return (fn(q, k, v).astype(jnp.float32) ** 2).mean()
    gd = jax.jit(jax.grad(lambda q: loss(dense_causal_attention, q, k, v)))(q)
    gf = jax.jit(jax.grad(lambda q: loss(flash_causal_attention, q, k, v)))(q)
    gerr = float(jnp.max(jnp.abs(gd.astype(jnp.float32) - gf.astype(jnp.float32))))
    gscale = float(jnp.max(jnp.abs(gd.astype(jnp.float32))))
    print(shape, "grad max err", gerr, "grad scale", gscale)
    # bf16 outputs of O(1) values: 2^-8 relative rounding on each path
    assert err < 0.05 and gerr < 0.05 * max(gscale, 1e-6), (shape, err, gerr)
print("FLASH PARITY OK")
