#!/usr/bin/env python
"""Long-context attention scaling on one chip: dense vs flash
(--attn_impl) for GPT-2 fwd+bwd at growing sequence length, constant
token budget per step.

Why this exists: at the flagship federated round's S=256 the flash
kernel LOSES to dense attention (grid overhead > what fusing a 256x256
softmax saves — runs/BREAKDOWN_gpt2.md). Attention cost scales O(S^2)
while everything else is O(S), so the crossover and the memory wall both
live at longer S — this script measures both. The dense path
materializes (B, H, S, S) logits; at S=4096 that is 1.6 GiB bf16 per
microbatch PER LAYER in the backward's saved activations, which is the
wall flash's O(S) memory removes. (Multi-chip long-context uses ring
attention over a "seq" mesh axis — parallel/ring.py — which composes
with the same federated round; this script is the single-chip half of
the story.)

Timing: chained lax.scan over grad steps, one dispatch per measurement.
MFU from the analytic FLOP model (bench_gpt2).

Usage: python scripts/bench_longctx.py [reps=4]
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    reps = int(sys.argv[1]) if len(sys.argv) > 1 else 4
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench_common import peak_flops
    from bench_gpt2 import gpt2_model_flops
    from commefficient_tpu.models.gpt2 import (GPT2Config, GPT2LMHead,
                                               resolve_attn)
    from commefficient_tpu.ops import ravel_params

    TOKENS = 16384  # per step, constant across S
    peak = peak_flops(jax.devices()[0])
    print(f"{'S':>6s} {'B':>3s} {'attn':>6s} {'ms/step':>9s} "
          f"{'tok/s':>9s} {'MFU':>6s}")
    for S in (1024, 2048, 4096):
        B = TOKENS // S
        for attn in ("dense", "flash"):
            gcfg = GPT2Config(n_positions=S, remat=True)
            model = GPT2LMHead(gcfg, attn_impl=resolve_attn(attn))
            rng = np.random.RandomState(0)
            ids = jnp.asarray(rng.randint(0, 50257, (B, S)), jnp.int32)
            labels = jnp.asarray(rng.randint(0, 50257, (B, S)), jnp.int32)
            params = model.init(jax.random.PRNGKey(0), ids[:1])
            vec, unravel = ravel_params(params)

            def loss_fn(v):
                logits = model.apply(unravel(v), ids)
                lp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
                tgt = labels[:, 1:]
                nll = -jnp.take_along_axis(lp, tgt[..., None], -1)
                return nll.mean()

            grad = jax.value_and_grad(loss_fn)

            def chain(v, n):
                def body(carry, _):
                    l, g = grad(carry)
                    return carry - 1e-12 * g, l
                v_out, ls = jax.lax.scan(body, v, None, length=n)
                return v_out[0] + ls[-1]

            run = jax.jit(chain, static_argnums=1)
            try:
                float(run(vec, reps))          # compile + warmup
                t0 = time.time()
                float(run(vec, reps))
                dt = (time.time() - t0) / reps
            except Exception as e:
                print(f"{S:6d} {B:3d} {attn:>6s}    FAILED "
                      f"{type(e).__name__}: {str(e).splitlines()[0][:60]}",
                      flush=True)
                continue
            flops = gpt2_model_flops(gcfg, B * S, S)
            print(f"{S:6d} {B:3d} {attn:>6s} {dt * 1e3:9.1f} "
                  f"{B * S / dt:9.0f} {flops / dt / peak:6.1%}", flush=True)


if __name__ == "__main__":
    main()
