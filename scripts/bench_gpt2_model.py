#!/usr/bin/env python
"""Isolated GPT-2 MODEL throughput (no federation): vmap-8-clients,
microbatched value_and_grad — the round's compute core, measured alone so
the federated overhead and the model ceiling can be attributed separately
(VERDICT r3 items 2-3).

Variants: remat on/off x attention dense/flash. Round-3 finding: with
dense attention, remat=False cannot even compile at this scale (the
(B, H, S, S) logits tensors of 12 layers x 8 microbatches overflow HBM);
flash attention removes those tensors, which is what makes the no-remat
(no-recompute) configuration reachable at all.

Timing is CHAINED on-device (lax.scan over grad steps, each step's params
perturbed by the previous gradient): one dispatch per measurement, no
per-step host time in the window. MFU uses the same analytic FLOP
model as bench_gpt2.py (cost_analysis undercounts scanned bodies).

Usage: python scripts/bench_gpt2_model.py [reps=6]
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    reps = int(sys.argv[1]) if len(sys.argv) > 1 else 6
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench_gpt2 import gpt2_model_flops
    from bench_common import peak_flops
    from commefficient_tpu.config import FedConfig, enable_compilation_cache
    from commefficient_tpu.core.client import make_forward_grad
    from commefficient_tpu.losses import make_gpt2_train_loss
    from commefficient_tpu.models.gpt2 import (GPT2Config, GPT2DoubleHeads,
                                               resolve_attn)
    from commefficient_tpu.ops import ravel_params

    W, B, NC, S = 8, 8, 2, 256
    rng = np.random.RandomState(0)
    batch = {
        "input_ids": jnp.asarray(
            rng.randint(0, 50257, (W, B, NC, S)), jnp.int32),
        "mc_token_ids": jnp.asarray(rng.randint(0, S, (W, B, NC)), jnp.int32),
        "lm_labels": jnp.asarray(
            rng.randint(0, 50257, (W, B, NC, S)), jnp.int32),
        "mc_label": jnp.asarray(rng.randint(0, NC, (W, B)), jnp.int32),
        "token_type_ids": jnp.asarray(
            rng.randint(0, 2, (W, B, NC, S)), jnp.int32),
    }
    mask = jnp.ones((W, B), bool)
    peak = peak_flops(jax.devices()[0])
    enable_compilation_cache(FedConfig())

    for label, remat, attn in (
            ("remat + dense (r3 baseline)", True, "dense"),
            ("remat + flash", True, "flash"),
            ("NO remat + flash", False, "flash"),
            ("NO remat + dense (expected OOM)", False, "dense")):
        gcfg = GPT2Config(remat=remat)
        model = GPT2DoubleHeads(gcfg, attn_impl=resolve_attn(attn))
        params = model.init(jax.random.PRNGKey(0), batch["input_ids"][0, :1],
                            batch["mc_token_ids"][0, :1],
                            batch["token_type_ids"][0, :1])
        vec, unravel = ravel_params(params)
        cfg = FedConfig(mode="uncompressed", error_type="none",
                        local_momentum=0.0, virtual_momentum=0.9,
                        weight_decay=0.0, num_workers=W, local_batch_size=B,
                        microbatch_size=8, num_clients=100,
                        track_bytes=False, num_results_train=2, lm_chunk=128)
        fwd = make_forward_grad(
            cfg, make_gpt2_train_loss(model, lm_chunk=cfg.lm_chunk),
            unravel, B)
        vfwd = jax.vmap(fwd, in_axes=(None, 0, 0, 0))
        rngs = jax.random.split(jax.random.PRNGKey(1), W)

        def chain(p, n):
            def body(carry, _):
                g, res, nv, _ = vfwd(carry, batch, mask, rngs)
                # serialize: next step's params depend on this gradient
                return carry - 1e-12 * g.sum(axis=0), res[0].mean()
            p_out, losses = jax.lax.scan(body, p, None, length=n)
            return p_out[0] + losses[-1]

        run = jax.jit(chain, static_argnums=1)
        try:
            t0 = time.time()
            float(run(vec, 1))       # compile the body + 1 step
            compile_s = time.time() - t0
            float(run(vec, reps))    # warmup: n=reps is its own program
            t0 = time.time()
            float(run(vec, reps))    # steady-state chained timing
            dt = (time.time() - t0) / reps
        except Exception as e:
            print(f"{label:34s}: FAILED {type(e).__name__}: "
                  f"{str(e).splitlines()[0][:90]}")
            continue
        toks = W * B * NC * S
        flops = gpt2_model_flops(gcfg, toks, S)
        mfu = flops / dt / peak
        print(f"{label:34s}: {dt * 1e3:7.1f} ms/step  "
              f"{toks / dt:9.0f} tok/s  MFU {mfu:.3f}  "
              f"(compile {compile_s:.0f}s)", flush=True)


if __name__ == "__main__":
    main()
