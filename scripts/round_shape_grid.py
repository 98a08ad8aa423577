#!/usr/bin/env python
"""Round-shape tuning grid for the CIFAR flagship sketch round (VERDICT
r4 weak #3 / next-round #8): MFU and throughput over a
(clients-per-round W x local-batch B) grid with the same machinery as
bench.py, so the batch-starved 18.7%-MFU parity headline gets a
shape-vs-MFU story instead of a caveat sentence.

Prints a table + one JSON line; the committed narrative lives in
runs/ROUND_SHAPE.md.

Usage: python scripts/round_shape_grid.py
"""

from __future__ import annotations

import itertools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def measure(W: int, B: int, n_rounds: int = 10):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench_common import peak_flops, timed_rounds
    from commefficient_tpu import models
    from commefficient_tpu.config import FedConfig, enable_compilation_cache
    from commefficient_tpu.core import FedRuntime
    from commefficient_tpu.losses import make_cv_loss

    cfg = FedConfig(
        mode="sketch", error_type="virtual", local_momentum=0.0,
        virtual_momentum=0.9, weight_decay=5e-4,
        num_workers=W, local_batch_size=B,
        k=50_000, num_rows=5, num_cols=500_000, num_blocks=20,
        num_clients=max(100, W), track_bytes=False, approx_topk=True)
    enable_compilation_cache(cfg)
    model = models.ResNet9(num_classes=10)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 32, 32, 3), jnp.float32))
    loss_fn = make_cv_loss(model, "bfloat16")
    runtime = FedRuntime(cfg, params, loss_fn, num_clients=cfg.num_clients)
    rng = np.random.RandomState(0)
    batch = {"image": jnp.asarray(rng.randn(W, B, 32, 32, 3), jnp.float32),
             "target": jnp.asarray(rng.randint(0, 10, (W, B)), jnp.int32)}
    args = (jnp.arange(W, dtype=jnp.int32), batch, jnp.ones((W, B), bool),
            0.1)
    dt, _, _ = timed_rounds(runtime, args, warmup=2, rounds=n_rounds,
                            desc=f"W{W}xB{B}")
    ips = n_rounds * W * B / dt
    peak = peak_flops(jax.devices()[0])
    return ips, peak, runtime, params, loss_fn, batch


def flops_per_image():
    """One XLA cost analysis of the bare value_and_grad (per image)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from commefficient_tpu import models
    from commefficient_tpu.losses import make_cv_loss

    model = models.ResNet9(num_classes=10)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 32, 32, 3), jnp.float32))
    loss_fn = make_cv_loss(model, "bfloat16")
    N = 512
    rng = np.random.RandomState(0)
    batch = {"image": jnp.asarray(rng.randn(N, 32, 32, 3), jnp.float32),
             "target": jnp.asarray(rng.randint(0, 10, (N,)), jnp.int32)}
    mask = jnp.ones((N,), bool)
    g = jax.jit(jax.value_and_grad(lambda p: loss_fn(p, batch, mask)[0]))
    return float(g.lower(params).compile().cost_analysis()["flops"]) / N


def main():
    fpi = flops_per_image()
    print(f"model FLOPs/image {fpi:.3e}", flush=True)
    rows = []
    for W, B in itertools.product((8, 16, 32), (64, 256, 512)):
        try:
            ips, peak, *_ = measure(W, B)
        except Exception as e:  # OOM at the big corner etc.
            print(f"W={W:3d} B={B:4d}: FAILED ({type(e).__name__})",
                  flush=True)
            rows.append({"W": W, "B": B, "error": type(e).__name__})
            continue
        mfu = ips * fpi / peak
        print(f"W={W:3d} B={B:4d} round={W*B:6d} img: "
              f"{ips:9.0f} img/s  MFU {mfu:6.1%}", flush=True)
        rows.append({"W": W, "B": B, "img_per_s": round(ips),
                     "mfu": round(mfu, 4)})
    print(json.dumps({"metric": "cifar_round_shape_grid", "rows": rows,
                      "flops_per_image": fpi}))


if __name__ == "__main__":
    main()
