#!/usr/bin/env python
"""Headline benchmark: federated-round throughput, ResNet-9/CIFAR10-shape,
FetchSGD sketch compression (the reference's flagship config,
``cv_train.py --mode sketch``), plus the GPT-2 (124M) sketched round as a
nested secondary metric so one driver run records both flagship configs.

Measures end-to-end rounds of the jitted federated step — per-client
forward/backward, count-sketch encode, aggregation, server unsketch/top-k
update — and reports images/second. ``vs_baseline`` is the ratio against a
2000 img/s nominal single-GPU figure (cifar10_fast lineage trains CIFAR10 in
~24 epochs x ~25 s on one V100; the reference publishes no numbers of its
own — BASELINE.md).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "mfu",
"gpt2": {...}}. ``vs_baseline`` divides by a NOMINAL (not measured)
single-GPU anchor; ``mfu`` is the measured model-FLOPs utilization — the
MODEL's fwd+bwd FLOPs for the round's images (XLA cost analysis of the bare
value_and_grad; the sketch/server ops the round also executes are real
work but not model FLOPs) over wall-clock x peak bf16 FLOP/s — and is
the number to trust.

Runs on a TPU only, and exits non-zero if any stage fails: each finished
stage's JSON is logged to stderr as it completes, the combined line is
printed only when all three ran.
"""

from __future__ import annotations

import argparse
import json

import numpy as np

from bench_common import log, peak_flops, timed_rounds

NOMINAL_SINGLE_GPU_IMG_PER_SEC = 2000.0


def run_cifar(result: dict, W: int = 8, B: int = 64,
              n_rounds: int = 20, telemetry=None, profiler=None,
              compile_cache=None, wire_dtype: str = "float32") -> None:
    """Fill ``result`` in place.

    Default (W=8, B=64) is the flagship-parity round shape — 512
    images/round, which a v5e finishes in ~0.5 ms of model time per
    client: the round is BATCH-bound there (model isolated ~51% MFU, the
    round ~17%). The saturating point below (B=512) exists to show the
    framework's ceiling when the round actually feeds the chip."""
    import jax
    import jax.numpy as jnp

    from commefficient_tpu import models
    from commefficient_tpu.config import FedConfig, enable_compilation_cache
    from commefficient_tpu.core import FedRuntime
    from commefficient_tpu.losses import make_cv_loss

    log("devices:", jax.devices())
    cfg = FedConfig(
        mode="sketch", error_type="virtual", local_momentum=0.0,
        virtual_momentum=0.9, weight_decay=5e-4,
        num_workers=W, local_batch_size=B,
        k=50_000, num_rows=5, num_cols=500_000, num_blocks=20,
        num_clients=100, track_bytes=False,
        # TPU-tuned select: approx_max_k (0.95 recall) for the top-k
        # sparsification — itself an approximation — instead of a 20x
        # slower exact sort-based select. Sketch: the default circulant
        # impl (fp32 tables); --wire_dtype selects the table wire
        # (f32 / bf16 / int8-quantized — ops/wire.py).
        approx_topk=True,
        wire_dtype=wire_dtype,
    )
    # persistent compile cache (config.enable_compilation_cache_dir):
    # --compile_cache names the directory where the environment names
    # none (empty string = disable, for true cold-start warmup_s
    # measurements; None = keep the default)
    if compile_cache is not None:
        cfg = cfg.replace(compilation_cache_dir=compile_cache)
    enable_compilation_cache(cfg)

    model = models.ResNet9(num_classes=10)
    x0 = jnp.ones((1, 32, 32, 3), jnp.float32)
    params = model.init(jax.random.PRNGKey(0), x0)
    loss_fn = make_cv_loss(model, "bfloat16")

    runtime = FedRuntime(cfg, params, loss_fn, num_clients=cfg.num_clients)
    if telemetry is not None:
        # compile events (lower/compile wall time + cost-analysis FLOPs)
        # for the warmup's compiles land in the shared stream
        telemetry.instrument(runtime)
        telemetry.memory_event(f"cifar_w{W}_b{B}_init")

    rng = np.random.RandomState(0)
    batch = {
        "image": jnp.asarray(rng.randn(W, B, 32, 32, 3), jnp.float32),
        "target": jnp.asarray(rng.randint(0, 10, (W, B)), jnp.int32),
    }
    mask = jnp.ones((W, B), bool)
    client_ids = jnp.arange(W, dtype=jnp.int32)
    lr = 0.1

    dt, metrics, phases = timed_rounds(runtime, (client_ids, batch, mask, lr),
                                       warmup=2, rounds=n_rounds, desc="cifar",
                                       profiler=profiler)

    images = n_rounds * W * B
    ips = images / dt
    log(f"{n_rounds} rounds in {dt:.3f}s -> {ips:.1f} img/s")
    loss = float(np.asarray(metrics["results"][0]).mean())
    log(f"final mean client loss {loss:.4f}")

    result["value"] = round(ips, 1)
    result["vs_baseline"] = round(ips / NOMINAL_SINGLE_GPU_IMG_PER_SEC, 3)
    result["timed_rounds"] = n_rounds
    # quantized-wire arm identity (schema v9 / ISSUE 14): which table
    # wire this arm ran, and the exact simulated per-round upload
    # payload (W clients x the wire-dtype cell cost incl. int8 scales)
    # — what lets BENCH_r* trajectory files distinguish wire arms
    result["wire_dtype"] = cfg.wire_dtype
    result["wire_bytes_per_round"] = W * cfg.upload_wire_bytes(
        runtime._wire_block or None)
    # compile+warmup wall seconds BEFORE the timed window (cold against
    # warm compile cache)
    result["warmup_s"] = phases.pop("warmup_s", None)
    # where the timed wall clock went: dispatch (async round calls),
    # device_wait (trailing completion barrier), host (loop remainder)
    result["phase_split"] = phases
    # headline starvation fraction, gateable by `teleview diff
    # --input_wait_rise` on the bench trajectory (not just run streams)
    result["input_wait_frac"] = round(phases["host_s"] / dt, 6)

    # MFU numerator = MODEL FLOPs (the ResNet-9 fwd+bwd for the round's
    # W*B images, from XLA's cost analysis of the bare value_and_grad — no
    # scans there, so the count is trustworthy), consistent with
    # bench_gpt2's analytic model-FLOPs definition. The sketch/server ops
    # the round also executes are real work but not "model FLOPs".
    flat = {k: v.reshape((-1,) + v.shape[2:]) for k, v in batch.items()}
    fmask = mask.reshape(-1)
    g = jax.jit(jax.value_and_grad(lambda p: loss_fn(p, flat, fmask)[0]))
    flops = float(g.lower(params).compile().cost_analysis()["flops"])
    peak = peak_flops(jax.devices()[0])
    mfu = (flops * n_rounds / dt) / peak
    log(f"model FLOPs/round {flops:.3e}, peak {peak:.0f}, MFU {mfu:.3f}")
    result["mfu"] = round(mfu, 4)
    if telemetry is not None:
        # schema-validated utilization event in the shared stream: the
        # same MFU the JSON line carries, plus the starvation fractions
        # and (v6) the roofline fields — the round executable's bytes
        # accessed come from the JitWatcher's cost analysis (the warmup
        # compiled through it), so AI/bound ride the same stream
        from commefficient_tpu.telemetry.utilization import emit_from_totals
        round_bytes = telemetry.watcher().bytes.get("round_step")
        ufields = emit_from_totals(
            telemetry, rnd=n_rounds, rounds=n_rounds, wall_s=dt,
            host_s=phases["host_s"], dispatch_s=phases["dispatch_s"],
            device_s=phases["device_wait_s"],
            flops_per_round=flops,
            flops_source="cost_analysis",
            device_kind=getattr(jax.devices()[0], "device_kind", "unknown"),
            bytes_per_round=(float(round_bytes) if round_bytes else None),
            bytes_source="cost_analysis")
        result["roofline"] = {
            k: ufields[k] for k in ("bytes_per_round",
                                    "arithmetic_intensity", "bound",
                                    "bw_frac")}
        telemetry.bench_event(result["metric"], result,
                              wire_dtype=cfg.wire_dtype)


def make_bench_telemetry(args, run_type: str):
    """Shared bench CLI: ``--telemetry_dir`` opens the same JSONL stream
    the drivers write (telemetry/schema.py); ``--profile_dir``/
    ``--profile_rounds`` place a jax trace over the timed rounds."""
    from commefficient_tpu.telemetry import ProfilerWindow, RunTelemetry
    telemetry = None
    if args.telemetry_dir:
        telemetry = RunTelemetry(args.telemetry_dir, run_type)
        if telemetry.active:
            log(f"telemetry: {telemetry.path}")
        else:
            telemetry = None  # constructor warned; no stream to feed
    profiler = (ProfilerWindow(args.profile_dir, args.profile_rounds,
                               log=log)
                if args.profile_dir else None)
    return telemetry, profiler


def add_bench_args(ap: argparse.ArgumentParser) -> None:
    ap.add_argument("--telemetry_dir", default="",
                    help="write a telemetry.jsonl event stream here "
                         "(same schema as the drivers')")
    ap.add_argument("--profile_dir", default="",
                    help="write a jax profiler trace of the timed rounds")
    ap.add_argument("--profile_rounds", default="2:4",
                    help="1-based inclusive timed-round window for the "
                         "trace, START:STOP")
    ap.add_argument("--compile_cache", default=None,
                    help="persistent XLA compile cache DIR (unset: the "
                         "config default, <checkout>/.jax_cache; pass an "
                         "empty string to DISABLE and measure a true cold "
                         "start). Ignored when JAX_COMPILATION_CACHE_DIR "
                         "is set")
    ap.add_argument("--wire_dtype",
                    choices=("float32", "bfloat16", "int8"),
                    default="float32",
                    help="sketch-table wire dtype for the benched round "
                         "(int8 = block-quantized wire, ops/wire.py); "
                         "recorded in the headline JSON so BENCH "
                         "trajectory arms stay distinguishable")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    add_bench_args(ap)
    args = ap.parse_args(argv)
    import jax
    if jax.default_backend() != "tpu":
        raise SystemExit(
            f"bench.py measures a TPU; JAX found {jax.default_backend()!r} "
            f"({jax.devices()[0].device_kind}). A CPU run has no img/s, "
            "tok/s or MFU to report.")
    telemetry, profiler = make_bench_telemetry(args, "bench")
    result = {
        "metric": "cifar10_sketch_round_throughput",
        "value": None,
        "unit": "images/sec",
        "vs_baseline": None,
        "mfu": None,
    }
    try:
        run_cifar(result, telemetry=telemetry, profiler=profiler,
                  compile_cache=args.compile_cache,
                  wire_dtype=args.wire_dtype)
        # the measured headline lands in the stderr tail NOW, so a failure
        # in a later (long-compiling) stage still leaves it on record
        log("headline:", json.dumps(result))
        # second CIFAR point at a round size that FEEDS the chip (VERDICT
        # r3 item 4): same model/sketch config, 32 clients x 512 images —
        # the top of the round-shape grid (runs/ROUND_SHAPE.md). The
        # flagship-parity headline above is deliberately batch-starved
        # (its round shape matches the reference experiment, not the
        # hardware); this point records what the same machinery does when
        # the round is compute-bound.
        sat = {"metric": "cifar10_sketch_round_throughput_saturated",
               "value": None, "unit": "images/sec", "vs_baseline": None,
               "mfu": None, "round_images": 32 * 512}
        run_cifar(sat, W=32, B=512, n_rounds=10, telemetry=telemetry,
                  compile_cache=args.compile_cache,
                  wire_dtype=args.wire_dtype)
        result["cifar_saturated"] = sat
        log("saturated:", json.dumps(sat))
        # secondary metric: the GPT-2 (124M) sketched round
        import bench_gpt2
        result["gpt2"] = bench_gpt2.run(telemetry=telemetry,
                                        compile_cache=args.compile_cache,
                                        wire_dtype=args.wire_dtype)
    finally:
        if telemetry is not None:
            # total timed rounds across the stages that actually ran
            n_rounds = sum(
                stage.get("timed_rounds", 0)
                for stage in (result, result.get("cifar_saturated") or {},
                              result.get("gpt2") or {}))
            telemetry.write_summary(aborted="gpt2" not in result,
                                    n_rounds=n_rounds, final=result)
            telemetry.close()
    print(json.dumps(result))


if __name__ == "__main__":
    main()
