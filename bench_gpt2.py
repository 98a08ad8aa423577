#!/usr/bin/env python
"""Secondary benchmark: GPT-2 (124M) sketched federated round throughput
(BASELINE.md config 4: GPT2-small / PersonaChat-shaped batches, FetchSGD
sketch 5x500k, circulant impl). Prints ONE JSON line like bench.py; the
driver's headline metric remains bench.py (CIFAR10 sketch round
throughput), which nests this one under its ``"gpt2"`` key.

Round shape: W=8 clients x B=8 dialogues x C=2 candidates x S=256 tokens
= 32,768 tokens/round (VERDICT r1: the old 2,048-token round amortized the
124M-d sketch over almost nothing), microbatched 8 dialogues at a time
with rematerialized blocks, chunked LM cross-entropy (lm_chunk=128 — the
full fp32 (tokens, vocab) logits used to cap the microbatch at 4), bf16
compute. num_cols=524288 (vs the reference's 500,000): the 1024-aligned
column count enables the fused pallas decode kernel (21 ms vs 129 ms at
d=124M — ops/circulant_pallas.py) at the cost of a 4.9% larger table
upload; measured on one v5e this config lifts the round from ~51.7k
tok/s @ 20.2% MFU to ~67-68k tok/s @ ~26.5% MFU.

MFU is model-FLOPs utilization computed from ANALYTIC fwd+bwd model FLOPs
(gpt2_model_flops below) — not XLA's cost analysis, which counts each
lax.scan body once (no trip-count multiply) and so under-reports the
scanned round by ~10x — divided by wall-clock x the chip's peak bf16
FLOP/s.

Usage: python bench_gpt2.py  (cold and warm compile times of this round
on the v5e: PERF.md)
"""

from __future__ import annotations

import json

import numpy as np

from bench_common import log, peak_flops, timed_rounds
# the analytic FLOPs formula moved next to the model so the gpt2_train
# driver's utilization telemetry shares it (models/gpt2.py)
from commefficient_tpu.models.gpt2 import gpt2_model_flops  # noqa: F401

# PersonaChat-lineage throughput anchor (NOMINAL, not measured: a V100
# runs GPT-2-small fwd+bwd at ~4.5k tok/s; the reference publishes no
# numbers of its own — BASELINE.md)
NOMINAL_SINGLE_GPU_TOK_PER_SEC = 4500.0


def run(remat: bool = True, telemetry=None, profiler=None, *,
        remat_policy: str = "", microbatch: int = 8, lm_chunk: int = 128,
        fused_encode: str = "auto",
        n_rounds: int = 8, compile_cache=None,
        wire_dtype: str = "float32", dryrun: bool = False) -> dict:
    """Build, warm up and time the GPT-2 round; returns the result dict.

    ``remat=True`` is the shipping configuration. remat=False spends the
    HBM the fused-clients path freed on saved activations instead of
    backward recompute — measured SLOWER (69.3k vs 76.5k tok/s pre-pallas
    -encode: the saved-activation HBM traffic costs more than the
    recompute FLOPs); kept parameterized so the trade stays measurable.

    ``remat_policy``/``microbatch``/``lm_chunk`` parameterize the MFU
    sweep (scripts/gpt2_mfu_sweep.py): selective-remat policies between
    full remat and none, the microbatch/HBM trade, and the chunked-CE
    granularity — the three knobs runs/BREAKDOWN_gpt2.md names between
    the measured 33% and the 40% target. ``microbatch`` must divide the
    dialogue client batch.

    ``fused_encode`` passes through to --sketch_fused_encode: "auto"
    (the shipping default — the microbatch scan carries the sketch
    table and the dense (d,) gradient never materializes, ~0.5 GB of
    temp at the flagship scale), "off" (the pre-fusion round — the
    A/B arm whose ledger DOCUMENTS the dense materialization), or "on"
    (fail fast if ineligible).

    ``dryrun=True`` shrinks the model (GPT2Config.small) and the round
    shape so every arm runs in seconds on the CPU container — the sweep
    mechanics, compiled-executable cost/memory analysis and roofline
    fields stay live while the throughput numbers are explicitly NOT
    the flagship measurement (the result carries ``dryrun: true``)."""
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.config import FedConfig, enable_compilation_cache
    from commefficient_tpu.core import FedRuntime
    from commefficient_tpu.losses import make_gpt2_train_loss
    from commefficient_tpu.models.gpt2 import GPT2Config, GPT2DoubleHeads

    log("devices:", jax.devices())
    if dryrun:
        gcfg = GPT2Config.small(remat=remat, remat_policy=remat_policy)
        W, B, NC, S = 4, 4, 2, 64
    else:
        gcfg = GPT2Config(remat=remat, remat_policy=remat_policy)
        W, B, NC, S = 8, 8, 2, 256
    model = GPT2DoubleHeads(gcfg)
    rng = np.random.RandomState(0)
    V = gcfg.vocab_size
    batch = {
        "input_ids": jnp.asarray(
            rng.randint(0, V, (W, B, NC, S)), jnp.int32),
        "mc_token_ids": jnp.asarray(rng.randint(0, S, (W, B, NC)), jnp.int32),
        "lm_labels": jnp.asarray(
            rng.randint(0, V, (W, B, NC, S)), jnp.int32),
        "mc_label": jnp.asarray(rng.randint(0, NC, (W, B)), jnp.int32),
        "token_type_ids": jnp.asarray(
            rng.randint(0, 2, (W, B, NC, S)), jnp.int32),
    }
    params = model.init(jax.random.PRNGKey(0),
                        batch["input_ids"][0, :1], batch["mc_token_ids"][0, :1],
                        batch["token_type_ids"][0, :1])

    if dryrun:
        # microbatch keeps its RATIO meaning (arms sweep 2/4/8 over the
        # full-scale client batch of 8; the dryrun batch is 4, so
        # mb8 -> 4, mb4 -> 2, mb2 -> 1 — each arm still A/Bs a DISTINCT
        # live-set size; a plain min-clamp would collapse mb8 and mb4
        # into the same configuration) and the sketch shrinks with the
        # model — the arm still exercises the same code paths, just at
        # smoke scale
        microbatch = max(1, (microbatch * B) // 8)
        lm_chunk = min(lm_chunk, S)
        sketch_kw = dict(k=1_000, num_rows=3, num_cols=16_384,
                         num_blocks=2)
    else:
        sketch_kw = dict(k=50_000, num_rows=5, num_cols=524_288,
                         num_blocks=20)
    cfg = FedConfig(mode="sketch", error_type="virtual", local_momentum=0.0,
                    virtual_momentum=0.9, weight_decay=0.0,
                    num_workers=W, local_batch_size=B,
                    microbatch_size=microbatch,
                    num_clients=100, track_bytes=False, approx_topk=True,
                    num_results_train=2, lm_chunk=lm_chunk,
                    sketch_fused_encode=fused_encode,
                    wire_dtype=wire_dtype, **sketch_kw)
    if compile_cache is not None:  # "" = disable (true cold start)
        cfg = cfg.replace(compilation_cache_dir=compile_cache)
    enable_compilation_cache(cfg)
    runtime = FedRuntime(cfg, params,
                         make_gpt2_train_loss(model, lm_chunk=cfg.lm_chunk),
                         num_clients=cfg.num_clients)
    if telemetry is not None:
        # the cold compile of this round becomes a visible
        # compile event (wall time + cost analysis) in the shared stream
        telemetry.instrument(runtime)
        telemetry.memory_event("gpt2_init")
    mask = jnp.ones((W, B), bool)
    ids = jnp.arange(W, dtype=jnp.int32)

    dt, metrics, phases = timed_rounds(runtime, (ids, batch, mask, 0.1),
                                       warmup=1, rounds=n_rounds, desc="gpt2",
                                       profiler=profiler)
    warmup_s = phases.pop("warmup_s", None)

    toks = n_rounds * W * B * NC * S
    tps = toks / dt
    loss = float(np.asarray(metrics["results"][0]).mean())

    # analytic model FLOPs: the round's scans (microbatch, scan-over-
    # layers) make XLA's cost analysis under-report by the trip counts
    flops = gpt2_model_flops(gcfg, W * B * NC * S, S)
    peak = peak_flops(jax.devices()[0])
    mfu = (flops * n_rounds / dt) / peak
    log(f"{n_rounds} rounds in {dt:.3f}s -> {tps:.0f} tok/s, loss {loss:.3f}")
    log(f"model FLOPs/round {flops:.3e}, peak {peak:.0f}, MFU {mfu:.3f}")

    # roofline attribution of the compiled round: cost-analysis bytes
    # accessed + the memory_analysis ledger (under the fused encode the
    # dense (d,) gradient no longer appears in temp bytes; the
    # fused_encode="off" A/B arm documents what it cost — see
    # telemetry/memory_ledger.py SKETCH_ENCODE_FUSED). With telemetry on
    # the JitWatcher already captured both at the warmup compile (and
    # instrument() replaced runtime._round with the watcher's closure,
    # which has no .lower) — read its channels like bench.py does; only
    # the bare path pays a lower+compile, near-free under the persistent
    # compile cache. NOTE the same scan caveat as flops: XLA's
    # bytes-accessed counts each scan body once, so the measured
    # arithmetic intensity is an UPPER bound for the scanned round.
    if telemetry is not None:
        w = telemetry.watcher()
        nbytes = w.bytes.get("round_step")
        mledger = w.memory.get("round_step")
    else:
        from commefficient_tpu.telemetry.memory_ledger import \
            ledger_from_compiled
        compiled = runtime._round.lower(
            runtime.init_state(), ids, batch, mask,
            jnp.asarray(0.1, jnp.float32), runtime.cs).compile()
        nbytes = compiled.cost_analysis().get("bytes accessed")
        mledger = ledger_from_compiled(compiled)
    from commefficient_tpu.telemetry.utilization import roofline_fields
    from bench_common import peak_hbm_gbps as _peak_hbm
    roof = roofline_fields(
        rounds=n_rounds, wall_s=dt, flops_per_round=flops,
        bytes_per_round=(float(nbytes) if nbytes else None),
        bytes_source="cost_analysis",
        peak_flops=peak, peak_hbm_gbps=_peak_hbm(jax.devices()[0]))
    if roof["bound"] is not None:
        log(f"roofline: AI {roof['arithmetic_intensity']:.1f} FLOP/B "
            f"(ridge {roof['ridge_intensity']:.1f}) -> {roof['bound']}-"
            f"bound, bw_frac {roof['bw_frac']}")

    result = {
        "metric": "gpt2_sketch_round_throughput",
        "value": round(tps, 1),
        "unit": "tokens/sec",
        "vs_baseline": round(tps / NOMINAL_SINGLE_GPU_TOK_PER_SEC, 3),
        "mfu": round(mfu, 4) if np.isfinite(mfu) else None,
        "tokens_per_round": W * B * NC * S,
        "timed_rounds": n_rounds,
        # quantized-wire arm identity (schema v9 / ISSUE 14): the table
        # wire dtype and the exact per-round simulated upload payload
        "wire_dtype": cfg.wire_dtype,
        "wire_bytes_per_round": W * cfg.upload_wire_bytes(
            runtime._wire_block or None),
        "warmup_s": warmup_s,
        "phase_split": phases,
        "input_wait_frac": round(phases["host_s"] / dt, 6),
        "roofline": roof,
        "memory_ledger": mledger,
        "dryrun": dryrun,
        # the sweep knobs this arm ran under (scripts/gpt2_mfu_sweep.py)
        "config": {"remat": remat, "remat_policy": remat_policy,
                   "microbatch": microbatch, "lm_chunk": lm_chunk,
                   "fused_encode": fused_encode},
    }
    if telemetry is not None:
        from commefficient_tpu.telemetry.utilization import emit_from_totals
        emit_from_totals(
            telemetry, rnd=n_rounds, rounds=n_rounds, wall_s=dt,
            host_s=phases["host_s"], dispatch_s=phases["dispatch_s"],
            device_s=phases["device_wait_s"],
            flops_per_round=flops, flops_source="analytic",
            device_kind=getattr(jax.devices()[0], "device_kind", "unknown"),
            bytes_per_round=(float(nbytes) if nbytes else None),
            bytes_source="cost_analysis")
        telemetry.bench_event(result["metric"], result,
                              wire_dtype=cfg.wire_dtype)
    return result


def ledger_ab(dryrun: bool = False) -> dict:
    """Compile-only fused-vs-unfused A/B of the split round's COHORT
    executable at a PARAMETER-DOMINATED GPT-2 geometry — the committed
    proof the dense-gradient floor moved (runs/BREAKDOWN_gpt2.md
    §Round 7).

    The throughput sweep's smoke geometry (GPT2Config.small, 4x4x2x64)
    cannot show the win: there d*4 is ~0.5 MB against ~10 MB of
    activation working set, and backward-scheduling noise at that scale
    is larger than the dense gradient itself. This A/B instead uses the
    geometry class the fusion exists for — parameters >> activations
    (the flagship 124M round is d*4 ~0.5 GB against ~tens of MB of
    remat'd activations): ``dryrun=True`` runs a mid-size GPT-2
    (d ~5.6M, one 32-token dialogue, microbatch 1) that compiles in
    ~a minute on the CPU container; ``dryrun=False`` uses the flagship
    config and round shape (TPU: the cohort compile is the same one the
    bench pays, cache-shared). Nothing executes — the ledger reads
    ``memory_analysis()`` off the compiled executables."""
    import jax
    import jax.numpy as jnp
    from jax.flatten_util import ravel_pytree

    from commefficient_tpu.config import FedConfig
    from commefficient_tpu.core import FedRuntime
    from commefficient_tpu.losses import make_gpt2_train_loss
    from commefficient_tpu.models.gpt2 import GPT2Config, GPT2DoubleHeads
    from commefficient_tpu.telemetry.memory_ledger import \
        ledger_from_compiled

    if dryrun:
        gcfg = GPT2Config(vocab_size=8192, n_positions=128, n_embd=256,
                          n_layer=4, n_head=4, remat=True)
        W, B, NC, S, mb = 1, 1, 1, 32, 1
        sketch_kw = dict(k=5_000, num_rows=3, num_cols=262_144,
                         num_blocks=8)
    else:
        gcfg = GPT2Config(remat=True)
        W, B, NC, S, mb = 8, 8, 2, 256, 8
        sketch_kw = dict(k=50_000, num_rows=5, num_cols=524_288,
                         num_blocks=20)
    model = GPT2DoubleHeads(gcfg)
    rng = np.random.RandomState(0)
    V = gcfg.vocab_size
    batch = {
        "input_ids": jnp.asarray(
            rng.randint(0, V, (W, B, NC, S)), jnp.int32),
        "mc_token_ids": jnp.asarray(rng.randint(0, S, (W, B, NC)),
                                    jnp.int32),
        "lm_labels": jnp.asarray(
            rng.randint(0, V, (W, B, NC, S)), jnp.int32),
        "mc_label": jnp.asarray(rng.randint(0, NC, (W, B)), jnp.int32),
        "token_type_ids": jnp.asarray(
            rng.randint(0, 2, (W, B, NC, S)), jnp.int32),
    }
    params = model.init(jax.random.PRNGKey(0), batch["input_ids"][0, :1],
                        batch["mc_token_ids"][0, :1],
                        batch["token_type_ids"][0, :1])
    d = ravel_pytree(params)[0].shape[0]
    mask = jnp.ones((W, B), bool)
    ids = jnp.arange(W, dtype=jnp.int32)
    rec = {"metric": "gpt2_fused_encode_ledger_ab", "d": int(d),
           "dense_grad_bytes": int(d) * 4, "dryrun": dryrun,
           "round_shape": [W, B, NC, S], "microbatch": mb,
           "arms": {}}
    for fe in ("auto", "off"):
        cfg = FedConfig(mode="sketch", error_type="virtual",
                        local_momentum=0.0, virtual_momentum=0.9,
                        weight_decay=0.0, num_workers=W,
                        local_batch_size=B, microbatch_size=mb,
                        num_clients=100, track_bytes=False,
                        approx_topk=True, num_results_train=2,
                        lm_chunk=min(128, S), sketch_fused_encode=fe,
                        async_agg=True, max_inflight=1, buffer_goal=1,
                        telemetry=False, **sketch_kw)
        runtime = FedRuntime(
            cfg, params, make_gpt2_train_loss(model, lm_chunk=cfg.lm_chunk),
            num_clients=cfg.num_clients)

        compiled = runtime._cohort.lower(
            runtime.init_state(), ids, batch, mask,
            jnp.asarray(0.1, jnp.float32), runtime.cs).compile()
        led = ledger_from_compiled(compiled)
        rec["arms"][fe] = led
        t = (led or {}).get("temp_bytes")
        log(f"ledger_ab fe={fe}: cohort temp {t} "
            f"({t / (d * 4):.2f}x d*4)" if t is not None else
            f"ledger_ab fe={fe}: no ledger")
    a, o = rec["arms"].get("auto") or {}, rec["arms"].get("off") or {}
    if a.get("temp_bytes") is not None and o.get("temp_bytes") is not None:
        rec["temp_drop_bytes"] = o["temp_bytes"] - a["temp_bytes"]
        rec["drop_covers_dense_grad"] = bool(
            rec["temp_drop_bytes"] >= d * 4)
        log(f"ledger_ab: temp drop {rec['temp_drop_bytes']} B vs dense "
            f"grad {d * 4} B -> covers: {rec['drop_covers_dense_grad']}")
    return rec


def main(argv=None):
    import argparse

    from bench import add_bench_args, make_bench_telemetry
    ap = argparse.ArgumentParser(description=__doc__)
    add_bench_args(ap)
    args = ap.parse_args(argv)
    telemetry, profiler = make_bench_telemetry(args, "bench_gpt2")
    result = run(telemetry=telemetry, profiler=profiler,
                 compile_cache=args.compile_cache,
                 wire_dtype=args.wire_dtype)
    if telemetry is not None:
        telemetry.write_summary(aborted=False,
                                n_rounds=result["timed_rounds"],
                                final=result)
        telemetry.close()
    print(json.dumps(result))


if __name__ == "__main__":
    main()
