#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the federated round still runs
on the chip.

Drives the two drivers a user would call, in this one process, at the full
width of the models they train:

- Stage A: ``commefficient_tpu.cv_train.main`` — ResNet-9 (full channels,
  d = 6.57M), FetchSGD sketch 5 x 500,736, 8 clients x 64 images, the
  eight rounds of one epoch and a validation pass over the synthetic
  CIFAR10 the dataset generates from its seed when there is no network.
- Stage B: ``commefficient_tpu.gpt2_train.main`` — GPT-2 12 layers x 768,
  S = 256, microbatch scan + remat + chunked cross-entropy, sketch
  5 x 524,288, four rounds and a validation pass over a corpus written by
  ``scripts/make_persona_corpus.py`` from its seed. Offline the tokenizer
  is the hash tokenizer, so the vocabulary is 8,197 and d = 92.1M: the
  widths, depth and sketch are the flagship's, the vocabulary is NOT
  GPT-2's 50,262.
- With more than one chip visible: Stage A once more over a mesh of all
  of them.

Each stage ASSERTS (no try/except around a stage; the first failure is
the exit): the driver returned a summary; every per-round loss read back
from the run's telemetry stream is finite and the last differs from the
first; every array of the final state lives on a TPU; the sketch reports
the Pallas path and the compiled round holds both Mosaic custom calls
(encode and decode), so a kernel that gave way to the XLA rolls fails
here instead of passing 6x slower; the device reports a non-zero memory
peak. The times it prints are smoke timings (the drivers' own clocks,
each ending in block_until_ready), not benchmark results.

Without a TPU it refuses: exit code 4 before any model is built.
``--rehearse`` runs the same stages at the drivers' ``--test`` size on
whatever backend there is, to debug this script; it proves nothing, says
so, and cannot print the pass line.

The last line of stdout on success is the JSON the driver reads:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import importlib.metadata
import importlib.util
import json
import math
import os
import shutil
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(REPO, "chip_smoke_out")

CV_ARGS = [
    "--dataset_name", "CIFAR10", "--model", "ResNet9", "--mode", "sketch",
    "--error_type", "virtual", "--local_momentum", "0",
    "--virtual_momentum", "0.9", "--num_workers", "8",
    "--local_batch_size", "64", "--k", "50000", "--num_rows", "5",
    "--num_cols", "500000", "--approx_topk",
    "--synthetic_per_class", "512", "--valid_batch_size", "512",
    "--num_epochs", "1",
]
GPT2_ARGS = [
    "--mode", "sketch", "--error_type", "virtual", "--local_momentum", "0",
    "--virtual_momentum", "0.9", "--weight_decay", "0",
    "--num_workers", "8", "--local_batch_size", "8",
    "--microbatch_size", "8", "--max_seq_len", "256", "--remat",
    "--lm_chunk", "128", "--num_cols", "524288", "--num_rows", "5",
    "--k", "50000", "--approx_topk",
    # 64 personalities / 8 clients a round = 8 rounds an epoch
    "--num_epochs", "0.5",
]
# every round synced and recorded, kernels required: what turns a run of
# a driver into a checked one
CHECKED = ["--telemetry_every", "1", "--pallas", "on"]


def say(*a):
    print("[chip_smoke]", *a, flush=True)


def read_events(logdir):
    with open(os.path.join(logdir, "telemetry.jsonl")) as f:
        return [json.loads(line) for line in f]


def check_losses(events, min_rounds):
    """Finite, enough of them, and moving. Returns the loss list."""
    losses = [e["loss"] for e in events if e["event"] == "round"]
    assert len(losses) >= min_rounds, (len(losses), min_rounds)
    assert all(x is not None and math.isfinite(x) for x in losses), losses
    # (a --test rehearsal is one round long)
    assert min_rounds == 1 or losses[-1] != losses[0], (
        f"loss never moved: {losses}")
    return losses


def report_times(stage, events):
    """Compile and round times from the driver's own clocks: each round
    record's dispatch_s + device_s ends in block_until_ready."""
    compiles = {e["name"]: e["lower_s"] + e["compile_s"]
                for e in events if e["event"] == "compile"}
    rounds = [e["dispatch_s"] + e["device_s"]
              for e in events if e["event"] == "round"]
    say(f"{stage}: smoke timing (not a benchmark result): lower+compile "
        + ", ".join(f"{k} {v:.1f} s" for k, v in compiles.items())
        + f"; compile+first round {rounds[0]:.1f} s; steady round mean "
        f"{1e3 * sum(rounds[1:]) / max(len(rounds) - 1, 1):.1f} ms over "
        f"{len(rounds) - 1} rounds (each synced for its record)")


def check_on_chip(stage, runtime, state, n_devices=1):
    """What only the live run can show. Called by the driver through
    ``main(on_finish=...)`` once training has returned."""
    import jax
    from commefficient_tpu.ops.circulant_pallas import (DECODE_KERNEL_NAME,
                                                        ENCODE_KERNEL_NAME,
                                                        encode_hbm_bytes)
    from commefficient_tpu.telemetry.collectives import ledger_from_hlo

    platforms = {d.platform for leaf in jax.tree_util.tree_leaves(state)
                 for d in leaf.devices()}
    assert platforms == {"tpu"}, f"final state lives on {platforms}"
    cs = runtime.cs
    assert cs.kernel_path == "pallas", cs.pallas_blocker()
    hlo = runtime.compile_watcher.executables["round_step"].as_text()
    n_mosaic = hlo.count('custom_call_target="tpu_custom_call"')
    say(f"{stage}: sketch {cs.r} x {cs.c} over d = {cs.d} (m = {cs.m} "
        f"blocks), kernel path {cs.kernel_path}; compiled round holds "
        f"{n_mosaic} Mosaic custom call(s)")
    moved = encode_hbm_bytes(cs.c, cs.r, cs.m)
    say(f"{stage}: one encode call moves {moved} HBM bytes by its "
        f"BlockSpecs, {moved / (4 * (cs.d + cs.r * cs.c)):.3f} x the "
        "vector and the table once each")
    if n_devices == 1:
        assert n_mosaic >= 2, n_mosaic
        assert ENCODE_KERNEL_NAME in hlo and DECODE_KERNEL_NAME in hlo
    else:
        # on a mesh the sharded server tail decodes its range with the
        # decode kernel too (core/server.sharded_sketch_server_update);
        # the replicated tail, under GSPMD, with XLA rolls
        assert n_mosaic >= 1 and ENCODE_KERNEL_NAME in hlo, n_mosaic
        assert (DECODE_KERNEL_NAME in hlo) == runtime._server_tail_pallas
        for name in ("ps_weights", "Vvelocity", "Verror"):
            span = len(getattr(state, name).sharding.device_set)
            assert span == n_devices, (name, span, n_devices)
        # the table reduce must cross the chips. The round asks for a
        # reduce-scatter of the (r, c) table (psum_scatter); the TPU
        # compiler may emit it under that name or as an all-reduce of
        # the whole table — either proves the aggregation is one
        # program over n chips, and which it was is said, not assumed
        ledger = ledger_from_hlo(hlo)
        kinds = sorted({e["kind"] for e in ledger})
        say(f"{stage}: collectives in the compiled round: "
            + ", ".join(f"{sum(e['kind'] == k for e in ledger)} {k}"
                        for k in kinds))
        table = cs.r * cs.c
        reduces = [e for e in ledger
                   if (e["kind"], e["n_elements"]) in (
                       ("reduce-scatter", table // n_devices),
                       ("all-reduce", table))]
        assert reduces, [(e["kind"], e["n_elements"]) for e in ledger]
        say(f"{stage}: table reduce compiled as "
            + ", ".join(f"{e['kind']} of {e['dtype']}[{e['n_elements']}]"
                        for e in reduces))
    for dev in jax.devices()[:n_devices]:
        stats = dev.memory_stats()
        assert stats["bytes_in_use"] > 0, (dev, stats)
        assert stats["peak_bytes_in_use"] > 0, (dev, stats)
        # the runtime books a program's temporaries as reserved, not
        # in use: the high-water mark of the chip is the sum
        say(f"{stage}: {dev} peak_bytes_in_use "
            f"{stats['peak_bytes_in_use'] / 2**30:.2f} GiB + "
            f"peak_bytes_reserved "
            f"{stats['peak_bytes_reserved'] / 2**30:.2f} GiB of "
            f"{stats['bytes_limit'] / 2**30:.2f} GiB")


def run_stage(stage, main, argv, min_rounds, rehearse, n_devices=1):
    """One driver run, checked. Returns the per-round losses."""
    logdir = os.path.join(OUT, stage)
    argv = argv + ["--logdir", logdir] + CHECKED
    if rehearse:
        argv.append("--test")
        min_rounds = 1
    say(f"{stage}: {main.__module__}.main({' '.join(argv)})")
    t0 = time.perf_counter()
    summary = main(
        argv, on_finish=None if rehearse else
        lambda runtime, state, _: check_on_chip(stage, runtime, state,
                                                n_devices))
    wall = time.perf_counter() - t0
    assert summary is not None, f"{stage}: the driver returned no summary"
    events = read_events(logdir)
    losses = check_losses(events, min_rounds)
    say(f"{stage}: {len(losses)} rounds, loss {losses[0]:.8f} -> "
        f"{losses[-1]:.8f}, validation loss {summary['test_loss']:.4f}; "
        f"{wall:.0f} s wall in the driver")
    report_times(stage, events)
    return losses


def write_corpus(out_dir):
    spec = importlib.util.spec_from_file_location(
        "make_persona_corpus",
        os.path.join(REPO, "scripts", "make_persona_corpus.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "personachat_self_original.json"),
              "w") as f:
        json.dump(mod.make_corpus(n_train=64, n_valid=8, seed=17), f)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="run the stages at --test size on any backend to "
                         "debug this script; proves nothing, never passes")
    args = ap.parse_args()

    import jax
    import jaxlib
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    say(f"device: {json.dumps(device)}; jax {jax.__version__}, jaxlib "
        f"{jaxlib.__version__}, libtpu "
        f"{importlib.metadata.version('libtpu')}")
    if jax.default_backend() != "tpu" and not args.rehearse:
        say("no TPU: JAX's default backend is "
            f"{jax.default_backend()!r}. This script checks the program "
            "on the chip and does not run its stages anywhere else.")
        return 4

    from commefficient_tpu import cv_train, gpt2_train
    from commefficient_tpu.config import (DEFAULT_COMPILATION_CACHE_DIR,
                                          enable_compilation_cache_dir)
    say("compile cache: "
        f"{enable_compilation_cache_dir(DEFAULT_COMPILATION_CACHE_DIR)}")
    shutil.rmtree(OUT, ignore_errors=True)
    if args.rehearse:
        say("REHEARSAL at --test size: no device assertion runs; this "
            "proves nothing about the chip")

    cifar = ["--dataset_dir", os.path.join(OUT, "cifar")]
    losses_a = run_stage("stage_a_resnet9", cv_train.main, CV_ARGS + cifar,
                         8, args.rehearse)

    write_corpus(os.path.join(OUT, "persona"))
    from commefficient_tpu.data.fed_persona import get_tokenizer
    tok = get_tokenizer()
    say(f"stage_b_gpt2: tokenizer {type(tok).__name__}, vocabulary "
        f"{len(tok)}" + ("" if len(tok) > 50000 else " — NOT GPT-2's 50,262"))
    run_stage("stage_b_gpt2", gpt2_train.main,
              GPT2_ARGS + ["--dataset_dir", os.path.join(OUT, "persona")],
              3, args.rehearse)

    n = device["count"]
    if n > 1:
        losses_m = run_stage(f"stage_a_mesh{n}", cv_train.main,
                             CV_ARGS + cifar + ["--mesh_shape", str(n)],
                             8, args.rehearse, n_devices=n)
        rel = abs(losses_m[0] - losses_a[0]) / abs(losses_a[0])
        say(f"stage_a_mesh{n}: first-round loss {losses_m[0]:.8f} vs one "
            f"chip {losses_a[0]:.8f} (relative difference {rel:.1e})")
        assert rel <= 1e-3, rel

    if args.rehearse:
        say("rehearsal finished; it is not a pass")
        return 5
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
