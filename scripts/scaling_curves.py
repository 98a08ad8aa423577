#!/usr/bin/env python
"""Weak/strong scaling curves for the sharded sketch round.

ROADMAP item 2's committed evidence harness: run the SAME sharded
sketch federated round over meshes of growing device count and record
throughput, per-chip throughput, the compiled round's collective
inventory and schema-v7 ``utilization`` events — then gate the weak
curve's per-chip throughput with ``teleview diff --perchip_drop``.

Arms (each a SUBPROCESS, because the virtual device count must be
pinned in ``XLA_FLAGS`` before jax initializes — the exact flags a real
multi-chip slice run drops in favor of its physical topology):

- **weak scaling**: clients grow with the mesh (W = 2n, fixed
  per-client batch) — per-chip work constant, so per-chip throughput
  staying flat is the "added chips add capacity" contract;
- **strong scaling**: a fixed client population (W = 8) sharded over
  1..n devices — total work constant, wall time should fall.

On this container the "chips" are ``--xla_force_host_platform_device_
count`` virtual CPU devices sharing one socket, so the committed curve
validates the HARNESS — arm mechanics, collective shapes (the
reduce-scattered table + candidate gathers land in every arm's
ledger), schema-v7 per-chip fields, the teleview gate wiring — and
bounds scheduling overhead, NOT ICI bandwidth. A real v5e slice runs
the identical script with no XLA_FLAGS override; the gate threshold
then tightens from the virtual-device default (see --perchip_drop).

Usage:
    python scripts/scaling_curves.py --out runs/scaling_dryrun.jsonl
    python scripts/scaling_curves.py --arm weak --n 4 --stream DIR  # internal

The launcher writes one JSONL line per arm plus a final ``gate`` line
recording the teleview verdict; ``__graft_entry__.dryrun_multichip``
asserts the committed artifact carries a weak curve whose gate passed.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DEFAULT_DEVICES = (1, 2, 4, 8)
STRONG_WORKERS = 8      # fixed population for the strong arms
WEAK_PER_DEVICE = 2     # clients per device for the weak arms
BATCH = 8
# per-chip drop tolerance for the committed VIRTUAL-device weak curve:
# the 2->8 device arms share one CPU socket, so the gate bounds
# harness/scheduling overhead, not ICI (measured headroom over the
# observed drop; a real slice passes a far tighter threshold — see the
# module docstring and runs/BREAKDOWN_scaling.md)
DRYRUN_PERCHIP_DROP = 0.55
# int8 table-reduce wire-byte ceiling vs the f32 arm (scales included):
# the ISSUE-14 contract, shared with __graft_entry__._wire_gate
WIRE_BYTES_CEILING = 0.30


def _configure(n: int) -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()


def run_arm(scaling: str, n: int, stream_dir: str, rounds: int,
            warmup: int, wire_dtype: str = "float32",
            compile_cache: str = "") -> None:
    """One arm: n-device mesh, the sharded sketch round, telemetry +
    timing; prints a ``RESULT {...}`` line the launcher collects."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from commefficient_tpu import models
    from commefficient_tpu.config import (FedConfig,
                                          enable_compilation_cache_dir)
    from commefficient_tpu.core import FedRuntime
    from commefficient_tpu.losses import make_cv_loss
    from commefficient_tpu.parallel import make_mesh
    from commefficient_tpu.telemetry import RunTelemetry, UtilizationTracker
    from commefficient_tpu.telemetry.schema import validate_file

    assert len(jax.devices()) == n, (len(jax.devices()), n)
    # persistent XLA compile cache: without it EVERY subprocess arm pays
    # the cold round compile — the launcher threads --compile_cache
    # through so repeat sweeps start warm; warmup_s below records what
    # was paid
    enable_compilation_cache_dir(compile_cache)
    mesh = make_mesh((n,), ("clients",)) if n > 1 else None

    W = WEAK_PER_DEVICE * n if scaling == "weak" else STRONG_WORKERS
    model = models.ResNet9(num_classes=10,
                           channels={"prep": 4, "layer1": 8,
                                     "layer2": 8, "layer3": 8})
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 32, 32, 3), jnp.float32))
    cfg = FedConfig(mode="sketch", error_type="virtual",
                    local_momentum=0.0, virtual_momentum=0.9,
                    weight_decay=0.0, num_workers=W, local_batch_size=BATCH,
                    k=8, num_rows=3, num_cols=512, num_blocks=2,
                    num_clients=2 * W, track_bytes=False,
                    wire_dtype=wire_dtype)
    runtime = FedRuntime(cfg, params, make_cv_loss(model, "float32"),
                         num_clients=cfg.num_clients, mesh=mesh)
    state = runtime.init_state()

    tel = RunTelemetry(stream_dir, "scaling_arm", cfg=runtime.cfg)
    tel.instrument(runtime)
    util = UtilizationTracker(tel, peak_flops=1e12, peak_hbm_gbps=100.0,
                              watcher=tel.watcher(), n_devices=n,
                              mesh_shape=[n])

    key = jax.random.PRNGKey(0x5CA1)

    def batch_for(g):
        k1, k2 = jax.random.split(jax.random.fold_in(key, g))
        return {"image": jax.random.normal(k1, (W, BATCH, 32, 32, 3),
                                           jnp.float32),
                "target": jax.random.randint(k2, (W, BATCH), 0, 10,
                                             jnp.int32)}

    ids = jnp.arange(W, dtype=jnp.int32)
    mask = jnp.ones((W, BATCH), bool)

    tw = time.perf_counter()
    for g in range(1, warmup + 1):          # compile + cache warm
        state, m = runtime.round(state, ids, batch_for(g), mask, 0.1)
    jax.block_until_ready(m["results"][0])
    # compile + warmup wall seconds BEFORE the timed window — the
    # number --compile_cache exists to shrink (tracked per arm so the
    # cold-compile tax of a sweep is visible in the committed artifact)
    warmup_s = time.perf_counter() - tw

    t0 = time.perf_counter()
    for g in range(warmup + 1, warmup + rounds + 1):
        r0 = time.perf_counter()
        state, m = runtime.round(state, ids, batch_for(g), mask, 0.1)
        r1 = time.perf_counter()
        jax.block_until_ready(m["results"][0])
        util.observe_round(host_s=0.0, dispatch_s=r1 - r0,
                           device_s=time.perf_counter() - r1)
    wall = time.perf_counter() - t0
    util.emit(warmup + rounds)

    losses = np.asarray(m["results"][0])
    assert np.all(np.isfinite(losses)), losses
    items = W * BATCH * rounds
    result = {
        "scaling": scaling,
        "devices": n,
        "num_workers": W,
        "batch": BATCH,
        "rounds": rounds,
        "wire_dtype": wire_dtype,
        "warmup_s": round(warmup_s, 3),
        "wall_s": round(wall, 6),
        "items_per_s": round(items / wall, 3),
        "per_chip_items_per_s": round(items / wall / n, 3),
        "round_ms": round(1e3 * wall / rounds, 3),
        "sharded_server": bool(runtime._sharded_server),
        "d": int(cfg.grad_size),
        "final_loss": float(losses.mean()),
    }
    # collective inventory of the compiled round: the JitWatcher parsed
    # it at the warmup compile and emitted it into the arm's own stream
    # (instrument() swapped _round for its closure, so a fresh .lower()
    # is unavailable — the PR-8 bench_gpt2 lesson; the stream IS the
    # record)
    counts = {}
    table_reduce_bytes = None
    with open(tel.path) as f:
        for ln in f:
            e = json.loads(ln)
            if (e.get("event") == "collectives"
                    and e.get("name") == "round_step"):
                counts = e.get("counts") or {}
                table_reduce_bytes = e.get("table_reduce_bytes")
    result["collectives"] = counts
    result["table_reduce_bytes"] = table_reduce_bytes
    if mesh is not None:
        assert runtime._sharded_server, "sharded server lost eligibility"
        if wire_dtype == "int8":
            # the quantized wire REPLACES the reduce-scatter with the
            # int8 all_to_all pair — a reduce-scatter here means the
            # f32 reduce silently came back
            assert counts.get("all-to-all", 0) >= 2, (
                "the int8 arm compiled without the quantized all_to_all "
                f"reduce: {counts}")
            assert counts.get("reduce-scatter", 0) == 0, (
                "the int8 arm still compiled the f32 reduce-scatter — "
                f"the quantized wire is not engaged: {counts}")
        else:
            assert counts.get("reduce-scatter", 0) >= 1, (
                "the sharded sketch round compiled without its "
                f"reduce-scattered table aggregation: {counts}")
    tel.bench_event("scaling_arm", result)
    tel.write_summary(aborted=False, n_rounds=warmup + rounds)
    tel.close()
    assert validate_file(tel.path) == [], "arm stream schema-invalid"
    print("RESULT " + json.dumps(result), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arm", choices=("weak", "strong"), default=None,
                    help="internal: run one arm in THIS process")
    ap.add_argument("--n", type=int, default=1)
    ap.add_argument("--stream", default=None,
                    help="internal: arm telemetry directory")
    ap.add_argument("--out", default="runs/scaling_dryrun.jsonl")
    ap.add_argument("--devices", default=",".join(map(str,
                                                      DEFAULT_DEVICES)))
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--wire_dtype", default="float32",
                    help="comma list of table wire dtypes to sweep "
                         "(float32,bfloat16,int8); non-f32 dtypes run "
                         "the WEAK arms only (the per-chip contract is "
                         "the weak curve; int8's own gate compares its "
                         "table-reduce wire bytes against the f32 arm)")
    ap.add_argument("--compile_cache",
                    default=os.path.join(
                        os.path.dirname(os.path.dirname(
                            os.path.abspath(__file__))), ".jax_cache"),
                    help="persistent XLA compile cache DIR threaded "
                         "into every subprocess arm (empty string "
                         "disables — each arm then pays the cold round "
                         "compile recorded as its warmup_s)")
    ap.add_argument("--workdir", default=None,
                    help="keep arm telemetry streams here; without it "
                         "the streams live in a temp dir that is "
                         "deleted after the gate runs (the JSONL is "
                         "the committed record)")
    ap.add_argument("--perchip_drop", type=float,
                    default=DRYRUN_PERCHIP_DROP)
    args = ap.parse_args()

    if args.arm is not None:
        _configure(args.n)
        run_arm(args.arm, args.n, args.stream or tempfile.mkdtemp(),
                args.rounds, args.warmup,
                wire_dtype=args.wire_dtype.split(",")[0],
                compile_cache=args.compile_cache)
        return 0

    # ------------------------------------------------------- launcher
    devices = [int(x) for x in args.devices.split(",") if x]
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    script = os.path.abspath(__file__)

    wire_dtypes = [w for w in args.wire_dtype.split(",") if w]
    workdir = args.workdir or tempfile.mkdtemp(prefix="scaling_")
    os.makedirs(workdir, exist_ok=True)
    lines = []
    streams = {}
    for wire in wire_dtypes:
        for scaling in ("weak", "strong"):
            if scaling == "strong" and wire != "float32":
                # non-f32 wires sweep the weak arms only: the per-chip
                # contract is the weak curve, and the int8 wire gate
                # below compares against the f32 weak arm directly
                continue
            for n in devices:
                if scaling == "strong" and STRONG_WORKERS % n:
                    print(f"skip strong n={n}: {STRONG_WORKERS} clients "
                          "not divisible")
                    continue
                sdir = os.path.join(workdir, f"{scaling}_{wire}_n{n}")
                os.makedirs(sdir, exist_ok=True)
                cmd = [sys.executable, script, "--arm", scaling,
                       "--n", str(n), "--stream", sdir,
                       "--rounds", str(args.rounds),
                       "--warmup", str(args.warmup),
                       "--wire_dtype", wire,
                       "--compile_cache", args.compile_cache]
                t0 = time.perf_counter()
                p = subprocess.run(cmd, env=env, cwd=repo,
                                   capture_output=True,
                                   text=True, timeout=1200)
                if p.returncode != 0:
                    print(p.stdout[-3000:])
                    print(p.stderr[-3000:])
                    print(f"{scaling} {wire} n={n} FAILED "
                          f"(rc={p.returncode})")
                    return 1
                rline = [ln for ln in p.stdout.splitlines()
                         if ln.startswith("RESULT ")]
                assert rline, p.stdout[-2000:]
                rec = json.loads(rline[0][len("RESULT "):])
                rec["kind"] = "arm"
                rec["dryrun"] = True
                rec["backend"] = "cpu-virtual"
                rec["arm_wall_s"] = round(time.perf_counter() - t0, 3)
                lines.append(rec)
                streams[(scaling, wire, n)] = os.path.join(
                    sdir, "telemetry.jsonl")
                print(f"{scaling:6s} {wire:8s} n={n}: "
                      f"{rec['items_per_s']:9.1f} img/s "
                      f"({rec['per_chip_items_per_s']:8.1f}/chip), "
                      f"round {rec['round_ms']:.1f} ms, "
                      f"warmup {rec['warmup_s']:.1f} s, "
                      f"collectives {rec['collectives']}")

    # ---- the weak-scaling per-chip gate: teleview diff between the
    # smallest MULTI-device weak arm (same compiled program family —
    # n=1 compiles no collectives, so its ledger diff would be
    # vacuously different) and the largest. Every other diff gate is
    # slackened wide: arms at different scales legitimately differ in
    # norms/MFU/bytes, and the per-chip contract is what this
    # comparison is FOR.
    multi = sorted(n for s, w, n in streams
                   if s == "weak" and w == "float32" and n > 1)
    rc = None
    if len(multi) >= 2:
        base_n, cand_n = multi[0], multi[-1]
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "teleview", os.path.join(repo, "scripts", "teleview.py"))
        tv = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(tv)
        rc = tv.main(["diff", streams[("weak", "float32", base_n)],
                      streams[("weak", "float32", cand_n)],
                      "--perchip_drop", str(args.perchip_drop),
                      "--mfu_drop", "0.95", "--signal_ratio", "1000",
                      "--loss_ratio", "1000", "--bytes_ratio", "1000",
                      "--temp_bytes_growth", "1000",
                      "--wire_bytes_growth", "1000",
                      "--count_slack", "0"])
        lines.append({"kind": "gate", "gate": "teleview_diff_perchip",
                      "scaling": "weak", "baseline_devices": base_n,
                      "candidate_devices": cand_n,
                      "perchip_drop": args.perchip_drop,
                      "rc": rc, "passed": rc == 0})
        print(f"weak-scaling per-chip gate (n={base_n} -> n={cand_n}, "
              f"drop <= {args.perchip_drop:.0%}): "
              f"{'PASS' if rc == 0 else 'FAIL'}")

    # ---- the int8 wire gate: at the largest shared weak-arm device
    # count, the int8 arm's ledger-measured table-reduce wire bytes
    # must sit at <= WIRE_BYTES_CEILING of the f32 arm's (scales
    # included) — the committed form of ISSUE-14's dryrun gate
    wire_rc = None
    if "int8" in wire_dtypes:
        shared = sorted(n for s, w, n in streams
                        if s == "weak" and w == "int8" and n > 1
                        and ("weak", "float32", n) in streams)
        if shared:
            n = shared[-1]
            by_arm = {}
            for w in ("float32", "int8"):
                rec = next(ln for ln in lines
                           if ln.get("kind") == "arm"
                           and ln.get("scaling") == "weak"
                           and ln.get("wire_dtype") == w
                           and ln.get("devices") == n)
                by_arm[w] = rec.get("table_reduce_bytes")
            ok = (by_arm["float32"] and by_arm["int8"]
                  and by_arm["int8"] <= WIRE_BYTES_CEILING
                  * by_arm["float32"])
            wire_rc = 0 if ok else 1
            lines.append({"kind": "gate", "gate": "wire_bytes_int8",
                          "devices": n,
                          "ceiling": WIRE_BYTES_CEILING,
                          "f32_table_reduce_bytes": by_arm["float32"],
                          "int8_table_reduce_bytes": by_arm["int8"],
                          "rc": wire_rc, "passed": ok})
            print(f"int8 wire gate (n={n}): table-reduce "
                  f"{by_arm['int8']} B vs f32 {by_arm['float32']} B "
                  f"(ceiling {WIRE_BYTES_CEILING:.2f}x): "
                  f"{'PASS' if ok else 'FAIL'}")

    with open(args.out, "w") as f:
        for rec in lines:
            f.write(json.dumps(rec, sort_keys=True) + "\n")
            f.flush()
            os.fsync(f.fileno())
    if args.workdir is None:
        # the JSONL is the committed record; unrequested stream dirs
        # must not accumulate in /tmp across runs
        import shutil
        shutil.rmtree(workdir, ignore_errors=True)
        where = "(streams deleted; pass --workdir to keep them)"
    else:
        where = f"arm streams in {workdir}"
    print(f"wrote {args.out} ({len(lines)} lines); {where}")
    if wire_rc not in (0, None):
        return 1
    return 1 if rc not in (0, None) else 0


if __name__ == "__main__":
    sys.exit(main())
