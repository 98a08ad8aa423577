#!/usr/bin/env python3
"""The benchmark's one command.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

runs one cell of ``BENCHMARK.json`` on the chips of this machine and prints,
as the last line of standard output, one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device`` (``--trace 1``: the
per-layer metrics and a ``breakdown``). Everything else it has to say goes
on earlier lines, prefixed ``[perfbench]``. See ``perfbench/README.md``.

Without a TPU, or with fewer chips than the cell asks for, it exits with
code 3 before building a model and prints no result. ``--rehearse`` walks
the same code at tiny sizes on the CPU (four virtual devices) to debug the
harness; it proves nothing, says so, exits 5 and never prints the result
line.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse      # noqa: E402
import importlib     # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import shutil        # noqa: E402
import sys           # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)
EXIT_REHEARSAL = 5


def say(*a):
    print("[perfbench]", *a, flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="length of the measured window (default: "
                         "run_seconds of BENCHMARK.json)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--dump-trace", default="",
                    help="also write the traced stretch in the reducer's "
                         "plain JSON form to this path (to cut a test "
                         "trace from)")
    args = ap.parse_args(argv)

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4")

    from perfbench.harness import (arith, checks, devices, readers, spec,
                                   timing, traceio, tracered)
    bench = spec.load_benchmark()
    cell = spec.Cell(bench, args.workload)
    seconds = args.seconds if args.seconds is not None else bench[
        "run_seconds"]
    config, traffic = cell.config, cell.traffic
    flags = cell.flags + ["--seed", str(args.seed)]
    if args.rehearse:
        say("REHEARSAL on the CPU at tiny sizes: proves nothing about "
            "the chip, prints no result")
        config = {**config, **config.get("rehearse", {})}
        flags += traffic.get("rehearse_flags", [])

    import jax
    try:
        devs = devices.require_chips(cell.chips, rehearse=args.rehearse)
    except devices.NoChip as e:
        print(e, file=sys.stderr)
        return devices.EXIT_NO_CHIP
    device = devices.device_report(devs)
    peaks = None if args.rehearse else devices.peaks_for(device["kind"])
    say("device:", json.dumps(device), "jax", jax.__version__)

    # the program under test, from this checkout
    from commefficient_tpu.config import enable_compilation_cache_dir
    from commefficient_tpu.core import FedRuntime
    from commefficient_tpu.cv_train import build_mesh
    from commefficient_tpu.data.device_store import make_device_store
    from commefficient_tpu.telemetry.collectives import ledger_from_hlo
    from commefficient_tpu.telemetry.compilewatch import JitWatcher

    cache = enable_compilation_cache_dir(os.path.join(REPO, ".jax_cache"))
    # every program of the cell goes into the cache, however quickly it
    # compiled, so that a second run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    say("compile cache:", cache)

    # what JAX itself reports: compilations asked for (to show that none
    # falls inside the window) and what the persistent cache answered
    counts = {"backend_compile_duration": 0, "cache_hits": 0,
              "cache_misses": 0}

    def count(name, *_a, **_kw):
        key = name.rsplit("/", 1)[-1]
        if key in counts:
            counts[key] += 1

    jax.monitoring.register_event_duration_secs_listener(count)
    jax.monitoring.register_event_listener(count)

    family = importlib.import_module(f"perfbench.families.{cell.family}")
    cfg = family.parse(flags)
    mesh = build_mesh(cfg)
    t = time.perf_counter()
    built = family.build(cfg, config, args.seed)
    jax.block_until_ready((built.params, built.dataset.arrays))
    cfg = cfg.replace(num_clients=built.dataset.num_clients)
    d = sum(int(x.size) for x in jax.tree_util.tree_leaves(built.params))
    say(f"family {cell.family}: d = {d}, {len(built.dataset)} items over "
        f"{built.dataset.num_clients} clients, weights and data from seed "
        f"{args.seed} in {time.perf_counter() - t:.2f} s")

    # ---- the cell's runtime, as the drivers build it
    class Recorder:
        """What ``JitWatcher`` reports to: keeps the compile events."""

        def __init__(self):
            self.events = []

        def event(self, kind, **kw):
            self.events.append({"event": kind, **kw})

    recorder = Recorder()
    runtime = FedRuntime(cfg, built.params, built.loss_fn,
                         num_clients=built.dataset.num_clients, mesh=mesh)
    runtime.set_compile_watcher(JitWatcher(recorder))
    state = runtime.init_state()
    store = make_device_store(
        built.dataset, built.store_name, True, mesh=mesh,
        out_shardings=(runtime.batch_sharding() if mesh is not None
                       else None), no_augment=cfg.no_augment)
    if store is None:
        raise RuntimeError("the data set does not fit a DeviceStore")
    lr = family.lr_array(built, cfg, runtime, traffic["lr"])
    source = timing.RoundSource(built.dataset, store, runtime.cfg,
                                args.seed)
    loop = timing.Loop(runtime, state, source, lr, traffic["sync_every"])
    del state

    try:
        # ---- warm-up: every shape the window uses; counted as set-up
        t = time.perf_counter()
        warm = loop.chunk(traffic["warmup_rounds"])
        compiles = [e for e in recorder.events if e["event"] == "compile"]
        compile_s = sum(e["lower_s"] + e["compile_s"] for e in compiles)
        say(f"warm-up: {warm['rounds']} rounds in "
            f"{time.perf_counter() - t:.2f} s, loss {warm['loss']:.6f}; "
            "lower+compile " + ", ".join(
                f"{e['name']} {e['lower_s'] + e['compile_s']:.2f} s"
                for e in compiles))
        hlo = runtime.compile_watcher.executables["round_step"].as_text()
        ledger = ledger_from_hlo(hlo)
        kinds = sorted({e["kind"] for e in ledger})
        say("collectives in the compiled round: " + (", ".join(
            f"{sum(e['kind'] == k for e in ledger)} {k}" for k in kinds)
            or "none"))
        model_flops = family.model_flops_per_round(built, runtime.cfg)
        setup_s = time.perf_counter() - T0
        compiles_before = counts["backend_compile_duration"]

        # ---- the measured part
        trace = None
        traced_rounds = 0
        if args.trace == 0:
            chunks = loop.window(seconds)
            summary = timing.summarize(chunks)
            all_chunks = chunks
        else:
            # a short untraced stretch for the host clocks, then the same
            # stretch under the profiler; the difference is what tracing costs
            untraced = [loop.chunk(traffic["trace_rounds"])
                        for _ in range(traffic.get("trace_chunks", 2))]
            summary = timing.summarize(untraced)
            trace_dir = os.path.join(REPO, ".perfbench_out", "trace", cell.name)
            shutil.rmtree(trace_dir, ignore_errors=True)
            os.makedirs(trace_dir)
            ann = jax.profiler.TraceAnnotation
            # device events and the benchmark's own annotations only: the
            # Python tracer would slow the host that the trace is to observe
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            try:
                with ann(tracered.STRETCH):
                    traced = loop.chunk(traffic["trace_rounds"], annotate=ann)
            finally:
                jax.profiler.stop_trace()
            traced_rounds = traced["rounds"]
            all_chunks = untraced + [traced]
            t = time.perf_counter()
            raw = traceio.load(
                trace_dir,
                keep_line=lambda plane, line: (
                    tracered.DEVICE_PLANE.match(plane) is None
                    or line == tracered.OPS_LINE))
            for line in traceio.describe(raw)[:12]:
                say("trace:", line)
            if args.dump_trace:
                os.makedirs(os.path.dirname(os.path.abspath(args.dump_trace)),
                            exist_ok=True)
                traceio.dump(raw, args.dump_trace)
            trace = tracered.reduce(raw, rehearse=args.rehearse)
            shutil.rmtree(trace_dir, ignore_errors=True)
            traced_ms = traced["wall_s"] / traced["rounds"] * 1e3
            say(f"traced stretch: {traced_rounds} rounds, "
                f"{traced_ms:.3f} ms/round against {summary['round_ms']:.3f} "
                f"untraced (tracing costs {traced_ms - summary['round_ms']:+.3f}"
                f" ms/round); window {trace['window_s']:.4f} s from "
                f"{trace['window_source']}, device busy {trace['busy_s']:.4f} s,"
                f" idle share {100 * trace['idle_share']:.2f} %, longest gap "
                f"{1e3 * trace['longest_gap_s']:.3f} ms; reduced in "
                f"{time.perf_counter() - t:.2f} s")
        compiles_in_window = (counts["backend_compile_duration"]
                              - compiles_before)
    finally:
        source.close()     # stops the prefetch thread and joins it

    # ---- after the window: memory, byte ledger, invariants
    peak_bytes, mem_stats = devices.memory_peak_bytes(devs)
    say("memory:", json.dumps(mem_stats))
    state = loop.state
    rcfg = runtime.cfg
    table_shape = (tuple(state.Vvelocity.shape) if rcfg.mode == "sketch"
                   else None)
    up = jax.device_get(loop.last_metrics["upload_bytes"])
    n_participants = int((up > 0).sum())
    upload_program = float(up.sum() / max(n_participants, 1))
    upload_own = arith.upload_bytes_per_client(
        rcfg.mode, d, table_shape, rcfg.wire_dtype, rcfg.k)
    nan_round = int(jax.device_get(state.nan_round))
    rounds_done = sum(c["rounds"] for c in all_chunks)
    say(f"set-up {setup_s:.2f} s; window:", json.dumps(summary))
    rate = built.samples_per_round / (summary["round_ms"] * 1e-3)
    say(f"{rate:.1f} {family.SAMPLE_UNIT}/s at "
        f"{built.samples_per_round} {family.SAMPLE_UNIT}/round"
        + ("" if args.rehearse else
           f"; end-to-end model-FLOPs utilization "
           f"{arith.mfu_pct(model_flops, summary['round_ms'] * 1e-3, cell.chips, peaks):.2f} % "
           f"of {cell.chips} x {peaks['bf16_flops'] / 1e12:.0f} TFLOP/s "
           f"({model_flops / 1e12:.3f} TFLOP/round)"))

    state_problems = checks.state_invariants(runtime, state, devs,
                                             args.rehearse)

    # ---- the reference checks, last: they hold the program to the plain
    # references at the cell's full size, and run after the memory peak was
    # read so that the peak is the round's own and not theirs
    loop.state = state = None
    del store, source
    t = time.perf_counter()
    chk_model = checks.model_step(family, built, cfg, args.seed)
    say("check model_step:", json.dumps(chk_model))
    t_model = time.perf_counter() - t
    t = time.perf_counter()
    chk_algebra = checks.round_algebra(cfg, d, args.seed, mesh=mesh)
    say("check round_algebra:", json.dumps(chk_algebra))
    say(f"reference checks took {t_model:.2f} s (model step) + "
        f"{time.perf_counter() - t:.2f} s (round algebra), after the "
        "window and outside set-up")

    problems = []
    if not chk_model["ok"]:
        problems.append("model step disagrees with the plain reference")
    if not chk_algebra["ok"]:
        problems.append("round algebra disagrees with the plain server")
    problems += state_problems
    kp, n_mosaic = checks.kernel_invariants(runtime, hlo, rcfg.mode,
                                            len(devs), args.rehearse)
    problems += kp
    say(f"compiled round holds {n_mosaic} Mosaic custom call(s); "
        f"{compiles_in_window} compilation(s) inside the window")
    if compiles_in_window:
        problems.append(f"{compiles_in_window} compilation(s) inside the "
                        "measured window")
    losses = [warm["loss"]] + [c["loss"] for c in all_chunks]
    if not all(map(lambda x: x == x and abs(x) != float("inf"), losses)):
        problems.append(f"non-finite loss: {losses}")
    elif not losses[-1] < losses[0]:
        problems.append(f"loss did not fall: {losses[0]} -> {losses[-1]}")
    if nan_round >= 0:
        problems.append(f"non-finite update at round {nan_round}")
    if abs(upload_program - upload_own) > 0.5:
        problems.append(f"upload bytes: program {upload_program}, "
                        f"benchmark's arithmetic {upload_own}")
    if peak_bytes <= 0 and not args.rehearse:
        problems.append("the device reports no memory peak")
    say(f"persistent compile cache: {counts['cache_hits']} hit(s), "
        f"{counts['cache_misses']} miss(es) in this process")
    for p in problems:
        say("NOT CORRECT:", p)

    values = {
        "round_ms": (summary["round_ms"], "ms"),
        "peak_hbm_gib": (peak_bytes / 2**30, "GiB"),
        # the benchmark's own arithmetic; a run whose byte ledger says
        # otherwise is not correct (above)
        "upload_mib": (upload_own / 2**20, "MiB"),
        "setup_s": (setup_s, "s"),
    }
    e2e_names, per_layer = spec.cell_metrics(bench, cell)
    if args.trace == 0:
        metrics = {n: {"value": values[n][0], "unit": values[n][1]}
                   for n in e2e_names}
    else:
        ctx = {"host": summary, "trace": trace,
               "traced_rounds": traced_rounds, "compile_s": compile_s,
               "model_flops_per_round": model_flops, "chips": cell.chips,
               "peaks": peaks, "d": d, "table_shape": table_shape,
               "facts": cell.facts()}
        metrics = {}
        for m in per_layer:
            value = readers.read(m, ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    result = {"correct": not problems, "attempted": rounds_done,
              "failed": loop.failed + (1 if nan_round >= 0 else 0),
              "metrics": metrics,
              "device": {**device, "memory_peak_bytes": int(peak_bytes)}}
    if trace is not None:
        result["device"]["busy_s"] = trace["busy_s"]
        result["device"]["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    if args.rehearse:
        say("rehearsal result (NOT a measurement):", json.dumps(result))
        say("rehearsal finished; it is not a pass")
        return EXIT_REHEARSAL
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
