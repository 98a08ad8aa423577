"""Shared helpers for the driver benchmarks (``bench.py``, ``bench_gpt2.py``):
the device-peak lookups (which refuse a device they do not know) and the
warmup + timed-rounds loop with its host/dispatch/device-wait phase split.
"""

from __future__ import annotations

import sys
import time

# peak bf16 FLOP/s and HBM GB/s by generation — single source of truth
# in telemetry/utilization.py (the `utilization` events and the benches
# must agree on the MFU/roofline denominators)
from commefficient_tpu.telemetry.utilization import (peak_flops_for,
                                                     peak_hbm_for)


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def peak_flops(device) -> float:
    """Peak bf16 FLOP/s of ``device``. A device the table does not know
    is an error: an MFU against a guessed denominator is not a
    measurement."""
    kind = getattr(device, "device_kind", "")
    peak = peak_flops_for(kind)
    if peak is None:
        raise ValueError(
            f"unknown device kind {kind!r}: no peak FLOP/s in "
            "telemetry/utilization.PEAK_FLOPS_BY_KIND — add the chip "
            "there, with its source")
    return peak


def peak_hbm_gbps(device) -> float:
    """Peak HBM GB/s of ``device``; raises on an unknown kind, as
    :func:`peak_flops` does."""
    kind = getattr(device, "device_kind", "")
    peak = peak_hbm_for(kind)
    if peak is None:
        raise ValueError(
            f"unknown device kind {kind!r}: no peak HBM bandwidth in "
            "telemetry/utilization.PEAK_HBM_GBPS_BY_KIND — add the chip "
            "there, with its source")
    return peak


def timed_rounds(runtime, round_args, *, warmup, rounds, desc: str,
                 profiler=None, round_args_fn=None):
    """Warmup, then time ``rounds`` federated rounds on the warmed state.

    ``round_args_fn(i)`` (optional) builds round ``i``'s args INSIDE the
    warmup/timed loops instead of reusing the pre-staged ``round_args``
    (pass None for it then) — for benches whose per-round input staging
    is part of what they measure (a per-round host->device batch copy vs
    a device-store gather, scripts/bench_imagenet.py). Its wall time
    lands in the ``host_s`` phase, i.e. the bench's ``input_wait_frac``.

    ``profiler`` (telemetry.ProfilerWindow) places a jax trace over the
    TIMED rounds, numbered 1..rounds — the warmup (and its compile) stays
    out of the trace. Profiling syncs the device inside the loop, so a
    profiled run's timing is not a clean throughput number; pass a
    profiler only when the trace is the point of the run.

    The timed window ends in ``jax.block_until_ready`` on the whole
    state. On the v5e it agrees with a scalar host fetch of the state
    to 0.3 ms on a 91 ms round and to 0.04 ms/round over 20 chained
    rounds (PR 21 chip run).

    Returns ``(dt_seconds, last_metrics, phases)`` for ``rounds`` timed
    rounds. ``phases`` splits the wall clock: ``dispatch_s`` (time inside
    the async round calls), ``device_wait_s`` (the trailing completion
    barrier) and ``host_s`` (everything else — loop overhead and, when
    profiling, the per-round syncs; the batch is pre-staged here so
    there is no data-fetch phase), plus ``warmup_s`` — the compile +
    warmup wall seconds BEFORE the timed window (cold against warm
    compile cache; callers lift it into the bench json). All clocks are
    ``perf_counter`` — an NTP step during a long timing loop must not
    skew the headline.
    """
    import jax

    log(f"{desc}: compiling + warmup...")
    t0 = time.perf_counter()
    s = runtime.init_state()
    for w in range(warmup):
        args = round_args if round_args_fn is None else round_args_fn(w)
        s, m = runtime.round(s, *args)
    jax.block_until_ready(s)
    warmup_s = time.perf_counter() - t0
    log(f"{desc}: warmup done in {warmup_s:.1f}s")

    t0 = time.perf_counter()
    dispatch_s = 0.0
    try:
        for i in range(rounds):
            if profiler is not None:
                profiler.maybe_start(i + 1)
            # input staging OUTSIDE the dispatch timer: a per-round
            # batch build/copy shows up as host_s (input wait)
            args = round_args if round_args_fn is None else round_args_fn(i)
            td = time.perf_counter()
            s, m = runtime.round(s, *args)
            dispatch_s += time.perf_counter() - td
            if profiler is not None:
                profiler.maybe_stop(
                    i + 1, lambda: jax.block_until_ready(s.ps_weights))
    except BaseException:
        # never leak an open trace into the profiler's process-global
        # state (the caller may be a test that goes on)
        if profiler is not None:
            profiler.abort()
        raise
    if profiler is not None:
        # window STOP beyond the timed round count: keep the partial
        # trace instead of leaking the open profiler
        profiler.finalize(lambda: jax.block_until_ready(s.ps_weights))
    t1 = time.perf_counter()
    jax.block_until_ready(s)
    t2 = time.perf_counter()
    phases = {"host_s": round(t1 - t0 - dispatch_s, 6),
              "dispatch_s": round(dispatch_s, 6),
              "device_wait_s": round(t2 - t1, 6),
              # OUTSIDE the timed wall: the fractions above stay
              # fractions of the timed window
              "warmup_s": round(warmup_s, 3)}
    return t2 - t0, m, phases
