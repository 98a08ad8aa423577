"""Profiler window management: ``--profile_rounds START:STOP``.

Replaces the window hardcoded to rounds 2-4 of ``cv_train.py`` only:
every driver (cv_train, gpt2_train) and both benchmarks now place the
jax profiler trace over an arbitrary round range of the run. Rounds are
1-based and the window is inclusive — the default "2:4" captures rounds
2, 3 and 4, exactly the old behavior (skipping round 1 keeps the first
compile out of the trace).

``phase(name)`` names the round's phases INSIDE the compiled program:
a ``jax.named_scope`` whose name lands in the ``op_name`` metadata of
every HLO instruction traced under it (and in the profile viewer's name
scope rows), so device time can be read per phase from the executable's
own HLO. Trace-time only: nothing runs on the host's path of a round.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

# The round's phases, flat and prefixed so that none can collide with a
# jitted function's name in an op_name path (``jit(topk)``, ``jit(sort)``).
# Where two nest (the encode, the table reduce, attention, the latent
# projections and the expert layer sit inside the client step, and a
# prediction module's block inside ``fed_mtp``) the INNERMOST names the
# instruction; nothing else nests.
PHASES = (
    "fed_client_step",      # client_block: forward/backward, local rows, sum
    "fed_sketch_encode",    # every sketch encode of client gradients
    "fed_attention",        # models/laguna.py, joyai.py: scores, mask,
                            # softmax, values (or the kernel), both passes;
                            # not projections
    "fed_moe",              # models/layers.ExpertLayer: router, the held
                            # experts' batched products; not the shared one
    "fed_latent",           # models/joyai.py: latent attention's low-rank
                            # projection pairs, latent norms, rotary tables;
                            # ops/latent_pallas.py's kernels around the
                            # attention (or the plain path's rotary and
                            # concatenations); not W_o
    "fed_mtp",              # models/joyai.py: the prediction module's norms,
                            # W_eh, its block outside the three scopes above,
                            # its head norm and chunked cross-entropy
    "fed_table_reduce",     # the cross-chip aggregation (mesh only)
    "fed_server_tail",      # normalize, momentum/EF, decode, top-k, apply
    "fed_signals",          # telemetry/signals.py round_signals
    "fed_layer_signals",    # telemetry/layer_signals.py per-group masses
    "fed_client_stats",     # telemetry/clients.py quantile summaries
    "fed_byte_ledger",      # track_bytes: download counts, last-update maps
)

# the phases only a model with such layers has (models/laguna.py: the
# first two; models/joyai.py: all four)
MODEL_PHASES = ("fed_attention", "fed_moe", "fed_latent", "fed_mtp")


def phase(name: str):
    """``jax.named_scope(name)`` for one of ``PHASES``; any other name is
    a typo that would silently drop its operations out of every per-phase
    metric, so it raises."""
    if name not in PHASES:
        raise ValueError(f"unknown round phase {name!r}; one of {PHASES}")
    import jax
    return jax.named_scope(name)


def parse_profile_rounds(spec: str) -> Tuple[int, int]:
    """Parse "START:STOP" (inclusive, 1-based). A bare "N" profiles the
    single round N. Raises ValueError with an actionable message."""
    s = spec.strip()
    try:
        if ":" in s:
            a, b = s.split(":", 1)
            start, stop = int(a), int(b)
        else:
            start = stop = int(s)
    except ValueError:
        raise ValueError(
            f"--profile_rounds {spec!r} is not START:STOP (two integers, "
            "e.g. '2:4') or a single round number") from None
    if start < 1 or stop < start:
        raise ValueError(
            f"--profile_rounds {spec!r}: need 1 <= START <= STOP")
    return start, stop


class ProfilerWindow:
    """Start/stop a jax profiler trace over a round window.

    ``maybe_start(rnd)`` goes before the round's dispatch and
    ``maybe_stop(rnd, sync)`` after it; ``sync`` is called before
    stopping so the trace contains completed device work (a
    ``block_until_ready`` on something the round produced). ``abort()``
    closes a live trace on an error path — a retried benchmark attempt
    must not leak an open trace into the profiler's global state.
    """

    def __init__(self, outdir: str, rounds: str = "2:4",
                 log: Callable[[str], None] = print):
        self.outdir = outdir
        self.start, self.stop = (parse_profile_rounds(rounds) if outdir
                                 else (0, 0))
        self._log = log
        self.active = False
        self.done = False

    @property
    def enabled(self) -> bool:
        return bool(self.outdir)

    def maybe_start(self, rnd: int) -> None:
        if (self.enabled and not self.done and not self.active
                and self.start <= rnd <= self.stop):
            import jax
            jax.profiler.start_trace(self.outdir)
            self.active = True

    def maybe_stop(self, rnd: int,
                   sync: Optional[Callable[[], None]] = None) -> None:
        if self.active and rnd >= self.stop:
            import jax
            if sync is not None:
                sync()
            jax.profiler.stop_trace()
            self.active = False
            self.done = True
            self._log(f"profiler trace written to {self.outdir}")

    def finalize(self, sync: Optional[Callable[[], None]] = None) -> None:
        """Close a window the run ended inside of (STOP beyond the last
        round, a NaN abort, a fractional final epoch): the rounds captured
        so far still become a trace — and the profiler's process-global
        state is released — instead of silently losing both. No-op when
        the window already closed (or never opened)."""
        if self.active:
            import jax
            if sync is not None:
                sync()
            jax.profiler.stop_trace()
            self.active = False
            self.done = True
            self._log(f"profiler trace written to {self.outdir} "
                      "(window closed early: run ended before round "
                      f"{self.stop})")

    def abort(self) -> None:
        if self.active:
            self.active = False
            self.done = True
            try:
                import jax
                jax.profiler.stop_trace()
            except Exception:
                pass
