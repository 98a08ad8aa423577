"""Compile observability for the jitted round/val steps.

``JitWatcher.wrap(name, fn)`` returns a callable that manages its own
AOT cache keyed on the argument signature (treedef + leaf shape/dtype).
The first call with a new signature runs ``fn.lower`` and ``.compile()``
under split wall timers and logs a ``compile`` event carrying the XLA
``cost_analysis()`` FLOPs / bytes-accessed — so a RECOMPILE (a shape
change, a donation miss materializing a new layout) shows up as a
second ``compile`` event for the same name instead of a silent
multi-second (or, at GPT-2 scale, multi-minute) stall. Subsequent calls
dispatch straight to the cached compiled executable, bypassing jit's
own re-trace.

Never trades correctness for observability: any failure in the AOT path
(an input the signature key cannot describe, an executable rejecting an
aval/sharding the plain jit path would accept) permanently drops the
wrapper into pass-through mode for that function, logging one final
``compile`` event with ``fallback: true``.
"""

from __future__ import annotations

import re
import time
from typing import Any, Callable, Dict

import jax

from commefficient_tpu.telemetry import tracing

# latest compiled executable per watched name, process-wide: whoever has
# no handle on the runtime (a metric reader, an operator's notebook) reads
# the HLO of the program that is running — same pattern as
# tracing.current(). The executable object only (behind ``WatchedText``):
# no copy, no text kept.
LATEST: Dict[str, Any] = {}

# a Pallas kernel's ``kernel_metadata`` frontend attribute is printed as
# JSON with line breaks, which puts the ``metadata={op_name=...}`` of
# that custom call on a line of its own
_BROKEN_LINE = re.compile(r'\n(?="|\}\})')


class WatchedText:
    """A watched executable as its readers hold it: the executable's own
    attributes, and ``as_text()`` with every instruction on ONE line.
    Readers join device events to phases line by line
    (perfbench/harness/phase_reader.py); an instruction that spans three
    lines (jax's splash attention kernels do, see ``_BROKEN_LINE``) would
    lose its ``op_name`` to them and count with whatever calls its
    computation."""

    def __init__(self, compiled):
        self._compiled = compiled

    def __getattr__(self, name):
        return getattr(self._compiled, name)

    def as_text(self) -> str:
        return _BROKEN_LINE.sub(" ", self._compiled.as_text())


def latest(name: str) -> Any:
    """The executable a ``JitWatcher`` compiled last under ``name``
    (``"round_step"``, ...), or None."""
    return LATEST.get(name)


def _compile(lowered) -> Any:
    """``lowered.compile()`` with the persistent cache keyed on the
    instructions' metadata too. JAX strips it from the key by default,
    so a cache shared with another checkout hands back an executable
    that computes the same but carries THAT checkout's ``op_name``
    paths: the round's phase names (profiling.PHASES) that a reader of
    ``latest()`` and the profile viewer's name-scope rows rely on would
    be stale or missing (tried: a scope renamed between two processes
    came back under its old name). The price is a recompile of the
    watched executables — not of anything else — when their source
    lines move."""
    flag = "jax_compilation_cache_include_metadata_in_key"
    prev = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        return lowered.compile()
    finally:
        jax.config.update(flag, prev)


def _signature(args) -> Any:
    leaves, treedef = jax.tree_util.tree_flatten(args)
    return (treedef, tuple(
        (tuple(getattr(leaf, "shape", ())),
         str(getattr(leaf, "dtype", type(leaf).__name__)))
        for leaf in leaves))


def _cost_analysis(compiled) -> Dict[str, Any]:
    try:
        return dict(compiled.cost_analysis() or {})
    except Exception:
        return {}


class JitWatcher:
    """Wraps jitted callables; reports compiles to a RunTelemetry."""

    def __init__(self, telemetry):
        self._telemetry = telemetry
        self.n_compiles = 0
        # latest cost-analysis FLOPs per watched name (None when XLA
        # returned no count) — the MFU numerator utilization.py joins
        # with the round's wall time; a recompile overwrites, so the
        # count always describes the executable that is actually running
        self.flops: Dict[str, Any] = {}
        # latest cost-analysis bytes-accessed per watched name — the
        # roofline denominator (arithmetic intensity = flops / bytes);
        # same overwrite-on-recompile semantics as `flops`
        self.bytes: Dict[str, Any] = {}
        # latest memory_analysis ledger per watched name (memory_ledger
        # .py) — the per-executable static byte inventory; the flight
        # recorder ships the aborting executable's entry in memory.json
        self.memory: Dict[str, Dict[str, Any]] = {}
        # latest compiled executable per watched name: the program that
        # actually runs, for whoever must read its HLO (chip_smoke.py
        # counts the Mosaic custom calls in the round) without paying a
        # second lower+compile
        self.executables: Dict[str, Any] = {}

    def wrap(self, name: str, fn: Callable) -> Callable:
        cache: Dict[Any, Any] = {}
        state = {"fallback": False}

        def emit(n, lower_s, compile_s, cost, fallback=False):
            self.n_compiles += 1
            if cost.get("flops"):
                self.flops[name] = cost.get("flops")
            if cost.get("bytes accessed"):
                self.bytes[name] = cost.get("bytes accessed")
            self._telemetry.event(
                "compile", name=name, n_compiles=n,
                lower_s=round(lower_s, 6), compile_s=round(compile_s, 6),
                flops=cost.get("flops"),
                bytes_accessed=cost.get("bytes accessed"),
                fallback=fallback)

        def wrapped(*args):
            if state["fallback"]:
                return fn(*args)
            try:
                key = _signature(args)
            except Exception:
                state["fallback"] = True
                emit(len(cache), 0.0, 0.0, {}, fallback=True)
                return fn(*args)
            compiled = cache.get(key)
            if compiled is None:
                try:
                    # also as spans, under whatever span is open: a
                    # recompile inside a timed window is a child of its
                    # round_launch
                    t0 = time.perf_counter()
                    with tracing.span("compile_lower"):
                        lowered = fn.lower(*args)
                    t1 = time.perf_counter()
                    with tracing.span("compile_backend"):
                        compiled = _compile(lowered)
                    t2 = time.perf_counter()
                except Exception:
                    # un-lowerable input (or an AOT-unsupported transform
                    # nesting): give up on observation, keep the run alive
                    state["fallback"] = True
                    emit(len(cache), 0.0, 0.0, {}, fallback=True)
                    return fn(*args)
                cache[key] = compiled
                self.executables[name] = LATEST[name] = WatchedText(
                    compiled)
                emit(len(cache), t1 - t0, t2 - t1,
                     _cost_analysis(compiled))
                # collective ledger of the fresh executable (count/kind/
                # bytes of every cross-device collective) — best-effort,
                # like every observability path here
                if hasattr(self._telemetry, "collectives_event"):
                    try:
                        from commefficient_tpu.telemetry.collectives import \
                            ledger_from_compiled
                        self._telemetry.collectives_event(
                            name, ledger_from_compiled(compiled))
                    except Exception:
                        pass
                # memory ledger of the fresh executable (memory_analysis
                # temp/argument/output/alias/generated-code bytes) —
                # the per-executable HBM inventory, emitted next to the
                # compile event like the collectives; a backend without
                # memory_analysis yields no event, not an all-null one
                if hasattr(self._telemetry, "memory_ledger_event"):
                    try:
                        from commefficient_tpu.telemetry.memory_ledger \
                            import ledger_from_compiled as _mem_ledger
                        mledger = _mem_ledger(compiled)
                        if mledger is not None:
                            self.memory[name] = mledger
                            self._telemetry.memory_ledger_event(
                                name, mledger)
                    except Exception:
                        pass
            try:
                return compiled(*args)
            except Exception:
                # AOT executables validate input avals/shardings more
                # strictly than jit dispatch; if this signature's inputs
                # slip past our key but not the executable, never risk the
                # run — pass through to the plain jit path from here on.
                state["fallback"] = True
                emit(len(cache), 0.0, 0.0, {}, fallback=True)
                # ONLY retry when the inputs are still alive: a failure
                # DURING execution (OOM at scale) may already have
                # consumed donated buffers, and retrying with deleted
                # arrays would bury the real error under a confusing
                # "Array has been deleted" — re-raise the original then.
                if any(getattr(leaf, "is_deleted", lambda: False)()
                       for leaf in jax.tree_util.tree_leaves(args)):
                    raise
                return fn(*args)

        wrapped.__name__ = f"watched_{name}"
        return wrapped
