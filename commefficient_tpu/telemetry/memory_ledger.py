"""HBM memory ledger + live-residency accounting: prove where the bytes go.

Two complementary instruments, both host-side-only (zero hot-path cost,
nothing here ever enters a jitted computation):

**Ledger** — per-executable STATIC byte accounting from XLA's
``compiled.memory_analysis()`` (``CompiledMemoryStats``): temp buffers,
argument/output/alias and generated-code bytes. The ``JitWatcher``
records it on every compile of a watched executable and emits a
schema-v6 ``memory_ledger`` event next to the ``compile`` event, so a
buffer-size regression (a fusion break materializing a ``(W, d)``
per-client gradient, the dense ``(d,)`` f32 gradient the sketch round
still pays — ~2.9 GB at GPT-2 124M) shows in every run's stream and is
asserted as hard per-executable byte ceilings by
``__graft_entry__.dryrun_multichip``.

**Residency** — per-phase DYNAMIC allocator tracking from
``device.memory_stats()``: live bytes, allocator high-water peak, the
peak's growth since the previous snapshot (which phase grew the
high-water: rounds vs validation vs checkpoint), fragmentation
(peak - live) and the headroom fraction against the device limit — the
near-OOM precursor ``telemetry/health.py``'s ``hbm_pressure`` rule
watches so the flight recorder arms BEFORE the allocator dies.
Backends without ``memory_stats`` (the CPU container) degrade to null
fields with a one-time stderr note — never fake zeros, never a crash.
"""

from __future__ import annotations

import sys
from typing import Any, Dict, List, Optional

# byte fields of one ``memory_ledger`` event (beyond the executable
# name). ``total_bytes`` = argument + output + temp + generated-code —
# the executable's whole static footprint (aliased bytes are a subset
# of argument/output, counted once). scripts/teleview.py mirrors these
# as literals for jax-free analysis; tests/test_memory.py pins them.
MEMORY_LEDGER_KEYS = ("temp_bytes", "argument_bytes", "output_bytes",
                      "alias_bytes", "generated_code_bytes", "total_bytes")

# derived residency fields of the enriched (schema v6) ``memory`` event;
# every one is null when the backend reports no allocator stats
MEMORY_KEYS = ("live_bytes", "peak_bytes", "delta_peak_bytes",
               "fragmentation_bytes", "limit_bytes", "headroom_frac")

# The acceptance gate ROADMAP item 1's encode-fusion work committed to
# flip (PR 8 staged it False): the sketch-mode round used to
# MATERIALIZE the dense (d,) f32 aggregated gradient before encoding it
# (temp_bytes >= d*4 — the structural HBM suspect behind the flat GPT-2
# MFU). With the fused encode (core/client.py make_forward_grad /
# make_fused_grad: the microbatch scan carries the (r, c) sketch table,
# --sketch_fused_encode) the dense gradient never exists, so the
# dryrun_multichip sketch gate now asserts the INVERSE: temp_bytes <
# d*4 — a regression that re-materializes the dense aggregate fails the
# dryrun. check_dense_grad_floor(fused=False) keeps the pre-fusion
# direction testable (and gates the explicit --sketch_fused_encode off
# arm).
SKETCH_ENCODE_FUSED = True

# attribute name on the CompiledMemoryStats object -> ledger field
_STATS_ATTRS = {
    "temp_size_in_bytes": "temp_bytes",
    "argument_size_in_bytes": "argument_bytes",
    "output_size_in_bytes": "output_bytes",
    "alias_size_in_bytes": "alias_bytes",
    "generated_code_size_in_bytes": "generated_code_bytes",
}


def ledger_from_stats(stats: Any) -> Optional[Dict[str, Any]]:
    """Parse a ``CompiledMemoryStats``-shaped object (attribute access,
    so tests can drive it with a stub) into the ledger dict. Returns
    None when the object exposes NO recognizable byte field — an
    unknown-shape result must yield no event, not an all-null one."""
    out: Dict[str, Any] = {k: None for k in MEMORY_LEDGER_KEYS}
    found = False
    for attr, key in _STATS_ATTRS.items():
        v = getattr(stats, attr, None)
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            out[key] = int(v)
            found = True
    if not found:
        return None
    parts = [out[k] for k in ("argument_bytes", "output_bytes",
                              "temp_bytes", "generated_code_bytes")]
    if any(p is not None for p in parts):
        out["total_bytes"] = int(sum(p for p in parts if p is not None))
    return out


def ledger_from_compiled(compiled) -> Optional[Dict[str, Any]]:
    """Ledger of a ``lowered.compile()`` result. Best-effort like every
    observability path: a backend without ``memory_analysis`` (or one
    that raises) yields None rather than an exception."""
    try:
        return ledger_from_stats(compiled.memory_analysis())
    except Exception:
        return None


def round_memory_ledger(runtime, state, client_ids, batch, mask,
                        lr: float = 0.1) -> Optional[Dict[str, Any]]:
    """Lower+compile the runtime's round step on the given arguments and
    return its memory ledger — the dryrun/test entry point (the
    telemetry path instead hooks the JitWatcher's compile), mirroring
    ``collectives.round_ledger``."""
    import jax.numpy as jnp
    lowered = runtime._round.lower(
        state, client_ids, batch, mask,
        jnp.asarray(lr, jnp.float32), runtime.cs)
    return ledger_from_compiled(lowered.compile())


# ------------------------------------------------------------------ ceilings


def check_ceilings(ledger: Optional[Dict[str, Any]],
                   ceilings: Dict[str, float]) -> List[str]:
    """Hard byte-ceiling gate over one ledger: every ceiled field must be
    PRESENT and within its ceiling. A null field fails too — a gate that
    silently passes when the measurement vanished proves nothing (the
    collective-ledger lesson: absence of evidence read as health)."""
    problems: List[str] = []
    if ledger is None:
        return [f"no memory ledger (memory_analysis unavailable) but "
                f"ceilings were asserted: {sorted(ceilings)}"]
    for key, limit in sorted(ceilings.items()):
        v = ledger.get(key)
        if v is None:
            problems.append(f"{key} is null (cannot prove <= {limit:.0f})")
        elif v > limit:
            problems.append(f"{key} {v} exceeds ceiling {limit:.0f}")
    return problems


def check_dense_grad_floor(ledger: Optional[Dict[str, Any]], d: int,
                           fused: bool = SKETCH_ENCODE_FUSED) -> List[str]:
    """The sketch-mode dense-gradient gate (see SKETCH_ENCODE_FUSED):
    un-fused, the round's temp buffers must CONTAIN the dense (d,) f32
    aggregated gradient (temp >= d*4 — documenting today's cost);
    fused, they must NOT (temp < d*4 — the fusion PR's acceptance
    proof). Returns a problems list, empty = the expected regime."""
    if ledger is None or ledger.get("temp_bytes") is None:
        return ["temp_bytes is null (cannot check the dense-gradient "
                "floor)"]
    temp, floor = int(ledger["temp_bytes"]), int(d) * 4
    if not fused and temp < floor:
        return [f"temp_bytes {temp} < d*4 = {floor}: the dense gradient "
                "no longer materializes — flip SKETCH_ENCODE_FUSED and "
                "invert this gate (the item-1 fusion acceptance)"]
    if fused and temp >= floor:
        return [f"temp_bytes {temp} >= d*4 = {floor}: SKETCH_ENCODE_FUSED "
                "claims the encode is fused into the accumulator scan, "
                "but the round still materializes a dense-gradient-sized "
                "temp buffer"]
    return []


def _tree_bytes(tree) -> int:
    import jax
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        size = getattr(leaf, "size", None)
        itemsize = getattr(getattr(leaf, "dtype", None), "itemsize", None)
        if size is not None and itemsize is not None:
            total += int(size) * int(itemsize)
    return total


def round_memory_ceilings(runtime, state, batch) -> Dict[str, float]:
    """Per-executable byte ceilings for ONE federated round, computed
    from the run's own geometry so the gate scales from the dryrun's
    tiny shapes to real models:

    - ``argument_bytes``: the state + batch trees the round actually
      takes (everything else — ids/mask/lr/sketch constants — rides in
      the slack term);
    - ``output_bytes``: the new state + metrics (metrics are O(W) + a
      handful of diagnostics; state dominates);
    - ``temp_bytes``: the round's legitimate working set — per-client
      activation traffic (a multiple of the batch bytes), the dense
      federated vectors (client gradients aggregate through O(1) d-sized
      buffers since the fused-clients change — a per-client (W, d)
      materialization blows through this, which is the point), and the
      sketch tables.

    The multipliers carry measured headroom (CPU XLA on the dryrun
    shapes sits at roughly half of each ceiling); the regression class
    this gate exists to catch — a de-fusion re-materializing per-client
    d-vectors — scales with W and overshoots by the client count."""
    d_pad = int(runtime.d_pad)
    cfg = runtime.cfg
    table = int(cfg.num_rows) * int(cfg.num_cols)
    state_bytes = _tree_bytes(state)
    batch_bytes = _tree_bytes(batch)
    slack = 16 * 2**20  # constants, control scalars, codegen rounding
    return {
        "argument_bytes": 1.25 * (state_bytes + batch_bytes) + slack,
        "output_bytes": 1.25 * state_bytes + batch_bytes + slack,
        # activations: <= 48x the batch bytes live at once (ResNet-scale
        # forward+backward per microbatch); dense vectors: <= 8 d-sized
        # f32 buffers (grad, velocity, error, update + transient pairs);
        # tables: <= 8 copies (encode/decode + transposes)
        "temp_bytes": (48.0 * batch_bytes + 8.0 * 4 * d_pad
                       + 8.0 * 4 * table + slack),
    }


# ----------------------------------------------------------------- residency


def residency_fields(device_stats: List[Optional[Dict[str, Any]]],
                     prev_peak: Optional[float] = None) -> Dict[str, Any]:
    """Derive the MEMORY_KEYS residency fields from a list of per-device
    ``memory_stats()`` dicts (None / empty for devices that report
    nothing). Aggregation is worst-device over reporting devices — the
    binding constraint on a replicated-state mesh is the worst device —
    and the DERIVED fields (fragmentation, headroom) are computed
    per-device BEFORE aggregating, so they always describe a real
    device: max live/peak paired with an independently-maxed limit
    would overstate the headroom of a small-limit device about to OOM.
    Every field is null when no device reports — never a fake zero."""
    def _num(s, key):
        v = s.get(key) if isinstance(s, dict) else None
        return v if isinstance(v, (int, float)) else None

    lives, peaks, limits, frags, headrooms = [], [], [], [], []
    for s in device_stats:
        live, peak, limit = (_num(s, "bytes_in_use"),
                             _num(s, "peak_bytes_in_use"),
                             _num(s, "bytes_limit"))
        if live is not None:
            lives.append(live)
        if peak is not None:
            peaks.append(peak)
        if limit is not None:
            limits.append(limit)
        if peak is not None and live is not None:
            frags.append(peak - live)
        if limit and peak is not None:
            headrooms.append((limit - peak) / limit)
    peak = max(peaks) if peaks else None
    out: Dict[str, Any] = {
        "live_bytes": max(lives) if lives else None,
        "peak_bytes": peak,
        "delta_peak_bytes": (peak - prev_peak
                             if peak is not None and prev_peak is not None
                             else None),
        "fragmentation_bytes": max(frags) if frags else None,
        "limit_bytes": max(limits) if limits else None,
        "headroom_frac": (round(min(headrooms), 6)
                          if headrooms else None),
    }
    return out


class ResidencyTracker:
    """Owns the snapshot-to-snapshot state of the residency fields (the
    previous peak for delta attribution) and the one-time degradation
    note for backends without ``memory_stats``.

    ``snapshot(devices)`` returns ``(device_records, derived_fields)``
    ready for the ``memory`` event: per-device ``{id, kind, stats}``
    (stats null when unavailable) plus the MEMORY_KEYS fields. A device
    whose ``memory_stats`` method is missing, raises, or returns an
    empty dict degrades to null — the stream shape stays
    backend-independent and the degradation is announced ONCE."""

    def __init__(self):
        self._prev_peak: Optional[float] = None
        self._warned = False

    def snapshot(self, devices) -> tuple:
        records, stats_list = [], []
        for d in devices:
            stats = None
            try:
                getter = getattr(d, "memory_stats", None)
                if getter is not None:
                    stats = getter()
            except Exception:
                stats = None
            if not stats:          # missing method, raised, or empty dict
                stats = None
            records.append({"id": int(getattr(d, "id", 0)),
                            "kind": getattr(d, "device_kind", "unknown"),
                            "stats": stats})
            stats_list.append(stats)
        derived = residency_fields(stats_list, self._prev_peak)
        if derived["peak_bytes"] is not None:
            self._prev_peak = derived["peak_bytes"]
        # the degradation note fires only on FULL absence — a backend
        # exposing partial stats (live but no peak) keeps its non-null
        # fields and must not be announced as "unavailable"
        if (not self._warned and devices
                and all(derived[k] is None for k in MEMORY_KEYS)):
            self._warned = True
            print("NOTE: device memory_stats() unavailable/empty on this "
                  "backend; memory-event residency fields (live/peak/"
                  "fragmentation/headroom) will be null — null means "
                  "'not measurable here', never zero", file=sys.stderr)
        return records, derived
