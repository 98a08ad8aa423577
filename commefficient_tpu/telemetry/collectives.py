"""HLO collective ledger: count, kind and byte size of every cross-device
collective in a compiled executable.

Round 5's post-mortem (VERDICT weak #2) is the reason this exists: the
per-client-row home<->compute layout conversion silently unrolled into 32
separate 492-element all_to_alls per round, and nothing noticed — the
multichip dryrun asserted collective *size* only, so a pathology that
multiplies collective *count* (32 launches of pure latency per round at
GPT-2 scale) regressed invisibly. The ledger walks the compiled HLO text
(``lowered.compile().as_text()`` — the same artifact
``__graft_entry__._collective_report`` already parses for sizes) and
records every all-reduce / reduce-scatter / all-gather / all-to-all /
collective-permute with its element count, dtype and byte size, so both
the telemetry stream (``collectives`` events, emitted by the JitWatcher
on every compile) and the dryruns (hard count assertions) see the same
inventory.

Parsing notes, measured against the XLA versions in this image:
- async scheduling splits ops into ``-start``/``-done`` pairs; only the
  ``-start`` (or the sync form) is counted, never both.
- combined collectives have tuple result types (``(f32[3,64], f32[])``);
  each tuple element is one ledger entry (they travel as one launch but
  the payload accounting wants every element). ``combined_in``
  back-references the launch index so count-of-launches stays exact.
- ``/*index=N*/`` comments inside >5-element tuple types are stripped
  before matching (their ``=`` breaks the op match).
"""

from __future__ import annotations

import re
from typing import Any, Dict, List

COLLECTIVE_KINDS = ("all-reduce", "reduce-scatter", "all-gather",
                    "all-to-all", "collective-permute")

# Per-round LAUNCH-count ceilings for EVERY collective kind, asserted
# by __graft_entry__.dryrun_multichip (all 5 modes) and
# scripts/multihost_dryrun.py — one dict so the two dryruns and the
# tests can never drift apart. Measured on the current toolchain:
# local_topk runs the intended 4 tiled all_to_alls (vel+err x
# home->compute and back), every mode stays <= 10 all-reduces, 1
# reduce-scatter, <= 23 all-gathers, and the sketch round's top-k /
# signal machinery peaks at 293 collective-permutes. The bounds add
# slack for scheduler variation; the round-5 regression class (a layout
# conversion unrolling into per-ROW launches, VERDICT weak #2) scales
# with the row/shard count and blows through whichever kind it hits by
# ~an order of magnitude — bounding only the aggregation kinds would
# leave a gather/permute unroll invisible, the exact blind spot this
# ledger exists to close.
ROUND_COLLECTIVE_LAUNCH_BOUNDS = {
    "all-to-all": 4,
    "reduce-scatter": 2,
    "all-reduce": 12,
    "all-gather": 32,
    "collective-permute": 384,
}

# dtype -> bytes per element, for the dtypes XLA spells in result types
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_OP_RE = re.compile(
    r"=\s+(\(?[^=]*?)\s*"
    r"(all-reduce|reduce-scatter|all-gather|all-to-all|collective-permute)"
    r"(-start)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def ledger_from_hlo(hlo_text: str) -> List[Dict[str, Any]]:
    """One entry per collective result element:
    ``{kind, n_elements, dtype, bytes, combined_in}``.

    ``combined_in`` is the 0-based index of the LAUNCH the entry belongs
    to — entries sharing it came from one combined (tuple-result)
    collective, so ``len({e['combined_in']})`` is the true launch count
    while ``len(entries)`` counts payload elements.
    """
    entries: List[Dict[str, Any]] = []
    launch = 0
    for line in hlo_text.splitlines():
        # strip /*index=N*/ comments: XLA annotates tuple types beyond 5
        # elements with them, and their '=' breaks the op match
        line = re.sub(r"/\*.*?\*/", "", line)
        m = _OP_RE.search(line)
        if not m:
            continue
        if m.group(3) and "-done(" in line:
            continue  # defensive; -done never matches the -start group
        result_type, kind = m.group(1), m.group(2)
        found = False
        for dtype, dims_s in _SHAPE_RE.findall(result_type):
            dims = [int(x) for x in dims_s.split(",") if x]
            n = 1
            for d in dims:
                n *= d
            nbytes = n * _DTYPE_BYTES.get(dtype, 4)
            entries.append({"kind": kind, "n_elements": n, "dtype": dtype,
                            "bytes": nbytes, "combined_in": launch})
            found = True
        if found:
            launch += 1
    return entries


def ledger_from_compiled(compiled) -> List[Dict[str, Any]]:
    """Ledger of a ``lowered.compile()`` result. Best-effort: an
    executable that cannot render its HLO yields an empty ledger rather
    than an exception (observability never kills the run)."""
    try:
        return ledger_from_hlo(compiled.as_text())
    except Exception:
        return []


def collective_wire_bytes(entry: Dict[str, Any],
                          n_devices: int) -> float:
    """Modeled per-device ICI bytes of one collective under ring
    algorithms — what actually crosses the wire, as opposed to the
    entry's RESULT bytes (a reduce-scatter's result is 1/n of its
    input, so raw result bytes would under-count it n-fold against an
    all_to_all of the same payload):

    - all-reduce: 2 * bytes * (n-1)/n (reduce-scatter + all-gather);
    - reduce-scatter: input = n * result, each device sends
      (n-1)/n of it -> result_bytes * (n-1);
    - all-gather / all-to-all: each device sends (n-1)/n of the
      (result-sized) payload;
    - collective-permute: the whole payload moves once.

    n == 1 is zero: a single-device "collective" crosses no wire.
    """
    n = max(int(n_devices), 1)
    if n == 1:
        return 0.0
    b = float(entry["bytes"])
    kind = entry["kind"]
    if kind == "all-reduce":
        return 2.0 * b * (n - 1) / n
    if kind == "reduce-scatter":
        return b * (n - 1)
    if kind in ("all-gather", "all-to-all"):
        return b * (n - 1) / n
    return b


def table_reduce_wire_bytes(entries: List[Dict[str, Any]],
                            n_devices: int) -> float:
    """Per-device ICI bytes of the round's table-REDUCE collectives:
    the reduce-scattered f32/bf16 table, or the int8 column-shard +
    f32-scale all_to_alls that replace it under ``--wire_dtype int8``
    (ops/wire.py). In the sketch round these two kinds ARE the table
    reduce — the rows_cols all_to_alls exist only for dense-mode client
    rows — so filtering by kind needs no size heuristics. This is the
    quantity ISSUE-14's dryrun gate bounds (int8 <= 0.30x f32) and
    ``teleview diff --wire_bytes_growth`` regresses."""
    return sum(collective_wire_bytes(e, n_devices) for e in entries
               if e["kind"] in ("reduce-scatter", "all-to-all"))


def summarize_ledger(entries: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Aggregate a ledger into the ``collectives`` telemetry event body:
    per-kind launch counts, total payload bytes, and the raw ops list."""
    counts: Dict[str, int] = {}
    launches_seen: Dict[str, set] = {}
    total_bytes = 0
    for e in entries:
        launches_seen.setdefault(e["kind"], set()).add(e["combined_in"])
        total_bytes += e["bytes"]
    for kind, launches in launches_seen.items():
        counts[kind] = len(launches)
    return {
        "n_collectives": sum(counts.values()),
        "counts": counts,
        "total_bytes": total_bytes,
        "ops": entries,
    }


def round_ledger(runtime, state, client_ids, batch, mask, lr=0.1):
    """Lower+compile the runtime's round step on the given arguments and
    return its collective ledger — the dryrun/test entry point (the
    telemetry path instead hooks the JitWatcher's compile)."""
    import jax.numpy as jnp
    lowered = runtime._round.lower(
        state, client_ids, batch, mask,
        jnp.asarray(lr, jnp.float32), runtime.cs)
    return ledger_from_compiled(lowered.compile())
