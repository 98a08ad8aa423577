"""Host time per span of the round, read from the program's own tracer.

The program records its host spans always (``telemetry/tracing.py``: a
bounded ring of ``{id, parent, round, name, ts, dur_s, tid, ...}``) and
reaches them through ``tracing.current()``, as ``compilewatch.latest``
hands out the running executable. A span's ``round`` is the global round
its work is for, on whichever thread it ran, and the spans a ``FedRuntime``
opens carry that runtime's ordinal, so

    round_dispatch of the cell's runtime -> its round -> every span of it

picks out of the ring the rounds the host clocks of a ``--trace 1`` run
were taken over: the *untraced* stretch, ``ctx["host"]["rounds"]`` rounds
that precede the last ``ctx["traced_rounds"]``. Warm-up lies before them,
the traced stretch after, and the rounds of the ``round_algebra`` check
belong to a second runtime. The metrics read durations and counts only:
the ring is on the program's clock, a profiler session's clock starts with
the session, and nothing here lays one over the other.

Where the program keeps no ring (a checkout from before it) or the ring
has dropped part of what is asked for, a reader returns None and the
harness leaves the metric out.

    python3 -m perfbench.harness.span_reader DUMP.json

prints, from a ``run.py --dump-trace`` file, the first chip's idle gaps by
the innermost ``fed:`` annotation (the same spans, on the profiler's
clock) that covers most of each, beside the ``bench:`` attribution.
"""

from __future__ import annotations

import sys

from perfbench.harness import tracered

PREFIX = "fed:"


def ring():
    """(completed spans oldest first, how many the ring ever dropped), or
    None where the program has no recording tracer."""
    try:
        from commefficient_tpu.telemetry import tracing
    except ImportError:
        return None
    tracer = tracing.current()
    if not hasattr(tracer, "snapshot"):
        return None
    return tracer.snapshot(), tracer.dropped_total


def _end(span):
    return span["ts"] + span["dur_s"]


def _first_runtime(spans):
    """Spans of the runtime built first, and those no runtime marked."""
    ordinals = [s["runtime"] for s in spans if "runtime" in s]
    first = min(ordinals, default=None)
    return [s for s in spans if s.get("runtime", first) == first]


def _whole(held, dropped, wanted):
    """False where the ring may have lost one of ``wanted``: it drops the
    oldest, so after any drop only what closed later than the oldest span
    it still holds is sure to be there."""
    return not dropped or not wanted or _end(held[0]) <= min(
        s["ts"] for s in wanted)


def stretch(ctx, held=None):
    """``{"rounds": [global rounds], "spans": [every span of them],
    "epoch_first": {rounds that open an epoch}}`` for the untraced stretch
    of a trace run, or None (module docstring).
    ``held``: ``ring()``'s pair, for a test."""
    held = ring() if held is None else held
    if held is None or not held[0]:
        return None
    spans, dropped = held
    mine = _first_runtime(spans)
    dispatches = sorted((s for s in mine if s["name"] == "round_dispatch"),
                        key=lambda s: s["ts"])
    n, traced = int(ctx["host"]["rounds"]), int(ctx["traced_rounds"])
    picked = dispatches[:len(dispatches) - traced][-n:]
    rounds = [s["round"] for s in picked]
    if len(picked) < n or None in rounds or len(set(rounds)) < n:
        return None
    wanted = [s for s in mine if s["round"] in set(rounds)]
    per_round = {(s["round"], s["name"]) for s in wanted}
    if not _whole(spans, dropped, wanted) or any(
            (g, name) not in per_round for g in rounds
            for name in ("round_stage", "round_launch", "data_fetch")):
        return None
    return {"rounds": rounds, "spans": wanted,
            # the rounds an epoch's pipeline was opened for
            "epoch_first": {s["round"] for s in wanted
                            if s["name"] == "pipeline_open"}}


def per_round_ms(ctx, names, where=lambda span, found: True):
    """Time in the stretch's spans called one of ``names`` (and passing
    ``where``), over the stretch's rounds, in ms; None without a stretch."""
    found = stretch(ctx)
    if found is None:
        return None
    total = sum(s["dur_s"] for s in found["spans"]
                if s["name"] in names and where(s, found))
    return total / len(found["rounds"]) * 1e3


def ready_pct(ctx):
    """Rounds whose batch was queued when the loop asked for it, of the
    stretch's rounds, in %."""
    found = stretch(ctx)
    if found is None:
        return None
    ready = sum(1 for s in found["spans"]
                if s["name"] == "data_wait" and s.get("ready"))
    return 100.0 * ready / len(found["rounds"])


def setup_s(names):
    """Seconds in the first runtime's (or no runtime's) spans called one of
    ``names``, of which only the first of each name counts; None where the
    ring has none, or has dropped any span at all (set-up is the oldest
    thing it holds)."""
    held = ring()
    if held is None or held[1]:
        return None
    total, seen = 0.0, set()
    for s in sorted(_first_runtime(held[0]), key=lambda s: s["ts"]):
        if s["name"] in names and s["name"] not in seen:
            seen.add(s["name"])
            total += s["dur_s"]
    return total if seen else None


# ------------------------------------------------- idle gaps, from a dump

def innermost(spans):
    """Cut nested ``[(name, start, end)]`` into disjoint pieces, each named
    by the span open there that started last (of two that start together
    the shorter): a parent keeps only what no child covers."""
    edges = sorted({t for _n, s, e in spans for t in (s, e)})
    pieces = []
    for lo, hi in zip(edges, edges[1:]):
        open_here = [(s, -e, n) for n, s, e in spans if s <= lo and e >= hi]
        if not open_here:
            continue
        name = max(open_here)[2]
        if pieces and pieces[-1][0] == name and pieces[-1][2] == lo:
            pieces[-1] = (name, pieces[-1][1], hi)
        else:
            pieces.append((name, lo, hi))
    return pieces


def gaps_by_span(raw, rehearse=False):
    """(first chip's reduction, [[fed: span, s]], [[bench: span, s]]): its
    idle gaps by the innermost ``fed:`` span, and by the benchmark's."""
    chips = tracered.device_ops(raw, rehearse=rehearse)
    if not chips:
        raise ValueError("the dump has no device events")
    lo, hi, _source = tracered.window_of(raw, chips)
    first = tracered.reduce_chip(chips[min(chips)], lo, hi)
    fed = innermost(tracered.host_spans(raw, prefix=PREFIX))
    return (first, tracered.attribute_gaps(first["gaps"], fed),
            tracered.attribute_gaps(first["gaps"], tracered.host_spans(raw)))


def main(argv):
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("dump")
    ap.add_argument("--rounds", type=int, default=1,
                    help="rounds in the traced stretch (trace_rounds)")
    args = ap.parse_args(argv)
    with open(args.dump) as f:
        raw = json.load(f)
    on_chip = any(tracered.DEVICE_PLANE.match(p["name"])
                  for p in raw["planes"])
    first, fed, bench = gaps_by_span(raw, rehearse=not on_chip)
    idle = tracered.length(first["gaps"]) * tracered.NS
    print(f"first chip idle {idle / args.rounds * 1e3:.3f} ms/round in "
          f"{len(first['gaps'])} gap(s) over {args.rounds} round(s)")
    for title, rows in (("by the innermost fed: span", fed),
                        ("by bench: span", bench)):
        print(title)
        for name, secs in rows:
            print(f"  {name:28s} {secs / args.rounds * 1e3:9.3f} ms/round"
                  f" {100 * secs / max(idle, 1e-12):6.2f} %")
    spans = tracered.host_spans(raw, prefix=PREFIX)
    print("fed: annotations in the dump, ms each")
    for name in sorted({n for n, _s, _e in spans}):
        durs = [(e - s) * 1e-6 for n, s, e in spans if n == name]
        print(f"  {name:28s} x{len(durs):3d}  "
              + " ".join(f"{d:.3f}" for d in durs[:12]))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
