"""Device time per phase of the round, read through the program's own HLO.

The program names its phases with ``jax.named_scope`` (``fed_client_step``,
``fed_server_tail``, ...: ``telemetry/profiling.PHASES``). A scope's name
lands in the ``op_name`` metadata of every instruction traced under it, and
``compiled.as_text()`` prints that metadata. The trace's device events are
named by HLO instruction name, which is unique in a module, so

    event name -> instruction of the compiled round -> phase

needs nothing from the trace's own stats and works the same on the chip and
in a rehearsal on the CPU. Nested scopes: the innermost names the
instruction. An instruction without a phase of its own (the asynchronous
copies and slices the TPU compiler puts into a loop's body carry no
metadata at all) takes the phase of the instruction that calls its
computation: a ``copy-done`` in the body of the client scan's ``while`` is
the client step waiting for its prefetch. What still maps to no phase (such
an instruction of the entry computation, an event of another executable in
the window) is the *unnamed* rest, reported as a metric of its own so that
it stays in plain sight.

Known limits. A fusion carries one ``op_name``, so operations XLA fused
across a scope's edge count on one side. An event of another executable
whose instruction name also exists in the round is counted with the round's
phase.

    python3 -m perfbench.harness.phase_reader DUMP.json HLO.txt [--rounds N]

prints the whole table from a ``run.py --dump-trace`` file and the text of
the round's executable (``dump_hlo`` writes it).
"""

from __future__ import annotations

import re
import sys

_LINE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s(.*)$")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$")
_CALLS = re.compile(
    r"\b(?:body|condition|calls|to_apply|select|scatter)=%?([\w.\-]+)")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_TOKEN = re.compile(r"fed_[a-z_]+")

_cache = {"exe": None, "table": None}


def program_phases():
    """The phase names of this checkout's program; () where it has none."""
    from commefficient_tpu.telemetry import profiling
    return tuple(getattr(profiling, "PHASES", ()))


def parse_hlo(text, phases):
    """{instruction name: phase or None} for every instruction of an HLO
    text. The phase is the last ``fed_*`` token of the instruction's
    ``op_name`` path that is one of ``phases``; an instruction without one
    takes the phase of the callers of its computation, where they agree."""
    phases = set(phases)
    own, where, callers = {}, {}, {}
    computation = None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            computation = head.group(1)
            continue
        m = _LINE.match(line)
        if not m:
            continue
        name, rest = m.groups()
        op = _OP_NAME.search(rest)
        found = [t for t in _TOKEN.findall(op.group(1))
                 if t in phases] if op else []
        own[name] = found[-1] if found else None
        where[name] = computation
        for called in _CALLS.findall(rest):
            callers.setdefault(called, []).append(name)
    inherited = {}

    def phase_of(name):
        if own[name] is not None:
            return own[name]
        computation = where[name]
        if computation not in inherited:
            inherited[computation] = None       # a cycle ends here
            got = {phase_of(c) for c in callers.get(computation, ())}
            inherited[computation] = got.pop() if len(got) == 1 else None
        return inherited[computation]

    return {name: phase_of(name) for name in own}


def round_table():
    """The table of the round executable that is running, parsed once per
    executable; None where the program keeps no handle on it or names no
    phases (a checkout from before the scopes)."""
    from commefficient_tpu.telemetry import compilewatch
    latest = getattr(compilewatch, "latest", None)
    exe = latest("round_step") if latest else None
    phases = program_phases()
    if exe is None or not phases:
        return None
    if _cache["exe"] is not exe:
        _cache.update(exe=exe, table=parse_hlo(exe.as_text(), phases))
    return _cache["table"]


def dump_hlo(path):
    """Write the running round's HLO text to ``path``, for the table."""
    from commefficient_tpu.telemetry import compilewatch
    with open(path, "w") as f:
        f.write(compilewatch.latest("round_step").as_text())


def phase_ms(ctx, phases, table=None):
    """Self time of the device events whose instruction maps to one of
    ``phases`` (None: to no phase), per traced round, in ms, on the chip
    where it is largest. None without a trace or a table."""
    table = table if table is not None else round_table()
    if ctx.get("trace") is None or table is None:
        return None
    want = {None} if phases is None else set(phases)
    return max(sum(t[1] for t in chip["selfs"] if table.get(t[0]) in want)
               for chip in ctx["trace"]["chips"].values()
               ) / ctx["traced_rounds"] * 1e-6


def report(trace, table, phases, rounds):
    """The whole table as lines: every phase and the unnamed rest on the
    first chip, ms a round, share of busy, the five largest operations."""
    chip = trace["chips"][min(trace["chips"])]
    busy_ms = chip["busy_s"] / rounds * 1e3
    rows = {}
    for name, self_ns, *_ in chip["selfs"]:
        key = table.get(name) or ("(no phase, in the round's HLO)"
                                  if name in table
                                  else "(not in the round's HLO)")
        ops = rows.setdefault(key, {})
        ops[name] = ops.get(name, 0) + self_ns
    selfs_ms = sum(t[1] for t in chip["selfs"]) / rounds * 1e-6
    out = [f"busy {busy_ms:.3f} ms/round over {rounds} round(s); self "
           f"times add up to {selfs_ms:.3f}"]
    for key in list(phases) + sorted(set(rows) - set(phases)):
        ops = rows.get(key, {})
        ms = sum(ops.values()) / rounds * 1e-6
        top = sorted(ops.items(), key=lambda kv: -kv[1])[:5]
        out.append(f"{key:34s} {ms:10.3f} ms/round {100 * ms / busy_ms:6.2f}"
                   f" %  {len(ops):5d} ops  " + ", ".join(
                       f"{n} {v / rounds * 1e-6:.3f}" for n, v in top))
    return out


def main(argv):
    import argparse
    import json

    from perfbench.harness import tracered
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("dump")
    ap.add_argument("hlo")
    ap.add_argument("--rounds", type=int, default=1,
                    help="rounds in the traced stretch (trace_rounds)")
    args = ap.parse_args(argv)
    with open(args.dump) as f:
        raw = json.load(f)
    on_chip = any(tracered.DEVICE_PLANE.match(p["name"])
                  for p in raw["planes"])
    trace = tracered.reduce(raw, rehearse=not on_chip)
    phases = program_phases()
    with open(args.hlo) as f:
        table = parse_hlo(f.read(), phases)
    print("\n".join(report(trace, table, phases, args.rounds)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
