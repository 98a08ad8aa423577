#!/usr/bin/env python3
"""Cut a small test trace out of a dumped one.

    python3 perfbench/tests/cut_trace.py DUMP.json[.gz] OUT.json START_MS LEN_MS [MAX_EVENTS]

``DUMP`` is what ``run.py --trace 1 --dump-trace`` wrote (the reducer's plain
form). Keeps, of every chip's ``XLA Ops`` line, the events that lie wholly
inside [START, START + LEN) counted from the start of ``bench:stretch``, and
the ``bench:`` host spans that overlap it (clipped; the stretch itself is cut
to the slice), shifts all times so that the slice starts at 0, and drops the
``meta`` strings except the opcode. With ``MAX_EVENTS`` the slice ends early,
after that many device events per chip. The result is small enough to read
and to check by hand.
"""

import gzip
import json
import re
import sys


def main(src, dst, start_ms, len_ms, max_events=None):
    opener = gzip.open if src.endswith(".gz") else open
    with opener(src, "rt") as f:
        trace = json.load(f)
    stretch = [e for p in trace["planes"] for ln in p["lines"]
               for e in ln["events"] if e[0] == "bench:stretch"][0]
    lo = stretch[1] + int(float(start_ms) * 1e6)
    hi = lo + int(float(len_ms) * 1e6)
    device = re.compile(r"^/device:TPU:\d+$")
    if max_events:
        for p in trace["planes"]:
            if device.match(p["name"]):
                inside = sorted(e[1] + e[2] for ln in p["lines"]
                                for e in ln["events"]
                                if e[1] >= lo and e[1] + e[2] <= hi)
                if len(inside) > int(max_events):
                    hi = min(hi, inside[int(max_events) - 1] + 1)
    out = []
    for p in trace["planes"]:
        lines = []
        for ln in p["lines"]:
            if device.match(p["name"]):
                ev = [[e[0], e[1] - lo, e[2], e[3].split(" ")[0]]
                      for e in ln["events"]
                      if e[1] >= lo and e[1] + e[2] <= hi]
            else:
                ev = []
                for e in ln["events"]:
                    if e[0].startswith("bench:") and e[1] < hi and e[
                            1] + e[2] > lo:
                        s, t = max(e[1], lo), min(e[1] + e[2], hi)
                        ev.append([e[0], s - lo, t - s, ""])
            if ev:
                lines.append({"name": ln["name"], "events": ev})
        if lines:
            out.append({"name": p["name"], "lines": lines})
    with open(dst, "w") as f:
        json.dump({"planes": out, "cut": {"from": src.split("/")[-1],
                                          "start_ms": float(start_ms),
                                          "len_ns": hi - lo}}, f)
    n = sum(len(ln["events"]) for p in out for ln in p["lines"])
    print(f"{dst}: {n} events over {(hi - lo) / 1e6:.3f} ms")


if __name__ == "__main__":
    main(*sys.argv[1:])
