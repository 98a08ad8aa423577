"""The trace reducer: from device and host events to numbers.

Works on the neutral form of ``traceio.py``. All times in the form are
nanoseconds on the profiler's clock; everything returned is in seconds.

What it knows:
- a chip is a plane named ``/device:TPU:<n>``; its operations are the events
  of the line ``XLA Ops``. Module, step and framework lines repeat the same
  time at a coarser grain and are never added up;
- operations nest (a ``while`` spans its body's operations): *busy* is the
  union of intervals, and per-name sums use *self* time (an event's duration
  less what its children cover), so nothing is counted twice;
- the benchmark's own ``jax.profiler.TraceAnnotation`` spans (names starting
  ``bench:``) are on host planes, on the same clock; ``bench:stretch`` is the
  traced window, and a device idle gap is attributed to the ``bench:`` span
  that covers most of it.
"""

from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
STRETCH = "bench:stretch"
NS = 1e-9


def device_ops(trace, rehearse=False):
    """{chip: [(name, start_ns, end_ns, meta), ...]} sorted by start."""
    chips = {}
    for p in trace["planes"]:
        m = DEVICE_PLANE.match(p["name"])
        if not m:
            continue
        for ln in p["lines"]:
            if ln["name"] == OPS_LINE:
                chips[int(m.group(1))] = _by_start(
                    (e[0], e[1], e[1] + e[2], e[3]) for e in ln["events"])
    if not chips and rehearse:
        # a CPU trace has no device plane: take the XLA runtime's host
        # threads as "chip 0" so that a rehearsal walks the same code
        ev = []
        for p in trace["planes"]:
            for ln in p["lines"]:
                if re.search(r"xla|pjrt|eigen", ln["name"], re.I):
                    ev += [(e[0], e[1], e[1] + e[2], e[3])
                           for e in ln["events"]
                           if not e[0].startswith("bench:")]
        if ev:
            chips[0] = _by_start(ev)
    return chips


def _by_start(events):
    """By start time, and of two that start together the longer first, so
    that a parent always comes before its children."""
    return sorted(events, key=lambda e: (e[1], -e[2]))


def host_spans(trace, prefix="bench:"):
    """[(name, start_ns, end_ns)] of the benchmark's annotations."""
    out = []
    for p in trace["planes"]:
        if DEVICE_PLANE.match(p["name"]):
            continue
        for ln in p["lines"]:
            out += [(e[0], e[1], e[1] + e[2]) for e in ln["events"]
                    if e[0].startswith(prefix)]
    return sorted(out, key=lambda s: s[1])


def union(intervals):
    """Merge [(start, end)] into disjoint sorted intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals):
    return sum(e - s for s, e in intervals)


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b):
    """Parts of the disjoint sorted intervals ``a`` not covered by ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        i = j
        while i < len(b) and b[i][0] < e:
            if b[i][0] > cur:
                out.append((cur, b[i][0]))
            cur = max(cur, b[i][1])
            i += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(events):
    """[(name, self_ns, is_leaf, start, end, meta)] for nested events,
    which must come sorted by start, a parent before its children. An
    event's parent is the nearest earlier event that contains it wholly;
    two that merely overlap are siblings."""
    out, stack = [], []          # stack of indices into out, innermost last
    for name, s, e, meta in events:
        while stack and (out[stack[-1]][4] <= s or out[stack[-1]][4] < e):
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            parent[1] -= e - s
            parent[2] = False
        out.append([name, e - s, True, s, e, meta])
        stack.append(len(out) - 1)
    return [(n, max(d, 0), leaf, s, e, m) for n, d, leaf, s, e, m in out]


def window_of(trace, chips):
    """(start_ns, end_ns, source). The ``bench:stretch`` annotation where
    it is on the devices' clock, else the extent of the device events."""
    lo = min(min(e[1] for e in ev) for ev in chips.values())
    hi = max(max(e[2] for e in ev) for ev in chips.values())
    for name, s, e in host_spans(trace, STRETCH):
        # the same clock: the device's work lies inside the host's span,
        # give or take the launch of the first operation
        if s - 5e6 <= lo and hi <= e + 5e6:
            return s, e, "annotation"
    return lo, hi, "device_extent"


def matches(pattern, name, meta):
    return bool(pattern.search(name) or (meta and pattern.search(meta)))


def reduce_chip(events, lo, hi):
    """Busy union, gaps, and self-timed leaf events of one chip, clipped
    to the window."""
    busy = clip(union((s, e) for _n, s, e, _m in events), lo, hi)
    return {"busy": busy, "busy_s": length(busy) * NS,
            "gaps": subtract([(lo, hi)], busy),
            "selfs": [t for t in self_times(events)
                      if t[4] > lo and t[3] < hi]}


def sum_matching(selfs, pattern):
    """Self time (s) of the events whose name or meta matches."""
    return sum(t[1] for t in selfs if matches(pattern, t[0], t[5])) * NS


def exposed(selfs, pattern):
    """Seconds of matching events during which no other leaf operation
    runs on the chip."""
    mine = union((t[3], t[4]) for t in selfs
                 if t[2] and matches(pattern, t[0], t[5]))
    other = union((t[3], t[4]) for t in selfs
                  if t[2] and not matches(pattern, t[0], t[5]))
    return length(subtract(mine, other)) * NS


def top_ops(selfs, n=10):
    """[[name, seconds]] by self time; instances of one operation
    (``fusion.12``, ``fusion.13``) stay apart, a kernel's calls add up."""
    agg = {}
    for name, self_ns, _leaf, _s, _e, _meta in selfs:
        agg[name] = agg.get(name, 0) + self_ns
    top = sorted(agg.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * NS] for k, v in top]


def attribute_gaps(gaps, spans, n=10):
    """[[host phase, seconds]]: each idle gap goes to the ``bench:`` span
    (other than the stretch itself) that covers most of it."""
    agg = {}
    spans = [s for s in spans if s[0] != STRETCH]
    for gs, ge in gaps:
        best, best_ov = "(no host span)", 0
        for name, s, e in spans:
            if e <= gs:
                continue
            if s >= ge:
                break
            ov = min(e, ge) - max(s, gs)
            if ov > best_ov:
                best, best_ov = name, ov
        agg[best] = agg.get(best, 0) + (ge - gs)
    top = sorted(agg.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * NS] for k, v in top]


def reduce(trace, rehearse=False):
    """Everything the metric readers ask for, per chip and overall."""
    chips = device_ops(trace, rehearse=rehearse)
    if not chips:
        raise ValueError("the trace has no device plane with an "
                         f"{OPS_LINE!r} line: nothing ran on a chip?")
    lo, hi, source = window_of(trace, chips)
    per_chip = {c: reduce_chip(ev, lo, hi) for c, ev in chips.items()}
    first = per_chip[min(per_chip)]
    busy_mean = sum(r["busy_s"] for r in per_chip.values()) / len(per_chip)
    return {
        "window_s": (hi - lo) * NS, "window_source": source,
        "busy_s": busy_mean, "chips": per_chip,
        "idle_share": 1.0 - busy_mean / ((hi - lo) * NS),
        "device_ops": top_ops(first["selfs"]),
        "idle_gaps": attribute_gaps(first["gaps"], host_spans(trace)),
        "longest_gap_s": max((e - s for s, e in first["gaps"]),
                             default=0) * NS,
    }
