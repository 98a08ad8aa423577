"""From the profiler's ``.xplane.pb`` to the benchmark's own plain form.

``jax.profiler.ProfileData`` reads the file with nothing but JAX. The
reducer (``tracered.py``) works on a neutral form so that it can be checked
on small recorded traces kept as JSON beside the tests:

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, dur_ns, meta], ...]}]}]}

``meta`` is a short string of the event's stats that help to name it (the
HLO category, the op's long name, a kernel's name), or "".
"""

from __future__ import annotations

import glob
import json
import os
import re

META_KEYS = ("hlo_category", "tf_op", "long_name", "kernel_details",
             "hlo_op", "name", "deduplicated_name")


_OPCODE = re.compile(r"(?<![A-Za-z0-9_\-])([a-z][a-z0-9\-]*[a-z0-9])\(")


def short_name(name):
    """On the TPU an event of the ``XLA Ops`` line is named by the whole
    HLO instruction (``%fusion.12 = f32[..]{..} fusion(...), kind=...``).
    Returns (instruction name, "op=<opcode>"): ``("fusion.12", "op=fusion")``;
    a name of another form comes back as it is."""
    if not (name.startswith("%") and " = " in name):
        return name, ""
    short, rest = name[1:].split(" = ", 1)
    m = _OPCODE.search(rest)
    return short, f"op={m.group(1)}" if m else ""


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _meta(event):
    parts = []
    try:
        for key, value in event.stats:
            if key in META_KEYS and value is not None:
                parts.append(f"{key}={str(value)[:120]}")
    except Exception:
        pass
    return " ".join(parts)[:400]


def load(trace_dir, keep_line=None):
    """Read the newest trace under ``trace_dir`` into the neutral form.
    ``keep_line(plane, line)`` drops what the reducer never reads (the
    devices' module and step lines) before events are copied."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(find_xplane(trace_dir))
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            if keep_line and not keep_line(plane.name, line.name):
                continue
            events = []
            for ev in line.events:
                name, op = short_name(ev.name)
                meta = (op + " " + _meta(ev)).strip()
                events.append([name, int(ev.start_ns), int(ev.duration_ns),
                               meta])
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def describe(trace):
    """One line per plane and line, with counts: what to read before
    writing a rule against a trace."""
    out = []
    for p in trace["planes"]:
        for ln in p["lines"]:
            ev = ln["events"]
            first = ev[0]
            out.append(f"{p['name']} | {ln['name']} | {len(ev)} events | "
                       f"first: {first[0]!r} {first[3][:160]!r}")
    return out


def dump(trace, path):
    with open(path, "w") as f:
        json.dump(trace, f)
