"""Per-layer metric readers.

A metric's file (``metrics/<name>.json``) names its reader:

``{"kind": "host", "field": F}``
    field F of the summary of the trace run's *untraced* stretch (host
    clocks of the timing loop: ``input_wait_ms``, ``dispatch_ms``, ...).
``{"kind": "device_events", "match": REGEX}``
    self time of the device events whose name (or meta) matches, per round,
    in ms, on the chip where it is largest. ``"zero_if_absent": true``
    reports 0 where nothing matches; otherwise nothing is reported.
``{"kind": "device_exposed", "match": REGEX}``
    the part of those events during which no other operation runs on that
    chip, per round, in ms, worst chip.
``{"kind": "compile"}``
    lower + compile wall seconds of the cell's executables in this run.
``{"kind": "module"}``
    ``metrics/<name>.py`` beside the file, with ``read(ctx) -> value|None``.

A reader that finds nothing to read returns None and the harness leaves the
metric out of the line.
"""

from __future__ import annotations

import importlib.util
import re

from perfbench.harness import arith, spec, tracered


def _per_round_ms(ctx, fn, pattern):
    vals = [fn(chip["selfs"], pattern) for chip in ctx["trace"]["chips"]
            .values()]
    return max(vals) / ctx["traced_rounds"] * 1e3


def _n_matching(ctx, pattern):
    return sum(1 for chip in ctx["trace"]["chips"].values()
               for t in chip["selfs"]
               if tracered.matches(pattern, t[0], t[5]))


def kernel_roofline(ctx, kernel, bytes_fn):
    """Roofline share (%) of one of the sketch kernels: the bytes one call
    on the round's whole gradient needs (``bytes_fn(d, rows, cols)``) over
    peak bandwidth, over the kernel's device time per round on the chip
    where that is largest. Memory-bound: one add per element read. A path
    that calls the kernel more often than once a round shows a lower share.
    None without a trace, a table of peaks, a sketch or a matching event."""
    if None in (ctx.get("trace"), ctx.get("peaks"), ctx.get("table_shape")):
        return None
    pattern = re.compile(kernel)
    secs = max(tracered.sum_matching(chip["selfs"], pattern)
               for chip in ctx["trace"]["chips"].values())
    if secs <= 0:
        return None
    rows, cols = ctx["table_shape"]
    pct, _bound = arith.roofline_pct(
        bytes_fn(ctx["d"], rows, cols), 2.0 * ctx["d"] * rows,
        secs / ctx["traced_rounds"], ctx["peaks"])
    return pct


def read(metric, ctx):
    r = metric["reader"]
    kind = r["kind"]
    if kind == "host":
        return ctx["host"].get(r["field"])
    if kind in ("device_events", "device_exposed"):
        if ctx.get("trace") is None:
            return None
        pattern = re.compile(r["match"])
        if not _n_matching(ctx, pattern):
            return 0.0 if r.get("zero_if_absent") else None
        fn = (tracered.sum_matching if kind == "device_events"
              else tracered.exposed)
        return _per_round_ms(ctx, fn, pattern)
    if kind == "compile":
        return ctx["compile_s"]
    if kind == "module":
        path = spec.metric_path(metric["name"], "py")
        mod_spec = importlib.util.spec_from_file_location(
            f"perfbench_metric_{metric['name']}", path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        return mod.read(ctx)
    raise ValueError(f"metric {metric['name']}: unknown reader kind "
                     f"{kind!r}")
